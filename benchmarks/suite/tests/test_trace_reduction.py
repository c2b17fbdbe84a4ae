"""Self-check of the trace reduction and the work arithmetic.

``data/trace_small.json`` is a recorded trace: the events of a short traced
window of ``thermal.d3-r1`` on one TPU v5 lite, cut to a few fits' worth.
The synthetic cases pin the interval arithmetic exactly.
"""
import json
from pathlib import Path

import pytest

from benchmarks.suite import tracing, work
from benchmarks.suite.tracing import Event

DATA = Path(__file__).resolve().parent / "data"
DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, t0, t1):
    return Event(plane, line, name, float(t0), float(t1 - t0))


def test_interval_arithmetic():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing.intersect([(0, 3), (5, 8)], [(2, 6)]) == [(2, 3), (5, 6)]
    assert tracing.length([(0, 3), (5, 8)]) == 6


def test_summary_of_a_synthetic_window():
    events = [
        _ev(HOST, "main", "bench.window", 0, 1000),
        _ev(HOST, "main", "bench.fc", 0, 300),
        _ev(HOST, "main", "bench.sis", 300, 600),
        _ev(HOST, "main", "bench.l0", 600, 1000),
        _ev(HOST, "main", "PjitFunction(step)", 560, 640),
        _ev(DEV, "XLA Modules", "jit_screen(12)", 350, 500),
        _ev(DEV, "XLA Ops", "fusion.3", 350, 400),
        _ev(DEV, "XLA Ops", "dot.1", 400, 500),
        _ev(DEV, "XLA Modules", "jit_gather(7)", 700, 900),
        _ev(DEV, "XLA Ops", "_kernel", 700, 900),
        _ev(DEV, "XLA Ops", "before", -50, 10),     # clipped to the window
    ]
    s = tracing.summarize(events, n_chips=1)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(360e-9)
    assert s.idle_share == pytest.approx(0.64)
    assert s.phase_busy_s["fc"] == pytest.approx(10e-9)
    assert s.phase_busy_s["sis"] == pytest.approx(150e-9)
    assert s.phase_busy_s["l0"] == pytest.approx(200e-9)
    assert s.device_ops[0] == ("jit_gather/_kernel", pytest.approx(200e-9))
    labels = dict(s.device_ops)
    assert labels["jit_screen/dot"] == pytest.approx(100e-9)
    assert labels["?/before"] == pytest.approx(10e-9)
    # the longest idle gap: 10..350, inside the FC span and then SIS
    assert s.idle_gaps[0][0] == "fc|python"
    assert s.idle_gaps[0][1] == pytest.approx(340e-9)
    # 500..700: its middle lies at the end of the SIS span, in a dispatch
    assert s.idle_gaps[1] == ("sis|PjitFunction(step)", pytest.approx(200e-9))
    assert s.idle_gaps[2] == ("l0|python", pytest.approx(100e-9))


def test_recorded_trace():
    rec = json.loads((DATA / "trace_small.json").read_text())
    events = [Event(*e) for e in rec["events"]]
    s = tracing.summarize(events, n_chips=1)
    want = rec["summary"]
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert 0 < s.busy_s < s.window_s
    for p in tracing.PHASES:
        assert s.phase_busy_s[p] == pytest.approx(want["phase_busy_s"][p],
                                                  rel=1e-12)
        assert s.phase_busy_s[p] <= s.phase_span_s[p] + 1e-12
    assert sum(s.phase_busy_s.values()) <= s.busy_s * (1 + 1e-12)
    assert [k for k, _ in s.device_ops] == [k for k, _ in want["device_ops"]]
    assert [v for _, v in s.device_ops] == pytest.approx(
        [v for _, v in want["device_ops"]], rel=1e-12)
    assert [k for k, _ in s.idle_gaps] == [k for k, _ in want["idle_gaps"]]
    assert [v for _, v in s.idle_gaps] == pytest.approx(
        [v for _, v in want["idle_gaps"]], rel=1e-12)
    # the busy time is at most the sum of the op durations inside it
    ops = [e for e in events if e.line == tracing.OPS_LINE]
    assert s.busy_s <= sum(e.dur_ns for e in ops) * 1e-9 * (1 + 1e-12)


def test_work_counts():
    assert [work.elimination_ops(n) for n in (1, 2, 3)] == [3, 13, 34]
    shape = {"samples": 156, "tasks": 2, "itemsize": 8, "dims": {
        1: {"screened": 606, "residuals": 1, "subspace": 200},
        3: {"screened": 206, "residuals": 10, "subspace": 600}}}
    ops, nbytes = work.sis_work(shape)
    assert ops == 606 * (2 * 156 + 3 * 156) + 206 * (20 * 156 + 3 * 156)
    assert nbytes == (606 + 206) * 156 * 8
    ops, _ = work.l0_work(shape)
    assert ops == 200 * 2 * 3 + 35_820_200 * 2 * 34


def test_peaks_table():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 1.97e14 and peak["bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    t, bound = work.roofline_seconds(1.97e14, 1.0, peak)
    assert (t, bound) == (1.0, "compute")
