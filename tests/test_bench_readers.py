"""The benchmark's readers of the program's spans and counters
(benchmarks/suite/metrics), on a synthetic run."""
import dataclasses
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks.suite import harness, tracing  # noqa: E402
from repro.runtime import trace  # noqa: E402

FALLBACK = "exact fp64 (window not certified)"
KERNEL = "Gram-gather kernel"


class Counted(tuple):
    """A fit run through the program's record that counted these
    ``(key, n)``; the readers find its counters among the recent fits."""


def _fit(spec):
    if not isinstance(spec, Counted):
        return harness.FitRecord(seconds=1.0, timings=spec, shape={},
                                 answers=None)
    with trace.collecting() as rec:
        for key, n in spec:
            trace.count(key, n)
    return harness.FitRecord(seconds=1.0,
                             timings={"fit": rec.seconds("sisso.fit")},
                             shape={}, answers=None)


def _programs(*lowered):
    return Counted(((("programs", f"sisso.s{i}", "lowered"), n)
                    for i, n in enumerate(lowered)))


def _paths(by_width):
    return Counted(((("l0_paths", w, path), n)
                    for w, paths in by_width.items()
                    for path, n in paths.items()))


def _sharded(devices, **gathered_rows):
    return Counted([(("sharded", "devices"), devices)]
                   + [(("sharded", "gathered_rows", phase), n)
                      for phase, n in gathered_rows.items()])


CASES = [
    ("descriptor_s", [{"descriptor": 0.5}, {"descriptor": 0.7}], 0.6),
    ("descriptor_s", [{"descriptor": 0.5}, {"fc": 1.0}], None),
    ("descriptor_s", [], None),
    ("l0_wait_s", [{"l0_wait": 2.0}, {"l0_wait": 4.0}], 3.0),
    ("l0_wait_s", [{"l0": 2.0}], None),
    ("programs_lowered", [_programs(3, 4), _programs(9)], 8.0),
    # a program that keeps no record of its fits reports no "fit" timing
    ("programs_lowered", [{"fc": 1.0}], None),
    ("programs_lowered", [_programs()], 0.0),
    ("l0_fp64_block_share", [
        _paths({"3": {KERNEL: 6, FALLBACK: 2},
                "2": {"closed-form pairs": 1}}),
        _paths({"3": {KERNEL: 8}})], 12.5),
    ("l0_fp64_block_share", [
        _paths({"1": {"jnp": 1}, "2": {"closed-form pairs": 3}})], None),
    ("l0_fp64_block_share", [Counted()], None),
    ("l0_fp64_block_share", [_paths({"4": {FALLBACK: 1}})], 100.0),
    ("fc_enumerate_s", [{"fc_enumerate": 1.5}, {"fc_enumerate": 2.5}], 2.0),
    # a program without the span
    ("fc_enumerate_s", [{"fc": 3.0}], None),
    ("sis_wait_s", [{"sis_wait": 0.25}, {"sis_wait": 0.75}], 0.5),
    # a stored space, or a program without the span
    ("sis_wait_s", [{"sis": 1.0}, {"sis_wait": 0.75}], None),
    ("collective_rows", [_sharded(4, sis=1600, l0=2048),
                         _sharded(4, sis=400, l0=2048)], 3048.0),
    # one device, or a program without the counter
    ("collective_rows", [_programs(3)], None),
    ("collective_rows", [{"fc": 1.0}], None),
    # no trace
    ("fused_sis_roofline", [_sharded(4, sis=400, l0=0)], None),
    # the newest record is not the window's fit
    ("programs_lowered", [_programs(3), {"fit": -1.0}], None),
    ("l0_fp64_block_share", [{"fit": -1.0}, _paths({"3": {KERNEL: 1}})],
     None),
]


@pytest.mark.parametrize(
    "metric,fits,want", CASES,
    ids=[f"{m}-{i}" for i, (m, _, _) in enumerate(CASES)])
def test_reader(metric, fits, want):
    run = harness.Run(setup_s=1.0, fits=[_fit(f) for f in fits], trace=None,
                      peak={})
    got = harness.load_reader(metric)(run)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


SHAPE = {"samples": 100, "tasks": 2, "itemsize": 8, "dims": {
    1: {"screened": 1000, "residuals": 1, "subspace": 10},
    2: {"screened": 990, "residuals": 10, "subspace": 20}}}
PEAK = {"flops_per_s": 1e12, "bytes_per_s": 1e9}


def _traced(fits, sis_busy):
    trace_summary = tracing.Summary(
        window_s=10.0, busy_s=1.0,
        phase_busy_s={"fc": 0.0, "sis": sis_busy, "l0": 0.0},
        phase_span_s={}, device_ops=[], idle_gaps=[])
    records = [dataclasses.replace(_fit(f), shape=SHAPE) for f in fits]
    return harness.Run(setup_s=1.0, fits=records, trace=trace_summary,
                       peak=PEAK)


@pytest.mark.parametrize("devices", [1, 4])
def test_fused_sis_roofline_counts_every_chip(devices):
    """The least time of one chip for the fits' SIS work, over the chips
    the fit sharded over times their mean busy time in the SIS spans."""
    run = _traced([_sharded(devices, sis=1), _sharded(devices, sis=1)],
                  sis_busy=2e-3)
    # bytes bound it: (1000 + 990) candidates x 100 samples x 8 bytes
    least = 2 * (1000 + 990) * 100 * 8 / PEAK["bytes_per_s"]
    got = harness.load_reader("fused_sis_roofline")(run)
    assert got == pytest.approx(100.0 * least / (devices * 2e-3))


@pytest.mark.parametrize("fits", [[{"fc": 1.0}], [_programs(3)]],
                         ids=["no-record", "no-counter"])
def test_fused_sis_roofline_without_the_counter(fits):
    run = _traced(fits, sis_busy=2e-3)
    assert harness.load_reader("fused_sis_roofline")(run) is None
