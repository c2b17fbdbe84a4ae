"""The run of each cell, and the comparison that decides ``correct``, on
the CPU at a tiny size (fewer samples and SIS features than the cells).

* each cell runs end to end and comes out correct, its result line naming
  the device and its metrics;
* the control, the reference computed one precision lower than the
  configuration states and put in the program's place, comes out not
  correct;
* a run whose timed path is broken underneath comes out not correct, once
  for each fault such a cell can have: SIS leaves half of its batch out, a
  dimension screens against an unchanged state, an ℓ0 answer is altered
  where it is produced.  (The cells run on one chip: there is no exchange
  between chips to leave out.)

Slow (the Pallas kernels run in interpret mode): a few minutes.
"""
import dataclasses
import time

import numpy as np
import pytest

from benchmarks.suite import compare, harness
from benchmarks.suite.data import make_data

CELLS = ("thermal.d3-r1", "kaggle.d2-r1")
SEED = 2_147_483_659


def tiny(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    per_task = cell.config["samples_per_task"]
    cell.config = dict(cell.config,
                       samples_per_task=[max(16, n // 8) for n in per_task])
    cell.settings = {**cell.config, **cell.traffic, "n_sis": 12}
    return cell


def run(cell: harness.Cell) -> dict:
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                            need_chip=False)


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_and_is_correct(name):
    out = run(tiny(name))
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"fit_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    assert list(out)[-1] == "checks" and out["checks"]


def test_traced_run_reads_the_per_layer_metrics(monkeypatch):
    """The traced path end to end; on the CPU no TPU plane exists, so the
    device readings are absent or zero and only the spans' timings read."""
    from benchmarks.suite import work

    monkeypatch.setattr(work, "peaks", lambda kind: {
        "flops_per_s": 1.97e14, "bytes_per_s": 8.19e11})
    out = harness.run_cell(tiny(CELLS[0]), SEED, 0.5, True,
                           time.perf_counter(), need_chip=False)
    assert out["correct"], out["checks"]
    assert {"fc_s", "sis_s", "l0_s"} <= set(out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_machine_without_a_tpu_is_refused():
    with pytest.raises(harness.NoChip):
        harness.check_device(1)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny(name)
    data = make_data(cell.config, SEED)
    ref = harness.reference_campaign(cell, data)
    ctl = harness.reference_campaign(cell, data, **{
        "store": cell.config["control"]["store"],
        "compute": cell.config["control"]["compute"]})
    j = compare.Judge(ref, data.x, data.y, data.names, data.task_slices)
    readings = j.readings(compare.control_answers(ctl))
    assert any(readings[k] > lim for k, lim in cell.limits.items()), readings
    # and the reference, put in the same place, reads as correct
    same = j.readings(compare.control_answers(ref))
    assert all(same[k] <= lim for k, lim in cell.limits.items()), same


def _half_batch(monkeypatch):
    from repro.engine.pallas_backend import PallasBackend

    real = PallasBackend.sis_topk

    def half(self, values, ctx, n_keep, mask=None):
        keep = np.arange(len(values)) % 2 == 0
        if mask is not None:
            keep &= np.asarray(mask, bool)
        return real(self, values, ctx, n_keep, mask=keep)

    monkeypatch.setattr(PallasBackend, "sis_topk", half)


def _state_unchanged(monkeypatch):
    from repro.core.problem import RegressionProblem

    monkeypatch.setattr(RegressionProblem, "update_state",
                        lambda self, y, layout, models, values_of:
                        np.asarray(y, np.float64)[None, :])


def _answer_altered(monkeypatch):
    import repro.core.solver as solver

    real = solver.l0_search

    def altered(*args, **kwargs):
        res = real(*args, **kwargs)
        tuples = np.array(res.tuples)
        tuples[0] = tuples[-1]          # the best model is another one
        return dataclasses.replace(res, tuples=tuples)

    monkeypatch.setattr(solver, "l0_search", altered)


FAULTS = {"half_batch": _half_batch, "state_unchanged": _state_unchanged,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(monkeypatch, name, fault):
    FAULTS[fault](monkeypatch)
    out = run(tiny(name))
    assert not out["correct"], out["checks"]
