"""Fault injection, journal v2 crash consistency, leases, and the
resilient execution wrapper (runtime/faults.py, runtime/journal.py,
engine/resilient.py)."""
import json
import os

import numpy as np
import pytest

from repro.core.l0 import l0_search
from repro.core.sis import TaskLayout
from repro.core.solver import SissoConfig, SissoSolver
from repro.engine import Engine, get_engine
from repro.engine.resilient import ResilientExecution, wrap_engine_resilient
from repro.runtime import (
    FaultPlan, KernelFailure, LeaseTable, TransientDeviceError, WorkJournal,
    faults, merge_block_results,
)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.install(None)


# ---------------------------------------------------------------------------
# FaultPlan: selectors, parsing, delivery
# ---------------------------------------------------------------------------

def test_fault_plan_occurrence_selectors():
    p = FaultPlan.parse("a:err@2;b:kill@3+;c:nan@2-4;d:fatal;e:torn~0.5")
    assert [p.fire("a") for _ in range(4)] == [None, "err", None, None]
    assert [p.fire("b") for _ in range(4)] == [None, None, "kill", "kill"]
    assert [p.fire("c") for _ in range(5)] == [None, "nan", "nan", "nan", None]
    assert p.fire("d") == "fatal" and p.fire("d") == "fatal"  # '*' default
    assert p.fire("unwired") is None
    assert p.occurrences("a") == 4
    assert p.fired_at("a") == 1 and p.fired_at("b", "kill") == 2

    # probabilistic triggers replay identically for the same seed
    seq = [FaultPlan.parse("e:torn~0.5", seed=7).fire("e") is not None
           for _ in range(1)]
    p1 = FaultPlan.parse("e:torn~0.5", seed=7)
    p2 = FaultPlan.parse("e:torn~0.5", seed=7)
    seq1 = [p1.fire("e") for _ in range(50)]
    seq2 = [p2.fire("e") for _ in range(50)]
    assert seq1 == seq2
    assert any(seq1) and not all(k == "torn" for k in seq1)
    del seq

    with pytest.raises(ValueError):
        FaultPlan().add("x", "segfault")
    with pytest.raises(ValueError):
        FaultPlan.parse("missing-colon-clause")


def test_check_delivers_raising_kinds():
    faults.install(FaultPlan().add("t", "err", at=1).add("t", "fatal", at=2)
                   .add("t", "nan", at=3))
    with pytest.raises(TransientDeviceError) as ei:
        faults.check("t")
    assert ei.value.site == "t" and ei.value.occurrence == 1
    with pytest.raises(KernelFailure):
        faults.check("t")
    assert faults.check("t") == "nan"
    assert faults.check("t") is None  # past every trigger
    faults.install(None)
    assert faults.check("t") is None  # no plan: no-op


def test_env_spec_activates_plan(monkeypatch):
    faults.install(None)
    monkeypatch.setenv("REPRO_FAULTS", "env.site:nan@1")
    assert faults.check("env.site") == "nan"
    assert faults.check("env.site") is None  # counters persist (cached plan)
    monkeypatch.delenv("REPRO_FAULTS")
    assert faults.active_plan() is None


# ---------------------------------------------------------------------------
# journal v2: torn writes, .bak fallback, v1 migration, checksums
# ---------------------------------------------------------------------------

def _panels():
    return (np.asarray([0.5, 1.5]), np.asarray([[0, 1], [2, 3]]))


def test_torn_write_restores_from_bak(tmp_path):
    path = str(tmp_path / "j.json")
    j = WorkJournal(path)
    j.record(3, *_panels(), meta={"sweep": 1})
    faults.install(FaultPlan().add("journal.write", "torn", at=1))
    j.record(4, np.asarray([0.1, 0.2]), np.asarray([[4, 5], [6, 7]]),
             meta={"sweep": 1})
    faults.install(None)
    # the current file is torn mid-JSON; a fresh reader must fall back
    with pytest.raises(ValueError):
        json.load(open(path))
    j2 = WorkJournal(path)
    assert j2.has_state()
    sse, tuples, nxt = j2.restore()
    assert nxt == 3 and j2.recovered_from_bak
    np.testing.assert_array_equal(sse, _panels()[0])
    # a post-recovery record writes a good generation again
    j2.record(4, *_panels(), meta={"sweep": 1})
    j3 = WorkJournal(path)
    assert j3.restore()[2] == 4 and not j3.recovered_from_bak


def test_torn_write_without_bak_reads_as_absent(tmp_path):
    j = WorkJournal(str(tmp_path / "j.json"))
    faults.install(FaultPlan().add("journal.write", "torn", at=1))
    j.record(2, *_panels())
    faults.install(None)
    j2 = WorkJournal(j.path)
    assert not j2.has_state()  # restart cleanly, don't crash


def test_checksum_rejects_bitrot(tmp_path):
    j = WorkJournal(str(tmp_path / "j.json"))
    j.record(5, *_panels())
    with open(j.path) as f:
        doc = json.load(f)
    doc["payload"]["next_block"] = 9  # flip state without updating sha1
    with open(j.path, "w") as f:
        json.dump(doc, f)
    j2 = WorkJournal(j.path)
    assert not j2.has_state()  # no .bak: corrupt current reads as absent


def test_v1_journal_migrates_to_v2(tmp_path):
    path = str(tmp_path / "v1.json")
    with open(path, "w") as f:
        json.dump({"kind": "blocks", "next_block": 6, "best_sse": [1.0],
                   "best_tuples": [[0, 2]], "reissues": 3}, f)
    j = WorkJournal(path)
    assert j.has_state()
    sse, tuples, nxt = j.restore()
    assert nxt == 6 and j.journal_version == 1 and j.reissues == 3
    assert j.meta is None  # v1 carries no sweep signature: fail closed
    j.record(7, *_panels(), meta={"sweep": 1})  # upgrade on next record
    j2 = WorkJournal(path)
    j2.restore()
    assert j2.journal_version == 2


def test_elastic_state_roundtrip(tmp_path):
    j = WorkJournal(str(tmp_path / "e.json"))
    table = LeaseTable(4, ttl=30.0)
    table.next_unit("w0", now=0.0)
    table.ack(0, "w0")
    table.next_unit("w1", now=1.0)
    results = {0: _panels()}
    j.record_elastic(table, results, meta={"sweep": 2})
    t2, r2 = WorkJournal(j.path).restore_elastic()
    assert t2.acked == {0} and t2.outstanding() == [1]
    assert t2.leases[1]["worker"] == "w1"
    np.testing.assert_array_equal(r2[0][0], results[0][0])
    np.testing.assert_array_equal(r2[0][1], results[0][1])


# ---------------------------------------------------------------------------
# LeaseTable: expiry, reissue accounting, idempotent ack
# ---------------------------------------------------------------------------

def test_lease_expiry_and_reissue_accounting():
    t = LeaseTable(3, ttl=10.0)
    assert t.next_unit("w0", now=0.0) == 0
    assert t.next_unit("w1", now=0.0) == 1
    assert t.next_unit("w2", now=0.0) == 2
    # everything leased and live: nothing issuable
    assert t.next_unit("w3", now=5.0) is None and t.reissues == 0
    # w0's lease expires: unit 0 reissues, and only that one
    assert t.next_unit("w3", now=11.0) == 0
    assert t.reissues == 1
    # idempotent ack: first ack True, duplicates False and uncounted
    assert t.ack(0, "w3") and not t.ack(0, "w0")
    assert t.next_unit("w0", now=11.0) == 1 and t.reissues == 2
    t.ack(1)
    t.ack(2)
    assert t.done and t.outstanding() == []


def test_release_worker_reissues_without_waiting_out_ttl():
    t = LeaseTable(2, ttl=1e9)
    t.next_unit("w0", now=0.0)
    t.next_unit("w1", now=0.0)
    assert t.release_worker("w0") == [0]
    assert t.next_unit("w1", now=1.0) == 0 and t.reissues == 1


def test_merge_block_results_matches_l0_search():
    rng = np.random.default_rng(3)
    x = rng.uniform(0.5, 3.0, (10, 40))
    y = 1.2 * x[1] - 0.5 * x[6] + rng.normal(0, 0.05, 40)
    layout = TaskLayout.single(40)
    ref = l0_search(x, y, layout, n_dim=2, n_keep=6, block=8,
                    engine="reference")
    eng = get_engine("reference")
    prob = eng.prepare_l0(x, y, layout)
    from repro.core.l0 import TupleEnumerator
    enum = TupleEnumerator(10, 2, 8)
    results = {}
    for bi in range(enum.n_blocks):
        tuples = np.asarray(enum.block_tuples(bi))
        rb = eng.l0_topk(prob, tuples, 6)
        results[bi] = (rb.scores, tuples[rb.indices].astype(np.int64))
    sse, tuples = merge_block_results(results, 6)
    np.testing.assert_array_equal(sse, ref.sses)
    np.testing.assert_array_equal(tuples, ref.tuples)


# ---------------------------------------------------------------------------
# fault sites threaded through the sweep loop
# ---------------------------------------------------------------------------

def _sweep_case():
    rng = np.random.default_rng(11)
    x = rng.uniform(0.5, 3.0, (8, 32))
    y = 0.8 * x[0] + 1.1 * x[3] + rng.normal(0, 0.02, 32)
    return x, y, TaskLayout.single(32)


def test_nan_score_panel_is_scrubbed_not_propagated():
    x, y, layout = _sweep_case()
    ref = l0_search(x, y, layout, n_dim=2, n_keep=4, block=8, engine="jnp")
    # one block's score panel comes back all-NaN (faulted device): the
    # merge must rank it last, not poison the top-k with NaN ordering
    faults.install(FaultPlan().add("l0.block_scores", "nan", at=2))
    res = l0_search(x, y, layout, n_dim=2, n_keep=4, block=8, engine="jnp")
    faults.install(None)
    assert np.isfinite(res.sses).all()
    # block 2 of C(8,2)=28 in blocks of 8 holds ranks 8..15; unless a true
    # winner lived there the top-k is unchanged — assert no NaN leaked and
    # every reported winner is a genuinely scored tuple
    assert res.n_evaluated == ref.n_evaluated


def test_block_scores_err_surfaces_without_resilient_wrapper():
    x, y, layout = _sweep_case()
    faults.install(FaultPlan().add("l0.block_scores", "err", at=1))
    with pytest.raises(TransientDeviceError):
        l0_search(x, y, layout, n_dim=2, n_keep=4, block=8, engine="jnp")


def test_prefetch_fetch_fault_reraised_in_order():
    x, y, layout = _sweep_case()
    faults.install(FaultPlan().add("prefetch.fetch", "err", at=2))
    with pytest.raises(TransientDeviceError) as ei:
        l0_search(x, y, layout, n_dim=2, n_keep=4, block=8, engine="jnp")
    assert ei.value.site == "prefetch.fetch"


# ---------------------------------------------------------------------------
# ResilientExecution: retry, backoff bounds, demotion, pass-through
# ---------------------------------------------------------------------------

def _fast_resilient(inner="jnp", **kw):
    kw.setdefault("base_delay", 1e-4)
    kw.setdefault("max_delay", 1e-3)
    return ResilientExecution(inner=inner, **kw)


def _l0_case(eng):
    rng = np.random.default_rng(5)
    x = rng.uniform(0.5, 3.0, (6, 24))
    y = 2.0 * x[1] - x[4]
    prob = eng.prepare_l0(x, y, TaskLayout.single(24))
    tuples = np.asarray([[1, 4], [0, 2], [3, 5]], np.int32)
    return prob, tuples


def test_transient_errors_retry_then_succeed():
    be = _fast_resilient()
    eng = Engine(be)
    prob, tuples = _l0_case(eng)
    want = eng.l0_topk(prob, tuples, 3)
    faults.install(FaultPlan().add("l0.block_scores", "err", at=1, upto=2))
    calls = {"n": 0}
    inner_topk = be._inner.l0_topk

    def flaky(prob, tuples, n_keep):
        calls["n"] += 1
        faults.check("l0.block_scores")
        return inner_topk(prob, tuples, n_keep)

    be._inner.l0_topk = flaky
    out = be.l0_topk(prob, tuples, 3)
    np.testing.assert_array_equal(out.indices, want.indices)
    np.testing.assert_array_equal(out.scores, want.scores)
    assert calls["n"] == 3  # 2 transient failures + 1 success
    assert be.fault_stats["retries"] == 2
    assert be.fault_stats["demotions"] == {}


def test_exhausted_retries_demote_then_complete():
    be = _fast_resilient(max_attempts=2)
    eng = Engine(be)
    prob, tuples = _l0_case(eng)
    ref = get_engine("reference")
    want = ref.l0_topk(ref.prepare_l0(prob.x, prob.y, prob.layout),
                       tuples, 3)

    def always_down(prob, tuples, n_keep):
        raise TransientDeviceError("l0.block_scores", 1)

    be._inner.l0_topk = always_down
    out = be.l0_topk(prob, tuples, 3)
    np.testing.assert_array_equal(out.indices, want.indices)
    np.testing.assert_allclose(out.scores, want.scores, rtol=1e-9)
    st = be.fault_stats
    assert st["retries"] == 1  # max_attempts=2 -> one in-place retry
    assert st["demotions"]["l0_topk"] >= 1
    assert st["active_backend"]["l0_topk"] in ("jnp", "reference")


def test_programming_errors_neither_retried_nor_demoted():
    be = _fast_resilient()
    prob, tuples = _l0_case(Engine(be))

    def buggy(prob, tuples, n_keep):
        raise ValueError("contract violation")

    be._inner.l0_topk = buggy
    with pytest.raises(ValueError):
        be.l0_topk(prob, tuples, 3)
    assert be.fault_stats == {
        "retries": 0, "demotions": {}, "active_backend": {}}


def test_backoff_is_capped_and_jittered():
    be = _fast_resilient(base_delay=0.1, max_delay=0.3, jitter=0.5)
    delays = [be._backoff(a) for a in range(1, 6)]
    for a, d in enumerate(delays, start=1):
        base = min(0.3, 0.1 * 2 ** (a - 1))
        assert base <= d <= base * 1.5
    assert max(delays) <= 0.45  # cap * (1 + jitter)


def test_nested_resilient_rejected_and_wrap_idempotent():
    eng = get_engine("resilient:jnp")
    assert eng.name == "resilient[jnp]"
    with pytest.raises(ValueError):
        ResilientExecution(inner=eng.backend)
    assert wrap_engine_resilient(eng) is eng


def test_resilient_fit_demotes_broken_pallas_kernel():
    """A pallas fit whose ℓ0 kernels persistently fail (fatal at the
    kernel.l0 site, below the wrapper) must complete on the fallback
    backend and surface the demotion in fit stats."""
    rng = np.random.default_rng(2)
    x = rng.uniform(0.5, 3.0, (4, 96))
    y = 3.0 * x[0] * x[2] + 0.05 * rng.normal(size=96)
    base = dict(max_rung=1, n_dim=2, n_sis=10, n_residual=3,
                op_names=("add", "mul", "sq"), on_the_fly_last_rung=True)
    fit_ref = SissoSolver(SissoConfig(**base)).fit(x, y, list("abcd"))
    faults.install(FaultPlan().add("kernel.l0", "fatal"))
    fit = SissoSolver(SissoConfig(backend="pallas", resilient=True,
                                  **base)).fit(x, y, list("abcd"))
    faults.install(None)
    res = fit.stats["resilience"]
    assert res["demotions"], res
    assert all(be in ("jnp", "reference")
               for be in res["active_backend"].values())
    mr, mk = fit_ref.best(2), fit.best(2)
    assert {f.expr for f in mr.features} == {f.expr for f in mk.features}
    assert mk.sse == pytest.approx(mr.sse, rel=1e-6)


def test_resilient_spec_composes_with_sharded():
    eng = get_engine("resilient:sharded:jnp")
    assert eng.name == "resilient[sharded]"
    # transparency: the inner's capabilities read through
    assert eng.backend.kernel_problems == eng.backend._inner.kernel_problems
    assert eng.backend.l0_widths == eng.backend._inner.l0_widths


# ---------------------------------------------------------------------------
# serving validation (api/serving.py satellite)
# ---------------------------------------------------------------------------

def _tiny_server():
    from repro.api import SissoRegressor
    from repro.api.serving import SissoServer

    rng = np.random.default_rng(0)
    X = rng.uniform(0.5, 3.0, (40, 3))
    y = 2.0 * X[:, 0] + X[:, 1]
    reg = SissoRegressor(max_rung=1, n_dim=1, n_sis=5,
                         op_names=("add", "mul")).fit(X, y)
    return SissoServer(reg.fitted_), X


def test_serving_rejects_malformed_batches():
    server, X = _tiny_server()
    server.predict(X[:5])
    assert server.stats["rejected"] == 0

    with pytest.raises(ValueError, match="rejected request batch"):
        server.predict(X[:4, :2])  # wrong feature width
    with pytest.raises(ValueError, match="non-finite"):
        bad = X[:4].copy()
        bad[2, 1] = np.nan
        server.predict(bad)
    with pytest.raises(ValueError, match="non-numeric"):
        server.predict([["a", "b", "c"]])
    stats = server.stats
    assert stats["rejected"] == 3
    assert stats["requests"] == 1  # rejected batches never count as served
