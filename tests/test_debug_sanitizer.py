"""Contract tests for the runtime sanitizer (src/repro/debug).

Two halves: deliberately broken fake backends must be *caught* at the
call boundary (bad sentinel, dropped coverage, NaN leak, malformed
shapes), and a healthy fit — regression and classification, reference
and pallas — must pass untouched with REPRO_DEBUG=1.
"""
import numpy as np
import pytest

from repro.core.sis import ReducedBlock, TaskLayout, build_score_context
from repro.core.solver import SissoConfig, SissoSolver
from repro.debug import (
    ContractViolation,
    DebugBackend,
    LEVEL_STRUCTURAL,
    LEVEL_VERIFY,
    maybe_wrap_engine,
    wrap_backend,
)
from repro.engine import get_engine
from repro.engine.base import Engine, ScoringBackend


class FakeBackend(ScoringBackend):
    """Minimal conformant backend the breakage fixtures subclass: it
    scores whole blocks and reduces them on the host unless a fixture
    overrides a reduced entry."""

    name = "fake"

    def eval_block(self, op_id, a, b, l_bound, u_bound):
        a = np.asarray(a, np.float64)
        return a.copy(), np.ones(a.shape[0], bool)

    def sis_scores(self, values, ctx):
        # deterministic, finite, shape-conformant
        return np.asarray(values, np.float64).sum(axis=1)

    def l0_scores(self, prob, tuples):
        return np.arange(np.shape(tuples)[0], dtype=np.float64)


def _ctx():
    return None  # fakes ignore the score context


def _values(b=6, s=4, seed=0):
    return np.random.default_rng(seed).normal(size=(b, s))


# ---------------------------------------------------------------------------
# broken backends are caught
# ---------------------------------------------------------------------------

def test_bad_sentinel_leak_is_caught():
    class BadSentinel(FakeBackend):
        def sis_topk(self, values, ctx, n_keep, mask=None):
            # +inf sentinel lane crosses the host boundary unfiltered
            return ReducedBlock(
                indices=np.array([0, 1], np.int64),
                scores=np.array([np.inf, 1.0]),
                n_source=np.shape(values)[0],
            )

    wrapped = DebugBackend(BadSentinel(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="sentinel"):
        wrapped.sis_topk(_values(), _ctx(), 2)


def test_padding_index_sentinel_is_caught():
    class BadIndex(FakeBackend):
        def sis_topk(self, values, ctx, n_keep, mask=None):
            return ReducedBlock(
                indices=np.array([-1, 1], np.int64),  # -1 padding leaked
                scores=np.array([2.0, 1.0]),
                n_source=np.shape(values)[0],
            )

    wrapped = DebugBackend(BadIndex(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="outside"):
        wrapped.sis_topk(_values(), _ctx(), 2)


def test_dropped_coverage_is_caught_at_verify_level():
    class DropsCoverage(FakeBackend):
        def sis_topk(self, values, ctx, n_keep, mask=None):
            # under-filled panel: k_epi < min(n_keep, n_valid) bug class
            return ReducedBlock(
                indices=np.array([0], np.int64),
                scores=np.asarray(values).sum(axis=1)[:1].astype(np.float64),
                n_source=np.shape(values)[0],
            )

    vals = np.ones((6, 4))
    wrapped = DebugBackend(DropsCoverage(), LEVEL_VERIFY)
    with pytest.raises(ContractViolation, match="coverage"):
        wrapped.sis_topk(vals, _ctx(), 3)
    # structural level cannot know the valid count — must stay silent
    assert len(DebugBackend(DropsCoverage(), LEVEL_STRUCTURAL)
               .sis_topk(vals, _ctx(), 3).indices) == 1


def test_wrong_winners_are_caught_at_verify_level():
    class WrongWinners(FakeBackend):
        def sis_topk(self, values, ctx, n_keep, mask=None):
            scores = np.asarray(values, np.float64).sum(axis=1)
            order = np.argsort(scores, kind="stable")[:n_keep]  # worst-k!
            return ReducedBlock(
                indices=order.astype(np.int64),
                scores=np.sort(scores)[::-1][:n_keep].copy(),
                n_source=len(scores),
            )

    wrapped = DebugBackend(WrongWinners(), LEVEL_VERIFY)
    with pytest.raises(ContractViolation, match="diverge"):
        wrapped.sis_topk(_values(), _ctx(), 2)


def test_nan_leak_in_scores_is_caught():
    class NanLeak(FakeBackend):
        def sis_scores(self, values, ctx):
            scores = np.asarray(values, np.float64).sum(axis=1)
            scores[0] = np.nan
            return scores

    wrapped = DebugBackend(NanLeak(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="NaN"):
        wrapped.sis_topk(_values(), _ctx(), 3)


def test_nan_leak_in_device_scores_is_caught_via_checkify():
    import jax.numpy as jnp

    class DeviceNanLeak(FakeBackend):
        def sis_scores(self, values, ctx):
            scores = jnp.asarray(values).sum(axis=1)
            return scores.at[0].set(jnp.nan)

    wrapped = DebugBackend(DeviceNanLeak(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="NaN"):
        wrapped.sis_topk(_values(), _ctx(), 3)


def test_neg_inf_in_l0_objective_is_caught():
    class NegInfL0(FakeBackend):
        def l0_scores(self, prob, tuples):
            scores = np.arange(np.shape(tuples)[0], dtype=np.float64)
            scores[1] = -np.inf  # "infinitely good" model
            return scores

    wrapped = DebugBackend(NegInfL0(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="-inf"):
        wrapped.l0_topk(None, np.zeros((4, 2), np.int32), 2)


def test_shape_mismatch_is_caught():
    class ShortScores(FakeBackend):
        def sis_scores(self, values, ctx):
            return np.zeros(np.shape(values)[0] - 1)

    wrapped = DebugBackend(ShortScores(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="shape"):
        wrapped.sis_topk(_values(), _ctx(), 3)


def test_invalid_rows_flagged_valid_are_caught():
    class LeakyEval(FakeBackend):
        def eval_block(self, op_id, a, b, l_bound, u_bound):
            vals = np.asarray(a, np.float64).copy()
            vals[0, 0] = np.inf  # non-finite row still flagged valid
            return vals, np.ones(vals.shape[0], bool)

    wrapped = DebugBackend(LeakyEval(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="valid"):
        wrapped.eval_block(0, _values(), _values(), 1e-5, 1e8)


def test_misordered_winners_are_caught():
    class Misordered(FakeBackend):
        def sis_topk(self, values, ctx, n_keep, mask=None):
            return ReducedBlock(
                indices=np.array([0, 1], np.int64),
                scores=np.array([1.0, 2.0]),  # ascending: not best-first
                n_source=np.shape(values)[0],
            )

    wrapped = DebugBackend(Misordered(), LEVEL_STRUCTURAL)
    with pytest.raises(ContractViolation, match="sorted"):
        wrapped.sis_topk(_values(), _ctx(), 2)


# ---------------------------------------------------------------------------
# healthy paths pass, wiring behaves
# ---------------------------------------------------------------------------

def _toy_problem(seed=0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.5, 3.0, (6, 48))
    y = 2.0 * x[1] * x[3] + 0.5
    return x, y, [f"f{i}" for i in range(6)]


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_healthy_regression_fit_passes_with_repro_debug(backend, monkeypatch):
    monkeypatch.setenv("REPRO_DEBUG", "1")
    x, y, names = _toy_problem()
    cfg = SissoConfig(max_rung=1, n_dim=2, n_sis=10, backend=backend)
    solver = SissoSolver(cfg)
    assert "debug[" in solver.engine.name
    fit = solver.fit(x, y, names)
    model = fit.best()
    assert np.isfinite(model.sse)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_healthy_classification_fit_passes_with_repro_debug(backend,
                                                            monkeypatch):
    monkeypatch.setenv("REPRO_DEBUG", "1")
    x, y, names = _toy_problem(seed=3)
    labels = (x[1] * x[3] > np.median(x[1] * x[3])).astype(np.float64)
    cfg = SissoConfig(max_rung=1, n_dim=2, n_sis=10, backend=backend,
                      problem="classification")
    fit = SissoSolver(cfg).fit(x, labels, names)
    assert fit.models_by_dim


def test_verify_level_full_fit_on_pallas(monkeypatch):
    # the strongest setting: every reduced top-k cross-checked against
    # the backend's own full-vector scorer
    monkeypatch.setenv("REPRO_DEBUG", "2")
    x, y, names = _toy_problem(seed=5)
    cfg = SissoConfig(max_rung=1, n_dim=2, n_sis=8, backend="pallas")
    fit = SissoSolver(cfg).fit(x, y, names)
    assert fit.best() is not None


def test_sanitizer_off_by_default(monkeypatch):
    monkeypatch.delenv("REPRO_DEBUG", raising=False)
    solver = SissoSolver(SissoConfig(backend="jnp"))
    assert "debug[" not in solver.engine.name


def test_config_debug_checks_forces_on(monkeypatch):
    monkeypatch.delenv("REPRO_DEBUG", raising=False)
    solver = SissoSolver(SissoConfig(backend="jnp", debug_checks=True))
    assert solver.engine.name == "debug[jnp]"


def test_config_debug_checks_false_overrides_env(monkeypatch):
    monkeypatch.setenv("REPRO_DEBUG", "1")
    solver = SissoSolver(SissoConfig(backend="jnp", debug_checks=False))
    assert "debug[" not in solver.engine.name


def test_wrap_is_idempotent_and_transparent():
    inner = get_engine("pallas").backend
    wrapped = wrap_backend(inner, LEVEL_STRUCTURAL)
    assert wrap_backend(wrapped, LEVEL_STRUCTURAL) is wrapped
    # capability flags read through
    assert wrapped.kernel_problems == inner.kernel_problems
    assert wrapped.fused_deferred == inner.fused_deferred
    assert wrapped.compute_dtype == inner.compute_dtype
    eng = maybe_wrap_engine(Engine(wrapped), True)
    assert eng.backend is wrapped  # no double wrap


def test_healthy_topk_roundtrip_through_engine():
    # a real reducing backend through the sanitized Engine facade
    eng = maybe_wrap_engine(Engine(get_engine("pallas").backend), True)
    rng = np.random.default_rng(7)
    values = rng.normal(size=(32, 24))
    y = rng.normal(size=24)
    layout = TaskLayout.single(24)
    ctx = build_score_context(y[None, :], layout)
    out = eng.sis_topk(values, ctx, 5)
    assert isinstance(out, ReducedBlock)
    assert len(out.indices) == 5
    assert np.isfinite(np.asarray(out.scores)).all()


def test_host_reduced_entries_match_the_inner_backend_bit_for_bit():
    """For a backend that reduces on the host, the sanitizer forms the full
    vector itself and reduces it the same way: the winners it returns are
    the inner backend's own, bit for bit."""
    from repro.core import operators as om

    inner = get_engine("jnp").backend
    wrapped = DebugBackend(inner, LEVEL_STRUCTURAL)
    rng = np.random.default_rng(11)
    values = rng.uniform(0.5, 3.0, (40, 24))
    ctx = build_score_context(rng.normal(size=(2, 24)), TaskLayout.single(24))
    mask = np.ones(40, bool)
    mask[[2, 9]] = False
    pairs = [
        (wrapped.sis_topk(values, ctx, 7, mask=mask),
         inner.sis_topk(values, ctx, 7, mask=mask)),
        (wrapped.sis_topk_deferred(om.DIV, values[:20], values[20:], ctx,
                                   1e-5, 1e8, 5),
         inner.sis_topk_deferred(om.DIV, values[:20], values[20:], ctx,
                                 1e-5, 1e8, 5)),
    ]
    prob = inner.prepare_l0(values[:8], values[8], TaskLayout.single(24))
    tuples = np.asarray([(i, j) for i in range(8) for j in range(i + 1, 8)],
                        np.int32)
    pairs.append((wrapped.l0_topk(prob, tuples, 6),
                  inner.l0_topk(prob, tuples, 6)))
    for got, want in pairs:
        assert np.array_equal(got.indices, want.indices)
        assert np.array_equal(got.scores, want.scores)
        assert got.n_source == want.n_source


def test_verify_level_checks_a_distribution_wrapper_against_reference():
    """A wrapper that forms no full vector of its own (the sharded
    execution layer) is cross-checked at verify level against the
    reference oracle on the same operands — and a wrong winner from such
    a wrapper is caught."""
    from repro.core import operators as om
    from repro.engine import ShardedExecution

    sharded = ShardedExecution(inner="jnp")
    wrapped = DebugBackend(sharded, LEVEL_VERIFY)
    rng = np.random.default_rng(12)
    values = rng.uniform(0.5, 3.0, (30, 24))
    ctx = build_score_context(rng.normal(size=(1, 24)), TaskLayout.single(24))
    assert len(wrapped.sis_topk(values, ctx, 6)) == 6
    assert len(wrapped.sis_topk_deferred(om.MUL, values[:15], values[15:],
                                         ctx, 1e-5, 1e8, 4)) == 4
    x = values[:7]
    y = 1.5 * x[2] - x[5] + 0.01 * rng.normal(size=24)
    prob = wrapped.prepare_l0(x, y, TaskLayout.single(24))
    tuples = np.asarray([(i, j) for i in range(7) for j in range(i + 1, 7)],
                        np.int32)
    rb = wrapped.l0_topk(prob, tuples, 4)
    assert tuple(tuples[rb.indices[0]]) == (2, 5)

    class SwapsWinners(ShardedExecution):
        def sis_topk(self, values, ctx, n_keep, mask=None):
            rb = super().sis_topk(values, ctx, n_keep, mask=mask)
            return ReducedBlock(indices=rb.indices[::-1].copy(),
                                scores=rb.scores, n_source=rb.n_source)

    bad = DebugBackend(SwapsWinners(inner="jnp"), LEVEL_VERIFY)
    with pytest.raises(ContractViolation, match="wrong candidates"):
        bad.sis_topk(values, ctx, 6)
