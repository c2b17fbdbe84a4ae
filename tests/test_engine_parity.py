"""Backend parity suite: every phase, every backend, vs the reference oracle.

The engine layer's contract (ISSUE 1 / ARCHITECTURE.md) is that screening
math behaves identically on every backend: same validity masks, same SIS
top-k, same ℓ0 winners (within fp32 score tolerance on the Pallas path).
All on the thermal reduced case — multi-task, on-the-fly deferred last rung
— plus synthetic single-task layouts.
"""
import numpy as np
import pytest

from repro.configs.sisso_thermal import thermal_conductivity_case
from repro.core import SissoConfig, SissoSolver, compile_features, \
    operators as om
from repro.core.feature_space import FeatureSpace
from repro.core.l0 import l0_search
from repro.core.sis import TaskLayout, build_score_context, sis_screen
from repro.engine import BACKENDS, Engine, get_engine

DEVICE_BACKENDS = ["jnp", "pallas", "sharded", "sharded:pallas"]
ALL_BACKENDS = ["reference"] + DEVICE_BACKENDS


@pytest.fixture(scope="module")
def case():
    return thermal_conductivity_case(reduced=True)


def _fspace(case):
    cfg = case.config
    return FeatureSpace(
        case.x, case.names, case.units, op_names=cfg.op_names,
        max_rung=cfg.max_rung, l_bound=cfg.l_bound, u_bound=cfg.u_bound,
        on_the_fly_last_rung=True,
    ).generate()


def test_registry_has_all_backends():
    assert set(BACKENDS) == {
        "reference", "jnp", "pallas", "sharded", "resilient"
    }
    for name in BACKENDS:
        eng = get_engine(name)
        assert isinstance(eng, Engine)
        if name == "resilient":
            # the fault-tolerance wrapper names its (default jnp) inner
            assert eng.name == "resilient[jnp]"
        else:
            assert eng.name == name
    with pytest.raises(ValueError):
        get_engine("cuda")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_eval_block_validity_parity(rng, backend):
    """Canonical value rules agree on every backend, including the cases
    that historically split host vs kernel semantics."""
    s = 64
    ids = np.repeat([0, 1], s // 2)
    x = np.stack([
        rng.uniform(0.5, 3.0, s),          # plain valid
        np.linspace(-1.0, 1.0, s),         # straddles zero (div -> inf)
        np.full(s, 2.0),                   # zero variance everywhere
        np.where(ids == 0, 1.0, 2.0),      # constant per task, varies across
        rng.uniform(1e5, 1e6, s),          # mul -> exceeds u_bound=1e8? no
        rng.uniform(1e7, 1e8, s),          # mul -> exceeds u_bound
    ])
    ia = np.array([0, 1, 2, 3, 4, 5])
    ib = np.array([0, 0, 2, 3, 4, 5])
    ref = get_engine("reference")
    v_ref, m_ref = ref.eval_block(om.DIV, x[ia], x[ib], 1e-5, 1e8)
    eng = get_engine(backend)
    v, m = eng.eval_block(om.DIV, x[ia], x[ib], 1e-5, 1e8)
    assert np.array_equal(m, m_ref)
    np.testing.assert_allclose(v[m], v_ref[m_ref], rtol=1e-12)
    # the per-task-constant row must be treated the same way everywhere
    v_ref2, m_ref2 = ref.eval_block(om.MUL, x[[3]], x[[3]], 1e-5, 1e8)
    v2, m2 = eng.eval_block(om.MUL, x[[3]], x[[3]], 1e-5, 1e8)
    assert np.array_equal(m2, m_ref2)
    assert m2[0]  # varies across tasks => whole-sample variance is real


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_sis_topk_parity_thermal(case, backend):
    """Identical SIS top-k (materialized + deferred candidates, multi-task)."""
    layout = TaskLayout.from_task_ids(case.task_ids)
    f_ref, s_ref = sis_screen(
        _fspace(case), case.y[None, :], layout, n_sis=25, exclude=set(),
        engine=get_engine("reference"),
    )
    f_b, s_b = sis_screen(
        _fspace(case), case.y[None, :], layout, n_sis=25, exclude=set(),
        engine=get_engine(backend),
    )
    assert [f.expr for f in f_b] == [f.expr for f in f_ref]
    np.testing.assert_allclose(s_b, s_ref, atol=5e-5)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_sis_scores_parity_single_task(rng, backend):
    """Raw block scores agree on a single-task, multi-residual layout."""
    x = rng.uniform(0.5, 3.0, (60, 100))
    resid = rng.normal(size=(4, 100))
    ctx = build_score_context(resid, TaskLayout.single(100))
    ref = get_engine("reference").sis_scores(x, ctx)
    got = get_engine(backend).sis_scores(x, ctx)
    np.testing.assert_allclose(got, ref, atol=1e-7)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_sis_deferred_parity(case, backend):
    """Fused / composed deferred-candidate scoring matches eval+score."""
    fs = _fspace(case)
    layout = TaskLayout.from_task_ids(case.task_ids)
    ctx = build_score_context(case.y[None, :], layout)
    x = fs.values_matrix().astype(np.float64)
    ref = get_engine("reference")
    eng = get_engine(backend)
    blk = next(fs.iter_candidate_batches(512))
    want = ref.sis_scores_deferred(
        blk.op_id, x[blk.child_a], x[blk.child_b], ctx, fs.l_bound, fs.u_bound)
    got = eng.sis_scores_deferred(
        blk.op_id, x[blk.child_a], x[blk.child_b], ctx, fs.l_bound, fs.u_bound)
    assert np.array_equal(np.isfinite(got), np.isfinite(want))
    f = np.isfinite(want)
    np.testing.assert_allclose(got[f], want[f], atol=5e-5)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("width", [1, 2, 3, 4])
def test_l0_scores_parity(rng, backend, width):
    """Per-tuple SSE matches the lstsq oracle for every tuple width.

    Widths 2–4 are native kernels on pallas (pair gathers + the blocked
    Gram-gather kernel); width 1 and everything ≥ 3 on sharded exercise
    the generic jnp delegation.  The suite's tuple counts sit inside the
    pallas backend's rescore window, so its values here are the exact
    fp64 phase-2 numbers — which is the bit-exactness contract the
    ℓ0 top-k merge relies on (m chosen so C(m, 4) < rescore_k)."""
    m, s = 12, 156
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * x[3] - 1.0 * x[7] + 0.1 * rng.normal(size=s)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], [75, 81]))
    tuples = np.asarray(
        list(__import__("itertools").combinations(range(m), width)), np.int32)
    ref = get_engine("reference")
    want = ref.l0_scores(ref.prepare_l0(x, y, layout), tuples)
    eng = get_engine(backend)
    got = eng.l0_scores(eng.prepare_l0(x, y, layout), tuples)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)
    assert np.argmin(got) == np.argmin(want)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("width", [3, 4])
def test_l0_search_ranking_parity_wide(rng, backend, width):
    """Full ℓ0 sweeps at widths 3/4: the final top-k tuples must be
    *bit-identical* to reference (and SSEs numerically equal) through the
    device enumerator + streaming loop + per-backend scoring."""
    m, s = 12, 80
    x = rng.uniform(0.5, 3.0, (m, s))
    y = (1.5 * x[5] - 2.5 * x[9] + 0.8 * x[2]
         + 0.4 * rng.normal(size=s))
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], 40))
    ref = l0_search(x, y, layout, n_dim=width, n_keep=7, block=61,
                    engine=get_engine("reference"))
    res = l0_search(x, y, layout, n_dim=width, n_keep=7, block=61,
                    engine=get_engine(backend))
    assert np.array_equal(res.tuples, ref.tuples)
    np.testing.assert_allclose(res.sses, ref.sses, rtol=1e-6, atol=1e-8)
    assert res.n_evaluated == ref.n_evaluated


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("method", ["gram", "qr"])
def test_l0_search_winners_parity(rng, backend, method):
    m, s = 24, 80
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 1.5 * x[5] - 2.5 * x[16] + 0.9
    res = l0_search(x, y, TaskLayout.single(s), n_dim=2, n_keep=5,
                    block=97, method=method, engine=get_engine(backend))
    assert tuple(res.tuples[0]) == (5, 16)
    assert res.sses[0] < 1e-6


@pytest.mark.parametrize("backend", ["pallas", "sharded:pallas"])
def test_sis_reduced_block_matches_full_reduction(case, backend):
    """The reduced-epilogue deferred screen must return exactly the
    ReducedBlock a host reduction of the full score vector yields — same
    winners, same order, same tie resolution — without ever materializing
    that vector on the kernel backends."""
    from repro.core.sis import ReducedBlock

    fs = _fspace(case)
    layout = TaskLayout.from_task_ids(case.task_ids)
    ctx = build_score_context(case.y[None, :], layout)
    x = fs.values_matrix().astype(np.float64)
    eng = get_engine(backend)
    assert eng.backend.reduces_blocks
    blk = next(fs.iter_candidate_batches(512))
    full = get_engine("reference").sis_scores_deferred(
        blk.op_id, x[blk.child_a], x[blk.child_b], ctx, fs.l_bound, fs.u_bound)
    want = ReducedBlock.reduce_host(full, 25)
    got = eng.backend.sis_topk_deferred(
        blk.op_id, x[blk.child_a], x[blk.child_b], ctx, fs.l_bound,
        fs.u_bound, 25)
    assert np.array_equal(got.indices, want.indices)
    np.testing.assert_allclose(got.scores, want.scores, atol=5e-5)
    assert got.n_source == len(blk.child_a)
    assert len(got.indices) <= 25  # O(k) payload, not O(B)


@pytest.mark.parametrize("backend", ["pallas", "sharded:pallas"])
@pytest.mark.parametrize("width", [3, 5])
def test_l0_reduced_block_matches_full_reduction(rng, backend, width):
    """ℓ0 reduced top-k (device epilogue + merge + fp64 rescore) returns the
    stable-sort winners of the full SSE vector with fp64-exact values."""
    import itertools

    m, s = 11, 90
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * x[3] - x[7] + 0.1 * rng.normal(size=s)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], 45))
    tuples = np.asarray(list(itertools.combinations(range(m), width)),
                        np.int32)
    ref = get_engine("reference")
    full = ref.l0_scores(ref.prepare_l0(x, y, layout), tuples)
    order = np.argsort(full, kind="stable")[:8]
    eng = get_engine(backend)
    prob = eng.backend.prepare_l0(x, y, layout)
    got = eng.backend.l0_topk(prob, tuples, 8)
    assert np.array_equal(got.indices, order)
    # fp64 Gram rescore vs the lstsq oracle: same precision, different
    # factorization — agreement to fp64 conditioning, not bitwise
    np.testing.assert_allclose(got.scores, full[order], rtol=1e-6)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_l0_search_ranking_parity_width5(rng, backend):
    """Full ℓ0 sweep at width 5 (the generalized Gram-gather kernel on the
    pallas backends, generic scorers elsewhere): bit-identical winners."""
    m, s = 10, 70
    x = rng.uniform(0.5, 3.0, (m, s))
    y = (1.2 * x[1] - 2.0 * x[4] + 0.7 * x[8] + 0.5 * x[2]
         + 0.3 * rng.normal(size=s))
    layout = TaskLayout.single(s)
    ref = l0_search(x, y, layout, n_dim=5, n_keep=6, block=53,
                    engine=get_engine("reference"))
    res = l0_search(x, y, layout, n_dim=5, n_keep=6, block=53,
                    engine=get_engine(backend))
    assert np.array_equal(res.tuples, ref.tuples)
    np.testing.assert_allclose(res.sses, ref.sses, rtol=1e-6, atol=1e-8)


def test_bf16_sis_winner_set_tolerance(case):
    """bf16 SIS screening: the winner *set* stays within a 2x-margin
    superset of the fp64 winners (exact ranking is not promised — the
    dtype-policy table documents the bf16 screen as approximate)."""
    layout = TaskLayout.from_task_ids(case.task_ids)
    f64, _ = sis_screen(
        _fspace(case), case.y[None, :], layout, n_sis=10, exclude=set(),
        engine=get_engine("reference"),
    )
    eng16 = get_engine("pallas")
    eng16.set_precision("bf16")
    f16, _ = sis_screen(
        _fspace(case), case.y[None, :], layout, n_sis=20, exclude=set(),
        engine=eng16,
    )
    missed = {f.expr for f in f64} - {f.expr for f in f16}
    assert not missed, f"bf16 screen lost fp64 winners: {missed}"


@pytest.mark.parametrize("width", [3, 4])
def test_bf16_l0_ranking_bit_identical_after_rescore(rng, width):
    """Under bf16 precision the ℓ0 prescreen stays pinned fp32 and the
    fp64 rescore rebuilds statistics from the master arrays, so the final
    ℓ0 ranking is bit-identical to an fp64-precision run."""
    m, s = 12, 80
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 1.5 * x[5] - 2.5 * x[9] + 0.8 * x[2] + 0.4 * rng.normal(size=s)
    layout = TaskLayout.single(s)
    res64 = l0_search(x, y, layout, n_dim=width, n_keep=7, block=61,
                      engine=get_engine("pallas"))
    eng16 = get_engine("pallas")
    eng16.set_precision("bf16")
    res16 = l0_search(x, y, layout, n_dim=width, n_keep=7, block=61,
                      engine=eng16)
    assert np.array_equal(res16.tuples, res64.tuples)
    np.testing.assert_array_equal(res16.sses, res64.sses)  # bitwise


def test_l0_search_ranking_parity_partial_rescore(rng):
    """The two-phase contract under *partial* rescoring: with blocks much
    larger than rescore_k, phase 1's fp32 ranking actually selects the
    rescore set, and the final top-k must still match reference exactly."""
    m, s = 24, 80
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 1.5 * x[5] - 2.5 * x[16] + 0.8 * x[2] + 0.4 * rng.normal(size=s)
    layout = TaskLayout.single(s)
    eng = get_engine("pallas", rescore_k=32)   # C(24,3)=2024 >> 32
    ref = l0_search(x, y, layout, n_dim=3, n_keep=8, block=2048,
                    engine=get_engine("reference"))
    res = l0_search(x, y, layout, n_dim=3, n_keep=8, block=2048, engine=eng)
    assert np.array_equal(res.tuples, ref.tuples)
    np.testing.assert_allclose(res.sses, ref.sses, rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("backend", ["pallas", "sharded:pallas"])
def test_l0_near_exact_width4_certified(rng, backend):
    """A near-exact fit: every width-4 superset of the planted pair (231 of
    them) lies within fp32 noise of the best, far more than a 64-tuple
    rescore window holds.  The prescreen cannot rank them; the exclusion
    floor must detect that and settle the block in fp64, so the top-k still
    equals the reference.  Blocks without near-ties stay on the kernel."""
    m, s = 24, 96
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * x[3] - x[7] + 1e-4 * rng.normal(size=s)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], s // 2))
    ref = l0_search(x, y, layout, n_dim=4, n_keep=10, block=4096,
                    engine=get_engine("reference"))
    eng = get_engine(backend)
    getattr(eng.backend, "inner", eng.backend).rescore_k = 64
    res = l0_search(x, y, layout, n_dim=4, n_keep=10, block=4096, engine=eng)
    assert np.array_equal(res.tuples, ref.tuples)
    np.testing.assert_allclose(res.sses, ref.sses, rtol=1e-6, atol=1e-8)
    paths = eng.backend.l0_paths[4]
    fallback = ("exact fp64 (window not certified)" if backend == "pallas"
                else "one-device fallback")
    assert paths.get(fallback, 0) >= 1
    assert sum(paths.values()) > paths[fallback]  # the rest: kernel path


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_full_fit_parity_thermal(case, backend):
    """End-to-end: identical descriptor and matching SSE on every backend
    (thermal reduced: multi-task + on-the-fly deferred last rung)."""
    import dataclasses
    fit_ref = SissoSolver(
        dataclasses.replace(case.config, backend="reference")
    ).fit(case.x, case.y, case.names, units=case.units, task_ids=case.task_ids)
    cfg = dataclasses.replace(case.config, backend=backend)
    fit = SissoSolver(cfg).fit(
        case.x, case.y, case.names, units=case.units, task_ids=case.task_ids)
    for dim in fit_ref.models_by_dim:
        mr, mb = fit_ref.best(dim), fit.best(dim)
        assert {f.expr for f in mr.features} == {f.expr for f in mb.features}
        assert mb.sse == pytest.approx(mr.sse, rel=1e-6)


# ---------------------------------------------------------------------------
# classification problem parity (core/problem.py): the same synthetic
# linearly-separable case must produce identical SIS winner sets and
# identical ℓ0 descriptors on every backend — the Problem-layer analogue
# of the regression rows above.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def class_case():
    from repro.data import classification_dataset

    x, labels, names = classification_dataset(n_samples=90, seed=7)
    y = (labels == "above").astype(float)
    return x, y, names


def _class_fspace(x, names):
    return FeatureSpace(
        x, names, None, op_names=("add", "sub", "mul", "div"),
        max_rung=1, on_the_fly_last_rung=True,
    ).generate()


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_sis_classification_winner_parity(class_case, backend):
    """Identical classification SIS winner sets (materialized + deferred
    candidates) on every backend."""
    x, y, names = class_case
    layout = TaskLayout.single(x.shape[1])
    state = np.ones((1, x.shape[1]))
    f_ref, s_ref = sis_screen(
        _class_fspace(x, names), state, layout, n_sis=12, exclude=set(),
        engine=get_engine("reference"), problem="classification", y=y,
    )
    f_b, s_b = sis_screen(
        _class_fspace(x, names), state, layout, n_sis=12, exclude=set(),
        engine=get_engine(backend), problem="classification", y=y,
    )
    assert {f.expr for f in f_b} == {f.expr for f in f_ref}
    np.testing.assert_allclose(sorted(s_b), sorted(s_ref), atol=1e-9)
    # the planted separating product must be among the winners, overlap-free
    assert any("f0 * f1" in f.expr for f in f_b)
    assert s_b[0] == pytest.approx(0.0, abs=1e-12)


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
@pytest.mark.parametrize("width", [1, 2, 3])
def test_l0_classification_descriptor_parity(class_case, backend, width):
    """Identical ℓ0 winner tuples for the overlap objective, every width."""
    x, y, _ = class_case
    layout = TaskLayout.from_task_ids(
        np.repeat([0, 1], [40, x.shape[1] - 40]))
    ref = l0_search(x[:6], y, layout, n_dim=width, n_keep=5, block=7,
                    engine=get_engine("reference"), problem="classification")
    res = l0_search(x[:6], y, layout, n_dim=width, n_keep=5, block=7,
                    engine=get_engine(backend), problem="classification")
    assert np.array_equal(res.tuples, ref.tuples)
    np.testing.assert_allclose(res.sses, ref.sses, atol=1e-9)
    assert res.n_evaluated == ref.n_evaluated


@pytest.mark.parametrize("backend", DEVICE_BACKENDS)
def test_full_fit_classification_parity(class_case, backend):
    """End-to-end classification fit: identical descriptors, overlap
    objectives and decision boundaries on every backend."""
    x, y, names = class_case
    cfg = SissoConfig(max_rung=1, n_dim=2, n_sis=8, n_residual=3,
                      problem="classification", backend="reference",
                      op_names=("add", "sub", "mul", "div"))
    import dataclasses
    fit_ref = SissoSolver(cfg).fit(x, y, names)
    fit_b = SissoSolver(
        dataclasses.replace(cfg, backend=backend)).fit(x, y, names)
    for dim in fit_ref.models_by_dim:
        mr, mb = fit_ref.best(dim), fit_b.best(dim)
        assert {f.expr for f in mr.features} == {f.expr for f in mb.features}
        assert mb.n_overlap == mr.n_overlap
        assert mb.score == pytest.approx(mr.score, abs=1e-9)
        np.testing.assert_allclose(mb.coefs, mr.coefs, rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_predict_on_train_matches_matrix_gather(case, backend):
    """The compiled-descriptor ``predict`` phase (api layer): replaying a
    selected feature's lineage tape through ``Engine.eval_program`` must
    reproduce the training ``values_matrix()`` gather *bit-for-bit* on
    every backend — the contract that makes out-of-sample prediction and
    artifact serving trustworthy."""
    import dataclasses
    cfg = dataclasses.replace(case.config, backend=backend)
    solver = SissoSolver(cfg)
    fit = solver.fit(
        case.x, case.y, case.names, units=case.units, task_ids=case.task_ids)
    xmat = fit.fspace.values_matrix()
    for dim, models in fit.models_by_dim.items():
        mdl = models[0]
        program = compile_features(mdl.features, fit.fspace)
        got = solver.engine.eval_program(program, case.x)
        want = xmat[[f.row for f in mdl.features]]
        assert np.array_equal(got, want), (
            f"backend={backend} dim={dim}: compiled descriptor diverged "
            f"(max |Δ| = {np.abs(got - want).max():g})"
        )
