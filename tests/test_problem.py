"""The Problem layer (core/problem.py): objectives, refits, state updates.

Covers the classification score math against brute force, jnp-vs-host
parity of both classification objectives, the LDA separating refit, the
ambiguity-mask state update, and the per-task R² centering fix.
"""
import numpy as np
import pytest

from repro.core import SissoConfig, SissoSolver, get_problem
from repro.core.model import SissoModel
from repro.core.problem import (
    ClassificationProblem, RegressionProblem, build_class_score_context,
    compute_class_stats, fit_discriminants,
    overlap_region_mask, overlap_scores_host, score_tuples_overlap,
    score_tuples_overlap_host,
)
from repro.core.sis import TaskLayout
from repro.engine import get_engine


def _sep_case(rng, s=80, p=4):
    """x (p, s) with feature 0 separating two classes with a margin."""
    x = rng.uniform(0.5, 3.0, (p, s))
    y = (x[0] > 1.7).astype(float)
    x[0] = np.where(y > 0, x[0] + 0.5, x[0] - 0.2)  # widen the margin
    return x, y


# ---------------------------------------------------------------------------
# problem registry
# ---------------------------------------------------------------------------

def test_get_problem_registry():
    assert isinstance(get_problem(None), RegressionProblem)
    assert isinstance(get_problem("regression"), RegressionProblem)
    assert isinstance(get_problem("classification"), ClassificationProblem)
    prob = ClassificationProblem()
    assert get_problem(prob) is prob
    with pytest.raises(ValueError, match="unknown problem"):
        get_problem("ranking")


# ---------------------------------------------------------------------------
# classification SIS score
# ---------------------------------------------------------------------------

def test_overlap_sis_score_matches_bruteforce():
    """Hand-checkable case: one feature with known interval overlap."""
    # class 0 values span [1, 4], class 1 spans [3, 6]: overlap [3, 4]
    v = np.asarray([[1.0, 2.0, 3.0, 4.0, 3.0, 4.0, 5.0, 6.0]])
    y = np.asarray([0, 0, 0, 0, 1, 1, 1, 1], float)
    layout = TaskLayout.single(8)
    ctx = build_class_score_context(np.ones((1, 8)), y, layout,
                                    dtype=np.float64)
    score = overlap_scores_host(v, ctx)
    # 4 samples inside [3,4] (two per class); tie = 0.5 * (1/5) -> w=0.5
    assert score[0] == pytest.approx(-(4 + 0.5 * (1.0 / 5.0)))
    # a fully separated feature: zero count, zero length
    v2 = np.asarray([[1.0, 1.5, 2.0, 2.5, 5.0, 5.5, 6.0, 6.5]])
    assert overlap_scores_host(v2, ctx)[0] == pytest.approx(0.0)


def test_overlap_sis_state_mask_restricts_counting():
    v = np.asarray([[1.0, 2.0, 3.0, 4.0, 3.0, 4.0, 5.0, 6.0]])
    y = np.asarray([0, 0, 0, 0, 1, 1, 1, 1], float)
    layout = TaskLayout.single(8)
    # mask out the two overlapping class-0 samples: intervals shrink
    mask = np.asarray([[1, 1, 0, 0, 1, 1, 1, 1]], float)
    ctx = build_class_score_context(mask, y, layout, dtype=np.float64)
    s = overlap_scores_host(v, ctx)
    # class 0 now spans [1,2], class 1 [3,6]: separated
    assert s[0] == pytest.approx(0.0)


def test_overlap_sis_jnp_matches_host(rng):
    x, y = _sep_case(rng, s=60, p=6)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], 30))
    prob = get_problem("classification")
    state = np.stack([np.ones(60), (rng.uniform(size=60) > 0.4)]).astype(float)
    ctx = prob.build_sis_context(state, y, layout, dtype=np.float64)
    host = get_engine("reference").backend.sis_scores(x, ctx)
    jnp_ = get_engine("jnp").backend.sis_scores(x, ctx)
    np.testing.assert_allclose(jnp_, host, atol=1e-12)


def test_separable_feature_wins_sis(rng):
    x, y = _sep_case(rng)
    layout = TaskLayout.single(x.shape[1])
    ctx = get_problem("classification").build_sis_context(
        np.ones((1, x.shape[1])), y, layout, dtype=np.float64)
    scores = overlap_scores_host(x, ctx)
    assert np.argmax(scores) == 0
    assert scores[0] == pytest.approx(0.0)   # fully separated -> no overlap


# ---------------------------------------------------------------------------
# classification ℓ0 objective
# ---------------------------------------------------------------------------

def test_overlap_l0_host_matches_bruteforce():
    # 2 features, 6 samples: feature 0 separates, feature 1 mixes
    x = np.asarray([
        [1.0, 2.0, 3.0, 7.0, 8.0, 9.0],
        [1.0, 5.0, 3.0, 2.0, 4.0, 6.0],
    ])
    y = np.asarray([0, 0, 0, 1, 1, 1], float)
    layout = TaskLayout.single(6)
    stats = compute_class_stats(x, y, layout)
    s1 = score_tuples_overlap_host(stats, np.asarray([[0], [1]]))
    assert np.floor(s1[0]) == 0                      # separated
    # feature 1: class0 in [1,5], class1 in [2,6] -> overlap [2,5] holds
    # samples {5,3,2,4} -> count 4
    assert np.floor(s1[1]) == 4
    # joint box overlap of (f0, f1): f0 boxes disjoint -> count 0
    s2 = score_tuples_overlap_host(stats, np.asarray([[0, 1]]))
    assert np.floor(s2[0]) == 0
    assert s1[0] < s1[1]


def test_overlap_l0_jnp_matches_host(rng):
    import itertools
    x, y = _sep_case(rng, s=50, p=5)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], 25))
    stats = compute_class_stats(x, y, layout)
    tuples = np.asarray(list(itertools.combinations(range(5), 2)), np.int32)
    host = score_tuples_overlap_host(stats, tuples)
    dev = np.asarray(score_tuples_overlap(stats, tuples))
    np.testing.assert_allclose(dev, host, atol=1e-12)


# ---------------------------------------------------------------------------
# separating refit + state update
# ---------------------------------------------------------------------------

def test_lda_refit_separates_separable_case(rng):
    x, y = _sep_case(rng)
    layout = TaskLayout.single(x.shape[1])
    coefs, inters = fit_discriminants(x[:1], y.astype(np.intp), 2, layout)
    df = x[:1].T @ coefs[0].T + inters[0]
    pred = np.argmax(df, axis=1)
    assert np.array_equal(pred, y.astype(int))       # margin recentering


def test_lda_absent_class_never_predicted(rng):
    x = rng.uniform(0.5, 3.0, (2, 40))
    codes = np.zeros(40, np.intp)
    codes[20:] = 1
    # 3 declared classes, class 2 absent
    layout = TaskLayout.single(40)
    coefs, inters = fit_discriminants(x, codes, 3, layout)
    df = x.T @ coefs[0].T + inters[0]
    assert not (np.argmax(df, axis=1) == 2).any()


def test_overlap_region_mask_flags_ambiguous_samples():
    d = np.asarray([[1.0, 2.0, 3.0, 4.0, 3.0, 4.0, 5.0, 6.0]])
    y = np.asarray([0, 0, 0, 0, 1, 1, 1, 1], float)
    mask = overlap_region_mask(d, y, TaskLayout.single(8))
    np.testing.assert_array_equal(
        mask, [False, False, True, True, True, True, False, False])


def test_classification_update_state(rng):
    x, y = _sep_case(rng)
    layout = TaskLayout.single(x.shape[1])
    prob = get_problem("classification")
    state = prob.initial_state(y, layout)
    assert state.shape == (1, x.shape[1]) and (state == 1).all()


def test_overlap_counts_exact_under_bf16(rng):
    """Sub-fp32 compute modes must not corrupt the integer overlap count.

    The count accumulates in >= fp32 even when values compute in bf16:
    with ~1200 samples a bf16 accumulator rounds counts to multiples of
    8 and collapses distinct candidates into ties.  Value-cast boundary
    rounding still drifts individual counts by a few samples (inherent
    to the precision mode, like bf16 regression screening), but the
    count *resolution* stays 1 and the winner is preserved.
    """
    s = 1200
    x = rng.uniform(0.5, 3.0, (8, s))
    y = (x[0] > 1.7).astype(float)
    layout = TaskLayout.single(s)
    prob = get_problem("classification")
    ctx = prob.build_sis_context(np.ones((1, s)), y, layout,
                                 dtype=np.float64)
    want = get_engine("reference").backend.sis_scores(x, ctx)
    eng = get_engine("jnp").set_precision("bf16")
    try:
        ctx16 = prob.build_sis_context(
            np.ones((1, s)), y, layout, dtype=eng.backend.score_ctx_dtype)
        got = eng.backend.sis_scores(x, ctx16)
    finally:
        eng.set_precision("fp64")
    assert np.abs(got - want).max() < 5        # boundary drift only
    assert not all(v % 8 == 0 for v in got)    # no bf16-grid collapse
    assert np.argmax(got) == np.argmax(want)


# ---------------------------------------------------------------------------
# end-to-end core solver
# ---------------------------------------------------------------------------

def test_solver_classification_recovers_separating_descriptor(rng):
    from repro.data import classification_dataset

    x, labels, names = classification_dataset(n_samples=100, seed=3)
    y = (labels == "above").astype(float)
    cfg = SissoConfig(max_rung=1, n_dim=2, n_sis=8, n_residual=3,
                      problem="classification", backend="jnp",
                      op_names=("add", "sub", "mul", "div"))
    fit = SissoSolver(cfg).fit(x, y, names)
    assert fit.problem == "classification"
    best = fit.best(1)
    assert best.n_overlap == 0
    assert "f0 * f1" in best.features[0].expr
    xm = fit.fspace.values_matrix()
    rows = [fit.fspace.features[f.fid].row for f in best.features]
    assert best.accuracy(y, xm[rows]) == 1.0


# ---------------------------------------------------------------------------
# satellite: per-task R² centering (SissoModel.r2)
# ---------------------------------------------------------------------------

def test_r2_centers_y_per_task():
    """A model predicting each task's mean explains nothing: R² must be 0.

    The old global-mean centering counted the between-task spread in
    ss_tot, reporting a large spurious R² for exactly this null model.
    """
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], 10))
    rng = np.random.default_rng(0)
    # two tasks with wildly different offsets
    y = np.concatenate([rng.normal(0.0, 1.0, 10), rng.normal(100.0, 1.0, 10)])
    fv = np.ones((1, 20))
    mu = np.asarray([y[:10].mean(), y[10:].mean()])
    mdl = SissoModel(features=[], coefs=np.zeros((2, 1)), intercepts=mu,
                     layout=layout, sse=0.0)
    # hack: predict uses coefs @ values; with zero coefs only intercepts act
    mdl.features = [None]
    assert mdl.r2(y, fv) == pytest.approx(0.0, abs=1e-12)
    # and a perfect per-task fit still reports 1
    mdl2 = SissoModel(features=[None], coefs=np.ones((2, 1)),
                      intercepts=np.zeros(2), layout=layout, sse=0.0)
    assert mdl2.r2(y, y[None, :]) == pytest.approx(1.0)
