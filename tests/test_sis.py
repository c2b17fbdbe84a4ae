import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import FeatureSpace
from repro.core.sis import (
    TaskLayout, TopK, build_score_context, score_block, sis_screen,
)


def naive_score(x, resid, slices):
    """max over residuals of mean-over-tasks |pearson r| — literal Eq. 1."""
    out = np.zeros(len(x))
    for fi, xv in enumerate(x):
        best = -np.inf
        for r in np.atleast_2d(resid):
            rs = []
            for lo, hi in slices:
                xs, ys = xv[lo:hi], r[lo:hi]
                xc, yc = xs - xs.mean(), ys - ys.mean()
                denom = np.sqrt((xc * xc).sum() * (yc * yc).sum())
                rs.append(abs((xc * yc).sum() / denom) if denom > 0 else 0.0)
            best = max(best, float(np.mean(rs)))
        out[fi] = best
    return out


def test_score_block_matches_naive_single_task(rng):
    x = rng.normal(size=(40, 100))
    y = rng.normal(size=(1, 100))
    layout = TaskLayout.single(100)
    ctx = build_score_context(y, layout)
    got = np.array(score_block(jnp.asarray(x), ctx))
    want = naive_score(x, y, layout.slices)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_score_block_multitask_multiresidual(rng):
    x = rng.normal(size=(25, 90))
    resid = rng.normal(size=(3, 90))
    layout = TaskLayout.from_task_ids(np.repeat([0, 1, 2], 30))
    ctx = build_score_context(resid, layout)
    got = np.array(score_block(jnp.asarray(x), ctx))
    want = naive_score(x, resid, layout.slices)
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_perfect_correlation_scores_one(rng):
    y = rng.normal(size=(1, 64))
    x = np.stack([3.0 * y[0] + 5.0, rng.normal(size=64)])
    ctx = build_score_context(y, TaskLayout.single(64))
    s = np.array(score_block(jnp.asarray(x), ctx))
    assert s[0] == pytest.approx(1.0, abs=1e-9)
    assert s[1] < 0.5


def test_task_layout_requires_grouped():
    with pytest.raises(ValueError):
        TaskLayout.from_task_ids(np.array([0, 1, 0]))


def test_topk_merging(rng):
    top = TopK(k=5)
    for chunk in np.split(rng.normal(size=100), 10):
        top.push(chunk, [("t", i) for i in range(len(chunk))])
    assert len(top.scores) == 5
    assert (np.diff(top.scores) <= 0).all()
    # -inf and nan never enter
    top.push(np.array([np.nan, -np.inf, 100.0]), [("n",), ("i",), ("big",)])
    assert top.scores[0] == 100.0
    assert np.isfinite(top.scores).all()


def _planted_space(rng, on_the_fly):
    x = rng.uniform(0.5, 3.0, size=(5, 80))
    y = 4.0 * x[0] * x[1] + 0.01 * rng.normal(size=80)
    fs = FeatureSpace(x, list("abcde"), op_names=("add", "mul", "sq"),
                      max_rung=1, on_the_fly_last_rung=on_the_fly).generate()
    return fs, y


@pytest.mark.parametrize("on_the_fly", [False, True])
def test_sis_screen_finds_planted_feature(rng, on_the_fly):
    fs, y = _planted_space(rng, on_the_fly)
    feats, scores = sis_screen(fs, y[None, :], TaskLayout.single(80),
                               n_sis=5, exclude=set())
    assert feats[0].expr == "(a * b)"
    assert scores[0] > 0.999
    assert (np.diff(scores) <= 1e-12).all()


def test_sis_screen_excludes_selected(rng):
    fs, y = _planted_space(rng, False)
    f1, _ = sis_screen(fs, y[None, :], TaskLayout.single(80), 3, exclude=set())
    sel = {f.fid for f in f1}
    f2, _ = sis_screen(fs, y[None, :], TaskLayout.single(80), 3, exclude=sel)
    assert sel.isdisjoint({f.fid for f in f2})


def test_sis_screen_otf_matches_materialized(rng):
    fs_m, y = _planted_space(rng, False)
    rng2 = np.random.default_rng(0)
    fs_o, _ = _planted_space(rng2, True)
    fm, sm = sis_screen(fs_m, y[None, :], TaskLayout.single(80), 8, set())
    fo, so = sis_screen(fs_o, y[None, :], TaskLayout.single(80), 8, set())
    assert [f.expr for f in fm] == [f.expr for f in fo]
    np.testing.assert_allclose(sm, so, rtol=1e-9)


def _value_classes(selected):
    """Each selected feature's values up to scale, sign and offset (one
    model span), rounded: the set a selection stands for."""
    out = set()
    for s in selected:
        z = s.values - s.values.mean()
        z = z / np.linalg.norm(z)
        z = z * np.sign(z[np.argmax(np.abs(z) > 1e-3)])
        out.add(tuple(np.round(z, 8)))
    return out


@pytest.fixture(scope="module")
def otf_and_stored_fits():
    import _reference_case as case

    d = case.data()
    ref = case.reference_campaign(case.CASE, d)
    fits = {}
    for otf in (True, False):
        settings = dict(case.CASE, on_the_fly_last_rung=otf)
        record, _ = case.fit(settings, d)
        fits[otf] = (record, case.readings(settings, d, record.answers, ref))
    return fits, ref


@pytest.mark.parametrize("on_the_fly", [True, False],
                         ids=["on-the-fly", "stored"])
def test_each_dimension_keeps_n_sis_features_as_the_reference(
        otf_and_stored_fits, on_the_fly):
    """Rung 2, last rung on the fly or stored: every dimension keeps
    ``n_sis`` new features, the ones the plain reference selects (no
    reference feature it keeps scores below the reference's cut), and
    the models are the reference's.  On the fly, features selected at an
    earlier dimension and their value-duplicates are screened again and
    take window slots, so the screen has to widen its window."""
    import _reference_case as case

    fits, ref = otf_and_stored_fits
    record, readings = fits[on_the_fly]
    n_sis = case.CASE["n_sis"]
    assert {d: len(s) for d, s in record.answers.selected.items()} \
        == {d: n_sis for d in range(1, case.CASE["n_dim"] + 1)}
    assert {d: len(dim.selected) for d, dim in ref.dims.items()} \
        == {d: n_sis for d in ref.dims}
    # fp64 on both sides, so only rounding separates them
    assert readings["sis_gap"] <= 1e-12
    assert readings["fc_gap"] <= 1e-12
    assert readings["l0_gap"] <= 1e-9


def test_on_the_fly_and_stored_select_the_same_features(otf_and_stored_fits):
    fits, _ = otf_and_stored_fits
    otf, stored = fits[True][0].answers, fits[False][0].answers
    for d in stored.selected:
        assert _value_classes(otf.selected[d]) \
            == _value_classes(stored.selected[d]), d
