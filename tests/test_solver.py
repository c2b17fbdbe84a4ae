import numpy as np
import pytest

from repro.core import SissoConfig, SissoSolver


def _feature_rows(fit, model):
    rows = [f.row for f in model.features]
    return fit.fspace.values_matrix()[rows]


@pytest.mark.parametrize("method", ["gram", "qr"])
def test_recovers_planted_formula(rng, method):
    x = rng.uniform(0.5, 3.0, size=(5, 120))
    y = 2.5 * (x[0] * x[1]) - 1.3 * (x[2] ** 2) + 0.7
    cfg = SissoConfig(max_rung=1, n_dim=2, n_sis=20, n_residual=5,
                      l0_method=method,
                      op_names=("add", "sub", "mul", "div", "sq", "sqrt", "inv"))
    fit = SissoSolver(cfg).fit(x, y, list("abcde"))
    m = fit.best(2)
    assert {f.expr for f in m.features} == {"(a * b)", "(c)^2"}
    assert m.rmse(y, _feature_rows(fit, m)) < 1e-8
    assert m.r2(y, _feature_rows(fit, m)) > 1 - 1e-12


def test_multitask_recovery(rng):
    x = rng.uniform(0.5, 3.0, size=(4, 156))
    ids = np.repeat([0, 1], [75, 81])
    y = np.where(ids == 0, 2.0 * x[0] * x[1] - 1.0 * x[2] + 0.5,
                 -1.5 * x[0] * x[1] + 3.0 * x[2] - 2.0)
    cfg = SissoConfig(max_rung=1, n_dim=2, n_sis=15, n_residual=5,
                      op_names=("add", "sub", "mul", "div", "sq"))
    fit = SissoSolver(cfg).fit(x, y, list("abcd"), task_ids=ids)
    m = fit.best(2)
    assert {f.expr for f in m.features} == {"(a * b)", "c"}
    np.testing.assert_allclose(
        sorted(m.coefs[:, [f.expr for f in m.features].index("c")]),
        [-1.0, 3.0], rtol=1e-6)
    assert m.rmse(y, _feature_rows(fit, m)) < 1e-8


def test_on_the_fly_equals_materialized(rng):
    x = rng.uniform(0.5, 3.0, size=(4, 64))
    y = 1.7 * x[0] / x[3] - 0.4 * x[2] + 0.1 * rng.normal(size=64)
    base = dict(max_rung=2, n_dim=2, n_sis=12, n_residual=4,
                op_names=("add", "mul", "div", "sq"))
    fit_m = SissoSolver(SissoConfig(**base)).fit(x, y, list("abcd"))
    fit_o = SissoSolver(SissoConfig(on_the_fly_last_rung=True, **base)).fit(
        x, y, list("abcd"))
    mm, mo = fit_m.best(2), fit_o.best(2)
    assert {f.expr for f in mm.features} == {f.expr for f in mo.features}
    assert mm.sse == pytest.approx(mo.sse, rel=1e-9)


def test_kernel_path_equals_reference(rng):
    x = rng.uniform(0.5, 3.0, size=(4, 96))
    y = 3.0 * x[0] * x[2] + 0.05 * rng.normal(size=96)
    base = dict(max_rung=1, n_dim=2, n_sis=10, n_residual=3,
                op_names=("add", "mul", "sq"), on_the_fly_last_rung=True)
    fit_ref = SissoSolver(SissoConfig(**base)).fit(x, y, list("abcd"))
    fit_ker = SissoSolver(SissoConfig(backend="pallas", **base)).fit(
        x, y, list("abcd"))
    mr, mk = fit_ref.best(2), fit_ker.best(2)
    assert {f.expr for f in mr.features} == {f.expr for f in mk.features}
    assert mr.sse == pytest.approx(mk.sse, rel=1e-6)


def test_dimension_progression_improves_fit(rng):
    x = rng.uniform(0.5, 3.0, size=(6, 200))
    y = (2.0 * x[0] - 1.0 * x[1] * x[2] + 0.5 * x[3] ** 2
         + 0.05 * rng.normal(size=200))
    cfg = SissoConfig(max_rung=1, n_dim=3, n_sis=15, n_residual=5,
                      op_names=("add", "mul", "sq"))
    fit = SissoSolver(cfg).fit(x, y, list("abcdef"))
    sses = [fit.best(d).sse for d in (1, 2, 3)]
    assert sses[0] > sses[1] > sses[2]
    assert fit.best(3).rmse(y, _feature_rows(fit, fit.best(3))) < 0.1


def test_timings_recorded(rng):
    x = rng.uniform(0.5, 3.0, size=(3, 40))
    y = x[0] + x[1]
    cfg = SissoConfig(max_rung=1, n_dim=1, n_sis=5, n_residual=2,
                      op_names=("add", "mul"))
    fit = SissoSolver(cfg).fit(x, y, list("abc"))
    # a solver run alone records its own fit; the descriptor stage
    # belongs to the estimator
    assert set(fit.timings) == {"fit", "fc", "sis", "l0", "models",
                                "l0_wait", "fc_enumerate"}
    assert all(v >= 0 for v in fit.timings.values())
