"""Deprecation shims: each warns exactly once per use and routes correctly.

One parametrized suite over every compatibility shim the engine/api
refactors left behind: the ``ShardedBackend`` constructor, the
``SissoConfig.use_kernels`` / ``l0_engine`` aliases, the
``repro.core.SissoRegressor`` driver alias, and the
``l0_search(engine="gram"|"qr")`` spelling.  "Routes correctly" means the
shim produces the exact object/behavior of its replacement.
"""
import dataclasses
import warnings

import numpy as np
import pytest

from repro.core import SissoConfig, SissoSolver
from repro.core import SissoRegressor as CoreSissoRegressor
from repro.core.l0 import l0_search
from repro.core.sis import TaskLayout
from repro.engine import (
    Engine, JnpBackend, ShardedBackend, ShardedExecution, get_engine,
)


def _warns_once(fn, match):
    """Run fn() capturing warnings; assert exactly one DeprecationWarning
    mentioning ``match``; return fn's result."""
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out = fn()
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1, [str(w.message) for w in rec]
    assert match in str(dep[0].message)
    return out


CASES = [
    "sharded_backend",
    "config_use_kernels",
    "config_l0_engine",
    "core_regressor",
    "l0_search_engine",
]


@pytest.mark.parametrize("case", CASES)
def test_shim_warns_once_and_routes(case, rng):
    if case == "sharded_backend":
        be = _warns_once(ShardedBackend, "ShardedExecution")
        # routes to the composable wrapper over jnp, same name/config spec
        assert isinstance(be, ShardedExecution)
        assert isinstance(be.inner, JnpBackend)
        assert be.name == "sharded"

    elif case == "config_use_kernels":
        cfg = _warns_once(lambda: SissoConfig(use_kernels=True), "use_kernels")
        assert cfg.backend == "pallas" and cfg.use_kernels is None
        # apply-and-clear: replace() must not re-warn nor resurrect
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            cfg2 = dataclasses.replace(cfg, n_dim=1)
        assert cfg2.backend == "pallas"

    elif case == "config_l0_engine":
        cfg = _warns_once(lambda: SissoConfig(l0_engine="qr"), "l0_engine")
        assert cfg.l0_method == "qr" and cfg.l0_engine is None
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            cfg2 = dataclasses.replace(cfg, backend="reference")
        assert cfg2.l0_method == "qr"

    elif case == "core_regressor":
        cfg = SissoConfig(max_rung=1, n_dim=1, n_sis=5)
        solver = _warns_once(
            lambda: CoreSissoRegressor(cfg), "repro.api.SissoRegressor")
        # the shim *is* the solver: same engine resolution, same fit surface
        assert isinstance(solver, SissoSolver)
        assert isinstance(solver.engine, Engine)
        assert solver.engine.name == cfg.backend

    elif case == "l0_search_engine":
        m, s = 10, 40
        x = rng.uniform(0.5, 3.0, (m, s))
        y = 1.5 * x[2] - 0.5 * x[7]
        layout = TaskLayout.single(s)
        res = _warns_once(
            lambda: l0_search(x, y, layout, n_dim=2, n_keep=3, block=17,
                              engine="gram"),
            "l0_search(engine=",
        )
        want = l0_search(x, y, layout, n_dim=2, n_keep=3, block=17,
                         method="gram", engine=get_engine("jnp"))
        np.testing.assert_array_equal(res.tuples, want.tuples)
        np.testing.assert_allclose(res.sses, want.sses)
