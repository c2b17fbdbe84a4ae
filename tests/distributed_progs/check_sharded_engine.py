"""Subprocess program: the ShardedExecution wrapper on a forced 8-device
CPU mesh — sharded-over-jnp and sharded-over-pallas(interpret), SIS
(materialized + fused deferred) and ℓ0 widths 2–3, winner sets vs the
single-device reference/jnp paths, with O(k) reduced-block payloads.

Runs standalone (CI) or under tests/test_distributed.py.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core import operators as om
from repro.core.l0 import l0_search
from repro.core.sis import ReducedBlock, TaskLayout, build_score_context
from repro.engine import get_engine


def main() -> int:
    assert jax.device_count() == 8, jax.device_count()
    rng = np.random.default_rng(0)
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], [78, 78]))
    resid = rng.normal(size=(3, 156))

    eng_j = get_engine("jnp")
    eng_sh = get_engine("sharded")            # wrapper over jnp, 8-shard mesh
    eng_shp = get_engine("sharded:pallas")    # wrapper over pallas(interpret)
    assert eng_sh.backend._nd == 8 and eng_shp.backend._nd == 8
    ctx = build_score_context(resid, layout,
                              dtype=eng_sh.backend.score_ctx_dtype)

    # ---- materialized SIS: f=101 forces in-shard padding masks ----
    f = 101
    x = rng.uniform(0.5, 3.0, (f, 156))
    serial = np.asarray(eng_j.backend.sis_scores(x, ctx), np.float64)
    want = set(np.argsort(-serial, kind="stable")[:9])
    for eng in (eng_sh, eng_shp):
        rb = eng.sis_topk(x, ctx, 9)
        assert isinstance(rb, ReducedBlock) and len(rb) == 9
        assert (rb.indices < f).all()
        assert set(rb.indices) == want, (sorted(rb.indices), sorted(want))
        np.testing.assert_allclose(
            rb.scores, serial[rb.indices], rtol=1e-9, atol=1e-12)
        # every row asked for: each real row once, padded rows masked on
        # device
        every = eng.sis_topk(x, ctx, f)
        assert sorted(every.indices) == list(range(f)), every.indices
        np.testing.assert_allclose(every.scores, serial[every.indices],
                                   rtol=1e-9, atol=1e-12)
    print("SIS sharded(8) == serial winners: OK")

    # ---- deferred SIS: fused shard_map kernel vs pallas host path ----
    pal = get_engine("pallas")
    a, b = x[:48], x[48:96]
    want_s = pal.backend.sis_scores_deferred(
        a=a, b=b, op_id=om.DIV, ctx=ctx, l_bound=1e-5, u_bound=1e8)
    worder = np.argsort(
        -np.where(np.isfinite(want_s), want_s, -np.inf), kind="stable")[:7]
    worder = worder[np.isfinite(np.asarray(want_s, np.float64)[worder])]
    rb = eng_shp.sis_topk_deferred(om.DIV, a, b, ctx, 1e-5, 1e8, 7)
    assert set(rb.indices) == set(worder), (rb.indices, worder)
    np.testing.assert_allclose(
        np.sort(rb.scores), np.sort(np.asarray(want_s, np.float64)[worder]),
        rtol=1e-6)
    # compose path (sharded-over-jnp) must agree on the winner set too
    rb_j = eng_sh.sis_topk_deferred(om.DIV, a, b, ctx, 1e-5, 1e8, 7)
    assert set(rb_j.indices) == set(worder), (rb_j.indices, worder)
    print("deferred SIS fused+sharded(8) == pallas winners: OK")

    # ---- ℓ0 widths 2-3: full sweeps, winner sets vs reference ----
    m, s = 12, 80
    xs = rng.uniform(0.5, 3.0, (m, s))
    y = 1.5 * xs[5] - 2.5 * xs[9] + 0.8 * xs[2] + 0.4 * rng.normal(size=s)
    lay = TaskLayout.from_task_ids(np.repeat([0, 1], 40))
    for width in (2, 3):
        ref = l0_search(xs, y, lay, n_dim=width, n_keep=7, block=61,
                        engine=get_engine("reference"))
        for eng in (eng_sh, eng_shp):
            res = l0_search(xs, y, lay, n_dim=width, n_keep=7, block=61,
                            engine=eng)
            assert res.n_evaluated == ref.n_evaluated
            assert {tuple(t) for t in res.tuples} == \
                {tuple(t) for t in ref.tuples}, (width, res.tuples, ref.tuples)
            np.testing.assert_allclose(
                np.sort(res.sses), np.sort(ref.sses), rtol=1e-6, atol=1e-8)
    print("L0 widths 2-3 sharded(8) == reference winners: OK")

    # ---- classification problem on the 8-device mesh: the overlap SIS
    # screen + generic ℓ0 reducer shard like regression, winners parity ----
    from repro.core.problem import get_problem

    yc = (xs[0] * xs[1] > np.median(xs[0] * xs[1])).astype(float)
    cprob = get_problem("classification")
    cctx = cprob.build_sis_context(np.ones((1, s)), yc, lay,
                                   dtype=eng_sh.backend.score_ctx_dtype)
    xcand = rng.uniform(0.5, 3.0, (53, s))  # 53 % 8 != 0: padding masks
    serial_c = np.asarray(eng_j.backend.sis_scores(xcand, cctx))
    want_c = set(np.argsort(-serial_c, kind="stable")[:6])
    for eng in (eng_sh, eng_shp):
        rb = eng.sis_topk(xcand, cctx, 6)
        assert isinstance(rb, ReducedBlock) and set(rb.indices) == want_c
        np.testing.assert_allclose(rb.scores, serial_c[rb.indices],
                                   rtol=1e-9, atol=1e-12)
        every = eng.sis_topk(xcand, cctx, len(xcand))
        assert sorted(every.indices) == list(range(len(xcand)))
        np.testing.assert_allclose(every.scores, serial_c[every.indices],
                                   rtol=1e-9, atol=1e-12)
    ref_c = l0_search(xs, yc, lay, n_dim=2, n_keep=5, block=13,
                      engine=get_engine("reference"),
                      problem="classification")
    for eng in (eng_sh, eng_shp):
        res_c = l0_search(xs, yc, lay, n_dim=2, n_keep=5, block=13,
                          engine=eng, problem="classification")
        assert np.array_equal(res_c.tuples, ref_c.tuples)
        np.testing.assert_allclose(res_c.sses, ref_c.sses, atol=1e-9)
    print("classification SIS+L0 sharded(8) == reference winners: OK")

    # ---- reduced-block contract: O(k), in-range, sorted ----
    prob = eng_sh.prepare_l0(xs, y, lay)
    tuples = np.asarray(
        list(__import__("itertools").combinations(range(m), 3)), np.int32)
    rb = eng_sh.l0_topk(prob, tuples, 5)
    assert isinstance(rb, ReducedBlock) and len(rb) == 5
    assert (rb.indices < len(tuples)).all() and (rb.scores[:-1]
                                                 <= rb.scores[1:]).all()
    print("reduced-block contract (O(k) winners): OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
