"""Subprocess program: padding rows never win on a 4-shard mesh.

ShardedExecution pads a block to a multiple of its candidate shards; the
padded rows must lose inside the sharded program, before the device merge,
because only the merged winners reach the host.  Each block here has a
length the four shards do not divide, asks for as many winners as the
padded block holds, and fills its padding with rows that would win: a
zero SIS row (score 0, or a finite overlap count) and the best pair or
triple of the ℓ0 case.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import sys

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core.problem import get_problem
from repro.core.sis import ReducedBlock, TaskLayout, build_score_context
from repro.engine import ReferenceBackend, ShardedExecution


def main() -> int:
    assert jax.device_count() == 4, jax.device_count()
    rng = np.random.default_rng(0)
    ref = ReferenceBackend()
    eng = ShardedExecution(inner="jnp")
    assert eng.n_shards == 4

    # ---- SIS: 10 rows pad to 12 ----
    f, s = 10, 96
    x = rng.uniform(0.5, 3.0, (f, s))
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], s // 2))
    ctx = build_score_context(rng.normal(size=(2, s)), layout,
                              dtype=np.float64)
    yc = (x[0] > np.median(x[0])).astype(float)
    cctx = get_problem("classification").build_sis_context(
        np.ones((1, s)), yc, layout, dtype=np.float64)
    for c in (ctx, cctx):
        rb = eng.sis_topk(x, c, 12)
        want = ReducedBlock.reduce_host(ref.sis_scores(x, c), 12)
        assert rb.n_source == f and (rb.indices < f).all(), rb.indices
        assert np.array_equal(rb.indices, want.indices), (rb.indices,
                                                          want.indices)
        np.testing.assert_allclose(rb.scores, want.scores, rtol=1e-9)
    print("SIS padding rows never win: OK")

    # ---- ℓ0: 7 pairs pad to 8 with (0, 1), the best pair; 5 triples
    # pad to 8 with (0, 1, 2), on the Gram-gather kernel's reducer ----
    m = 10
    xs = rng.uniform(0.5, 3.0, (m, 80))
    y = 2.0 * xs[0] - xs[1] + 0.1 * rng.normal(size=80)
    lay = TaskLayout.single(80)
    pairs = np.asarray([(2, 3), (4, 5), (0, 1), (6, 7), (1, 2), (3, 4),
                        (5, 6)], np.int32)
    triples = np.asarray([(2, 3, 4), (0, 1, 5), (4, 5, 6), (1, 7, 8),
                          (3, 6, 9)], np.int32)
    for inner, tuples in (("jnp", pairs), ("pallas", triples)):
        sh = ShardedExecution(inner=inner)
        rb = sh.l0_topk(sh.prepare_l0(xs, y, lay), tuples, 8)
        want = ReducedBlock.reduce_host(
            ref.l0_scores(ref.prepare_l0(xs, y, lay), tuples), 8,
            largest=False)
        assert rb.n_source == len(tuples), rb.n_source
        assert np.array_equal(rb.indices, want.indices), (inner, rb.indices,
                                                          want.indices)
        np.testing.assert_allclose(rb.scores, want.scores, rtol=1e-8)
    print("L0 padding tuples never win: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
