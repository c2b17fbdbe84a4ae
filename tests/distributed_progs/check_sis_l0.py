"""Subprocess program: ShardedExecution on sample-sharded meshes.

The distribution wrapper over jnp on a forced 8-device host mesh, either
(4, 2) ``("data", "model")`` or (2, 2, 2) ``("pod", "data", "model")``:
candidates and tuples shard over data (+pod), samples over model.  Its
``sis_topk`` and ``l0_topk`` must return the serial reference's stable
top-k, on blocks whose lengths the four candidate shards do not divide.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import itertools
import sys

import jax
import numpy as np

jax.config.update("jax_enable_x64", True)

from jax.sharding import Mesh

from repro.core.sis import ReducedBlock, TaskLayout, build_score_context
from repro.engine import ReferenceBackend, ShardedExecution


def host_mesh(shape, axes) -> Mesh:
    """Mesh over the first prod(shape) forced host devices."""
    n = int(np.prod(shape))
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)


def main() -> int:
    rng = np.random.default_rng(0)
    mesh_kind = sys.argv[1] if len(sys.argv) > 1 else "2d"
    if mesh_kind == "2d":
        mesh = host_mesh((4, 2), ("data", "model"))
    else:  # 3d multi-pod-style
        mesh = host_mesh((2, 2, 2), ("pod", "data", "model"))
    eng = ShardedExecution(inner="jnp", mesh=mesh)
    assert eng.n_shards == 4, eng.n_shards
    ref = ReferenceBackend()

    # ---- SIS: 61 candidates over 4 shards, two rows excluded; 157
    # samples, which the 2-way model axis does not divide either ----
    f, s = 61, 157
    x = rng.uniform(0.5, 3.0, (f, s))
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], [s // 2, s - s // 2]))
    # fp64 residuals: the fp32 context rounds away their centering, which
    # the reference's two-pass screen and the matmul screen absorb apart
    ctx = build_score_context(rng.normal(size=(3, s)), layout,
                              dtype=np.float64)
    mask = np.ones(f, bool)
    mask[[4, 17]] = False
    rb = eng.sis_topk(x, ctx, 9, mask=mask)
    want = ReducedBlock.reduce_host(ref.sis_scores(x, ctx), 9, mask=mask)
    assert np.array_equal(rb.indices, want.indices), (rb.indices,
                                                      want.indices)
    np.testing.assert_allclose(rb.scores, want.scores, rtol=1e-9)
    print("SIS distributed == serial: OK")

    # ---- ℓ0: every pair of 38 features (703 tuples over 4 shards) ----
    m = 38
    xs = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * xs[5] * xs[11] + rng.normal(0, 0.2, s)
    pairs = np.asarray(list(itertools.combinations(range(m), 2)), np.int32)
    rb = eng.l0_topk(eng.prepare_l0(xs, y, layout), pairs, 5)
    want = ReducedBlock.reduce_host(
        ref.l0_scores(ref.prepare_l0(xs, y, layout), pairs), 5,
        largest=False)
    assert np.array_equal(rb.indices, want.indices), (rb.indices,
                                                      want.indices)
    np.testing.assert_allclose(rb.scores, want.scores, rtol=1e-8)
    print("L0 distributed == serial: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
