"""Multi-device tests (subprocess: jax device count is locked at init,
so each mesh scenario runs in its own interpreter with forced host devices).
"""
import os
import subprocess
import sys


_DIR = os.path.join(os.path.dirname(__file__), "distributed_progs")
_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(prog, *args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(_SRC)
    out = subprocess.run(
        [sys.executable, os.path.join(_DIR, prog), *args],
        capture_output=True, text=True, timeout=520, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_sis_l0_2d_mesh():
    out = _run("check_sis_l0.py", "2d")
    assert "SIS distributed == serial: OK" in out
    assert "L0 distributed == serial: OK" in out


def test_distributed_sis_l0_3d_pod_mesh():
    out = _run("check_sis_l0.py", "3d")
    assert "L0 distributed == serial: OK" in out


def test_elastic_sweep_fault_tolerance():
    """Coordinator + 3 workers sharding a width-4 sweep by rank range,
    one worker killed mid-sweep (os._exit under an active lease), the
    coordinator's journal torn mid-publish then restored from the .bak:
    leases reissue, acked blocks never re-score, and the merged top-k is
    bit-identical to the fault-free single-process l0_search."""
    out = _run("check_elastic_sweep.py")
    assert "elastic: torn journal -> .bak recovery: OK" in out
    assert "elastic: worker kill + lease reissue: OK" in out
    assert "elastic: no re-issue of acked blocks: OK" in out
    assert "elastic: final top-k bit-identical to fault-free l0_search: OK" in out


def test_sharded_execution_engine_8dev():
    """ShardedExecution over jnp and pallas(interpret) on a forced 8-device
    mesh: SIS, fused deferred SIS, ℓ0 widths 2–3 winner-set parity plus the
    O(k) reduced-block contract."""
    out = _run("check_sharded_engine.py")
    assert "SIS sharded(8) == serial winners: OK" in out
    assert "deferred SIS fused+sharded(8) == pallas winners: OK" in out
    assert "L0 widths 2-3 sharded(8) == reference winners: OK" in out
    assert "classification SIS+L0 sharded(8) == reference winners: OK" in out
    assert "reduced-block contract (O(k) winners): OK" in out


def test_sharded_pallas_fit_4dev_gives_the_reference_models():
    """A whole fit on sharded:pallas (interpret) over a forced 4-device
    mesh, rung 2 with the last rung on the fly: every dimension keeps
    n_sis features and the models are the plain reference's; every
    deferred block runs the fused kernel inside shard_map and each ℓ0
    block is one k-sized merge."""
    out = _run("check_sharded_fit.py")
    assert "sharded:pallas fit (4) == reference models: OK" in out
    assert "sharded counters (4 devices): OK" in out


# ---------------------------------------------------------------------------
# in-process (1-device mesh) regression + contract tests for the
# distribution layer: the same code path as multi-shard, minus the padding
# -- which is why padding is injected manually below.
# ---------------------------------------------------------------------------

def _ctx_and_values(rng, f=24, s=96):
    import numpy as np
    from repro.core.sis import TaskLayout, build_score_context

    x = rng.uniform(0.5, 3.0, (f, s))
    layout = TaskLayout.from_task_ids(np.repeat([0, 1], s // 2))
    ctx = build_score_context(rng.normal(size=(2, s)), layout)
    return x, ctx, layout


def test_sis_padding_rows_masked_inside_sharded_fn():
    """Regression (prerequisite for device-side top-k): padding rows must
    come back -inf from the sharded fn itself, not rely on host slice-off.
    A zero-padded row scores 0.0 without the in-shard mask — which would
    beat weakly-correlated real candidates in a device merge."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.distributed import sis_scores_sharded
    from repro.engine.sharded import default_mesh

    rng = np.random.default_rng(0)
    x, ctx, _ = _ctx_and_values(rng)
    f = len(x)
    x_pad = np.zeros((f + 8, x.shape[1]))
    x_pad[:f] = x
    x_pad[f:] = x[0]  # adversarial padding: a row that would score well
    mask = np.arange(f + 8) < f
    scores = np.asarray(
        sis_scores_sharded(default_mesh(), jnp.asarray(x_pad), ctx,
                           jnp.asarray(mask)))
    assert np.all(scores[f:] == -np.inf)
    assert np.all(np.isfinite(scores[:f]))


def test_l0_padding_pairs_masked_inside_sharded_fn():
    """Benign padding pairs must be +inf on device: a real (0, 1) solve
    could genuinely win a block top-k and duplicate into the merge."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.distributed import l0_pair_sses_sharded
    from repro.engine.sharded import default_mesh

    rng = np.random.default_rng(1)
    m, s = 10, 80
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 2.0 * x[0] - x[1] + 0.1 * rng.normal(size=s)
    from repro.core.sis import TaskLayout

    layout = TaskLayout.single(s)
    pairs = np.zeros((8, 2), np.int32)
    pairs[:4] = [(2, 3), (4, 5), (0, 1), (6, 7)]
    pairs[4:] = (0, 1)  # padding uses the *best* real pair
    valid = np.arange(8) < 4
    sses = np.asarray(l0_pair_sses_sharded(
        default_mesh(), jnp.asarray(x), jnp.asarray(y), layout,
        jnp.asarray(pairs), jnp.asarray(valid)))
    assert np.all(sses[4:] == np.inf)
    assert np.all(np.isfinite(sses[:4]))


def test_fused_kernel_masks_padding_rows_in_kernel():
    """kernels/fused_sis.py n_valid: rows past the real count are -inf in
    the kernel output (not merely sliced off by the wrapper)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sis import TaskLayout, build_score_context
    from repro.kernels import ops as kops
    from repro.kernels.fused_sis import fused_gen_sis_pallas
    from repro.core import operators as om

    rng = np.random.default_rng(2)
    bsz, s = 20, 64
    a = rng.uniform(0.5, 3.0, (bsz, s))
    ctx = build_score_context(rng.normal(size=(1, s)), TaskLayout.single(s))
    a_p = jnp.ones((256, 128), jnp.float32).at[:bsz, :s].set(
        jnp.asarray(a, jnp.float32))
    m_p = jnp.zeros((1, 128), jnp.float32).at[:, :s].set(
        jnp.asarray(ctx.membership, jnp.float32))
    yt_p = jnp.zeros((1, 128), jnp.float32).at[:, :s].set(
        jnp.asarray(ctx.y_tilde, jnp.float32))
    cnt = jnp.asarray(ctx.counts, jnp.float32)[None, :]
    scores = np.asarray(fused_gen_sis_pallas(
        om.SQ, a_p, a_p, m_p, yt_p, cnt, n_residuals=1,
        l_bound=1e-5, u_bound=1e8, block_b=128, interpret=True,
        n_valid=bsz))
    assert np.all(scores[bsz:] == -np.inf)
    assert np.all(np.isfinite(scores[:bsz]))
    # and the public wrapper agrees with itself under padding
    got = np.asarray(kops.fused_gen_sis(
        om.SQ, jnp.asarray(a), jnp.asarray(a), ctx, 1e-5, 1e8,
        interpret=True))
    np.testing.assert_allclose(got, scores[:bsz], rtol=1e-6)


def test_reduced_block_contract_1shard_bit_identical():
    """On a 1-shard mesh the wrapper's device merge must equal the inner
    backend's full scores + stable host top-k, bit for bit."""
    import numpy as np
    from repro.core.sis import ReducedBlock
    from repro.engine import get_engine

    rng = np.random.default_rng(3)
    x, ctx, layout = _ctx_and_values(rng)
    eng = get_engine("sharded")
    inner = get_engine("jnp")
    rb = eng.sis_scores(x, ctx, n_keep=6)
    assert isinstance(rb, ReducedBlock)
    assert len(rb) <= 6 and rb.n_source == len(x)
    full = np.asarray(inner.sis_scores(x, ctx), np.float64)
    order = np.argsort(-full, kind="stable")[:6]
    assert np.array_equal(rb.indices, order)
    assert np.array_equal(rb.scores, full[order])
    # without n_keep the wrapper still serves full vectors (legacy path)
    legacy = np.asarray(eng.sis_scores(x, ctx), np.float64)
    assert legacy.shape == (len(x),)
    np.testing.assert_allclose(legacy, full, rtol=1e-12)


def test_reduced_block_l0_contract():
    """engine.l0_scores(n_keep=...) returns O(k) ascending-SSE winners
    whose values match the inner fp64 scores exactly."""
    import numpy as np
    from repro.core.sis import ReducedBlock, TaskLayout
    from repro.engine import get_engine

    rng = np.random.default_rng(4)
    m, s = 11, 64
    x = rng.uniform(0.5, 3.0, (m, s))
    y = 1.2 * x[3] - 0.7 * x[8] + 0.05 * rng.normal(size=s)
    layout = TaskLayout.single(s)
    tuples = np.asarray(
        list(__import__("itertools").combinations(range(m), 3)), np.int32)
    eng = get_engine("sharded")
    prob = eng.prepare_l0(x, y, layout)
    rb = eng.l0_scores(prob, tuples, n_keep=5)
    assert isinstance(rb, ReducedBlock) and len(rb) == 5
    assert (np.diff(rb.scores) >= 0).all()
    inner = get_engine("jnp")
    full = np.asarray(inner.l0_scores(inner.prepare_l0(x, y, layout), tuples))
    order = np.argsort(full, kind="stable")[:5]
    assert np.array_equal(rb.indices, order)
    np.testing.assert_allclose(rb.scores, full[order], rtol=1e-12)


def test_host_reduce_defaults_match_device_merge():
    """The Backend base-class *_topk defaults (ReducedBlock.reduce_host)
    are the reference semantics for any reducing backend: a host-reducing
    jnp backend must produce bit-identical ReducedBlocks to the device
    merge on a 1-shard mesh — same winners, same scores, same tie order."""
    import numpy as np
    from repro.core.sis import ReducedBlock, TaskLayout
    from repro.engine import Engine, JnpBackend, get_engine

    rng = np.random.default_rng(5)
    x, ctx, layout = _ctx_and_values(rng)
    host = JnpBackend()
    host.reduces_blocks = True  # opt the plain backend into n_keep routing
    eng_host, eng_dev = Engine(host), get_engine("sharded")

    mask = np.ones(len(x), bool)
    mask[3] = False
    rb_h = eng_host.sis_scores(x, ctx, n_keep=6, mask=mask)
    rb_d = eng_dev.sis_scores(x, ctx, n_keep=6, mask=mask)
    assert isinstance(rb_h, ReducedBlock)
    assert np.array_equal(rb_h.indices, rb_d.indices)
    assert np.array_equal(rb_h.scores, rb_d.scores)

    m, s = 10, 64
    xs = rng.uniform(0.5, 3.0, (m, s))
    y = 1.1 * xs[2] - 0.6 * xs[7] + 0.05 * rng.normal(size=s)
    lay = TaskLayout.single(s)
    tuples = np.asarray(
        list(__import__("itertools").combinations(range(m), 2)), np.int32)
    rb_h = eng_host.l0_scores(eng_host.prepare_l0(xs, y, lay), tuples,
                              n_keep=5)
    rb_d = eng_dev.l0_scores(eng_dev.prepare_l0(xs, y, lay), tuples,
                             n_keep=5)
    assert np.array_equal(rb_h.indices, rb_d.indices)
    np.testing.assert_allclose(rb_h.scores, rb_d.scores, rtol=1e-12)

    from repro.core import operators as om

    want = np.asarray(eng_host.sis_scores_deferred(
        om.DIV, x[:8], x[8:16], ctx, 1e-5, 1e8), np.float64)
    rb = eng_host.sis_scores_deferred(
        om.DIV, x[:8], x[8:16], ctx, 1e-5, 1e8, n_keep=3)
    order = np.argsort(-np.where(np.isfinite(want), want, -np.inf),
                       kind="stable")[:3]
    order = order[np.isfinite(want[order])]
    assert np.array_equal(rb.indices, order)


def test_sharded_backend_shim_deprecated():
    import pytest as _pytest
    from repro.engine import ShardedBackend, ShardedExecution

    with _pytest.warns(DeprecationWarning, match="ShardedBackend is deprecated"):
        shim = ShardedBackend()
    assert isinstance(shim, ShardedExecution)
    assert shim.name == "sharded" and shim.reduces_blocks


def test_sharded_spec_parsing_and_nesting_guard():
    import pytest as _pytest
    from repro.engine import ShardedExecution, get_engine

    eng = get_engine("sharded:pallas")
    assert eng.name == "sharded:pallas"
    assert eng.backend.inner.name == "pallas"
    with _pytest.raises(ValueError):
        get_engine("sharded:cuda")
    with _pytest.raises(ValueError):
        ShardedExecution(inner="sharded")
    with _pytest.raises(ValueError):
        ShardedExecution(inner=ShardedExecution())
