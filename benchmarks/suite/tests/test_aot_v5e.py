"""Compile-only rehearsal of the cells' device programs for a described TPU
v5e, at the cells' own shapes: what the chip's compiler would refuse
(alignment, VMEM, 64-bit values in a kernel) fails here without a chip.
Nothing runs, so this says nothing of results or speed.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/suite/tests/test_aot_v5e.py

The topology is described inside a fixture, never while the module is
imported (one process at a time may load the TPU's library).
"""
from math import comb

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: thermal.d3-r1: 606 stored features, 156 samples in 2 tasks, fp64, a
#: width-3 subspace of 600; kaggle.d2-r1: 447 features, 2400 samples, one
#: task, fp32, a width-2 subspace of 400
CELLS = {
    "thermal.d3-r1": dict(f=606, s=156, t=2, m=600, dtype=jnp.float64),
    "kaggle.d2-r1": dict(f=447, s=2400, t=1, m=400, dtype=jnp.float32),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("residuals", [1, 10])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_materialized_screen_compiles(one_chip, cell, residuals):
    from repro.engine.pallas_backend import _sis_topk_jit

    c = CELLS[cell]
    f, s, t, dt = c["f"], c["s"], c["t"], c["dtype"]
    args = (_shape(one_chip, (f, s), dt), _shape(one_chip, (t, s), dt),
            _shape(one_chip, (residuals * t, s), dt),
            _shape(one_chip, (t,), dt), _shape(one_chip, (f,), jnp.bool_))
    hlo = jax.jit(lambda v, m, yt, cnt, msk: _sis_topk_jit(
        v, m, yt, cnt, msk, residuals, 400)).lower(*args).compile().as_text()
    assert "dot" in hlo or "convolution" in hlo


def test_thermal_width3_gather_and_rescore_compile(one_chip):
    from repro.core.l0 import GramStats, score_tuples_gram
    from repro.kernels import ops as kops
    from repro.kernels.l0_gather import l0_gather_topk_pallas

    c = CELLS["thermal.d3-r1"]
    m, t, block, block_t = c["m"], c["t"], 65536, 256
    m_pad = kops._pad_to(m, 128)
    assert kops.gram_pack_nbytes(t, m) <= kops.GRAM_VMEM_BUDGET
    planes, bf16 = kops.GRAM_PLANES["float32"], jnp.bfloat16
    hlo = jax.jit(lambda tup, g, fs, b, sc: l0_gather_topk_pallas(
        tup, g, fs, b, sc, block, n=3, k=128, block_t=block_t)).lower(
        _shape(one_chip, (3, block), jnp.int32),
        _shape(one_chip, (planes, t, m_pad, m_pad), bf16),
        _shape(one_chip, (planes, t, m_pad), bf16),
        _shape(one_chip, (planes, t, m_pad), bf16),
        _shape(one_chip, (t, 8), jnp.float32)).compile().as_text()
    assert "tpu_custom_call" in hlo
    f64 = jnp.float64

    def rescore(g, fs, b, cnt, ys, yy, tup):
        stats = GramStats(gram=g, fsum=fs, b=b, n=cnt, ysum=ys, yty=yy, m=m)
        return score_tuples_gram(stats, tup)

    for rows in (512, block):       # the rescore window; a whole block
        jax.jit(rescore).lower(
            _shape(one_chip, (t, m, m), f64),
            *[_shape(one_chip, (t, m), f64)] * 2,
            *[_shape(one_chip, (t,), f64)] * 3,
            _shape(one_chip, (rows, 3), jnp.int32)).compile()


def test_kaggle_pairs_compile(one_chip):
    from repro.core.l0 import GramStats
    from repro.kernels import ops as kops

    c = CELLS["kaggle.d2-r1"]
    m, t, f32 = c["m"], c["t"], jnp.float32

    def pairs(g, fs, b, cnt, ys, yy, tup):
        stats = GramStats(gram=g, fsum=fs, b=b, n=cnt, ysum=ys, yty=yy, m=m)
        return kops.l0_score_pairs(stats, tup)

    jax.jit(pairs).lower(
        _shape(one_chip, (t, m, m), f32),
        *[_shape(one_chip, (t, m), f32)] * 2,
        *[_shape(one_chip, (t,), f32)] * 3,
        _shape(one_chip, (comb(m, 2), 2), jnp.int32)).compile()
