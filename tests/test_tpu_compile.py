"""Compile-only checks of the main path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, and refuses what the chip would refuse
(unaligned blocks, 64-bit values inside a kernel, over-budget VMEM) — all of
which interpret mode accepts.  Nothing runs, so these tests say nothing
about results or speed.

The topology is described inside a module-scoped fixture, never while the
module is imported: only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import operators as om
from repro.core.distributed import _fused_sis_topk_fn
from repro.core.l0 import GramStats, score_tuples_gram
from repro.engine.pallas_backend import _sis_topk_jit
from repro.kernels import ops as kops
from repro.kernels.fused_sis import (
    fused_gen_sis_pallas, fused_gen_sis_topk_pallas,
)
from repro.kernels.l0_gather import (
    l0_gather_topk_pallas, l0_gather_tuples_pallas,
)
from repro.kernels.unrank import unrank_lex


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


#: (tasks, residuals, samples) at the thermal and Kaggle widths
SIS_WIDTHS = {"thermal": (2, 10, 156), "kaggle": (1, 10, 2400)}


@pytest.mark.parametrize("topk", [False, True], ids=["full", "topk"])
@pytest.mark.parametrize("width", sorted(SIS_WIDTHS))
def test_fused_sis_compiles(one_chip, width, topk):
    t, r, s = SIS_WIDTHS[width]
    s_pad = kops._pad_to(s, 128)
    block_b = kops.fused_sis_block_rows(s, 256)
    bp = 4 * block_b
    f32 = jnp.float32
    args = (_shape(one_chip, (bp, s_pad), f32),
            _shape(one_chip, (bp, s_pad), f32),
            _shape(one_chip, (t, s_pad), f32),
            _shape(one_chip, (r * t, s_pad), f32),
            _shape(one_chip, (1, t), f32))

    def run(a, b, m, yt, cnt):
        if topk:
            return fused_gen_sis_topk_pallas(
                om.DIV, a, b, m, yt, cnt, r, 1e-5, 1e8, epilogue_k=200,
                block_b=block_b, n_valid=bp - 7)
        return fused_gen_sis_pallas(om.DIV, a, b, m, yt, cnt, r, 1e-5, 1e8,
                                    block_b=block_b, n_valid=bp - 7)

    assert "tpu_custom_call" in _compile(run, *args)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("op", om.THERMAL_OPS)
def test_fused_sis_compiles_every_operator(one_chip, op, dtype):
    t, r, s = SIS_WIDTHS["thermal"]
    s_pad, block_b = kops._pad_to(s, 128), 256
    args = (_shape(one_chip, (2 * block_b, s_pad), dtype),
            _shape(one_chip, (2 * block_b, s_pad), dtype),
            _shape(one_chip, (t, s_pad), dtype),
            _shape(one_chip, (r * t, s_pad), dtype),
            _shape(one_chip, (1, t), jnp.float32))
    op_id = om.OP_BY_NAME[op].op_id
    hlo = _compile(
        lambda a, b, m, yt, cnt: fused_gen_sis_topk_pallas(
            op_id, a, b, m, yt, cnt, r, 1e-5, 1e8, epilogue_k=64,
            block_b=block_b),
        *args)
    assert "tpu_custom_call" in hlo


def _largest_m_pad(n_tasks: int) -> int:
    """Largest lane-aligned subspace the Gram-gather VMEM budget admits."""
    m = 128
    while kops.gram_pack_nbytes(n_tasks, m + 128) <= kops.GRAM_VMEM_BUDGET:
        m += 128
    return m


@pytest.mark.parametrize("topk", [False, True], ids=["full", "topk"])
@pytest.mark.parametrize("n_tasks", [1, 2])
def test_gram_gather_compiles_at_vmem_budget(one_chip, n_tasks, topk):
    m_pad, n, block_t = _largest_m_pad(n_tasks), 3, 256
    planes = kops.GRAM_PLANES["float32"]
    bf16 = jnp.bfloat16
    args = (_shape(one_chip, (n, 4 * block_t), jnp.int32),
            _shape(one_chip, (planes, n_tasks, m_pad, m_pad), bf16),
            _shape(one_chip, (planes, n_tasks, m_pad), bf16),
            _shape(one_chip, (planes, n_tasks, m_pad), bf16),
            _shape(one_chip, (n_tasks, 8), jnp.float32))

    def run(tup, gram, fsum, bvec, scal):
        if topk:
            return l0_gather_topk_pallas(tup, gram, fsum, bvec, scal,
                                         4 * block_t - 5, n=n, k=128,
                                         block_t=block_t)
        return l0_gather_tuples_pallas(tup, gram, fsum, bvec, scal, n=n,
                                       block_t=block_t)

    assert "tpu_custom_call" in _compile(run, *args)


def test_materialized_sis_topk_compiles_at_kaggle_width(one_chip):
    f, s, r = 1024, 2400, 10
    f32 = jnp.float32
    args = (_shape(one_chip, (f, s), f32), _shape(one_chip, (1, s), f32),
            _shape(one_chip, (r, s), f32), _shape(one_chip, (1,), f32),
            _shape(one_chip, (f,), jnp.bool_))
    hlo = _compile(
        lambda v, m, yt, c, msk: _sis_topk_jit(v, m, yt, c, msk, r, 300),
        *args)
    assert "dot" in hlo


def test_width3_enumeration_and_exact_rescore_compile(one_chip):
    """Device unranking and the fp64 Gram solve at the thermal width-3
    sweep (600 features, 65,536-tuple blocks).  Both are emulated-int64 /
    fp64 XLA programs whose compile time on a TPU once grew with the block
    (minutes); each now compiles in seconds."""
    m, n, block, t = 600, 3, 65536, 2
    unrank_lex.lower(_shape(one_chip, (block,), jnp.int64), m, n).compile()
    f64 = jnp.float64

    def rescore(g, fs, b, cnt, ys, yy, tup):
        stats = GramStats(gram=g, fsum=fs, b=b, n=cnt, ysum=ys, yty=yy, m=m)
        return score_tuples_gram(stats, tup)

    _compile(rescore, _shape(one_chip, (t, m, m), f64),
             *[_shape(one_chip, (t, m), f64)] * 2,
             *[_shape(one_chip, (t,), f64)] * 3,
             _shape(one_chip, (block, n), jnp.int32))


def test_fused_sis_shard_map_compiles_on_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    t, r, s = SIS_WIDTHS["thermal"]
    s_pad, block_b = kops._pad_to(s, 128), 256
    b_pad = 4 * 2 * block_b
    fn = _fused_sis_topk_fn(mesh, om.MUL, r, 200, 200, 1e-5, 1e8, block_b,
                            False, 200)

    def on(spec, shape, dtype):
        return _shape(NamedSharding(mesh, spec), shape, dtype)

    f32 = jnp.float32
    hlo = fn.lower(
        on(P("data", None), (b_pad, s_pad), f32),
        on(P("data", None), (b_pad, s_pad), f32),
        on(P(None, None), (t, s_pad), f32),
        on(P(None, None), (r * t, s_pad), f32),
        on(P(None, None), (1, t), f32),
        on(P("data"), (4,), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    # each chip holds a quarter of the candidate rows, and the k-sized
    # winner panels meet in a collective (the compiler may lower the
    # all-gather as an all-reduce)
    assert f"f32[{b_pad // 4},{s_pad}]" in hlo.split("\n", 1)[0]
    assert "all-gather" in hlo or "all-reduce" in hlo


def test_l0_reduced_shard_map_compiles_on_four_chips(topo):
    """The ``sharded:pallas`` width-3 ℓ0 reducer at the thermal width: the
    Gram-gather top-k kernel per chip, the winners' all-gather merge and
    the exclusion floor's ``pmin``."""
    from repro.core.distributed import make_l0_topk_reduced_fn
    from repro.core.sis import TaskLayout
    from repro.engine.pallas_backend import PallasBackend

    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    m, s, n, block = 600, 156, 3, 65536
    rng = np.random.default_rng(0)
    backend = PallasBackend(interpret=False)
    prob = backend.prepare_l0(
        rng.normal(size=(m, s)), rng.normal(size=s),
        TaskLayout.from_task_ids(np.repeat([0, 1], [75, 81])))
    k_local = backend.rescore_window(10)
    reducer, operands = backend.l0_device_reducer(prob, n, k_local)
    fn = make_l0_topk_reduced_fn(mesh, reducer, k_local, k_local,
                                 len(operands))

    def on(spec, shape, dtype):
        return _shape(NamedSharding(mesh, spec), shape, dtype)

    hlo = fn.lower(
        on(P("data", None), (block, n), jnp.int32),
        on(P("data"), (block,), jnp.bool_),
        *[on(P(*[None] * op.ndim), op.shape, op.dtype) for op in operands],
    ).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" in hlo or "all-reduce" in hlo
