"""Pallas TPU kernel: blocked Gram-gather ℓ0 scoring for tuple widths ≥ 3.

The pair kernel (l0_tile.py) recomputes Gram *tiles* on the MXU because the
pair space is the m×m upper triangle — Gram reuse is the whole win.  For
widths ≥ 3 the tuple space is C(m, n) ≫ m², so the economics flip: the full
per-task Gram statistics (G = X Xᵀ, s = X·1, b = X·y — a few hundred KB for
SIS-sized subspaces) fit **resident in VMEM** and each tuple's least-squares
problem is a *gather* of an (n+1)×(n+1) SPD system from them, O(n³) per
tuple with zero O(S) work (core/l0.py engine-2 math, blocked).  The gather
and the unrolled SPD elimination are parameterized over the width ``n`` —
any n ≥ 3 works; VMEM, not the kernel, is the practical ceiling.

Per grid step (one tile of ``block_t`` tuples):

    VMEM-resident:   G (P, T, m_pad, m_pad), s/b (P, T, m_pad) bf16
                     planes, scalars (T, 8) fp32
    HBM → VMEM:      tuple tile (n, block_t) int32  — device-enumerated
                     by kernels/unrank.py, so no host traffic at all
    compute:         one-hot(idx_p)                 VPU  (iota compare)
                     G·onehot_p                     MXU  (the gather)
                     (n+1)×(n+1) solve + SSE        VPU  (unrolled
                                                    Gaussian elimination,
                                                    ref.eliminate_spd_sse)
    VMEM → HBM:      per-tuple SSE bound (1, block_t)  [full variant]
                     top-k (vals, idx) (1, k_pad)      [reduced variant]

Gathering by one-hot matmul instead of dynamic indexing keeps the kernel
Mosaic-lowerable (TPU has no fast arbitrary gather) and turns the hot loop
into n dense (m_pad × m_pad)·(m_pad × block_t) matmuls per task — MXU work
proportional to tuples scored, independent of sample count.

Compute dtype: the Gram pack (G, s, b) is stored as bf16 *planes* that
sum to each entry (``ops.pack_gram``: three planes carry an fp32 value
exactly, one is a bf16 rounding).  One-hots are bf16, so every gather is a
few native single-pass MXU matmuls that select entries bit for bit
(``ref.gather_planes``).  The elimination stays fp32 (scalars are always
fp32).

Output: per tuple, a certified *lower bound* on its SSE (the fp32 SSE less
its backward-error bound, ``ref.eliminate_spd_sse``).  The backend rescores
the tuples with the lowest bounds from fp64 Gram stats, and a tuple left
out has an exact SSE at least its bound — so the backend can prove that no
tuple it skipped belongs in the top k, and rescore the whole block in fp64
when it cannot.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import (
    eliminate_spd_sse, entry_error, gather_planes, gathered_system,
)
from .spec import block_spec, x32_kernel
from .topk import block_topk

#: scoped VMEM the gather kernels may use.  The resident Gram planes are
#: double-buffered, and with the (m_pad, block_t) one-hot and gather
#: panels a pack at ``ops.GRAM_VMEM_BUDGET`` needs ~21 MB, over the 16 MiB
#: default; a v5e core has 128 MiB.
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def _tile_sse(tup, gram, fsum, bvec, scal, *, n: int, n_tasks: int):
    """(1, block_t) fp32 total-SSE lower bounds for one tile of width-n
    tuples (``ref.eliminate_spd_sse``)."""
    m_pad, block_t = gram.shape[2], tup.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (m_pad, block_t), 0)
    onehots = [
        (iota == tup[p : p + 1, :]).astype(gram.dtype) for p in range(n)
    ]
    err = entry_error(n + 1, gram.shape[0])
    total = jnp.zeros((1, block_t), jnp.float32)
    for t in range(n_tasks):  # static unroll over tasks
        a, rhs = gathered_system(
            [gather_planes(gram[:, t], oh) for oh in onehots], onehots,
            [gather_planes(fsum[:, t : t + 1], oh) for oh in onehots],
            [gather_planes(bvec[:, t : t + 1], oh) for oh in onehots],
            scal[t, 0], scal[t, 1],
        )
        total = total + eliminate_spd_sse(a, rhs, scal[t, 2], err)
    return total


@x32_kernel
def _kernel(
    tup_ref,    # (n, block_t) int32 tuple tile (transposed: lanes = tuples)
    gram_ref,   # (P, T, m_pad, m_pad) bf16 planes
    fsum_ref,   # (P, T, m_pad)
    b_ref,      # (P, T, m_pad)
    scal_ref,   # (T, 8) fp32: [n_samples, ysum, yty, 0, ...]
    sse_out,    # (1, block_t)
    *, n: int, n_tasks: int, m_pad: int, block_t: int,
):
    sse_out[...] = _tile_sse(
        tup_ref[...], gram_ref[...], fsum_ref[...], b_ref[...], scal_ref[...],
        n=n, n_tasks=n_tasks,
    )


@x32_kernel
def _kernel_topk(
    tup_ref, gram_ref, fsum_ref, b_ref, scal_ref, nv_ref, val_ref, idx_ref,
    *, n: int, n_tasks: int, m_pad: int, block_t: int, k: int, k_pad: int,
):
    base = pl.program_id(0) * block_t
    total = _tile_sse(
        tup_ref[...], gram_ref[...], fsum_ref[...], b_ref[...], scal_ref[...],
        n=n, n_tasks=n_tasks,
    )
    # padding tuples are killed *in-kernel*: global tile position >= n_valid
    # becomes +inf, so the top-k epilogue can never select one
    rows = base + jax.lax.broadcasted_iota(jnp.int32, (1, block_t), 1)
    total = jnp.where(rows < nv_ref[0, 0], total, jnp.inf)
    vals, pos = block_topk(total, k, k_pad, largest=False)
    val_ref[...] = vals
    idx_ref[...] = jnp.where(pos >= 0, base + pos, -1)


@functools.partial(
    jax.jit, static_argnames=("n", "block_t", "interpret")
)
def l0_gather_tuples_pallas(
    tuples_t: jnp.ndarray,   # (n, b_pad) int32, b_pad % block_t == 0
    gram: jnp.ndarray,       # (P, T, m_pad, m_pad) bf16, m_pad % 128 == 0
    fsum: jnp.ndarray,       # (P, T, m_pad)
    bvec: jnp.ndarray,       # (P, T, m_pad)
    scal: jnp.ndarray,       # (T, 8) fp32
    n: int,
    block_t: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-tuple total SSE (b_pad,) fp32 for a padded tuple block."""
    planes, t, m_pad, _ = gram.shape
    b_pad = tuples_t.shape[1]
    assert b_pad % block_t == 0 and m_pad % 128 == 0
    ntiles = b_pad // block_t
    kern = functools.partial(
        _kernel, n=n, n_tasks=t, m_pad=m_pad, block_t=block_t
    )
    sse = pl.pallas_call(
        kern,
        grid=(ntiles,),
        in_specs=[
            block_spec((n, block_t), lambda i: (0, i)),
            block_spec((planes, t, m_pad, m_pad), lambda i: (0, 0, 0, 0)),
            block_spec((planes, t, m_pad), lambda i: (0, 0, 0)),
            block_spec((planes, t, m_pad), lambda i: (0, 0, 0)),
            block_spec((t, 8), lambda i: (0, 0)),
        ],
        out_specs=block_spec((1, block_t), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, b_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tuples_t, gram, fsum, bvec, scal)
    return sse.reshape(-1)


@functools.partial(
    jax.jit, static_argnames=("n", "k", "block_t", "interpret")
)
def l0_gather_topk_pallas(
    tuples_t: jnp.ndarray,   # (n, b_pad) int32, b_pad % block_t == 0
    gram: jnp.ndarray,       # (P, T, m_pad, m_pad) bf16, m_pad % 128 == 0
    fsum: jnp.ndarray,       # (P, T, m_pad)
    bvec: jnp.ndarray,       # (P, T, m_pad)
    scal: jnp.ndarray,       # (T, 8) fp32
    nv,                      # real tuple count (int or traced scalar)
    n: int,
    k: int,
    block_t: int = 256,
    interpret: bool = False,
):
    """Reduced-epilogue variant: each tile writes only its k best (lowest
    SSE) tuples as ``(vals (ntiles, k_pad) fp32, gidx (ntiles, k_pad)
    int32)`` winner panels for :func:`..kernels.topk.merge_block_topk`
    (``largest=False``).  Padding tuples (tile position >= ``nv``) are +inf
    in-kernel and can never be selected."""
    planes, t, m_pad, _ = gram.shape
    b_pad = tuples_t.shape[1]
    assert b_pad % block_t == 0 and m_pad % 128 == 0
    ntiles = b_pad // block_t
    k = max(1, min(int(k), block_t))
    k_pad = ((k + 127) // 128) * 128
    nv_arr = jnp.asarray(nv, jnp.int32).reshape(1, 1)
    kern = functools.partial(
        _kernel_topk, n=n, n_tasks=t, m_pad=m_pad, block_t=block_t,
        k=k, k_pad=k_pad,
    )
    vals, gidx = pl.pallas_call(
        kern,
        grid=(ntiles,),
        in_specs=[
            block_spec((n, block_t), lambda i: (0, i)),
            block_spec((planes, t, m_pad, m_pad), lambda i: (0, 0, 0, 0)),
            block_spec((planes, t, m_pad), lambda i: (0, 0, 0)),
            block_spec((planes, t, m_pad), lambda i: (0, 0, 0)),
            block_spec((t, 8), lambda i: (0, 0)),
            block_spec((1, 1), lambda i: (0, 0)),
        ],
        # lane-major panels: tile i owns lanes [i*k_pad, (i+1)*k_pad)
        out_specs=(
            block_spec((1, k_pad), lambda i: (0, i)),
            block_spec((1, k_pad), lambda i: (0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, ntiles * k_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, ntiles * k_pad), jnp.int32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(tuples_t, gram, fsum, bvec, scal, nv_arr)
    return vals.reshape(ntiles, k_pad), gidx.reshape(ntiles, k_pad)
