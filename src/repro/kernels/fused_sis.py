"""Pallas TPU kernel: fused on-the-fly feature generation + SIS projection.

Paper mapping: P1 (operator-outer-loop batched evaluation) + P2 (value-rule
validity fused with evaluation) + P3 (on-the-fly last rung) — deepened: the
candidate block's values live only in VMEM; they are generated, validated,
correlated against the residuals and *discarded*, never touching HBM.  The
paper's GPU version still round-trips global memory between the evaluation
and the Pearson pass ("re-evaluation and the subsequent Pearson correlation
calculation are performed consecutively on the GPU").

Layout (one grid step = one block of `block_b` candidates):

    HBM -> VMEM streams:  A, B        (block_b, s_pad)   child values
    VMEM-resident:        M (T,s_pad) task membership, Yt (R*T,s_pad)
    compute:              V = op(A,B)                     VPU
                          sums/sumsq/dots = V @ {M,Yt}ᵀ   MXU
                          epilogue: r, |r| mean/max, validity -> score
    VMEM -> HBM:          scores (1, block_b)           [full variant]
                          top-k (vals, idx) (1, k_pad)  [reduced variant]

Tiles are (8·k, 128·k)-aligned; the sample axis is padded to a multiple of
128 with neutral values (1.0 for children — safe for every operator domain —
and 0 rows in M/Yt so padding never contributes).

Compute dtype: A/B/M/Yt arrive in the backend's kernel dtype (bf16 under
``precision="bf16"``, fp32 otherwise).  Candidate values are generated,
masked and validity-checked in fp32 (the v5e VPU has no bf16) and cast to
that dtype for the MXU; every matmul accumulates in fp32 via
``preferred_element_type`` and the score epilogue is pure fp32.  bf16 shares
fp32's exponent range, so validity/overflow behaviour is unchanged; only
mantissa noise differs.

Layout for Mosaic: the screen's dots contract both operands on the sample
axis (``M · Vᵀ``), so every per-candidate quantity is a lane-major
``(1, block_b)`` row and the outputs are written as ``(1, nb·block_b)``
(or ``(1, nb·k_pad)``) rows, reshaped outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import operators as om
from ..core.operators import apply_op
from ..core.validity import variance_ok_from_moments
from .spec import block_spec, x32_kernel
from .topk import block_topk

_EPS = 1e-12
#: rsqrt guard by compute dtype.  bf16 products carry ~1e-2..1e-3 relative
#: noise into the fp32-accumulated moments, so the fp32-era epsilon would
#: let pure-noise variances pass through the normalization as huge scores.
_EPS_BY_DTYPE = {"float32": 1e-12, "bfloat16": 1e-6}


def _nt_dot(lhs, rhs):
    """``lhs (M, s) · rhs (B, s)ᵀ -> (M, B)`` fp32 on the MXU.

    Contracting both operands on the sample axis puts the candidates on the
    lanes of the result, so every per-candidate quantity below is a
    lane-major ``(1, B)`` row and the block's score row is written out with
    no relayout.  ``HIGHEST`` keeps fp32 operands at fp32 on the TPU MXU
    (its default is bf16 passes); bf16 operands take the default.
    """
    hi = lhs.dtype == jnp.float32
    return jax.lax.dot_general(
        lhs, rhs, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if hi else None,
        preferred_element_type=jnp.float32,
    )


def _kernel_op(op_id: int, a, b):
    """:func:`apply_op` in a form Mosaic lowers.

    Pallas TPU has no ``cbrt``: the cube root is ``sign(a)·exp(ln|a| / 3)``,
    within a few fp32 ulps of ``jnp.cbrt``.
    """
    if op_id != om.CBRT:
        return apply_op(op_id, a, b)
    r = jnp.exp(jnp.log(jnp.abs(a)) / 3.0)
    return jnp.where(a < 0, -r, r)


def _block_scores(
    a, b, m, yt, cnt, nv, *, op_id: int, n_tasks: int, n_residuals: int,
    l_bound: float, u_bound: float, first_row,
):
    """Masked (1, B) fp32 score row for one block; -inf marks invalid rows."""
    f32, dt = jnp.float32, a.dtype
    # values are generated, masked and checked in fp32 (the v5e VPU has no
    # bf16, and Mosaic's finiteness test and sin/cos take fp32 only); the
    # MXU operands are then cast to the compute dtype
    v32 = _kernel_op(op_id, a.astype(f32), b.astype(f32))   # (B, s_pad)
    col_mask = m.astype(f32).sum(axis=0, keepdims=True) > 0  # real samples
    vm32 = jnp.where(col_mask & jnp.isfinite(v32), v32, 0.0)
    vm = vm32.astype(dt)

    sums = _nt_dot(m, vm)                           # (T, B)
    sumsq = _nt_dot(m, vm * vm)                     # (T, B)
    dots = _nt_dot(yt, vm)                          # (R*T, B), row r*T + t
    # the value rule's finiteness and max-|v| bounds as per-row counts of
    # 0/1 indicators over real samples (membership rows cover each real
    # sample once): "all finite and max|v| <= u" <=> no real entry fails
    # |v| <= u (NaN/inf fail it); "max|v| >= l" <=> some entry has
    # |v| >= l.  Counting on the MXU keeps the result lane-major and
    # never reduces a bool vector.
    bad = jnp.where(col_mask & ~(jnp.abs(v32) <= u_bound), 1.0, 0.0)
    big = jnp.where(jnp.abs(vm32) >= l_bound, 1.0, 0.0)
    n_bad = _nt_dot(m, bad.astype(dt)).sum(axis=0, keepdims=True)  # (1, B)
    n_big = _nt_dot(m, big.astype(dt)).sum(axis=0, keepdims=True)

    eps = _EPS_BY_DTYPE.get(str(dt), _EPS)
    inv_norm = []
    for t in range(n_tasks):
        s_t = sums[t : t + 1]
        var = jnp.maximum(sumsq[t : t + 1] - s_t * s_t / cnt[:, t : t + 1],
                          0.0)
        inv_norm.append(jax.lax.rsqrt(var + eps))
    # max over residuals of mean over tasks |r|, by static row slices
    score = None
    for r in range(n_residuals):
        acc = None
        for t in range(n_tasks):
            row = r * n_tasks + t
            term = jnp.abs(dots[row : row + 1] * inv_norm[t])
            acc = term if acc is None else acc + term
        score = acc if score is None else jnp.maximum(score, acc)
    score = score / n_tasks                          # (1, B)

    valid = ((n_bad == 0) & (n_big > 0)
             & variance_ok_from_moments(sums, sumsq, cnt, axis=0,
                                        keepdims=True)
             & jnp.isfinite(score))
    # padding rows are invalidated *in-kernel*: their global row index
    # (grid step * block + lane) is >= n_valid, so the in-kernel top-k
    # epilogue / a device-side top-k downstream can never select one
    rows = first_row + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
    valid = valid & (rows < nv)
    return jnp.where(valid, score, -jnp.inf)


@x32_kernel
def _kernel(
    a_ref, b_ref, m_ref, yt_ref, cnt_ref, nv_ref, out_ref,
    *, op_id: int, n_tasks: int, n_residuals: int,
    l_bound: float, u_bound: float,
):
    bsz = a_ref.shape[0]
    score = _block_scores(
        a_ref[...], b_ref[...], m_ref[...], yt_ref[...], cnt_ref[...],
        nv_ref[0, 0], op_id=op_id, n_tasks=n_tasks, n_residuals=n_residuals,
        l_bound=l_bound, u_bound=u_bound,
        first_row=pl.program_id(0) * bsz,
    )
    out_ref[...] = score


@x32_kernel
def _kernel_topk(
    a_ref, b_ref, m_ref, yt_ref, cnt_ref, nv_ref, val_ref, idx_ref,
    *, op_id: int, n_tasks: int, n_residuals: int,
    l_bound: float, u_bound: float, k: int, k_pad: int,
):
    bsz = a_ref.shape[0]
    base = pl.program_id(0) * bsz
    score = _block_scores(
        a_ref[...], b_ref[...], m_ref[...], yt_ref[...], cnt_ref[...],
        nv_ref[0, 0], op_id=op_id, n_tasks=n_tasks, n_residuals=n_residuals,
        l_bound=l_bound, u_bound=u_bound, first_row=base,
    )
    vals, pos = block_topk(score, k, k_pad, largest=True)
    val_ref[...] = vals
    idx_ref[...] = jnp.where(pos >= 0, base + pos, -1)


def fused_gen_sis_pallas(
    op_id: int,
    a: jnp.ndarray,          # (B_pad, s_pad) compute dtype, B_pad % block_b == 0
    b: jnp.ndarray,
    membership: jnp.ndarray,  # (T, s_pad)
    y_tilde: jnp.ndarray,     # (R*T, s_pad)
    counts: jnp.ndarray,      # (1, T) fp32
    n_residuals: int,
    l_bound: float,
    u_bound: float,
    block_b: int = 256,
    interpret: bool = False,
    n_valid=None,  # real candidate rows (int or traced scalar); None -> all
) -> jnp.ndarray:
    bp, s_pad = a.shape
    t = membership.shape[0]
    assert bp % block_b == 0 and s_pad % 128 == 0, (bp, block_b, s_pad)
    nb = bp // block_b
    if n_valid is None:
        n_valid = bp
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    kern = functools.partial(
        _kernel, op_id=op_id, n_tasks=t, n_residuals=n_residuals,
        l_bound=float(l_bound), u_bound=float(u_bound),
    )
    out = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            block_spec((block_b, s_pad), lambda i: (i, 0)),
            block_spec((block_b, s_pad), lambda i: (i, 0)),
            block_spec((t, s_pad), lambda i: (0, 0)),
            block_spec((y_tilde.shape[0], s_pad), lambda i: (0, 0)),
            block_spec((1, t), lambda i: (0, 0)),
            block_spec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=block_spec((1, block_b), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, bp), jnp.float32),
        interpret=interpret,
    )(a, b, membership, y_tilde, counts, nv)
    return out.reshape(-1)


def fused_gen_sis_topk_pallas(
    op_id: int,
    a: jnp.ndarray,          # (B_pad, s_pad) compute dtype, B_pad % block_b == 0
    b: jnp.ndarray,
    membership: jnp.ndarray,  # (T, s_pad)
    y_tilde: jnp.ndarray,     # (R*T, s_pad)
    counts: jnp.ndarray,      # (1, T) fp32
    n_residuals: int,
    l_bound: float,
    u_bound: float,
    epilogue_k: int,
    block_b: int = 256,
    interpret: bool = False,
    n_valid=None,
):
    """Reduced-epilogue variant: each grid step writes only its top-k.

    Returns ``(vals (nb, k_pad) fp32, gidx (nb, k_pad) int32)`` — per-block
    winner panels with *global* candidate indices, ready for
    :func:`..kernels.topk.merge_block_topk`.  HBM writes drop from
    O(block_b) to O(k_pad) per grid step; invalid and padding rows are -inf
    in-kernel and can never be selected.
    """
    bp, s_pad = a.shape
    t = membership.shape[0]
    assert bp % block_b == 0 and s_pad % 128 == 0, (bp, block_b, s_pad)
    nb = bp // block_b
    k = max(1, min(int(epilogue_k), block_b))
    k_pad = ((k + 127) // 128) * 128
    if n_valid is None:
        n_valid = bp
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1, 1)
    kern = functools.partial(
        _kernel_topk, op_id=op_id, n_tasks=t, n_residuals=n_residuals,
        l_bound=float(l_bound), u_bound=float(u_bound), k=k, k_pad=k_pad,
    )
    vals, gidx = pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            block_spec((block_b, s_pad), lambda i: (i, 0)),
            block_spec((block_b, s_pad), lambda i: (i, 0)),
            block_spec((t, s_pad), lambda i: (0, 0)),
            block_spec((y_tilde.shape[0], s_pad), lambda i: (0, 0)),
            block_spec((1, t), lambda i: (0, 0)),
            block_spec((1, 1), lambda i: (0, 0)),
        ],
        # lane-major panels: grid step i owns lanes [i*k_pad, (i+1)*k_pad)
        out_specs=(
            block_spec((1, k_pad), lambda i: (0, i)),
            block_spec((1, k_pad), lambda i: (0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, nb * k_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, nb * k_pad), jnp.int32),
        ),
        interpret=interpret,
    )(a, b, membership, y_tilde, counts, nv)
    return vals.reshape(nb, k_pad), gidx.reshape(nb, k_pad)
