"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package has its reference here; tests sweep shapes and
dtypes and assert_allclose kernel-vs-oracle (interpret mode on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.operators import apply_op
from ..core.validity import value_rules_from_moments

_EPS = 1e-12
_DET_EPS = 1e-30


# ---------------------------------------------------------------------------
# fused feature-generation + SIS projection (kernel: fused_sis.py)
# ---------------------------------------------------------------------------

def fused_gen_sis_ref(
    op_id: int,
    a: jnp.ndarray,          # (B, S_pad) child-1 values (padding cols = 1.0)
    b: jnp.ndarray,          # (B, S_pad) child-2 values (== a for unary ops)
    membership: jnp.ndarray,  # (T, S_pad) 0/1 task mask (0 on padding)
    y_tilde: jnp.ndarray,     # (R*T, S_pad) per-task centered+normalized resid
    counts: jnp.ndarray,      # (T,)
    n_residuals: int,
    l_bound: float,
    u_bound: float,
) -> jnp.ndarray:
    """Scores (B,): max over residuals of mean-over-tasks |pearson r|.

    Invalid features (NaN/Inf, out-of-bounds max |value|, ~zero variance)
    score -inf.  This is the paper's P3 on-the-fly SIS with the value-rule
    check (P2 GPU side) fused in.
    """
    v = apply_op(op_id, a, b)                      # (B, S_pad)
    col_mask = (membership.sum(axis=0) > 0)        # (S_pad,) real samples
    vm = jnp.where(col_mask[None, :], v, 0.0)
    finite = jnp.where(col_mask[None, :], jnp.isfinite(v), True).all(axis=1)
    vm = jnp.where(jnp.isfinite(vm), vm, 0.0)
    max_abs = jnp.abs(vm).max(axis=1)

    sums = vm @ membership.T                       # (B, T)
    sumsq = (vm * vm) @ membership.T               # (B, T)
    dots = vm @ y_tilde.T                          # (B, R*T)

    var = sumsq - sums * sums / counts[None, :]
    var = jnp.maximum(var, 0.0)
    inv_norm = jax.lax.rsqrt(var + _EPS)
    bsz, t = sums.shape
    r = dots.reshape(bsz, n_residuals, t) * inv_norm[:, None, :]
    score = jnp.abs(r).mean(axis=2).max(axis=1)

    valid = value_rules_from_moments(
        finite, max_abs, sums, sumsq, counts, l_bound, u_bound
    )
    return jnp.where(valid & jnp.isfinite(score), score, -jnp.inf)


# ---------------------------------------------------------------------------
# ℓ0 pair scoring, closed form (kernel: l0_tile.py)
# ---------------------------------------------------------------------------

def solve3_sse(a, b, c, d, e, f, r1, r2, r3, yty):
    """SSE after solving the symmetric 3×3 system  M [c1 c2 c0]ᵀ = r.

        M = [[a, d, e],          r = [r1, r2, r3]
             [d, b, f],
             [e, f, c]]

    a=G_ii, b=G_jj, d=G_ij, e=Σx_i, f=Σx_j, c=n_samples, r1=x_i·y, r2=x_j·y,
    r3=Σy.  All broadcastable; used elementwise over (Bi, Bj) tiles on the
    VPU — the TPU replacement for the paper's per-thread QR (P4).
    """
    adj11 = b * c - f * f
    adj12 = e * f - d * c
    adj13 = d * f - b * e
    adj22 = a * c - e * e
    adj23 = d * e - a * f
    adj33 = a * b - d * d
    det = a * adj11 + d * adj12 + e * adj13
    safe = jnp.abs(det) > _DET_EPS
    inv_det = jnp.where(safe, 1.0 / jnp.where(safe, det, 1.0), 0.0)
    c1 = (adj11 * r1 + adj12 * r2 + adj13 * r3) * inv_det
    c2 = (adj12 * r1 + adj22 * r2 + adj23 * r3) * inv_det
    c3 = (adj13 * r1 + adj23 * r2 + adj33 * r3) * inv_det
    sse = yty - (c1 * r1 + c2 * r2 + c3 * r3)
    sse = jnp.where(safe & jnp.isfinite(sse), jnp.maximum(sse, 0.0), jnp.inf)
    return sse


# ---------------------------------------------------------------------------
# ℓ0 generic-width scoring, closed form (kernel: l0_gather.py)
# ---------------------------------------------------------------------------

def plane_roundoff(planes: int) -> float:
    """Relative rounding of a Gram-pack entry stored as ``planes`` bf16
    planes (``ops.pack_gram``): three carry its fp32 rounding exactly."""
    return max(2.0 ** -24, 2.0 ** -(8 * planes + 1))


def entry_error(k: int, planes: int) -> float:
    """Backward error per entry of a gathered k×k system, relative to
    sqrt(M_ii·M_jj): storage rounding of the pack plus the fp32
    elimination's γ_(k+1), with a 4x margin for the TPU's inexact
    reciprocal."""
    return plane_roundoff(planes) + 4.0 * (k + 2) * 2.0 ** -24


def gather_planes(planes, onehot):
    """fp32 ``Σ_i planes[i] @ onehot``: one-hot columns of a bf16-plane
    pack.  Each MXU product is one bf16 entry times 1 and the planes add
    up exactly, so the gather returns the packed value bit for bit —
    which an fp32 matmul at the TPU's default precision (one bf16 pass)
    would not, and one at ``HIGHEST`` costs six passes and VMEM for
    splitting the resident Gram on every grid step."""
    out = jnp.dot(planes[0], onehot, preferred_element_type=jnp.float32)
    for i in range(1, planes.shape[0]):
        out = out + jnp.dot(planes[i], onehot,
                            preferred_element_type=jnp.float32)
    return out


def gather_plane_columns(planes, onehot):
    """fp32 ``Σ_i onehot @ planes[i]ᵀ``: row r holds Gram column
    ``idx_r`` (``out[r, k] = G[k, idx_r]``) for a ``(rows, m_pad)`` one-hot
    of column indices.  The same exact selection as :func:`gather_planes`,
    laid out along lanes, so a panel row reads the entries the column
    gather reads — bit for bit, whether or not the stored G is exactly
    symmetric."""
    dims = (((1,), (1,)), ((), ()))
    out = jax.lax.dot_general(onehot, planes[0], dims,
                              preferred_element_type=jnp.float32)
    for i in range(1, planes.shape[0]):
        out = out + jax.lax.dot_general(onehot, planes[i], dims,
                                        preferred_element_type=jnp.float32)
    return out


#: least pivot, relative to its diagonal entry, of a system the bound
#: trusts: below it the computed coefficients (which the bound scales
#: with) may be far from the exact ones, and the tuple bounds at 0
MIN_REL_PIVOT = 2.0 ** -10


def eliminate_spd_sse(a, rhs, yty, entry_err, eps=1e-30):
    """Certified lower bound on the SSE of a k×k SPD least-squares system.

    ``a`` is a k×k nested list and ``rhs`` a length-k list of mutually
    broadcastable arrays — each entry is one coefficient *vectorized over a
    tile of tuples*, so every operation below is an elementwise VPU op and
    the loops unroll statically (k = n_dim+1, any width the backend lists
    in ``l0_widths``).  Shared by the Pallas gather kernel and its
    pure-jnp oracle.

    Gaussian elimination of the augmented SPD matrix M = [[A, r], [rᵀ,
    yty]] leaves the SSE ``yty - rᵀA⁻¹r`` as its last pivot.  Elimination
    without pivoting is backward stable on SPD matrices: the computed pivot
    is the exact SSE of some M + ΔM with |ΔM_ij| ≤ e·sqrt(M_ii·M_jj),
    e = ``entry_err`` (:func:`entry_error`), and such a ΔM moves the SSE by
    at most e·(Σ_p |c_p|·sqrt(A_pp) + sqrt(yty))² for the solution c.  The
    return value is the pivot minus twice that bound (the computed c stands
    in for the exact one), floored at 0: the exact SSE is never below it,
    so a prescreen that ranks by it can prove which tuples it may skip.  A
    system the bound cannot vouch for — a pivot under ``MIN_REL_PIVOT`` of
    its diagonal entry, or a non-finite result — bounds at 0 and is always
    left to the exact rescore.
    """
    k = len(rhs)
    diag = [a[p][p] for p in range(k)]
    a = [[a[i][j] for j in range(k)] for i in range(k)]
    rhs = list(rhs)
    sse = yty
    ok = True
    for p in range(k):
        piv = a[p][p]
        good = piv > jnp.maximum(MIN_REL_PIVOT * diag[p], eps)
        ok = good & ok
        inv = jnp.where(good, 1.0, 0.0) / jnp.where(good, piv, 1.0)
        for r in range(p + 1, k):
            f = a[r][p] * inv
            for c in range(p + 1, k):
                a[r][c] = a[r][c] - f * a[p][c]
            rhs[r] = rhs[r] - f * rhs[p]
        sse = sse - rhs[p] * inv * rhs[p]
    coef = [None] * k
    for p in range(k - 1, -1, -1):
        acc = rhs[p]
        for c in range(p + 1, k):
            acc = acc - a[p][c] * coef[c]
        coef[p] = acc * jnp.where(ok, 1.0, 0.0) / jnp.where(ok, a[p][p], 1.0)
    spread = jnp.sqrt(jnp.maximum(yty, 0.0))
    for p in range(k):
        spread = spread + jnp.abs(coef[p]) * jnp.sqrt(jnp.maximum(diag[p], 0.0))
    bound = sse - 2.0 * entry_err * spread * spread
    return jnp.where(ok & jnp.isfinite(bound), jnp.maximum(bound, 0.0), 0.0)


def gathered_system(g_cols, onehots, s_vals, b_vals, count, ysum):
    """Assemble the (n+1)×(n+1) normal equations for a tile of tuples.

    ``g_cols[p] = G @ onehot_p`` is the one-hot-matmul gather of Gram
    columns (the MXU-friendly gather: G[:, idx_p] as an (m_pad, B) panel);
    entries reduce out of it elementwise.  ``s_vals[p]``/``b_vals[p]`` are
    the gathered (1, B) feature sums and projections.  Returns (a, rhs) in
    the nested-list form ``eliminate_spd_sse`` takes.
    """
    n = len(onehots)
    k = n + 1
    a = [[None] * k for _ in range(k)]
    rhs = [None] * k
    for p in range(n):
        for q in range(p, n):
            e = jnp.sum(g_cols[p] * onehots[q], axis=0, keepdims=True)
            a[p][q] = e
            a[q][p] = e
        a[p][n] = s_vals[p]
        a[n][p] = s_vals[p]
        rhs[p] = b_vals[p]
    a[n][n] = count
    rhs[n] = ysum
    return a, rhs


def panel_system(cols, hits, gkk, s, b, count, ysum):
    """Assemble the (n+1)×(n+1) normal equations for a panel of tuples:
    one run per row (its (n-1)-prefix ``i_0..i_{n-2}``), the last index k
    along the lanes.

    ``cols[p]`` is the ``(rows, m_pad)`` panel of Gram columns ``G[k,
    i_p]`` (:func:`gather_plane_columns`) and ``hits[p]`` the mask ``lane
    == i_p``; ``gkk``/``s``/``b`` are the ``(1, m_pad)`` lane vectors
    ``G_kk``, ``s_k``, ``b_k``.  Prefix entries are per-row ``(rows, 1)``
    scalars read out of those panels, so every entry is the value
    :func:`gathered_system` assembles for the same tuple.  Returns (a,
    rhs) in the nested-list form ``eliminate_spd_sse`` takes.
    """
    def pick(x, hit):
        return jnp.sum(jnp.where(hit, x, 0.0), axis=1, keepdims=True)

    n = len(cols) + 1
    last = n - 1
    a = [[None] * (n + 1) for _ in range(n + 1)]
    rhs = [None] * (n + 1)
    for p in range(last):
        for q in range(p, last):
            a[p][q] = a[q][p] = pick(cols[p], hits[q])
        a[p][last] = a[last][p] = cols[p]
        a[p][n] = a[n][p] = pick(s, hits[p])
        rhs[p] = pick(b, hits[p])
    a[last][last] = gkk
    a[last][n] = a[n][last] = s
    rhs[last] = b
    a[n][n] = count
    rhs[n] = ysum
    return a, rhs


def l0_gather_sse_ref(
    gram: jnp.ndarray,   # (P, T, m_pad, m_pad) bf16 planes (zero-padded)
    fsum: jnp.ndarray,   # (P, T, m_pad)
    bvec: jnp.ndarray,   # (P, T, m_pad)
    scal: jnp.ndarray,   # (T, 8) fp32: [n, ysum, yty, 0, ...]
    tuples: jnp.ndarray,  # (B, n) int32
) -> jnp.ndarray:
    """Pure-jnp oracle for the gather kernel: same one-hot gathers, same
    elimination, whole batch at once.  Returns (B,) fp32 total SSE lower
    bounds (:func:`eliminate_spd_sse`)."""
    m_pad = gram.shape[2]
    n = tuples.shape[1]
    tup = tuples.T.astype(jnp.int32)                       # (n, B)
    iota = jax.lax.broadcasted_iota(jnp.int32, (m_pad, tup.shape[1]), 0)
    onehots = [(iota == tup[p][None, :]).astype(gram.dtype) for p in range(n)]
    err = entry_error(n + 1, gram.shape[0])
    total = jnp.zeros((1, tup.shape[1]), jnp.float32)
    for t in range(gram.shape[1]):
        a, rhs = gathered_system(
            [gather_planes(gram[:, t], oh) for oh in onehots], onehots,
            [gather_planes(fsum[:, t:t + 1], oh) for oh in onehots],
            [gather_planes(bvec[:, t:t + 1], oh) for oh in onehots],
            scal[t, 0], scal[t, 1],
        )
        total = total + eliminate_spd_sse(a, rhs, scal[t, 2], err)
    return total.reshape(-1)


def l0_pair_sse_ref(
    x: jnp.ndarray,        # (m, S) feature values, samples grouped by task
    y: jnp.ndarray,        # (S,)
    task_slices,           # ((lo, hi), ...)
    pairs: jnp.ndarray,    # (B, 2) int
) -> jnp.ndarray:
    """Total-SSE oracle for pair descriptors (per-task intercept fits)."""
    total = jnp.zeros((pairs.shape[0],), x.dtype)
    i, j = pairs[:, 0], pairs[:, 1]
    for lo, hi in task_slices:
        xt = x[:, lo:hi]
        yt = y[lo:hi]
        gii = (xt * xt).sum(axis=1)
        fsum = xt.sum(axis=1)
        b_ = xt @ yt
        n = float(hi - lo)
        ysum = yt.sum()
        yty = yt @ yt
        gij = (xt[i] * xt[j]).sum(axis=1)
        total = total + solve3_sse(
            gii[i], gii[j], n, gij, fsum[i], fsum[j], b_[i], b_[j], ysum, yty
        )
    return total
