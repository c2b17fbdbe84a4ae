"""Distributed SISSO phases over a (pod, data, model) mesh.

Mapping (DESIGN.md §4):
* `data` (+`pod`)  — candidate axis: SIS feature blocks / ℓ0 tuple blocks.
* `model`          — sample axis: Gram & projection partial sums, `psum`ed.

The heavy inner loops are collective-free; only O(k) score/argmin payloads
cross devices (vs the paper's serial gather/redistribute of features).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .sis import (
    ScoreContext, TaskLayout, scores_from_reductions, screen_reductions,
)


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _sample_axis(mesh: Mesh) -> Optional[str]:
    """Sample-sharding axis, or None when samples are replicated."""
    return "model" if "model" in mesh.axis_names else None


def _n_dp(mesh: Mesh) -> int:
    dp = _dp_axes(mesh)
    return int(np.prod([mesh.shape[a] for a in dp])) if dp else 1


def _shard_index(dp: Tuple[str, ...]):
    """Linearized shard index over the candidate axes (inside shard_map)."""
    return jax.lax.axis_index(dp[0] if len(dp) == 1 else dp)


@functools.lru_cache(maxsize=None)
def _sis_sharded_fn(mesh: Mesh, n_residuals: int):
    """Compiled sharded SIS scorer, cached per (mesh, n_residuals).

    The cache keeps the jitted closure alive across blocks — a fresh
    closure per call would retrace and recompile every block.
    """
    dp = _dp_axes(mesh)
    sample_ax = _sample_axis(mesh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, sample_ax), P(None, sample_ax), P(None, sample_ax),
                  P(None), P(dp)),
        out_specs=P(dp),
    )
    def local(x_blk, m_blk, yt_blk, counts, mask_blk):
        sums, sumsq, dots = screen_reductions(x_blk, m_blk, yt_blk)
        if sample_ax is not None:
            sums = jax.lax.psum(sums, sample_ax)
            sumsq = jax.lax.psum(sumsq, sample_ax)
            dots = jax.lax.psum(dots, sample_ax)
        scores = scores_from_reductions(sums, sumsq, dots, counts, n_residuals)
        # padding/masked rows are killed *inside* the sharded fn so a
        # device-side top-k can never select one — host slice-off is not a
        # defense once only winners cross the boundary
        return jnp.where(mask_blk, scores, -jnp.inf)

    return jax.jit(local)


def sis_scores_sharded(
    mesh: Mesh,
    x: jnp.ndarray,  # (F, S) candidate values; F % n_data_shards == 0
    ctx: ScoreContext,
    row_mask: Optional[jnp.ndarray] = None,  # (F,) bool; False -> -inf
) -> jnp.ndarray:
    """Full score vector (F,) with features sharded over data(+pod).

    Unlike :func:`sis_topk_sharded` (which merges a local top-k on device),
    this returns every score so the engine layer can apply the same
    host-side TopK policy as every other backend.  Samples shard over
    'model' when the mesh has that axis (partial sums psum'ed); otherwise
    they are replicated and the screen is collective-free.  ``row_mask``
    marks real rows: padding (and excluded) rows score ``-inf`` on device.
    """
    fn = _sis_sharded_fn(mesh, ctx.n_residuals)
    if row_mask is None:
        row_mask = jnp.ones((x.shape[0],), bool)
    return fn(
        x,
        jnp.asarray(ctx.membership, x.dtype),
        jnp.asarray(ctx.y_tilde, x.dtype),
        jnp.asarray(ctx.counts, x.dtype),
        jnp.asarray(row_mask, bool),
    )


@functools.lru_cache(maxsize=None)
def _sis_topk_fn(mesh: Mesh, n_residuals: int, k_local: int, k_merge: int):
    """Compiled sharded SIS screen with the merge *on device*: per-shard
    scores -> local top-``k_local`` -> ``all_gather`` of k-sized (score,
    index) payloads over the candidate axes -> replicated top-``k_merge``.
    Only O(k) winners ever leave the device mesh."""
    dp = _dp_axes(mesh)
    sample_ax = _sample_axis(mesh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, sample_ax), P(None, sample_ax), P(None, sample_ax),
                  P(None), P(dp)),
        out_specs=(P(None), P(None)),
        check_vma=False,
    )
    def local(x_blk, m_blk, yt_blk, counts, mask_blk):
        sums, sumsq, dots = screen_reductions(x_blk, m_blk, yt_blk)
        if sample_ax is not None:
            sums = jax.lax.psum(sums, sample_ax)
            sumsq = jax.lax.psum(sumsq, sample_ax)
            dots = jax.lax.psum(dots, sample_ax)
        scores = scores_from_reductions(sums, sumsq, dots, counts, n_residuals)
        scores = jnp.where(mask_blk, scores, -jnp.inf)
        vals, sel = jax.lax.top_k(scores, k_local)
        gidx = scores.shape[0] * _shard_index(dp) + sel
        gv = jax.lax.all_gather(vals, dp, tiled=True)    # (nd * k_local,)
        gi = jax.lax.all_gather(gidx, dp, tiled=True)
        v2, s2 = jax.lax.top_k(gv, k_merge)
        return v2, gi[s2]

    return jax.jit(local)


def sis_topk_sharded(
    mesh: Mesh,
    x: jnp.ndarray,                 # (F, S); F % n_data_shards == 0
    ctx: ScoreContext,
    row_mask: jnp.ndarray,          # (F,) bool; padding/excluded rows False
    n_keep: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Device-merged top-``n_keep`` (scores desc, global indices).

    The general-mesh form of the k-sized all-gather merge: candidates shard
    over data(+pod), samples over 'model' when present.  Masked rows can
    never win (in-shard ``-inf``); the host receives exactly
    ``min(n_keep, nd·k_local)`` entries and the caller drops ``-inf`` tails.
    """
    f = int(x.shape[0])
    nd = _n_dp(mesh)
    assert f % nd == 0, (f, nd)
    k_local = min(int(n_keep), f // nd)
    k_merge = min(int(n_keep), nd * k_local)
    fn = _sis_topk_fn(mesh, ctx.n_residuals, k_local, k_merge)
    vals, idx = fn(
        x,
        jnp.asarray(ctx.membership, x.dtype),
        jnp.asarray(ctx.y_tilde, x.dtype),
        jnp.asarray(ctx.counts, x.dtype),
        jnp.asarray(row_mask, bool),
    )
    return np.asarray(vals, np.float64), np.asarray(idx)


@functools.lru_cache(maxsize=None)
def _l0_pairs_sharded_fn(mesh: Mesh, n_tasks: int):
    """Compiled sharded pair scorer, cached per (mesh, n_tasks)."""
    from ..kernels.ref import solve3_sse

    dp = _dp_axes(mesh)
    sample_ax = _sample_axis(mesh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, sample_ax), P(sample_ax), P(None, sample_ax),
                  P(dp, None), P(dp)),
        out_specs=P(dp),
    )
    def local(x_blk, y_blk, mem_blk, prs, vld):
        def ps(v):
            return jax.lax.psum(v, sample_ax) if sample_ax is not None else v

        i, j = prs[:, 0], prs[:, 1]
        total = jnp.zeros((prs.shape[0],), x_blk.dtype)
        for ti in range(n_tasks):
            w = mem_blk[ti]
            xw = x_blk * w[None, :]
            gii = ps((xw * x_blk).sum(axis=1))
            fsum = ps(xw.sum(axis=1))
            bv = ps(xw @ y_blk)
            n = ps(w.sum())
            ysum = ps(w @ y_blk)
            yty = ps((w * y_blk) @ y_blk)
            gij = ps((xw[i] * x_blk[j]).sum(axis=1))
            total = total + solve3_sse(
                gii[i], gii[j], n, gij, fsum[i], fsum[j],
                bv[i], bv[j], ysum, yty)
        # padding pairs are +inf *inside* the sharded fn: a device-side
        # top-k must never pick a benign-padding solve as a winner
        return jnp.where(vld, total, jnp.inf)

    return jax.jit(local)


def l0_pair_sses_sharded(
    mesh: Mesh,
    x: jnp.ndarray,      # (m, S) subspace features
    y: jnp.ndarray,      # (S,)
    layout: TaskLayout,
    pairs: jnp.ndarray,  # (B, 2) int32; B % n_data_shards == 0
    valid: Optional[jnp.ndarray] = None,  # (B,) bool; False -> +inf
) -> jnp.ndarray:
    """Total SSE (B,) for explicit pairs, tuple space sharded over data(+pod).

    The per-shard math is the same closed-form solve as the Pallas tile
    kernel (kernels/ref.py:solve3_sse); per-task Gram partials psum over
    'model' when the mesh shards samples.  Rows where ``valid`` is False
    (padding pairs) come back ``+inf`` — masked on device, not host-sliced.
    """
    mem = jnp.asarray(layout.membership(x.shape[1], np.float64), x.dtype)
    fn = _l0_pairs_sharded_fn(mesh, layout.n_tasks)
    if valid is None:
        valid = jnp.ones((pairs.shape[0],), bool)
    return fn(x, y, mem, pairs, jnp.asarray(valid, bool))


# ---------------------------------------------------------------------------
# generic ℓ0 device-merged top-k: any width, any (traceable) scorer
# ---------------------------------------------------------------------------

def make_l0_topk_fn(mesh: Mesh, scorer, k_local: int, k_merge: int,
                    n_operands: int):
    """Build the compiled sharded ℓ0 block reducer for one sweep.

    ``scorer(tuples_blk, *operands) -> sse (b_local,)`` is any traceable
    scoring function (jnp Gram closed form, batched QR, …); ``operands``
    are replicated device arrays (Gram statistics are tiny — (T, m, m) —
    so replication is the right call; the *tuple space* is what shards).
    Per shard: score -> mask padding to +inf -> local top-``k_local`` ->
    all-gather the k-sized (sse, index) payloads over data(+pod) ->
    replicated top-``k_merge``.  The caller caches the returned closure per
    sweep (``L0Problem.cache``) exactly like the single-device jit paths.
    """
    dp = _dp_axes(mesh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, None), P(dp)) + (P(),) * n_operands,
        out_specs=(P(None), P(None)),
        check_vma=False,
    )
    def local(tup_blk, vld_blk, *ops):
        sse = scorer(tup_blk, *ops)
        sse = jnp.where(vld_blk, sse, jnp.inf)
        neg, sel = jax.lax.top_k(-sse, k_local)
        gidx = sse.shape[0] * _shard_index(dp) + sel
        gv = jax.lax.all_gather(neg, dp, tiled=True)
        gi = jax.lax.all_gather(gidx, dp, tiled=True)
        n2, s2 = jax.lax.top_k(gv, k_merge)
        return -n2, gi[s2]

    return jax.jit(local)


def make_l0_topk_reduced_fn(mesh: Mesh, reducer, k_local: int, k_merge: int,
                            n_operands: int):
    """Reduced-epilogue variant of :func:`make_l0_topk_fn`.

    ``reducer(tuples_blk, valid_blk, *operands) -> (sse (k_local,), local_idx
    (k_local,), floor)`` runs a *kernel-side* top-k (e.g. the Pallas
    Gram-gather reduced epilogue via ``Backend.l0_device_reducer``) so the
    full per-shard SSE vector never reaches HBM — only k-sized panels.  The
    reducer masks its own padding (valid rows form a global prefix, so each
    shard derives its live count from ``valid_blk``) and returns ascending
    fp32 SSE bounds with ``+inf`` sentinels and the floor under every bound
    it left out; indices are shard-local and lifted to global row numbers
    here before the all-gather merge.  Returns ``(bounds (k_merge,), gidx
    (k_merge,), floor)``, the floor taken over every shard and the merge.
    Because the reducer is an fp32 prescreen, the caller rescores the
    merged survivors in fp64 and certifies them against the floor.
    """
    dp = _dp_axes(mesh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, None), P(dp)) + (P(),) * n_operands,
        out_specs=(P(None), P(None), P()),
        check_vma=False,
    )
    def local(tup_blk, vld_blk, *ops):
        sse, lidx, floor = reducer(tup_blk, vld_blk, *ops)
        gidx = tup_blk.shape[0] * _shard_index(dp) + lidx
        gv = jax.lax.all_gather(-sse, dp, tiled=True)
        gi = jax.lax.all_gather(gidx, dp, tiled=True)
        n2, s2 = jax.lax.top_k(gv, k_merge)
        floor = jax.lax.pmin(floor, dp)
        cut = jnp.sum(jnp.isfinite(gv)) > jnp.sum(jnp.isfinite(n2))
        floor = jnp.where(cut, jnp.minimum(floor, -n2[-1]), floor)
        return -n2, gi[s2], floor

    return jax.jit(local)


def gram_topk_scorer(m: int):
    """Traceable Gram-closed-form scorer for :func:`make_l0_topk_fn`.

    Operand order matches :func:`gram_operands`; ``m`` (subspace size) is
    static so the rebuilt :class:`GramStats` has a concrete shape."""
    from .l0 import GramStats, score_tuples_gram

    def scorer(tup_blk, gram, fsum, b, n, ysum, yty):
        stats = GramStats(gram=gram, fsum=fsum, b=b, n=n, ysum=ysum,
                          yty=yty, m=m)
        return score_tuples_gram(stats, tup_blk)

    return scorer


def gram_operands(stats) -> Tuple[jnp.ndarray, ...]:
    return (stats.gram, stats.fsum, stats.b, stats.n, stats.ysum, stats.yty)


def qr_topk_scorer(layout: TaskLayout, dtype):
    """Traceable paper-faithful QR scorer (operands: x (m, S), y (S,))."""
    from .l0 import score_tuples_qr

    def scorer(tup_blk, x, y):
        return score_tuples_qr(x, y, layout, tup_blk, dtype)

    return scorer


def overlap_topk_scorer():
    """Traceable classification overlap scorer for :func:`make_l0_topk_fn`.

    Operand order matches :func:`overlap_operands`; static loop counts
    come from the replicated operand shapes at trace time."""
    from .problem import ClassStats, score_tuples_overlap

    def scorer(tup_blk, task_mem, class_mem, cmin, cmax, x):
        stats = ClassStats(task_mem=task_mem, class_mem=class_mem,
                           cmin=cmin, cmax=cmax, x=x)
        return score_tuples_overlap(stats, tup_blk)

    return scorer


def overlap_operands(cstats) -> Tuple[jnp.ndarray, ...]:
    return (jnp.asarray(cstats.task_mem), jnp.asarray(cstats.class_mem),
            jnp.asarray(cstats.cmin), jnp.asarray(cstats.cmax),
            jnp.asarray(cstats.x))


# ---------------------------------------------------------------------------
# classification SIS: sharded 1D class-domain overlap screen.  Candidates
# shard over data(+pod) exactly like the regression screen; the overlap
# score needs whole sample rows (per-class minima/maxima + in-interval
# counts), so these paths require a sample-replicated mesh — the sharded
# wrapper falls back to the inner backend + host merge on 'model' meshes.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _overlap_sis_fn(mesh: Mesh, topk: Optional[Tuple[int, int]]):
    """Compiled sharded classification screen, cached per (mesh, k-config).

    ``topk=None`` returns the full per-shard score vectors (host-merge
    callers); ``topk=(k_local, k_merge)`` merges on device with the same
    k-sized all-gather discipline as the regression screen."""
    from .problem import overlap_scores_ops

    dp = _dp_axes(mesh)
    assert _sample_axis(mesh) is None, (
        "classification SIS needs whole sample rows; use a "
        "sample-replicated mesh or the inner-backend fallback"
    )
    out_specs = P(dp) if topk is None else (P(None), P(None))

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, None), P(None, None), P(None, None), P(None, None),
                  P(dp)),
        out_specs=out_specs,
        check_vma=topk is None,
    )
    def local(x_blk, task_mem, class_mem, masks, mask_blk):
        scores = overlap_scores_ops(x_blk, task_mem, class_mem, masks)
        scores = jnp.where(mask_blk, scores, -jnp.inf)
        if topk is None:
            return scores
        k_local, k_merge = topk
        vals, sel = jax.lax.top_k(scores, k_local)
        gidx = scores.shape[0] * _shard_index(dp) + sel
        gv = jax.lax.all_gather(vals, dp, tiled=True)
        gi = jax.lax.all_gather(gidx, dp, tiled=True)
        v2, s2 = jax.lax.top_k(gv, k_merge)
        return v2, gi[s2]

    return jax.jit(local)


def overlap_sis_scores_sharded(
    mesh: Mesh,
    x: jnp.ndarray,                # (F, S); F % n_data_shards == 0
    ctx: ScoreContext,
    row_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full classification score vector (F,), features sharded over dp."""
    fn = _overlap_sis_fn(mesh, None)
    if row_mask is None:
        row_mask = jnp.ones((x.shape[0],), bool)
    return fn(
        x,
        jnp.asarray(ctx.membership, x.dtype),
        jnp.asarray(ctx.class_members, x.dtype),
        jnp.asarray(ctx.state_masks, x.dtype),
        jnp.asarray(row_mask, bool),
    )


def overlap_sis_topk_sharded(
    mesh: Mesh,
    x: jnp.ndarray,
    ctx: ScoreContext,
    row_mask: jnp.ndarray,
    n_keep: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Device-merged classification top-``n_keep`` (scores desc, indices)."""
    f = int(x.shape[0])
    nd = _n_dp(mesh)
    assert f % nd == 0, (f, nd)
    k_local = min(int(n_keep), f // nd)
    k_merge = min(int(n_keep), nd * k_local)
    fn = _overlap_sis_fn(mesh, (k_local, k_merge))
    vals, idx = fn(
        x,
        jnp.asarray(ctx.membership, x.dtype),
        jnp.asarray(ctx.class_members, x.dtype),
        jnp.asarray(ctx.state_masks, x.dtype),
        jnp.asarray(row_mask, bool),
    )
    return np.asarray(vals, np.float64), np.asarray(idx)


# ---------------------------------------------------------------------------
# fused + distributed deferred SIS: the Pallas gen+validate+score kernel
# wrapped in shard_map (candidates shard over data(+pod); samples replicated)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fused_sis_topk_fn(mesh: Mesh, op_id: int, n_residuals: int,
                       k_local: int, k_merge: int, l_bound: float,
                       u_bound: float, block_b: int, interpret: bool,
                       epilogue_k: int = 64):
    """Compiled shard_map-wrapped fused SIS kernel with device merge.

    Each shard runs the *reduced-epilogue* Pallas fused gen+validate+score
    kernel (kernels/fused_sis.py) on its candidate slice — values live only
    in that shard's VMEM, padding rows die in-kernel (``n_valid``) and each
    grid step emits only its top-k panel.  The shard flattens its panels,
    takes a local top-``k_local`` and joins the k-sized all-gather merge:
    no full per-shard score vector exists at any point.  This is the
    ROADMAP "fused sharded kernel": the deferred screen is fused *and*
    distributed, end-to-end O(k).
    """
    from ..kernels.fused_sis import fused_gen_sis_topk_pallas

    dp = _dp_axes(mesh)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(None, None), P(None, None),
                  P(None, None), P(dp)),
        out_specs=(P(None), P(None)),
        check_vma=False,
    )
    def local(a_blk, b_blk, m_blk, yt_blk, cnt, nv_blk):
        vals, gidx = fused_gen_sis_topk_pallas(
            op_id, a_blk, b_blk, m_blk, yt_blk, cnt,
            n_residuals=n_residuals, l_bound=l_bound, u_bound=u_bound,
            epilogue_k=epilogue_k, block_b=block_b, interpret=interpret,
            n_valid=nv_blk[0],
        )
        v1, sel = jax.lax.top_k(vals.reshape(-1), k_local)
        li = gidx.reshape(-1)[sel]
        # kernel indices are shard-local; lift to global row numbers
        # (sentinel lanes are -inf-valued and filtered by the caller)
        gi1 = a_blk.shape[0] * _shard_index(dp) + li
        gv = jax.lax.all_gather(v1, dp, tiled=True)
        gi = jax.lax.all_gather(gi1, dp, tiled=True)
        v2, s2 = jax.lax.top_k(gv, k_merge)
        return v2, gi[s2]

    return jax.jit(local)


def fused_sis_topk_sharded(
    mesh: Mesh,
    op_id: int,
    a: jnp.ndarray,    # (B, S) child-1 values
    b: jnp.ndarray,    # (B, S) child-2 values
    ctx: ScoreContext,
    n_keep: int,
    l_bound: float,
    u_bound: float,
    block_b: int = 256,
    interpret: bool = True,
    epilogue_k: int = 64,
    dtype=None,        # kernel compute dtype; None -> fp32
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-``n_keep`` (scores desc, indices) of a deferred candidate block,
    fused (Pallas) and distributed (shard_map), merged on device.

    Padding policy mirrors ``kernels/ops.py:fused_gen_sis`` — children pad
    with the domain-safe 1.0, the sample axis to a lane multiple of 128 —
    except rows are padded per-shard to a ``block_b`` grid multiple and
    masked in-kernel, so per-row fp32 scores are bit-identical to the
    single-device fused path.  Requires a sample-replicated mesh (no
    'model' axis): the kernel computes whole-sample reductions itself.
    """
    assert _sample_axis(mesh) is None, (
        "fused sharded SIS requires sample-replicated meshes; use the "
        "compose path (eval + sis_topk_sharded) on sample-sharded meshes"
    )
    from ..kernels.ops import fused_sis_block_rows

    dtype = jnp.float32 if dtype is None else jnp.dtype(dtype)
    bsz, s = a.shape
    block_b = fused_sis_block_rows(s, block_b)
    nd = _n_dp(mesh)
    s_pad = ((max(s, 128) + 127) // 128) * 128
    chunk = nd * block_b
    b_pad = ((max(bsz, chunk) + chunk - 1) // chunk) * chunk
    b_local = b_pad // nd

    def pad2(v, rows, cols, fill):
        out = jnp.full((rows, cols), fill, dtype)
        return out.at[: v.shape[0], : v.shape[1]].set(v.astype(dtype))

    a_p = pad2(jnp.asarray(a), b_pad, s_pad, 1.0)
    b_p = pad2(jnp.asarray(b), b_pad, s_pad, 1.0)
    m_p = pad2(jnp.asarray(ctx.membership), ctx.membership.shape[0], s_pad, 0.0)
    yt_p = pad2(jnp.asarray(ctx.y_tilde), ctx.y_tilde.shape[0], s_pad, 0.0)
    cnt = jnp.asarray(ctx.counts, jnp.float32)[None, :]
    # per-shard count of real rows (shard i holds rows [i*b_local, ...))
    nv = np.clip(bsz - np.arange(nd) * b_local, 0, b_local).astype(np.int32)

    k_local = min(int(n_keep), b_local)
    k_merge = min(int(n_keep), nd * k_local)
    # every grid step's window must cover k_local or a shard whose winners
    # cluster in one block would lose some before its local merge
    k_epi = min(block_b, max(int(epilogue_k), min(k_local, block_b)))
    fn = _fused_sis_topk_fn(
        mesh, int(op_id), ctx.n_residuals, k_local, k_merge,
        float(l_bound), float(u_bound), int(block_b), bool(interpret),
        int(k_epi),
    )
    vals, idx = fn(a_p, b_p, m_p, yt_p, cnt, jnp.asarray(nv))
    return np.asarray(vals, np.float64), np.asarray(idx)


def sis_scores_distributed(
    mesh: Mesh,
    x: jnp.ndarray,          # (F, S) candidate feature values
    ctx: ScoreContext,
    n_top: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k (scores, indices) with features sharded over data(+pod) and
    samples sharded over model.

    Inside each shard: three local matmuls (the SIS reductions) on the
    sample sub-axis; one psum over 'model' combines them; local top-k over
    the feature shard; a single all-gather of k-sized payloads merges.
    """
    dp = _dp_axes(mesh)
    f, s = x.shape
    nd = int(np.prod([mesh.shape[a] for a in dp]))
    nm = int(mesh.shape["model"])
    assert f % nd == 0 and s % nm == 0, (f, nd, s, nm)
    k_local = min(n_top, f // nd)

    m = jnp.asarray(ctx.membership, x.dtype)
    yt = jnp.asarray(ctx.y_tilde, x.dtype)
    counts = jnp.asarray(ctx.counts, x.dtype)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(dp, "model"), P(None, "model"), P(None, "model")),
        out_specs=(P(dp), P(dp)),
    )
    def local(x_blk, m_blk, yt_blk):
        sums, sumsq, dots = jax.lax.psum(
            screen_reductions(x_blk, m_blk, yt_blk), "model")
        scores = scores_from_reductions(sums, sumsq, dots, counts,
                                        ctx.n_residuals)
        vals, idx = jax.lax.top_k(scores, k_local)
        base = f // nd * jax.lax.axis_index(dp[0] if len(dp) == 1 else dp)
        return vals, idx + base

    vals, idx = jax.jit(local)(x, m, yt)
    vals, idx = np.asarray(vals), np.asarray(idx)
    order = np.argsort(-vals, kind="stable")[:n_top]
    return vals[order], idx[order]


def l0_pairs_distributed(
    mesh: Mesh,
    x: jnp.ndarray,      # (m, S) subspace features
    y: jnp.ndarray,      # (S,)
    task_slices,
    pairs: np.ndarray,   # (B, 2) — padded & sharded over data(+pod)
    n_keep: int = 10,
) -> Tuple[np.ndarray, np.ndarray]:
    """Distributed exhaustive pair scoring: tuple space over data(+pod),
    samples over model (per-task Gram partials psum'ed), top-k merge."""
    from ..kernels.ref import solve3_sse

    dp = _dp_axes(mesh)
    nd = int(np.prod([mesh.shape[a] for a in dp]))
    b = len(pairs)
    b_pad = ((b + nd - 1) // nd) * nd
    pairs_pad = np.zeros((b_pad, 2), np.int32)
    pairs_pad[:b] = pairs
    valid = np.zeros((b_pad,), bool)
    valid[:b] = True
    nm = int(mesh.shape["model"])
    s = x.shape[1]
    s_pad = ((s + nm - 1) // nm) * nm
    x_p = jnp.zeros((x.shape[0], s_pad), x.dtype).at[:, :s].set(x)
    y_p = jnp.zeros((s_pad,), y.dtype).at[:s].set(y)
    k_local = min(n_keep, b_pad // nd)

    # per-task membership rows for sample-sharded Gram partials
    t = len(task_slices)
    mem = np.zeros((t, s_pad), np.float64)
    for ti, (lo, hi) in enumerate(task_slices):
        mem[ti, lo:hi] = 1.0
    mem = jnp.asarray(mem, x.dtype)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(None, "model"), P("model"), P(None, "model"),
                  P(dp, None), P(dp)),
        out_specs=(P(dp), P(dp)),
    )
    def local(x_blk, y_blk, mem_blk, prs, vld):
        i, j = prs[:, 0], prs[:, 1]
        total = jnp.zeros((prs.shape[0],), x_blk.dtype)
        for ti in range(t):
            w = mem_blk[ti]
            xw = x_blk * w[None, :]
            gii = jax.lax.psum((xw * x_blk).sum(axis=1), "model")
            fsum = jax.lax.psum(xw.sum(axis=1), "model")
            bv = jax.lax.psum(xw @ y_blk, "model")
            n = jax.lax.psum(w.sum(), "model")
            ysum = jax.lax.psum(w @ y_blk, "model")
            yty = jax.lax.psum((w * y_blk) @ y_blk, "model")
            gij = jax.lax.psum((xw[i] * x_blk[j]).sum(axis=1), "model")
            total = total + solve3_sse(
                gii[i], gii[j], n, gij, fsum[i], fsum[j],
                bv[i], bv[j], ysum, yty)
        total = jnp.where(vld, total, jnp.inf)
        neg, idx = jax.lax.top_k(-total, k_local)
        base = prs.shape[0] * 0 + idx  # local indices within the shard
        shard = jax.lax.axis_index(dp[0] if len(dp) == 1 else dp)
        return -neg, base + shard * (b_pad // nd)

    sses, idx = jax.jit(local)(x_p, y_p, mem, jnp.asarray(pairs_pad),
                               jnp.asarray(valid))
    sses, idx = np.asarray(sses), np.asarray(idx)
    order = np.argsort(sses, kind="stable")[:n_keep]
    keep = np.isfinite(sses[order])
    return pairs_pad[idx[order][keep]].astype(np.int64), sses[order][keep]
