"""jit'd wrappers around the Pallas kernels (+ padding & layout policy).

Off a TPU the kernels run with ``interpret=True`` (the kernel body runs as
XLA on the host); on a TPU the same calls compile with Mosaic.  Interpret
mode accepts kernels the TPU compiler refuses, so only
tests/test_tpu_compile.py and a chip run show that a kernel lowers.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.l0 import GramStats
from ..core.sis import ScoreContext, TaskLayout
from ..runtime import faults
from .fused_sis import fused_gen_sis_pallas, fused_gen_sis_topk_pallas
from .l0_gather import l0_gather_topk_pallas, l0_gather_tuples_pallas
from .l0_tile import l0_pairs_tiled_pallas
from .ref import solve3_sse
from .topk import merge_block_topk


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


# ---------------------------------------------------------------------------
# fused generation + SIS
# ---------------------------------------------------------------------------

#: scoped VMEM the TPU compiler grants a kernel by default (16 MiB on v5e)
_SCOPED_VMEM_BYTES = 16 * 1024 * 1024
#: fp32 ``(block_b, s_pad)`` tiles the fused-SIS kernel keeps live: the two
#: double-buffered child streams plus the generated values and their
#: temporaries.  The v5e compiler asked for 25.7 MB at 256 × 2432, about
#: 10.3 tiles; 12 leaves headroom.
_FUSED_SIS_LIVE_TILES = 12


def fused_sis_block_rows(s: int, block_b: int) -> int:
    """Candidate rows per fused-SIS grid step for ``s`` samples.

    ``block_b`` halved (never below one 128-lane output row) until the
    kernel's live tiles fit the scoped VMEM limit: 256 rows at the thermal
    width (s_pad = 256), 128 at the Kaggle width (s_pad = 2432).
    """
    s_pad = _pad_to(max(s, 128), 128)
    rows = int(block_b)
    while rows > 128 and \
            rows * s_pad * 4 * _FUSED_SIS_LIVE_TILES > _SCOPED_VMEM_BYTES:
        rows //= 2
    return rows


def _sis_operands(a, b, ctx, block_b, dtype):
    """Pad/cast the fused-SIS operand set to kernel layout in ``dtype``."""
    bsz, s = a.shape
    s_pad = _pad_to(max(s, 128), 128)
    b_pad = _pad_to(max(bsz, block_b), block_b)

    def pad2(x, rows, cols, fill):
        out = jnp.full((rows, cols), fill, dtype)
        return out.at[: x.shape[0], : x.shape[1]].set(x.astype(dtype))

    a_p = pad2(a, b_pad, s_pad, 1.0)   # 1.0 is domain-safe for all operators
    b_p = pad2(b, b_pad, s_pad, 1.0)
    m_p = pad2(jnp.asarray(ctx.membership), ctx.membership.shape[0], s_pad, 0.0)
    yt_p = pad2(jnp.asarray(ctx.y_tilde), ctx.y_tilde.shape[0], s_pad, 0.0)
    cnt = jnp.asarray(ctx.counts, jnp.float32)[None, :]
    return a_p, b_p, m_p, yt_p, cnt


def fused_gen_sis(
    op_id: int,
    a: jnp.ndarray,   # (B, S) child-1 values
    b: jnp.ndarray,   # (B, S) child-2 values (any values for unary ops)
    ctx: ScoreContext,
    l_bound: float,
    u_bound: float,
    block_b: int = 256,
    interpret: Optional[bool] = None,
    dtype=None,       # kernel compute dtype; None -> fp32
) -> jnp.ndarray:
    """Scores (B,) for a same-operator candidate block; invalid -> -inf."""
    faults.check("kernel.sis")
    interpret = _interpret_default() if interpret is None else interpret
    dtype = jnp.float32 if dtype is None else jnp.dtype(dtype)
    bsz = a.shape[0]
    block_b = fused_sis_block_rows(a.shape[1], block_b)
    a_p, b_p, m_p, yt_p, cnt = _sis_operands(a, b, ctx, block_b, dtype)

    scores = fused_gen_sis_pallas(
        op_id, a_p, b_p, m_p, yt_p, cnt,
        n_residuals=ctx.n_residuals, l_bound=l_bound, u_bound=u_bound,
        block_b=block_b, interpret=interpret, n_valid=bsz,
    )
    return scores[:bsz]


def fused_gen_sis_topk(
    op_id: int,
    a: jnp.ndarray,
    b: jnp.ndarray,
    ctx: ScoreContext,
    l_bound: float,
    u_bound: float,
    n_keep: int,
    block_b: int = 256,
    epilogue_k: int = 64,
    interpret: Optional[bool] = None,
    dtype=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Reduced-epilogue fused SIS: top-``n_keep`` winners, O(k) transfer.

    The kernel emits per-block top-``k`` panels (k grows to cover
    ``n_keep`` so the top-k-of-union identity holds) which a device merge
    reduces to the global winners; only those cross the host boundary.
    Returns ``(scores (k',) f64 best-first, indices (k',) i64)`` with
    k' <= n_keep (invalid/padding rows can never appear).
    """
    faults.check("kernel.sis")
    interpret = _interpret_default() if interpret is None else interpret
    dtype = jnp.float32 if dtype is None else jnp.dtype(dtype)
    bsz = a.shape[0]
    block_b = fused_sis_block_rows(a.shape[1], block_b)
    a_p, b_p, m_p, yt_p, cnt = _sis_operands(a, b, ctx, block_b, dtype)

    # per-block window must cover n_keep, else a single block could hold
    # more than k of the global winners and the merge would drop some
    k_epi = min(block_b, max(int(epilogue_k), min(int(n_keep), block_b)))
    vals, gidx = fused_gen_sis_topk_pallas(
        op_id, a_p, b_p, m_p, yt_p, cnt,
        n_residuals=ctx.n_residuals, l_bound=l_bound, u_bound=u_bound,
        epilogue_k=k_epi, block_b=block_b, interpret=interpret, n_valid=bsz,
    )
    k_merge = min(int(n_keep), vals.shape[0] * k_epi, bsz)
    v, i = merge_block_topk(vals, gidx, k=k_merge, largest=True)
    v = np.asarray(v, np.float64)
    i = np.asarray(i)
    keep = np.isfinite(v)
    return v[keep], i[keep].astype(np.int64)


# ---------------------------------------------------------------------------
# ℓ0 pair scoring
# ---------------------------------------------------------------------------

def l0_score_pairs(stats: GramStats, pairs: jnp.ndarray) -> jnp.ndarray:
    """Closed-form total SSE for explicit (B, 2) pairs from Gram stats.

    Same math as the tile kernel, expressed as XLA gathers — used by the
    block-loop integration path (core/l0.py) and as the rescoring step of
    the two-phase tiled search.
    """
    faults.check("kernel.l0")
    i = pairs[:, 0]
    j = pairs[:, 1]
    total = jnp.zeros((pairs.shape[0],), stats.gram.dtype)
    for t in range(stats.n_tasks):
        g = stats.gram[t]
        total = total + solve3_sse(
            g[i, i], g[j, j], stats.n[t], g[i, j],
            stats.fsum[t][i], stats.fsum[t][j],
            stats.b[t][i], stats.b[t][j], stats.ysum[t], stats.yty[t],
        )
    return total


# ---------------------------------------------------------------------------
# ℓ0 generic-width scoring (Gram-gather kernel, widths >= 3)
# ---------------------------------------------------------------------------

#: VMEM budget for the resident Gram statistics (bytes).  SIS-sized
#: subspaces (m ≲ 1000) fit easily; beyond this the backend falls back to
#: the fp64 XLA-gather path rather than thrash VMEM.
GRAM_VMEM_BUDGET = 8 * 1024 * 1024


#: bf16 planes per packed Gram entry, by the precision of the gathered
#: value: three planes carry an fp32 rounding exactly
GRAM_PLANES = {"float32": 3, "bfloat16": 1}


def gram_pack_nbytes(n_tasks: int, m: int, dtype=jnp.float32) -> int:
    """Bytes :func:`pack_gram` would occupy at the given precision —
    computable *before* building the pack, so over-budget subspaces never
    pay the allocation.  (The (T, 8) fp32 scalar array is counted at the
    planes' size; a conservative-enough estimate.)"""
    m_pad = _pad_to(max(m, 128), 128)
    planes = GRAM_PLANES[jnp.dtype(dtype).name]
    return 2 * planes * n_tasks * (m_pad * m_pad + 2 * m_pad + 8)


def split_planes(a, planes: int) -> np.ndarray:
    """``(planes, *a.shape)`` bf16 array whose planes sum (in fp32, in
    order) to ``a`` rounded to fp32 — exactly for three planes: each plane
    takes the bf16 rounding of what the earlier planes left, and the
    remainders are exact in fp32."""
    rest = np.asarray(a, np.float32)
    out = []
    for _ in range(planes):
        hi = rest.astype(jnp.bfloat16)
        out.append(hi)
        rest = rest - hi.astype(np.float32)
    return np.stack(out)


def pack_gram(stats: GramStats, dtype=jnp.float32) -> dict:
    """Pad Gram statistics to lane-aligned bf16 planes for the gather
    kernel.

    ``dtype`` is the precision of the gathered entries: fp32 packs three
    planes per entry (an exact fp32 gather), bf16 one (a third of the VMEM
    and MXU passes, at bf16 rounding — the kernel's error bound widens to
    match).  The scalar array stays fp32.  Zero padding is inert: tuples
    only ever index real features, and padded Gram rows/columns are never
    touched by their one-hot gathers.
    """
    dtype = jnp.dtype(dtype)
    planes = GRAM_PLANES[dtype.name]
    t = stats.n_tasks
    m = stats.m
    m_pad = _pad_to(max(m, 128), 128)
    gram = np.zeros((t, m_pad, m_pad), np.float64)
    fsum = np.zeros((t, m_pad), np.float64)
    bvec = np.zeros((t, m_pad), np.float64)
    scal = np.zeros((t, 8), np.float32)
    gram[:, :m, :m] = np.asarray(stats.gram, np.float64)
    fsum[:, :m] = np.asarray(stats.fsum, np.float64)
    bvec[:, :m] = np.asarray(stats.b, np.float64)
    scal[:, 0] = np.asarray(stats.n, np.float32)
    scal[:, 1] = np.asarray(stats.ysum, np.float32)
    scal[:, 2] = np.asarray(stats.yty, np.float32)
    return {
        "gram": jnp.asarray(split_planes(gram, planes)),
        "fsum": jnp.asarray(split_planes(fsum, planes)),
        "bvec": jnp.asarray(split_planes(bvec, planes)),
        "scal": jnp.asarray(scal),
        "m": m, "m_pad": m_pad, "dtype": str(dtype),
        "vmem_bytes": gram_pack_nbytes(t, m, dtype),
    }


def pack_gram_fp32(stats: GramStats) -> dict:
    """fp32 :func:`pack_gram` (the historical default)."""
    return pack_gram(stats, jnp.float32)


def l0_score_tuples(
    pack: dict,
    tuples: jnp.ndarray,     # (B, n) int32 — may live on device (unrank.py)
    block_t: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """fp32 total-SSE lower bounds (B,) for width-n tuples via the
    Gram-gather kernel (each at most the tuple's exact SSE).

    Padding tuples are the benign (0, 1, …, n-1) combination, sliced off
    before returning.  The result stays on device so the caller can fuse
    the top-k / rescore selection without an extra transfer.
    """
    faults.check("kernel.l0")
    interpret = _interpret_default() if interpret is None else interpret
    tuples = jnp.asarray(tuples, jnp.int32)
    b, n = tuples.shape
    b_pad = _pad_to(max(b, block_t), block_t)
    if b_pad != b:
        fill = jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32)[None, :], (b_pad - b, n)
        )
        tuples = jnp.concatenate([tuples, fill], axis=0)
    sse = l0_gather_tuples_pallas(
        tuples.T, pack["gram"], pack["fsum"], pack["bvec"], pack["scal"],
        n=n, block_t=block_t, interpret=interpret,
    )
    return sse[:b]


def l0_topk_tuples(
    pack: dict,
    tuples: jnp.ndarray,     # (B, n) int32 — may live on device (unrank.py)
    n_keep: int,
    block_t: int = 256,
    epilogue_k: int = 64,
    interpret: Optional[bool] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Reduced-epilogue Gram-gather: the ``n_keep`` lowest SSE bounds.

    Per-tile top-k panels (window grown to cover ``n_keep``) merged on
    device; only the O(k) winners cross the host boundary.  Returns
    ``(bounds (k',) f64 ascending, indices (k',) i64, floor)`` — indices
    are positions into ``tuples``; padding tuples can never appear.
    ``floor`` is at most the bound of every tuple left out (a full tile's
    last panel entry, the merge's last kept entry when it cut), +inf when
    nothing was left out.
    """
    faults.check("kernel.l0")
    interpret = _interpret_default() if interpret is None else interpret
    tuples = jnp.asarray(tuples, jnp.int32)
    b, n = tuples.shape
    b_pad = _pad_to(max(b, block_t), block_t)
    if b_pad != b:
        fill = jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32)[None, :], (b_pad - b, n)
        )
        tuples = jnp.concatenate([tuples, fill], axis=0)
    k_epi = min(block_t, max(int(epilogue_k), min(int(n_keep), block_t)))
    vals, gidx = l0_gather_topk_pallas(
        tuples.T, pack["gram"], pack["fsum"], pack["bvec"], pack["scal"],
        b, n=n, k=k_epi, block_t=block_t, interpret=interpret,
    )
    k_merge = min(int(n_keep), vals.shape[0] * k_epi, b)
    v, i = merge_block_topk(vals, gidx, k=k_merge, largest=False)
    floor = float(excluded_floor(vals, k_epi, block_t, v))
    v = np.asarray(v, np.float64)
    i = np.asarray(i)
    keep = np.isfinite(v)
    return v[keep], i[keep].astype(np.int64), floor


def excluded_floor(panels: jnp.ndarray, k: int, block_t: int,
                   merged: jnp.ndarray):
    """Lower bound on the prescreen value of every tuple a reduced epilogue
    left out (+inf when it left none out).

    ``panels`` are the per-tile ``(ntiles, k_pad)`` winners (``k`` of each
    ``block_t``-tuple tile, ascending, +inf sentinels) and ``merged`` the
    ascending merge of them.  A tile with a full panel (finite k-th entry)
    may have cut tuples at or above that entry, unless it keeps its whole
    tile; a merge that kept fewer entries than the panels hold cut tuples
    at or above its last kept entry.  Traceable.
    """
    floor = jnp.min(panels[:, k - 1]) if k < block_t else jnp.inf
    cut = jnp.sum(jnp.isfinite(panels)) > jnp.sum(jnp.isfinite(merged))
    return jnp.where(cut, jnp.minimum(floor, merged[-1]), floor)


def _task_padded_layout(
    x: np.ndarray, y: np.ndarray, layout: TaskLayout, block: int
) -> Tuple[np.ndarray, np.ndarray, Tuple[Tuple[int, int], ...], np.ndarray]:
    """Repack samples so every task segment is 128-aligned (zero gaps).

    Zero padding contributes nothing to Gram sums; true counts are carried
    separately in the scalar array.
    """
    m, _ = x.shape
    m_pad = _pad_to(max(m, block), block)
    seg_pads = [_pad_to(max(hi - lo, 128), 128) for lo, hi in layout.slices]
    s_pp = sum(seg_pads)
    x_pp = np.zeros((m_pad, s_pp), np.float32)
    y_pp = np.zeros((s_pp,), np.float32)
    slices_pp = []
    off = 0
    for (lo, hi), sp in zip(layout.slices, seg_pads):
        n = hi - lo
        x_pp[:m, off : off + n] = x[:, lo:hi]
        y_pp[off : off + n] = y[lo:hi]
        slices_pp.append((off, off + sp))
        off += sp
    scal = np.zeros((layout.n_tasks, 8), np.float32)
    for t, (lo, hi) in enumerate(layout.slices):
        yt = y[lo:hi]
        scal[t, 0] = hi - lo
        scal[t, 1] = yt.sum()
        scal[t, 2] = (yt * yt).sum()
    return x_pp, y_pp, tuple(slices_pp), scal


def l0_search_tiled(
    x: np.ndarray,   # (m, S) subspace feature values (samples grouped by task)
    y: np.ndarray,   # (S,)
    layout: TaskLayout,
    n_keep: int = 10,
    block: int = 256,
    tiles_per_call: int = 2048,
    interpret: Optional[bool] = None,
    journal=None,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Kernel-accelerated exhaustive pair search; exact top-``n_keep``.

    Phase 1: tile sweep (Pallas) -> per-tile (min SSE, argmin).
    Phase 2: rescore the ≤ n_keep best tiles exactly (tile-min containment
    argument: every global top-k element lives in a tile whose min ≤ the
    global k-th value, and at most k tiles can satisfy that).
    Returns (tuples (k,2), sses (k,), n_evaluated).
    """
    interpret = _interpret_default() if interpret is None else interpret
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    m = x.shape[0]
    x_pp, y_pp, slices_pp, scal = _task_padded_layout(x, y, layout, block)
    m_pad = x_pp.shape[0]
    nb = m_pad // block

    # per-task per-feature vectors
    t_count = layout.n_tasks
    gii = np.zeros((t_count, m_pad), np.float32)
    fsum = np.zeros((t_count, m_pad), np.float32)
    bvec = np.zeros((t_count, m_pad), np.float32)
    for t, (lo, hi) in enumerate(slices_pp):
        seg = x_pp[:, lo:hi]
        gii[t] = (seg * seg).sum(axis=1)
        fsum[t] = seg.sum(axis=1)
        bvec[t] = seg @ y_pp[lo:hi]

    tiles = [(i, j) for i in range(nb) for j in range(i, nb)]
    x_dev = jnp.asarray(x_pp)
    gii_d, fs_d, b_d = jnp.asarray(gii), jnp.asarray(fsum), jnp.asarray(bvec)
    scal_d = jnp.asarray(scal)

    # running top tiles: (min_sse, tile_i, tile_j, local_idx)
    best: list = []
    start_chunk = 0
    if journal is not None and journal.has_state():
        best, start_chunk = journal.restore_tiles()

    chunks = [
        tiles[lo : lo + tiles_per_call]
        for lo in range(0, len(tiles), tiles_per_call)
    ]
    for ci, chunk in enumerate(chunks):
        if ci < start_chunk:
            continue
        # fault site: one tile chunk's device sweep (the tiled analogue
        # of l0.block_scores; "kill" after restore exercises tile resume)
        faults.check("tiles.chunk")
        ti = jnp.asarray([c[0] for c in chunk], jnp.int32)
        tj = jnp.asarray([c[1] for c in chunk], jnp.int32)
        sse, idx = l0_pairs_tiled_pallas(
            x_dev, gii_d, fs_d, b_d, scal_d, ti, tj,
            task_slices=slices_pp, m_true=m, block=block,
            interpret=interpret,
        )
        sse, idx = np.array(sse), np.array(idx)
        for k in range(len(chunk)):
            if np.isfinite(sse[k]):
                best.append((float(sse[k]), chunk[k][0], chunk[k][1], int(idx[k])))
        best.sort(key=lambda r: r[0])
        best = best[: n_keep + 1]
        if journal is not None:
            journal.record_tiles(ci + 1, best)

    # phase 2: exact rescoring of the winning tiles
    from ..core.l0 import compute_gram_stats

    stats = compute_gram_stats(jnp.asarray(x), jnp.asarray(y), layout, jnp.float64)
    cand_pairs = []
    for _, ti_, tj_, _ in best[:n_keep]:
        i0, j0 = ti_ * block, tj_ * block
        ii, jj = np.meshgrid(
            np.arange(i0, min(i0 + block, m)),
            np.arange(j0, min(j0 + block, m)),
            indexing="ij",
        )
        keep = ii < jj
        cand_pairs.append(np.stack([ii[keep], jj[keep]], axis=1))
    if not cand_pairs:
        return np.zeros((0, 2), np.int64), np.zeros((0,)), len(tiles)
    cand = np.unique(np.concatenate(cand_pairs), axis=0)
    sses = np.array(l0_score_pairs(stats, jnp.asarray(cand, jnp.int32)))
    order = np.argsort(sses, kind="stable")[:n_keep]
    n_eval = m * (m - 1) // 2
    return cand[order].astype(np.int64), sses[order], n_eval
