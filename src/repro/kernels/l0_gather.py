"""Pallas TPU kernel: Gram-gather ℓ0 scoring for tuple widths ≥ 3.

The pair kernel (l0_tile.py) recomputes Gram *tiles* on the MXU because the
pair space is the m×m upper triangle — Gram reuse is the whole win.  For
widths ≥ 3 the tuple space is C(m, n) ≫ m², so the economics flip: the full
per-task Gram statistics (G = X Xᵀ, s = X·1, b = X·y — a few hundred KB for
SIS-sized subspaces) fit **resident in VMEM** and each tuple's least-squares
problem is an (n+1)×(n+1) SPD system read out of them, O(n³) per tuple with
zero O(S) work (core/l0.py engine-2 math).  Any n ≥ 3 works; VMEM, not the
kernel, is the practical ceiling.

Tuple blocks are rank ranges of the lexicographic tuple space
(core/l0.py:TupleEnumerator), so consecutive tuples share their
(n-1)-prefix while the last index k counts up.  A *run* is a maximal
stretch of such tuples; the kernel scores one run per panel row, with k
along the lanes:

    row r, lane k  ->  tuple (i_0, …, i_{n-2}, k) of run r, live for
                       k in [k0_r, k0_r + len_r), +inf elsewhere

Every Gram entry a row needs is then a column of G gathered once for the
whole row (``G[k, i_p]`` for each prefix index), a fixed lane vector
(``G[k, k]``, ``s_k``, ``b_k``) or a per-row scalar (``G[i_p, i_q]``,
``s_i``, ``b_i``, read out of the gathered columns): no gather over k.

Per grid step (``block_t`` panel rows, each one run; fewer where the
step would hold more than ``STEP_ENTRIES`` system entries):

    VMEM-resident:   G (P, T, m_pad, m_pad) bf16 planes, lane vectors
                     (T, 3, m_pad) fp32, scalars (T, 8) fp32
    HBM → VMEM:      run table (block_t, n+1) int32: prefix, k0, k0+len
    compute, per task:
      onehot(i_p)·Gᵀ          MXU  (n-1)·P matmuls (block_t × m_pad)·
                                    (m_pad × m_pad): the prefix columns
      per-row scalars         VPU  masked lane sums of those columns
      (n+1)×(n+1) solve + SSE VPU  unrolled elimination on (block_t,
                                    m_pad) panels (ref.eliminate_spd_sse)
    VMEM → HBM:      bound panel (block_t, m_pad) fp32

So the MXU work per row is (n-1)·P·T products of 2·m_pad² operations,
shared by every tuple of the run, and the VPU work is one elimination per
lane.  A block in any other order still scores correctly, as runs of
length one; only the share of live lanes drops.

The run table is derived from the block on the device (:func:`run_table`);
its row count is data, so the caller reads the block's run count back
(:func:`count_runs`, one scalar) and picks a power-of-two panel height
(:func:`panel_rows`) — one compiled program per height.

Compute dtype: the Gram pack (G, s, b) is stored as bf16 *planes* that
sum to each entry (``ops.pack_gram``: three planes carry an fp32 value
exactly).  One-hots are bf16, so every gather is a few native single-pass
MXU matmuls that select entries bit for bit (``ref.gather_plane_columns``),
and the lane vectors sum the same planes in the same order: each system's
entries are the fp32 values the one-hot-gather oracle
(``ref.l0_gather_sse_ref``) assembles.  The elimination stays fp32.

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

from .ref import eliminate_spd_sse, entry_error, gather_plane_columns, \
    panel_system
from .spec import block_spec, x32_kernel

#: scoped VMEM the gather kernels may use: the double-buffered resident
#: Gram planes plus the (block_t, m_pad) panels of the elimination; a v5e
#: core has 128 MiB
VMEM_LIMIT_BYTES = 48 * 1024 * 1024
#: system entries a grid step may hold, rows × m_pad × (n+1)²: the
#: elimination's live panels grow with the system.  Steps of this size
#: compile for a v5e at the Gram budget's largest m_pad, widths 3 to 8;
#: a step of 9.6 M entries (width 6, m_pad 768, 256 rows) does not.
STEP_ENTRIES = 6 * 2 ** 20


def run_starts(tuples_t: jnp.ndarray, nv) -> jnp.ndarray:
    """(B,) bool: position q opens a run (its prefix differs from q-1's, or
    its last index is not q-1's plus one).  Positions from ``nv`` on are
    padding and open none."""
    n, b = tuples_t.shape
    last = tuples_t[n - 1]
    cont = last[1:] == last[:-1] + 1
    for p in range(n - 1):
        cont = cont & (tuples_t[p, 1:] == tuples_t[p, :-1])
    pos = jnp.arange(b, dtype=jnp.int32)
    return jnp.concatenate([jnp.ones((1,), bool), ~cont]) & (pos < nv)


@functools.partial(jax.jit, static_argnames=("shards",))
def count_runs(tuples_t: jnp.ndarray, nv, shards: int = 1) -> jnp.ndarray:
    """(shards,) int32 run counts of the ``shards`` equal contiguous chunks
    of a ``(n, B)`` tuple block whose first ``nv`` positions are live."""
    n, b = tuples_t.shape
    chunk = b // shards
    pos = jnp.arange(b, dtype=jnp.int32).reshape(shards, chunk)
    live = jnp.clip(nv - pos[:, 0], 0, chunk)
    starts = jax.vmap(run_starts, in_axes=(1, 0))(
        tuples_t.reshape(n, shards, chunk), live)
    return jnp.sum(starts.astype(jnp.int32), axis=1)


def panel_rows(runs: int) -> int:
    """Panel height for a block of ``runs`` runs: the next power of two,
    at least one sublane tile (8)."""
    rows = 8
    while rows < runs:
        rows *= 2
    return rows


def run_table(tuples_t: jnp.ndarray, nv, rows: int):
    """Run table of a ``(n, B)`` tuple block, traceable.

    Returns ``(table (rows, n+1) int32, run_id (B,) int32)``: row r holds
    run r's prefix, its first last index k0 and k0 + len (rows past the
    block's runs are empty windows), and ``run_id[q]`` is the run of
    position q.  ``rows`` must be at least the block's run count.
    """
    n, b = tuples_t.shape
    run_id = jnp.cumsum(run_starts(tuples_t, nv).astype(jnp.int32)) - 1
    pos = jnp.arange(b, dtype=jnp.int32)
    # non-decreasing: live positions by run, padding after every row
    key = jnp.where(pos < nv, run_id, rows)
    first = jnp.searchsorted(key, jnp.arange(rows, dtype=jnp.int32),
                             side="left").astype(jnp.int32)
    end = jnp.concatenate([first[1:], jnp.full((1,), nv, jnp.int32)])
    length = jnp.maximum(end - first, 0)
    head = tuples_t[:, jnp.minimum(first, b - 1)]          # (n, rows)
    k0 = jnp.where(length > 0, head[n - 1], 0)
    table = jnp.concatenate(
        [head[: n - 1].T, k0[:, None], (k0 + length)[:, None]], axis=1)
    return table.astype(jnp.int32), run_id


def lane_vectors(gram, fsum, bvec) -> jnp.ndarray:
    """(T, 3, m_pad) fp32 ``[G_kk, s_k, b_k]``: each the planes summed in
    order, as the oracle's one-hot gathers sum them."""
    def summed(planes):
        out = planes[0].astype(jnp.float32)
        for i in range(1, planes.shape[0]):
            out = out + planes[i].astype(jnp.float32)
        return out

    diag = jnp.diagonal(gram, axis1=2, axis2=3)
    return jnp.stack([summed(diag), summed(fsum), summed(bvec)], axis=1)


@x32_kernel
def _kernel(
    tbl_ref,    # (block_t, n+1) int32: prefix (n-1), k0, k0+len
    gram_ref,   # (P, T, m_pad, m_pad) bf16 planes
    vec_ref,    # (T, 3, m_pad) fp32: G_kk, s_k, b_k
    scal_ref,   # (T, 8) fp32: [n_samples, ysum, yty, 0, ...]
    out_ref,    # (block_t, m_pad) fp32 bound panel
    *, n: int, n_tasks: int,
):
    tbl = tbl_ref[...]
    rows, m_pad = out_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, m_pad), 1)
    hits = [lane == tbl[:, p : p + 1] for p in range(n - 1)]
    onehots = [h.astype(gram_ref.dtype) for h in hits]
    err = entry_error(n + 1, gram_ref.shape[0])
    scal = scal_ref[...]
    total = jnp.zeros((rows, m_pad), jnp.float32)
    for t in range(n_tasks):  # static unroll over tasks
        gram = gram_ref[:, t]
        vec = vec_ref[t]
        a, rhs = panel_system(
            [gather_plane_columns(gram, oh) for oh in onehots], hits,
            vec[0:1], vec[1:2], vec[2:3], scal[t, 0], scal[t, 1],
        )
        total = total + eliminate_spd_sse(a, rhs, scal[t, 2], err)
    live = (lane >= tbl[:, n - 1 : n]) & (lane < tbl[:, n : n + 1])
    out_ref[...] = jnp.where(live, total, jnp.inf)


def _bound_panel(table, gram, vecs, scal, n: int, block_t: int,
                 interpret: bool):
    """(rows, m_pad) fp32 bounds of a run table's tuples, +inf off-run."""
    planes, t, m_pad, _ = gram.shape
    rows = table.shape[0]
    block_t = min(block_t, rows)
    while block_t > 8 and block_t * m_pad * (n + 1) ** 2 > STEP_ENTRIES:
        block_t //= 2
    assert rows % block_t == 0 and m_pad % 128 == 0
    return pl.pallas_call(
        functools.partial(_kernel, n=n, n_tasks=t),
        grid=(rows // block_t,),
        in_specs=[
            block_spec((block_t, n + 1), lambda i: (i, 0)),
            block_spec((planes, t, m_pad, m_pad), lambda i: (0, 0, 0, 0)),
            block_spec((t, 3, m_pad), lambda i: (0, 0, 0)),
            block_spec((t, 8), lambda i: (0, 0)),
        ],
        out_specs=block_spec((block_t, m_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, m_pad), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(table, gram, vecs, scal)


def _block_bounds(tuples_t, gram, fsum, bvec, scal, nv, n, block_t, rows,
                  interpret):
    """(B,) fp32 bounds in position order, +inf from ``nv`` on, -inf (no
    bound) on the runs past the panel's ``rows``."""
    b = tuples_t.shape[1]
    m_pad = gram.shape[2]
    rows = panel_rows(b) if rows is None else rows
    table, run_id = run_table(tuples_t, nv, rows)
    panel = _bound_panel(table, gram, lane_vectors(gram, fsum, bvec), scal,
                         n, block_t, interpret)
    flat = run_id * m_pad + tuples_t[n - 1]
    bounds = jnp.take(panel.reshape(-1), flat, mode="clip")
    bounds = jnp.where(run_id < rows, bounds, -jnp.inf)
    return jnp.where(jnp.arange(b) < nv, bounds, jnp.inf)


@functools.partial(
    jax.jit, static_argnames=("n", "block_t", "rows", "interpret")
)
def l0_gather_tuples_pallas(
    tuples_t: jnp.ndarray,   # (n, B) int32
    gram: jnp.ndarray,       # (P, T, m_pad, m_pad) bf16, m_pad % 128 == 0
    fsum: jnp.ndarray,       # (P, T, m_pad)
    bvec: jnp.ndarray,       # (P, T, m_pad)
    scal: jnp.ndarray,       # (T, 8) fp32
    n: int,
    block_t: int = 256,
    rows=None,               # panel height >= the block's runs; None: B
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-tuple total SSE bounds (B,) fp32, in block order.

    ``rows`` is the panel height, at least the block's run count
    (:func:`count_runs`, :func:`panel_rows`); ``None`` takes B rows, the
    height of a block whose runs all have length one, at m_pad times the
    work of a lex block.  A height below the run count costs speed, not
    results: the tuples of the runs left out get the bound -inf, which no
    certificate passes, so the caller rescores them in fp64.
    """
    return _block_bounds(tuples_t, gram, fsum, bvec, scal,
                         tuples_t.shape[1], n, block_t, rows, interpret)


@functools.partial(
    jax.jit, static_argnames=("n", "k", "block_t", "rows", "interpret")
)
def l0_gather_topk_pallas(
    tuples_t: jnp.ndarray,   # (n, B) int32
    gram: jnp.ndarray,       # (P, T, m_pad, m_pad) bf16, m_pad % 128 == 0
    fsum: jnp.ndarray,       # (P, T, m_pad)
    bvec: jnp.ndarray,       # (P, T, m_pad)
    scal: jnp.ndarray,       # (T, 8) fp32
    nv,                      # live tuple count (int or traced scalar)
    n: int,
    k: int,
    block_t: int = 256,
    rows=None,               # panel height >= the block's runs; None: B
    interpret: bool = False,
):
    """Reduced variant: the ``k`` lowest bounds of the block's first ``nv``
    tuples, as ``(bounds (k,) fp32 ascending, positions (k,) int32, floor)``
    (``rows`` as in :func:`l0_gather_tuples_pallas`).

    Equal bounds resolve to the lowest position, the order a stable sort
    of the full vector gives.  ``floor`` lies under every live bound left
    out: the k-th bound when more than k tuples are live, else +inf.
    Entries past the live count are +inf and must be dropped by value.
    """
    bounds = _block_bounds(tuples_t, gram, fsum, bvec, scal, nv, n, block_t,
                           rows, interpret)
    k = max(1, min(int(k), tuples_t.shape[1]))
    neg, pos = jax.lax.top_k(-bounds, k)
    vals = -neg
    floor = jnp.where(jnp.asarray(nv) > k, vals[k - 1], jnp.inf)
    return vals, pos.astype(jnp.int32), floor
