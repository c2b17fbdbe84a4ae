"""ℓ0-regularized descriptor search — the third SISSO phase.

Given the ``m`` SIS-selected features, score **every** n-tuple by its
least-squares fit to the target and return the best models (paper §II.D:
"assemble the descriptor matrix → QR factorization → least squares →
mean squared deviation" for ~10^9–10^10 tuples).

Two engines:

* :func:`score_tuples_qr` — **paper-faithful baseline**: per tuple, assemble
  the (S × (n+1)) design matrix (per-task intercept column) and solve by QR,
  batched with ``vmap``.  O(S·n²) work per tuple; this is the GPU algorithm
  (P4) transcribed.
* :func:`score_tuples_gram` — **TPU adaptation**: precompute once per task
  the Gram statistics ``G = X Xᵀ, s = X·1, b = X·y, n, Σy, yᵀy`` (MXU
  matmuls), then each tuple's least-squares problem is the (n+1)×(n+1) SPD
  system gathered from them — O(n³) per tuple, zero O(S) work, identical
  minimizer.  The blocked/tiled form of this engine is the Pallas kernel in
  ``kernels/l0_tile.py``.

Both engines support multi-task SISSO: one coefficient set *per task*, score
= total SSE over tasks (paper §III.A: "same descriptor matrix, but different
coefficient matrices for each task").
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import warnings
from typing import Iterator, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sis import ReducedBlock, TaskLayout

_JITTER = 1e-10


# ---------------------------------------------------------------------------
# Gram statistics (computed once per ℓ0 sweep)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GramStats:
    """Per-task sufficient statistics for least squares over feature tuples.

    The statistics are those of the *task-centered* data (each feature and
    the target minus its per-task mean), so ``fsum`` and ``ysum`` are zero
    and the intercept decouples from the slopes.  An SSE is then a small
    difference of centered moments, not of raw second moments that the
    means dominate: the uncentered form lost the planted Kaggle model in
    fp32 and near-exact thermal fits in the fp32 kernel prescreen.  The
    means are kept for :func:`coefficients_for`'s intercepts.
    """

    gram: jnp.ndarray    # (T, m, m)   X_t X_tᵀ  (centered X_t)
    fsum: jnp.ndarray    # (T, m)      X_t · 1   (zero)
    b: jnp.ndarray       # (T, m)      X_t y_t   (centered X_t, y_t)
    n: jnp.ndarray       # (T,)        samples per task
    ysum: jnp.ndarray    # (T,)        (zero)
    yty: jnp.ndarray     # (T,)        centered y_t · y_t
    m: int
    xmean: Optional[np.ndarray] = None   # (T, m) fp64 per-task means
    ymean: Optional[np.ndarray] = None   # (T,)

    @property
    def n_tasks(self) -> int:
        return int(self.gram.shape[0])


def compute_gram_stats(
    x: jnp.ndarray,  # (m, S) feature values
    y: jnp.ndarray,  # (S,)
    layout: TaskLayout,
    dtype=jnp.float64,
) -> GramStats:
    """Centered per-task Gram statistics (see :class:`GramStats`).

    The centering runs on the host in fp64 whatever ``dtype`` is, so the
    rounding of the ``dtype`` moments is relative to the centered scale.
    """
    x = np.asarray(x, np.float64)
    y = np.asarray(y, np.float64)
    # HIGHEST: a TPU runs fp32 dots as bf16 passes by default
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    grams, bs, ytys, xmeans, ymeans = [], [], [], [], []
    for lo, hi in layout.slices:
        xm = x[:, lo:hi].mean(axis=1)
        ym = y[lo:hi].mean()
        xt = jnp.asarray(x[:, lo:hi] - xm[:, None], dtype)
        yt = jnp.asarray(y[lo:hi] - ym, dtype)
        grams.append(dot(xt, xt.T))
        bs.append(dot(xt, yt))
        ytys.append(dot(yt, yt))
        xmeans.append(xm)
        ymeans.append(ym)
    t, m = len(layout.slices), int(x.shape[0])
    return GramStats(
        gram=jnp.stack(grams), fsum=jnp.zeros((t, m), dtype),
        b=jnp.stack(bs),
        n=jnp.asarray([hi - lo for lo, hi in layout.slices], dtype),
        ysum=jnp.zeros((t,), dtype), yty=jnp.stack(ytys), m=m,
        xmean=np.stack(xmeans), ymean=np.asarray(ymeans),
    )


# ---------------------------------------------------------------------------
# engine 1: Gram-cached scoring (TPU-native)
# ---------------------------------------------------------------------------

def _spd_sse(a, rhs, yty):
    """``yty - rhsᵀ A⁻¹ rhs`` for a batch of small SPD systems.

    ``a`` is a k×k nested list and ``rhs`` a length-k list of (B,) arrays,
    one per coefficient.  The Cholesky factor is unrolled over the static
    ``k``, so every step is an elementwise op over the batch: on a TPU a
    batched ``jax.scipy.linalg.solve`` compiles in time that grows with the
    batch (90 s for 65,536 fp32 4×4 systems, longer in fp64), and a
    ``(B, k, k)`` array pads its two minor axes to a whole (8, 128) tile.
    A system that is not positive definite gives a NaN pivot, which the
    caller maps to +inf.
    """
    k = len(rhs)
    low = [[None] * k for _ in range(k)]
    z = []
    for j in range(k):
        d = a[j][j] - sum(low[j][p] * low[j][p] for p in range(j))
        ljj = jnp.sqrt(d)
        low[j][j] = ljj
        for i in range(j + 1, k):
            low[i][j] = (a[i][j]
                         - sum(low[i][p] * low[j][p] for p in range(j))) / ljj
        z.append((rhs[j] - sum(low[j][p] * z[p] for p in range(j))) / ljj)
    return yty - sum(zj * zj for zj in z)


def score_tuples_gram(stats: GramStats, tuples: jnp.ndarray) -> jnp.ndarray:
    """Total SSE over tasks for each tuple.  tuples: (B, n) int32.

    Per task, the LSQ fit with intercept is the (n+1)×(n+1) SPD system
    gathered from the Gram statistics; its SSE is ``yty - c·rhs``.
    """
    idx = jnp.asarray(tuples)
    n = idx.shape[1]
    cols = [idx[:, p] for p in range(n)]
    dtype = stats.gram.dtype
    if np.dtype(dtype).itemsize < 4:
        # sub-fp32 Gram stats (bf16 precision mode): bf16 is a
        # storage/matmul format, solves run in fp32
        dtype = jnp.float32
    total = jnp.zeros((idx.shape[0],), dtype)
    for t in range(stats.n_tasks):
        g = stats.gram[t].astype(dtype)
        fs = stats.fsum[t].astype(dtype)
        b = stats.b[t].astype(dtype)
        a = [[None] * (n + 1) for _ in range(n + 1)]
        for p in range(n):
            for q in range(p, n):
                a[p][q] = a[q][p] = g[cols[p], cols[q]]
            a[p][n] = a[n][p] = fs[cols[p]]
        a[n][n] = stats.n[t].astype(dtype)
        for p in range(n + 1):
            a[p][p] = a[p][p] + _JITTER
        rhs = [b[c] for c in cols] + [stats.ysum[t].astype(dtype)]
        sse = _spd_sse(a, rhs, stats.yty[t].astype(dtype))
        total = total + jnp.where(jnp.isfinite(sse), jnp.maximum(sse, 0.0),
                                  jnp.inf)
    return total


def coefficients_for(
    stats: GramStats, idx: Sequence[int]
) -> Tuple[np.ndarray, np.ndarray]:
    """(coefs (T,n), intercepts (T,)) of the LSQ fit for one tuple."""
    idx = jnp.asarray(idx, jnp.int32)
    coefs, intercepts = [], []
    solve_dtype = (
        jnp.float32 if np.dtype(stats.gram.dtype).itemsize < 4
        else stats.gram.dtype
    )
    for t in range(stats.n_tasks):
        k = idx.shape[0]
        gs = stats.gram[t][jnp.ix_(idx, idx)].astype(solve_dtype)
        ss = stats.fsum[t][idx].astype(solve_dtype)
        a = jnp.zeros((k + 1, k + 1), gs.dtype)
        a = a.at[:k, :k].set(gs).at[:k, k].set(ss).at[k, :k].set(ss)
        a = a.at[k, k].set(stats.n[t]) + _JITTER * jnp.eye(k + 1, dtype=gs.dtype)
        rhs = jnp.concatenate([stats.b[t][idx], stats.ysum[t][None]])
        c = jax.scipy.linalg.solve(a, rhs, assume_a="pos")
        coefs.append(np.asarray(c[:k]))
        intercepts.append(float(c[k]))
    coefs, intercepts = np.stack(coefs), np.asarray(intercepts)
    if stats.xmean is not None:
        # the statistics are centered: map the intercept back to raw data
        sel = np.asarray(idx)
        intercepts = intercepts + stats.ymean - np.einsum(
            "tn,tn->t", coefs.astype(np.float64), stats.xmean[:, sel])
    return coefs, intercepts


# ---------------------------------------------------------------------------
# engine 2: paper-faithful batched QR (baseline + oracle)
# ---------------------------------------------------------------------------

def score_tuples_qr(
    x: jnp.ndarray,  # (m, S)
    y: jnp.ndarray,  # (S,)
    layout: TaskLayout,
    tuples: jnp.ndarray,  # (B, n)
    dtype=jnp.float64,
) -> jnp.ndarray:
    """Per-tuple SSE via explicit design-matrix QR (paper §II.D steps)."""
    x = jnp.asarray(x, dtype)
    y = jnp.asarray(y, dtype)
    tuples = jnp.asarray(tuples)

    def one_task(lo: int, hi: int):
        xt = x[:, lo:hi]
        yt = y[lo:hi]

        def per_tuple(idx):
            a = xt[idx].T  # (S_t, n)
            a = jnp.concatenate([a, jnp.ones((a.shape[0], 1), dtype)], axis=1)
            q, r = jnp.linalg.qr(a)
            c = jax.scipy.linalg.solve_triangular(r, q.T @ yt, lower=False)
            resid = yt - a @ c
            sse = resid @ resid
            # rank-deficient tuples (zero/collinear features) yield NaN from
            # the triangular solve; rank them last, like the gram engine
            return jnp.where(jnp.isfinite(sse), jnp.maximum(sse, 0.0), jnp.inf)

        return jax.vmap(per_tuple)(tuples)

    total = jnp.zeros((tuples.shape[0],), dtype)
    for lo, hi in layout.slices:
        total = total + one_task(lo, hi)
    return total


# ---------------------------------------------------------------------------
# tuple-space enumeration (blocked; the unit of distribution & journaling)
# ---------------------------------------------------------------------------

def n_models(m: int, n_dim: int) -> int:
    """C(m, n) — paper Fig. 1d."""
    out = 1
    for i in range(n_dim):
        out = out * (m - i) // (i + 1)
    return out


class TupleEnumerator:
    """Rank-addressable blocked view of the C(m, n) lexicographic tuple space.

    A block is identified by its index alone — block ``bi`` covers ranks
    ``[bi·block, bi·block + count(bi))`` — which is exactly the contract
    the fault-tolerance work journal records (runtime/journal.py) and what
    lets resume skip finished blocks without enumerating them.

    Widths 1–2 slice host index arrays (cheap, O(m²) at most); widths ≥ 3
    materialize blocks **on device** via the combinatorial-unranking kernel
    (kernels/unrank.py) — the former host-side ``itertools`` generator
    serialized the dominant phase on single-core Python.  Spaces too large
    for exact device integer arithmetic fall back to host-exact unranking
    of the block start plus C-speed sequential stepping.
    """

    def __init__(self, m: int, n_dim: int, block: int):
        self.m = int(m)
        self.n_dim = int(n_dim)
        self.block = int(block)
        self.total = n_models(self.m, self.n_dim)
        self.n_blocks = -(-self.total // self.block) if self.total else 0
        # width-2 host index cache, built eagerly: block_tuples is called
        # from prefetch worker threads and must stay race-free
        self._pairs: Optional[np.ndarray] = None
        if self.n_dim == 2:
            iu = np.triu_indices(self.m, k=1)
            self._pairs = np.stack(iu, axis=1).astype(np.int32)

    def count(self, bi: int) -> int:
        """Tuples in block ``bi`` (== block except for the tail block)."""
        return max(0, min(self.block, self.total - bi * self.block))

    def block_tuples(self, bi: int):
        """The (count(bi), n_dim) int32 tuple block; device-backed for n ≥ 3."""
        lo = bi * self.block
        cnt = self.count(bi)
        if self.n_dim == 1:
            return np.arange(lo, lo + cnt, dtype=np.int32)[:, None]
        if self.n_dim == 2:
            return self._pairs[lo : lo + cnt]
        from ..kernels import unrank  # deferred: kernels package imports core

        if unrank.device_unrank_ok(self.m, self.n_dim):
            return unrank.unrank_block(lo, cnt, self.m, self.n_dim)
        return self._host_block(lo, cnt)

    def _host_block(self, lo: int, cnt: int) -> np.ndarray:
        """Host-exact fallback: unrank the block start, then step."""
        from ..kernels.unrank import unrank_lex_host

        m, n = self.m, self.n_dim
        a = unrank_lex_host(lo, m, n)
        out = np.empty((cnt, n), np.int32)
        for r in range(cnt):
            out[r] = a
            i = n - 1
            while i >= 0 and a[i] == m - n + i:
                i -= 1
            if i < 0:
                break
            a[i] += 1
            for j in range(i + 1, n):
                a[j] = a[j - 1] + 1
        return out

    def __iter__(self) -> Iterator[np.ndarray]:
        for bi in range(self.n_blocks):
            yield np.asarray(self.block_tuples(bi))


def tuple_blocks(m: int, n_dim: int, block: int) -> Iterator[np.ndarray]:
    """Yield (≤block, n_dim) int32 arrays covering all C(m, n_dim) tuples.

    Deterministic lexicographic order (``itertools.combinations`` order —
    asserted against it in the tests) => a block index fully identifies its
    tuples.  Kept as the stable generator API; :class:`TupleEnumerator`
    is the rank-addressable form the streaming ℓ0 loop uses.
    """
    return iter(TupleEnumerator(m, n_dim, block))


@dataclasses.dataclass
class L0Result:
    tuples: np.ndarray   # (k, n) best tuples, ascending SSE
    sses: np.ndarray     # (k,)
    n_evaluated: int


def l0_search(
    x: np.ndarray,  # (m, S) subspace feature values
    y: np.ndarray,  # (S,)
    layout: TaskLayout,
    n_dim: int,
    n_keep: int = 10,
    block: int = 65536,  # paper: "batch sizes should exceed 65536"
    method: str = "gram",
    engine=None,
    journal=None,
    dtype=None,  # None -> the engine's compute dtype (precision registry)
    prefetch_depth: int = 2,
    prob=None,
    problem=None,
) -> L0Result:
    """Exhaustive n_dim-tuple search over the SIS subspace, double-buffered.

    ``method``: 'gram' (TPU-native closed form) or 'qr' (paper-faithful
    baseline).  ``engine`` is the execution engine (engine/) that scores
    each tuple block — this loop only owns enumeration policy, the running
    top-k merge, and journaling, so there is no per-backend branching here.
    ``problem`` selects the tuple objective (core/problem.py; default
    regression) — the loop itself is objective-agnostic: it merges
    ascending "SSEs", which a problem defines as its lower-is-better
    objective (LSQ SSE, or domain-overlap count + tie term).
    ``journal``: optional runtime.journal.WorkJournal for restartable sweeps.
    ``prob``: optionally a pre-built ``engine.prepare_l0(...)`` problem —
    repeated sweeps over the same operands (benchmarks, residual re-ranks)
    then reuse its Gram statistics and per-problem jit caches.

    Blocks are rank ranges of the lexicographic tuple space
    (:class:`TupleEnumerator`); enumeration + device dispatch of block
    *k+1* overlap block *k*'s scoring via ``prefetch_depth``-deep streaming
    (engine/streaming.py), and the host merge runs off the critical path —
    skipped outright when a block's best SSE cannot enter the current
    top-k.
    """
    if isinstance(engine, str) and engine in ("gram", "qr"):
        # legacy alias: ``engine`` used to name the math method
        warnings.warn(
            f"l0_search(engine={engine!r}) is deprecated; pass "
            f"method={engine!r} (engine= now takes an execution engine)",
            DeprecationWarning, stacklevel=2,
        )
        method, engine = engine, None
    from ..engine import get_engine
    from ..engine.streaming import BlockPrefetcher
    from ..runtime import faults
    from .problem import get_problem

    engine = get_engine(engine)
    kind = get_problem(problem).kind
    if dtype is None:
        dtype = engine.backend.compute_dtype
    n_dim, n_keep, block = int(n_dim), int(n_keep), int(block)
    m = int(np.asarray(x).shape[0])
    if prob is None:
        prob = engine.prepare_l0(x, y, layout, method=method, dtype=dtype,
                                 problem=kind)
    elif (
        prob.method != method
        or prob.problem != kind
        or prob.backend != engine.name
        or prob.dtype != dtype
        or prob.layout != layout
        or prob.x.shape != np.shape(x)
        or not np.array_equal(prob.x, np.asarray(x, np.float64))
        or not np.array_equal(prob.y, np.asarray(y, np.float64))
    ):
        raise ValueError(
            f"pre-built prob (method={prob.method!r}, "
            f"backend={prob.backend!r}, m={prob.m}) was prepared from "
            f"different operands than this sweep (method={method!r}, "
            f"backend={engine.name!r}); prepare it with the same engine "
            f"and x/y/layout or omit prob="
        )
    enum = TupleEnumerator(m, n_dim, block)

    best_sse = np.full((n_keep,), np.inf)
    best_tuples = np.zeros((n_keep, n_dim), np.int64)
    n_eval = 0

    start_block = 0
    sweep = None
    if journal is not None:
        # sweep signature: geometry + a digest of the operands, so a
        # journal can only ever resume the sweep that wrote it —
        # same-shaped sweeps over different data (or a stale file surviving
        # a crash between completion and clear()) restart cleanly instead
        # of poisoning results.  Journal-less sweeps skip the hash.
        digest = hashlib.sha1()
        digest.update(prob.x.tobytes())
        digest.update(prob.y.tobytes())
        digest.update(repr(layout.slices).encode())
        sweep = {"m": m, "n_dim": n_dim, "block": block, "n_keep": n_keep,
                 "method": method, "problem": kind,
                 "dtype": np.dtype(dtype).name,
                 "data": digest.hexdigest()[:16]}
    if journal is not None and journal.has_state():
        j_sse, j_tuples, j_block = journal.restore()
        # only resume state from the *same* sweep: a journal left by a
        # different tuple width, block size, top-k or dataset must not
        # poison this search.  Files without a sweep signature
        # (pre-signature format) fail closed — a clean restart only
        # re-does one sweep's work, while resuming someone else's rank
        # ranges silently drops tuples.
        if j_tuples.shape == (n_keep, n_dim) and journal.meta == sweep:
            best_sse, best_tuples, start_block = j_sse, j_tuples, j_block
    # finished blocks: counted in closed form, not re-enumerated
    n_eval += min(start_block * block, enum.total)

    def score_block(bi: int):
        # fault site: raises TransientDeviceError/KernelFailure (for the
        # resilient wrapper / retry tests) or returns "nan" to corrupt
        # this block's score panel (the NaN scrub below must absorb it)
        kind = faults.check("l0.block_scores")
        tuples = enum.block_tuples(bi)
        # a reducing backend (engine/sharded.py) hands back a ReducedBlock
        # of O(n_keep) winners — only they cross the host boundary; every
        # other backend returns the block's full SSE vector
        res = engine.l0_scores(prob, tuples, n_keep=n_keep)
        if kind == "nan":
            if isinstance(res, ReducedBlock):
                # deliberately non-finite: this *is* the faulted panel the
                # merge loop's isfinite scrub must absorb
                res = ReducedBlock(  # reprolint: disable=RL007
                    indices=np.asarray(res.indices),
                    scores=np.full(len(res), np.nan),
                    n_source=res.n_source,
                )
            else:
                res = np.full((len(tuples),), np.nan)
        return tuples, res

    def winners_of(tuples, bi: int, indices: np.ndarray) -> np.ndarray:
        """Block-local winner indices -> (k, n_dim) int64 tuples.

        Widths ≥ 3 enumerate on device; unranking the k winning ranks on
        host keeps the block itself device-resident (no B×n transfer just
        to gather k rows).
        """
        if n_dim <= 2:
            return np.asarray(tuples)[indices].astype(np.int64)
        from ..kernels.unrank import unrank_lex_host

        base = bi * block
        return np.asarray(
            [unrank_lex_host(base + int(i), m, n_dim) for i in indices],
            np.int64,
        )

    stream = BlockPrefetcher(
        score_block, range(start_block, enum.n_blocks), depth=prefetch_depth
    )
    for bi, (tuples, res) in stream:
        n_eval += len(tuples)
        # merge block top-k into running top-k (host).  A block whose best
        # SSE cannot beat the current k-th best contributes nothing — skip
        # the concatenate+argsort (ties lose to incumbents either way).
        # Negated comparison so a NaN block-min (a backend without the
        # finite→inf guard) falls through to the merge, never to a skip.
        blk_sse = blk_tup = None
        if isinstance(res, ReducedBlock):
            if len(res) and not (res.scores.min() >= best_sse[-1]):
                blk_sse = res.scores
                blk_tup = winners_of(tuples, bi, res.indices)
        else:
            sses = np.asarray(res)
            if len(sses) and not (sses.min() >= best_sse[-1]):
                k = min(n_keep, len(sses))
                # stable selection: exact objective ties (routine for the
                # classification overlap count) must resolve to the same
                # winners as a device-reduced block's ordered top-k
                part = np.argsort(sses, kind="stable")[:k]
                blk_sse = sses[part]
                blk_tup = np.asarray(tuples)[part].astype(np.int64)
        if blk_sse is not None:
            # scrub non-finite panel entries (NaN from a faulted device,
            # ±inf sentinels) to +inf so a poisoned block loses to every
            # finite incumbent instead of corrupting the top-k order
            blk_sse = np.where(np.isfinite(blk_sse), blk_sse, np.inf)
            cat_sse = np.concatenate([best_sse, blk_sse])
            cat_tup = np.concatenate([best_tuples, blk_tup])
            order = np.argsort(cat_sse, kind="stable")[:n_keep]
            best_sse, best_tuples = cat_sse[order], cat_tup[order]
        if journal is not None:
            journal.record(bi + 1, best_sse, best_tuples, meta=sweep)
        # fault site: a worker preemption between blocks ("kill" exits the
        # process after the journal record, like a SIGKILL mid-sweep)
        faults.check("worker.tick")

    return L0Result(tuples=best_tuples, sses=best_sse, n_evaluated=n_eval)
