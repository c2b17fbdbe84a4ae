"""Sure-independence screening (SIS) — the second SISSO phase.

Scores every candidate feature by its Pearson correlation (paper Eq. 1)
against the target (dimension 1) or the residuals of the best previous-
dimension models, and selects the top ``n_sis`` features per dimension.

Multi-task SISSO: samples are partitioned into tasks; correlations are
computed *within* each task and combined as the mean of |r| over tasks; a
feature's score is the max over the supplied residuals (paper §III.A.1 uses
"ten residuals per SIS iteration").

Scalable formulation (the whole screen is three matmuls + an epilogue):
let ``M (T,S)`` be the 0/1 task-membership matrix and ``Ytilde (R*T, S)`` the
residuals centered and L2-normalized within each task and zero elsewhere.
For a block of candidate values ``V (B,S)``::

    sums  = V @ M.T          # (B,T)   per-task sums
    sumsq = (V*V) @ M.T      # (B,T)
    dots  = V @ Ytilde.T     # (B,R*T) numerators (residuals are centered)
    r[b,r,t] = dots[b,r,t] / sqrt(sumsq[b,t] - sums[b,t]^2 / n_t)

The same contraction is what kernels/fused_sis.py fuses with on-the-fly
feature generation (paper P3) so last-rung values never touch HBM.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .feature_space import CandidateBlock, Feature, FeatureSpace

_EPS = 1e-12


@dataclasses.dataclass(frozen=True)
class TaskLayout:
    """Static description of the task partition (samples grouped by task)."""

    slices: Tuple[Tuple[int, int], ...]  # [(start, stop)] per task

    @staticmethod
    def single(n_samples: int) -> "TaskLayout":
        return TaskLayout(((0, n_samples),))

    @staticmethod
    def from_task_ids(task_ids: np.ndarray) -> "TaskLayout":
        task_ids = np.asarray(task_ids)
        if not (np.diff(task_ids) >= 0).all():
            raise ValueError("samples must be grouped (sorted) by task id")
        slices = []
        for t in np.unique(task_ids):
            idx = np.nonzero(task_ids == t)[0]
            slices.append((int(idx[0]), int(idx[-1]) + 1))
        return TaskLayout(tuple(slices))

    @property
    def n_tasks(self) -> int:
        return len(self.slices)

    def membership(self, n_cols: int, dtype=np.float32) -> np.ndarray:
        m = np.zeros((self.n_tasks, n_cols), dtype)
        for t, (lo, hi) in enumerate(self.slices):
            m[t, lo:hi] = 1.0
        return m

    def counts(self) -> np.ndarray:
        return np.asarray([hi - lo for lo, hi in self.slices], np.float32)


@dataclasses.dataclass
class ScoreContext:
    """Precomputed screening operands, padded to ``s_pad`` columns.

    Problem-tagged (core/problem.py): ``problem`` names the objective the
    operands encode, so a backend dispatches on the *context*, never on
    config flags.  Regression fills ``y_tilde`` (centered+normalized
    residuals); classification fills ``class_members`` (0/1 per class)
    and ``state_masks`` (one 0/1 still-ambiguous mask per retained model,
    the classification analogue of the residual axis).
    """

    membership: np.ndarray  # (T, s_pad)
    y_tilde: np.ndarray     # (R*T, s_pad) per-task centered+normalized residuals
    counts: np.ndarray      # (T,)
    n_residuals: int
    s: int                  # true sample count
    s_pad: int
    problem: str = "regression"
    class_members: Optional[np.ndarray] = None  # (C, s_pad) 0/1
    state_masks: Optional[np.ndarray] = None    # (R, s_pad) 0/1


def build_score_context(
    residuals: np.ndarray,  # (R, S)
    layout: TaskLayout,
    s_pad: Optional[int] = None,
    dtype=np.float32,
) -> ScoreContext:
    residuals = np.atleast_2d(np.asarray(residuals, np.float64))
    r, s = residuals.shape
    s_pad = s_pad or s
    t = layout.n_tasks
    m = np.zeros((t, s_pad), dtype)
    m[:, :s] = layout.membership(s)
    y_tilde = np.zeros((r * t, s_pad), np.float64)
    for ri in range(r):
        for ti, (lo, hi) in enumerate(layout.slices):
            seg = residuals[ri, lo:hi]
            seg = seg - seg.mean()
            nrm = np.linalg.norm(seg)
            if nrm > _EPS:
                y_tilde[ri * t + ti, lo:hi] = seg / nrm
    return ScoreContext(
        membership=m, y_tilde=y_tilde.astype(dtype), counts=layout.counts(),
        n_residuals=r, s=s, s_pad=s_pad,
    )


def scores_from_reductions(
    sums: jnp.ndarray,   # (B, T)
    sumsq: jnp.ndarray,  # (B, T)
    dots: jnp.ndarray,   # (B, R*T)
    counts: jnp.ndarray,  # (T,)
    n_residuals: int,
) -> jnp.ndarray:
    """Epilogue: per-task Pearson r -> mean_t |r| -> max over residuals."""
    b, t = sums.shape
    var = sumsq - sums * sums / counts[None, :]
    inv_norm = jax.lax.rsqrt(jnp.maximum(var, _EPS))
    r = dots.reshape(b, n_residuals, t) * inv_norm[:, None, :]
    score = jnp.abs(r).mean(axis=2).max(axis=1)
    return jnp.where(jnp.isfinite(score), score, -jnp.inf)


def screen_reductions(
    values: jnp.ndarray,      # (B, S)
    membership: jnp.ndarray,  # (T, S)
    y_tilde: jnp.ndarray,     # (R*T, S)
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The screen's three matmuls ``(sums, sumsq, dots)``.

    Pinned to ``HIGHEST``: on a TPU an fp32 dot otherwise runs as bf16
    passes, whose ~1e-3 relative error reorders near-tied SIS scores.
    """
    hi = jax.lax.Precision.HIGHEST
    return (jnp.matmul(values, membership.T, precision=hi),
            jnp.matmul(values * values, membership.T, precision=hi),
            jnp.matmul(values, y_tilde.T, precision=hi))


def score_block(values: jnp.ndarray, ctx: ScoreContext) -> jnp.ndarray:
    """Pure-jnp scoring of a (B, s_pad) value block (oracle path)."""
    m = jnp.asarray(ctx.membership, values.dtype)
    yt = jnp.asarray(ctx.y_tilde, values.dtype)
    sums, sumsq, dots = screen_reductions(values, m, yt)
    return scores_from_reductions(
        sums, sumsq, dots, jnp.asarray(ctx.counts, values.dtype), ctx.n_residuals
    )


# ---------------------------------------------------------------------------
# top-k merge.  Two block shapes flow into the merge: full score vectors
# (host-side ranking, the paper's "transferred back to CPU ... used to rank
# the features") and *pre-reduced* blocks — a backend that merges on device
# (engine/sharded.py) returns only the block's top-k (index, score) winners,
# so O(k) payloads cross the host boundary instead of O(B) score vectors.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReducedBlock:
    """Device-reduced top-k of one score block.

    ``indices`` are positions *within the submitted block* (0 ≤ i < the
    block length the caller dispatched); ``scores`` are sorted best-first
    (descending for SIS projection scores, ascending for ℓ0 SSEs).  Entries
    are always finite: padding rows, invalid candidates and ±inf sentinels
    are filtered before the block crosses the host boundary.  Top-k of a
    union equals top-k of the per-block top-k union, so merging reduced
    blocks is exactly as good as merging full vectors.
    """

    indices: np.ndarray   # (k',) int64, k' <= n_keep
    scores: np.ndarray    # (k',) float64, best-first
    n_source: int         # block length the reduction ran over

    def __len__(self) -> int:
        return len(self.indices)

    @staticmethod
    def reduce_host(
        scores: np.ndarray,
        n_keep: int,
        mask: Optional[np.ndarray] = None,
        largest: bool = True,
    ) -> "ReducedBlock":
        """Host-side reference reduction (backends without a device merge).

        Stable first-occurrence tie order — the same order a stable
        descending/ascending sort of the full vector would produce, so a
        host-reduced block merges bit-identically to the full vector.
        """
        s = np.asarray(scores, np.float64)
        if mask is not None:
            s = np.where(np.asarray(mask, bool), s, -np.inf if largest else np.inf)
        order = np.argsort(-s if largest else s, kind="stable")[: int(n_keep)]
        keep = np.isfinite(s[order])
        order = order[keep]
        return ReducedBlock(
            indices=order.astype(np.int64), scores=s[order], n_source=len(s)
        )


@dataclasses.dataclass
class TopK:
    k: int
    scores: np.ndarray = dataclasses.field(default_factory=lambda: np.zeros(0))
    tags: list = dataclasses.field(default_factory=list)

    def push(self, scores: np.ndarray, tags: List[tuple]) -> None:
        scores = np.asarray(scores, np.float64)
        keep = np.isfinite(scores) & (scores > -np.inf)
        scores, tags = scores[keep], [t for t, k in zip(tags, keep) if k]
        if len(scores) == 0:
            return
        all_scores = np.concatenate([self.scores, scores])
        all_tags = self.tags + tags
        # stable first-occurrence tie order: exact score ties are routine
        # for the classification problem (mirror candidates share overlap
        # counts), and an unstable partition would let the full-vector and
        # device-reduced merge paths pick *different* tied winners
        idx = np.argsort(-all_scores, kind="stable")[: self.k]
        self.scores = all_scores[idx]
        self.tags = [all_tags[i] for i in idx]

    def push_reduced(self, rb: ReducedBlock, tag_of) -> None:
        """Merge a pre-reduced block; ``tag_of(i)`` builds the tag for
        block-local index ``i`` — called only for the O(k) winners, so the
        host never materializes a block-length tag list."""
        if len(rb) == 0:
            return
        self.push(rb.scores, [tag_of(int(i)) for i in rb.indices])


# ---------------------------------------------------------------------------
# full screen over a FeatureSpace
# ---------------------------------------------------------------------------

def sis_screen(
    fspace: FeatureSpace,
    residuals: np.ndarray,  # (R, S) problem state (residuals / ambiguity masks)
    layout: TaskLayout,
    n_sis: int,
    exclude: Set[int],
    batch: int = 1 << 16,
    engine=None,
    overselect: int = 2,
    problem=None,
    y: Optional[np.ndarray] = None,
) -> Tuple[List[Feature], np.ndarray]:
    """Select the top-``n_sis`` unselected features; returns (features, scores).

    Screens both materialized features and deferred last-rung candidates
    (paper P3 on-the-fly path).  All screening math runs on the supplied
    execution ``engine`` (engine/) — this function only owns batching and
    the top-k merge policy, so there is no per-backend branching here: a
    backend that merges on device (``engine.reduces_blocks``) hands back
    :class:`ReducedBlock` winners and the push indexes tags lazily; every
    other backend returns full score vectors and the classic host merge
    runs.

    ``problem`` selects the screening objective (core/problem.py; default
    regression): the problem builds the tagged :class:`ScoreContext` from
    ``residuals`` (the problem state) and, for classification, the class
    labels ``y``.  Scores are always merged descending — problems encode
    "lower is better" objectives as negated scores.
    """
    from ..engine import get_engine
    from .problem import get_problem

    engine = get_engine(engine)
    ctx = get_problem(problem).build_sis_context(
        residuals, y, layout, dtype=engine.backend.score_ctx_dtype
    )
    x = fspace.values_matrix().astype(np.float64)
    from ..engine.streaming import BlockPrefetcher

    def window(k: int) -> TopK:
        """The ``k`` best screenable features and deferred candidates."""
        top = TopK(k=k)
        # 1) materialized features (all rungs kept in memory)
        for lo in range(0, len(x), batch):
            hi = min(lo + batch, len(x))
            # mask of screenable rows: already-selected features must not
            # occupy winner slots (applied on device on reducing backends)
            blk_mask = None
            if exclude:
                blk_mask = np.ones(hi - lo, bool)
                for fid in exclude:
                    if lo <= fid < hi:
                        blk_mask[fid - lo] = False
            res = engine.sis_scores(x[lo:hi], ctx, n_keep=k, mask=blk_mask)
            if isinstance(res, ReducedBlock):
                top.push_reduced(res, lambda i, lo=lo: ("feat", lo + i))
            else:
                # the Engine already applied blk_mask (-inf) on this path
                top.push(np.asarray(res, np.float64),
                         [("feat", fid) for fid in range(lo, hi)])

        # 2) deferred last-rung candidates: generate -> score -> discard.
        #    Double-buffered (engine/streaming.py): block k+1's child-row
        #    gather and device dispatch overlap block k's scoring, and the
        #    host top-k push runs off the critical path.
        def score_deferred(blk: CandidateBlock):
            return engine.sis_scores_deferred(
                blk.op_id, x[blk.child_a], x[blk.child_b], ctx,
                fspace.l_bound, fspace.u_bound, n_keep=k,
            )

        for blk, s in BlockPrefetcher(
            score_deferred, fspace.iter_candidate_batches(batch),
            span="sisso.sis",
        ):
            if isinstance(s, ReducedBlock):
                top.push_reduced(
                    s,
                    lambda i, blk=blk: (
                        "cand", blk.op_id, int(blk.child_a[i]),
                        int(blk.child_b[i])
                    ),
                )
            else:
                tags = [
                    ("cand", blk.op_id, int(a), int(b))
                    for a, b in zip(blk.child_a, blk.child_b)
                ]
                top.push(s, tags)
        return top

    # 3) materialize winners in score order, skipping value-duplicates of
    #    existing features, until n_sis are collected.  A deferred
    #    candidate selected at an earlier dimension, and its duplicates,
    #    are screened again and take window slots; while the window ran
    #    out before n_sis were new, the screen runs again with a window
    #    twice as wide, and only winners not examined before are taken.
    selected: List[Feature] = []
    sel_scores: List[float] = []
    examined: Set[tuple] = set()
    k = n_sis * overselect
    while True:
        top = window(k)
        for score, tag in zip(top.scores, top.tags):
            if len(selected) >= n_sis:
                break
            if tag in examined:
                continue
            examined.add(tag)
            if tag[0] == "feat":
                feat = fspace.features[tag[1]]
                if feat.fid in exclude:
                    continue
            else:
                feat = fspace.materialize_candidate(tag[1], tag[2], tag[3])
                if feat is None:  # value-duplicate of an existing feature
                    continue
            selected.append(feat)
            sel_scores.append(float(score))
        if len(selected) >= n_sis or len(top.tags) < k:
            break
        k *= 2
    return selected, np.asarray(sel_scores)
