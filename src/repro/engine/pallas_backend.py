"""Pallas backend: jnp everywhere + Pallas kernels on the hot paths.

* deferred SIS — ``kernels/fused_sis.py``: candidates are generated,
  validated and scored in VMEM, never materialized to HBM (paper P3,
  deepened).  The kernel's reduced top-k epilogue + device merge return
  O(k) winners — full score vectors never exist, in HBM or on the host.
* ℓ0 pairs — ``kernels/ops.py:l0_score_pairs``: closed-form SSE gathered
  from Gram statistics (the tile kernel's math, XLA-gather form, in the
  compute dtype).
* ℓ0 widths ≥ 3 — ``kernels/l0_gather.py``: Gram-gather kernel over
  VMEM-resident Gram statistics (one run of prefix-sharing tuples per
  panel row, unrolled elimination along the lanes), **two-phase and
  certified**: the fp32 kernel bounds every
  tuple's SSE from below (with a reduced epilogue on the ``n_keep``
  path), the lowest bounds are rescored from fp64 Gram statistics, and
  the block's top-k is exact whenever its n_keep-th rescored SSE lies
  below the lowest bound left out — otherwise the whole block is
  rescored in fp64.  The blocks each path settled are counted in
  ``l0_paths`` (``Backend.record_l0_path``).

Compute dtype policy (``set_precision``):

=============  ======================  =================================
precision      SIS kernel operands     ℓ0 gather prescreen
=============  ======================  =================================
fp64 (default) fp32 (historical pin)   fp32 pack
fp32           fp32                    fp32 pack
bf16           bf16 (fp32 accumulate)  fp32 pack — see below
=============  ======================  =================================

The ℓ0 pack holds fp32 entries even under bf16 precision: the kernel's
error bound scales with the pack's rounding, and a bf16 pack would widen
it 2^15-fold, sending near-exact fits to the fp64 fallback on most
blocks.  bf16 belongs where the paper puts it: bulk child-value
generation + correlation matmuls, where errors stay relative and the
fp64 rescore pins final rankings.

Everything else (width-1 tuples, QR method, classification) inherits the
jnp implementation — the kernels accelerate, the semantics stay canonical.
Off a TPU the kernels run with ``interpret=True``; on a TPU they compile
with Mosaic (tests/test_tpu_compile.py compiles them for a described v5e).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.l0 import compute_gram_stats, score_tuples_gram
from ..core.sis import (
    ReducedBlock, ScoreContext, scores_from_reductions, screen_reductions,
)
from ..kernels import autotune
from ..kernels import ops as kops
from ..kernels.l0_gather import (
    count_runs, l0_gather_topk_pallas, l0_gather_tuples_pallas, panel_rows,
)
from ..runtime import trace
from .base import SIS_FUSED, L0Problem
from .jnp_backend import JnpBackend


@functools.partial(jax.jit, static_argnames=("n_residuals", "k"))
def _sis_topk_jit(values, membership, y_tilde, counts, mask, n_residuals, k):
    """Materialized-block SIS screen fused with a device top-k.

    Same score math as the jnp full-vector path, so the winners it returns
    are the ones a host stable sort of that vector would pick (lax.top_k
    ties resolve to the lowest index, matching stable order)."""
    sums, sumsq, dots = screen_reductions(values, membership, y_tilde)
    scores = scores_from_reductions(sums, sumsq, dots, counts, n_residuals)
    scores = jnp.where(mask, scores, -jnp.inf)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx


@functools.lru_cache(maxsize=None)
def _l0_shard_reducer(n: int, k: int, block_t: int, rows: int,
                      interpret: bool):
    """One shard's reduced Gram-gather at one static shape.  The same
    object for the same shape, in every fit, so the ``shard_map`` program
    built over it (``core/distributed.make_l0_topk_reduced_fn``, cached by
    its arguments) is lowered once per process and panel height."""

    def reducer(tup_blk, vld_blk, gram, fsum, bvec, scal):
        # valid rows form a global prefix, hence a prefix of each
        # contiguous shard chunk — the count is the local boundary
        nv = jnp.sum(vld_blk.astype(jnp.int32))
        return l0_gather_topk_pallas(
            jnp.asarray(tup_blk, jnp.int32).T, gram, fsum, bvec, scal, nv,
            n=n, k=k, block_t=block_t, rows=rows, interpret=interpret)

    return reducer


class PallasBackend(JnpBackend):
    name = "pallas"
    fused_deferred = True
    # width 2 = closed-form pair gather; widths >= 3 = the Gram-gather
    # kernel, whose prefix gathers and unrolled SPD elimination are
    # width-generic (8 is a compile-time sanity ceiling, not a kernel
    # limit: the elimination unrolls (n+1)^2 lanes per step)
    l0_widths = tuple(range(2, 9))
    # the fused-SIS and Gram-gather kernels encode the regression math;
    # classification contexts route to the inherited jnp implementations
    kernel_problems = ("regression",)

    def __init__(self, interpret: Optional[bool] = None, block_b: int = 256,
                 rescore_k: int = 512, block_t: int = 256,
                 epilogue_k: int = 64, autotune: bool = False):
        super().__init__()
        self.interpret = interpret  # None -> auto (interpret off-TPU)
        self.block_b = int(block_b)
        # per-block candidate count re-scored exactly in fp64 (phase 2 of
        # the gather path); must comfortably exceed any caller's n_keep
        self.rescore_k = int(rescore_k)
        # Gram-gather panel rows (runs of tuples) per grid step
        self.block_t = int(block_t)
        # per-grid-step winner count of the fused-SIS reduced epilogue;
        # grown automatically to cover a caller's n_keep
        self.epilogue_k = int(epilogue_k)
        # measure block/epilogue shapes on the first batch per (kernel,
        # device, padded shape, dtype) — kernels/autotune.py
        self.autotune = bool(autotune)

    @property
    def resolved_interpret(self) -> bool:
        """The interpret flag with the off-TPU auto-default applied.

        The distribution wrapper (engine/sharded.py) runs this backend's
        fused kernel inside ``shard_map`` and needs the resolved value —
        shard_map closures are cached per static config, so ``None`` must
        collapse to a concrete bool exactly once, here.
        """
        return kops._interpret_default() if self.interpret is None \
            else self.interpret

    @property
    def kernel_dtype(self):
        """Pallas kernel compute dtype for SIS operands.

        bf16 precision runs the kernels bf16-native (fp32 accumulation via
        ``preferred_element_type``); fp32/fp64 keep the historical fp32
        kernel operands — fp64 exactness comes from the rescore phase, not
        the pre-pass.
        """
        return jnp.bfloat16 \
            if jnp.dtype(self.compute_dtype) == jnp.bfloat16 else jnp.float32

    # -- SIS ------------------------------------------------------------

    def sis_scores_deferred(self, op_id, a, b, ctx: ScoreContext,
                            l_bound, u_bound):
        if ctx.problem not in self.kernel_problems:
            # eval -> (jnp) overlap score -> mask compose path
            return super().sis_scores_deferred(
                op_id, a, b, ctx, l_bound, u_bound
            )
        self.record_sis_path(SIS_FUSED)
        scores = kops.fused_gen_sis(
            int(op_id), jnp.asarray(a), jnp.asarray(b),
            ctx, l_bound=l_bound, u_bound=u_bound,
            block_b=self.block_b, interpret=self.interpret,
            dtype=self.kernel_dtype,
        )
        return np.asarray(scores)

    def sis_topk(self, values, ctx: ScoreContext, n_keep, mask=None):
        """Materialized block: score + top-k in one device program — only
        the k winners cross the host boundary."""
        if ctx.problem not in self.kernel_problems or len(values) == 0:
            return super().sis_topk(values, ctx, n_keep, mask=mask)
        v = jnp.asarray(values, self.compute_dtype)
        msk = jnp.ones((v.shape[0],), bool) if mask is None \
            else jnp.asarray(np.asarray(mask, bool))
        k = min(int(n_keep), v.shape[0])
        vals, idx = _sis_topk_jit(
            v, jnp.asarray(ctx.membership, v.dtype),
            jnp.asarray(ctx.y_tilde, v.dtype),
            jnp.asarray(ctx.counts, v.dtype), msk, ctx.n_residuals, k,
        )
        vals = np.asarray(vals, np.float64)
        idx = np.asarray(idx)
        keep = np.isfinite(vals)
        return ReducedBlock(indices=idx[keep].astype(np.int64),
                            scores=vals[keep], n_source=len(values))

    def sis_topk_deferred(self, op_id, a, b, ctx: ScoreContext,
                          l_bound, u_bound, n_keep):
        """Deferred block through the reduced-epilogue fused kernel: the
        full score vector never exists, in HBM or on the host."""
        if ctx.problem not in self.kernel_problems:
            return super().sis_topk_deferred(
                op_id, a, b, ctx, l_bound, u_bound, n_keep
            )
        self.record_sis_path(SIS_FUSED)
        a = jnp.asarray(a)
        b = jnp.asarray(b)
        block_b, k_epi = self._tuned_sis_cfg(
            int(op_id), a, b, ctx, l_bound, u_bound, n_keep
        )
        scores, gidx = kops.fused_gen_sis_topk(
            int(op_id), a, b, ctx, l_bound, u_bound, n_keep,
            block_b=block_b, epilogue_k=k_epi, interpret=self.interpret,
            dtype=self.kernel_dtype,
        )
        # finiteness filter lives in kops.fused_gen_sis_topk (ops.py): the
        # epilogue's ±inf sentinel lanes are dropped before return.
        return ReducedBlock(indices=gidx, scores=scores,  # reprolint: disable=RL007
                            n_source=a.shape[0])

    def _tuned_sis_cfg(self, op_id, a, b, ctx, l_bound, u_bound, n_keep):
        """First-batch (block_b, epilogue_k) search, cached per
        (device, padded shape, dtype) — paper §II.D launch tuning."""
        if not self.autotune:
            return self.block_b, self.epilogue_k
        shape = (kops._pad_to(max(a.shape[0], 1), 128),
                 kops._pad_to(max(a.shape[1], 128), 128))
        key = ("fused_sis_topk", autotune.device_kind(), shape,
               str(jnp.dtype(self.kernel_dtype)))
        cands = [(bb, ke) for bb in autotune.FUSED_SIS_BLOCKS
                 for ke in autotune.EPILOGUE_KS]

        def run(cfg):
            bb, ke = cfg
            return kops.fused_gen_sis_topk(
                op_id, a, b, ctx, l_bound, u_bound, n_keep, block_b=bb,
                epilogue_k=ke, interpret=self.interpret,
                dtype=self.kernel_dtype,
            )

        return autotune.pick_config(key, cands, run)

    # -- ℓ0 --------------------------------------------------------------

    def rescore_window(self, n_keep: int) -> int:
        """Prescreened tuples per block rescored in fp64: ``rescore_k``,
        grown to twice a caller's ``n_keep``."""
        return max(self.rescore_k, 2 * int(n_keep))

    def _fp64_stats(self, prob: L0Problem):
        """fp64 centered Gram stats, built once per problem from the fp64
        master ``x``/``y``: the source of the kernel pack and of every
        exact rescore, whatever the compute dtype of ``prob.stats``."""
        with self._l0_cache_lock:  # prefetch workers race the first fill
            stats = prob.cache.get("l0_fp64_stats")
            if stats is None:
                stats = prob.stats
                if jnp.dtype(stats.gram.dtype) != jnp.float64:
                    stats = compute_gram_stats(prob.x, prob.y, prob.layout,
                                               jnp.float64)
                prob.cache["l0_fp64_stats"] = stats
        return stats

    def _gram_pack(self, prob: L0Problem) -> dict:
        """fp32 kernel pack, rounded from the fp64 Gram statistics (the
        kernel's error bound counts one fp32 rounding per entry)."""
        stats = self._fp64_stats(prob)
        with self._l0_cache_lock:
            pack = prob.cache.get("gram_pack")
            if pack is None:
                pack = prob.cache["gram_pack"] = kops.pack_gram(stats)
        return pack

    @trace.span("sisso.l0.rescore")
    def _exact_rescore(self, prob: L0Problem, tuples_dev) -> np.ndarray:
        """fp64 SSEs of candidate tuples (jitted, cached per problem)."""
        stats = self._fp64_stats(prob)
        with self._l0_cache_lock:
            fn = prob.cache.get("l0_fp64_rescore")
            if fn is None:
                fn = jax.jit(functools.partial(score_tuples_gram, stats))
                prob.cache["l0_fp64_rescore"] = fn
        return np.asarray(fn(tuples_dev), np.float64)

    def _gather_eligible(self, prob: L0Problem, width: int) -> bool:
        return (prob.problem in self.kernel_problems
                and prob.method == "gram" and width >= 3
                and width in self.l0_widths
                and kops.gram_pack_nbytes(prob.stats.n_tasks, prob.stats.m)
                <= kops.GRAM_VMEM_BUDGET)

    def l0_scores(self, prob: L0Problem, tuples: np.ndarray) -> np.ndarray:
        width = int(tuples.shape[1])
        if len(tuples) == 0 or prob.problem not in self.kernel_problems \
                or prob.method != "gram" or width not in self.l0_widths:
            self.record_l0_path(width, "jnp")
            return super().l0_scores(prob, tuples)
        if width == 2:
            self.record_l0_path(width, "closed-form pairs")
            return np.asarray(
                kops.l0_score_pairs(prob.stats, jnp.asarray(tuples, jnp.int32))
            )
        return self._l0_scores_gather(prob, tuples)

    def _l0_scores_gather(self, prob: L0Problem, tuples) -> np.ndarray:
        """Widths ≥ 3: Gram-gather kernel bounds + exact fp64 rescore.

        Phase 1 bounds every tuple's SSE from below on device; phase 2
        rescores the ``rescore_k`` lowest bounds from fp64 Gram statistics
        and splices the exact values in.  Every tuple left out has an SSE
        at least ``floor``, the lowest bound left out; when the best half
        of the rescored tuples all lie below it, the vector's top
        ``rescore_k // 2`` are exact and in exact order.  Otherwise (a
        near-exact fit with more near-ties than the window) the whole
        block is rescored in fp64.
        """
        width = int(tuples.shape[1])
        if not self._gather_eligible(prob, width):
            # Gram stats would not fit in VMEM (huge subspace) — use the
            # generic device path; checked arithmetically so the fp32 pack
            # is never even allocated.
            self.record_l0_path(width, "jnp")
            return super().l0_scores(prob, tuples)
        pack = self._gram_pack(prob)
        block_t = self._tuned_l0_block(pack, tuples)
        out = np.asarray(
            kops.l0_score_tuples(pack, tuples, block_t=block_t,
                                 interpret=self.interpret), np.float64)
        r = min(len(out), self.rescore_k)
        # stable sort, not argpartition: equal bounds must admit the same
        # (lowest-index) candidates the reduced path's device merge keeps
        order = np.argsort(out, kind="stable")
        cand = order[:r]
        floor = out[order[r]] if r < len(out) else np.inf
        exact = self._exact_rescore(prob, jnp.asarray(tuples)[cand])
        if floor != np.inf and not np.sort(exact)[max(r // 2, 1) - 1] < floor:
            self.record_l0_path(width, "exact fp64 (window not certified)")
            return self._exact_rescore(prob, jnp.asarray(tuples))
        self.record_l0_path(width, "Gram-gather kernel")
        out[cand] = exact
        return out

    def l0_topk(self, prob: L0Problem, tuples, n_keep: int) -> ReducedBlock:
        """Reduced ℓ0 path: per-tile top-k epilogue → device merge → fp64
        rescore of the O(k) survivors.  Full SSE vectors never exist.

        The survivors are the lowest SSE bounds; the exclusion floor
        bounds every tuple left out.  The result is exact when the
        ``n_keep``-th best rescored SSE lies below that floor, and comes
        from an fp64 rescore of the whole block when it does not.
        """
        width = int(tuples.shape[1]) if len(tuples) else 0
        if len(tuples) == 0 or width < 3 \
                or not self._gather_eligible(prob, width):
            # width 2 (closed-form pairs) and delegated problems reduce on
            # host over the exact full-vector scores
            return super().l0_topk(prob, tuples, n_keep)
        pack = self._gram_pack(prob)
        tuples = jnp.asarray(tuples, jnp.int32)
        n_total = int(tuples.shape[0])
        r = min(n_total, self.rescore_window(n_keep))
        _, gidx, floor = kops.l0_topk_tuples(
            pack, tuples, n_keep=r, block_t=self._tuned_l0_block(pack, tuples),
            interpret=self.interpret,
        )
        # order candidates by global index before the stable rescore sort
        # so exact-SSE ties resolve to the lowest index — the order a
        # stable sort of the full vector produces
        gidx = np.sort(gidx)
        exact = self._exact_rescore(prob, tuples[jnp.asarray(gidx)]) \
            if len(gidx) else np.zeros((0,))
        order = np.argsort(exact, kind="stable")[: int(n_keep)]
        order = order[np.isfinite(exact[order])]
        kth = exact[order[-1]] if len(order) == int(n_keep) else np.inf
        if floor != np.inf and not kth < floor:
            self.record_l0_path(width, "exact fp64 (window not certified)")
            return ReducedBlock.reduce_host(
                self._exact_rescore(prob, tuples), int(n_keep), largest=False)
        self.record_l0_path(width, "Gram-gather kernel")
        return ReducedBlock(indices=gidx[order].astype(np.int64),
                            scores=exact[order], n_source=n_total)

    def l0_device_reducer(self, prob: L0Problem, width: int, k_local: int):
        """Traceable per-shard reduced Gram-gather for engine/sharded.py.

        Returns ``(specialize, operands)``: ``specialize(tuples, n_valid,
        n_shards)`` reads the padded block's per-shard run counts back and
        returns the reducer for that panel height (one object per static
        shape, :func:`_l0_shard_reducer`), which runs the kernel on one
        shard's tuple block and keeps its ``k_local`` lowest SSE bounds and
        the floor under every bound it left out — the wrapper rescores
        merged survivors via :meth:`_exact_rescore` and certifies them
        against the floor.
        ``None`` when the gather kernel does not cover this problem/width.
        """
        if width < 3 or not self._gather_eligible(prob, width):
            return None
        pack = self._gram_pack(prob)
        operands = (pack["gram"], pack["fsum"], pack["bvec"], pack["scal"])
        block_t = self.block_t
        interpret = self.resolved_interpret
        n, k = int(width), int(k_local)

        def specialize(tuples, n_valid: int, n_shards: int):
            rows = kops.l0_panel_rows(jnp.asarray(tuples, jnp.int32).T,
                                      n_valid, n_shards, pack["m_pad"])
            return _l0_shard_reducer(n, k, block_t, rows, interpret)

        return specialize, operands

    def _tuned_l0_block(self, pack: dict, tuples) -> int:
        """Panel rows per grid step of the Gram-gather kernel."""
        if not self.autotune:
            return self.block_t
        tuples_t = jnp.asarray(tuples, jnp.int32).T
        n, b = tuples_t.shape
        key = ("l0_gather_panel", autotune.device_kind(),
               (pack["m_pad"], n), pack.get("dtype", "float32"))
        # the probes time the kernel alone: no l0_rows counts, one
        # run-count read-back for all of them
        rows = panel_rows(int(count_runs(tuples_t, b)[0]))

        def run(bt):
            return l0_gather_tuples_pallas(
                tuples_t, pack["gram"], pack["fsum"], pack["bvec"],
                pack["scal"], n=n, block_t=bt, rows=rows,
                interpret=self.resolved_interpret)

        return autotune.pick_config(key, autotune.L0_TILE_BLOCKS, run)
