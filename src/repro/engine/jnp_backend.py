"""jnp backend: jit-cached XLA implementations of the three hot phases.

This is the default engine: the SIS screen is three MXU matmuls plus an
epilogue (core/sis.py docstring), ℓ0 is the Gram-cached closed form or the
paper-faithful batched QR (core/l0.py).  All entry points funnel through
module-level ``jax.jit`` wrappers so repeated blocks of the same shape reuse
the compiled executable.
"""
from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..core.l0 import compute_gram_stats, score_tuples_gram, score_tuples_qr
from ..core.operators import apply_op
from ..core.problem import (
    ClassStats, overlap_scores_ops, score_tuples_overlap,
)
from ..core.sis import (
    ScoreContext, scores_from_reductions, screen_reductions,
)
from ..core.validity import value_rules_jnp
from .base import L0Problem, ScoringBackend


@functools.partial(jax.jit, static_argnames=("op_id",))
def _eval_jit(op_id, a, b, l_bound, u_bound):
    v = apply_op(op_id, a, b)
    return v, value_rules_jnp(v, l_bound, u_bound)


@functools.partial(jax.jit, static_argnames=("n_residuals",))
def _score_jit(values, membership, y_tilde, counts, n_residuals):
    sums, sumsq, dots = screen_reductions(values, membership, y_tilde)
    return scores_from_reductions(sums, sumsq, dots, counts, n_residuals)


#: classification SIS: jit per (B, S, T, C, R) shape combination — same
#: caching discipline as the regression screen above
_overlap_score_jit = jax.jit(overlap_scores_ops)


class JnpBackend(ScoringBackend):
    name = "jnp"

    def __init__(self):
        # compiled descriptor programs -> jit closure (jax.jit then caches
        # one executable per batch shape — the serving compile cache)
        self._programs = {}
        # guards per-problem cache fills: l0_scores runs on prefetch worker
        # threads (engine/streaming.py), and an unguarded check-then-build
        # would trace+compile the scoring closure once per worker
        self._l0_cache_lock = threading.Lock()

    def eval_program(self, program, x):
        fn = self._programs.get(program)
        if fn is None:
            from ..core.descriptor import program_evaluator_jnp

            fn = self._programs[program] = program_evaluator_jnp(program)
        return np.asarray(fn(jnp.asarray(x, jnp.float64)), np.float64)

    def eval_block(self, op_id, a, b, l_bound, u_bound):
        # deliberately fp64 at every precision: candidate *values* are the
        # feature store's master copy and the validity rules' operand —
        # precision selects the screening/solve dtype, not the store's
        v, valid = _eval_jit(
            int(op_id), jnp.asarray(a, jnp.float64), jnp.asarray(b, jnp.float64),
            float(l_bound), float(u_bound),
        )
        return np.asarray(v, np.float64), np.asarray(valid)

    def sis_scores(self, values, ctx: ScoreContext) -> np.ndarray:
        v = jnp.asarray(values, self.compute_dtype)
        if ctx.problem == "classification":
            scores = _overlap_score_jit(
                v,
                jnp.asarray(ctx.membership, v.dtype),
                jnp.asarray(ctx.class_members, v.dtype),
                jnp.asarray(ctx.state_masks, v.dtype),
            )
            return np.asarray(scores, np.float64)
        scores = _score_jit(
            v,
            jnp.asarray(ctx.membership, v.dtype),
            jnp.asarray(ctx.y_tilde, v.dtype),
            jnp.asarray(ctx.counts, v.dtype),
            ctx.n_residuals,
        )
        return np.asarray(scores, np.float64)

    def prepare_l0(self, x, y, layout, method="gram", dtype=np.float64,
                   problem="regression"):
        prob = super().prepare_l0(x, y, layout, method=method, dtype=dtype,
                                  problem=problem)
        if problem == "classification":
            # device-resident domain boxes (the host stats were built by the
            # base class); the in-box test operand x stays in compute dtype
            cs = prob.cstats
            prob.cstats = ClassStats(
                task_mem=jnp.asarray(cs.task_mem, dtype),
                class_mem=jnp.asarray(cs.class_mem, dtype),
                cmin=jnp.asarray(cs.cmin, dtype),
                cmax=jnp.asarray(cs.cmax, dtype),
                x=jnp.asarray(cs.x, dtype),
            )
        elif method == "gram":
            prob.stats = compute_gram_stats(
                jnp.asarray(prob.x), jnp.asarray(prob.y), layout, dtype
            )
        return prob

    def _score_fn(self, prob: L0Problem):
        with self._l0_cache_lock:
            fn = prob.cache.get("jnp_l0")
            if fn is None:
                if prob.problem == "classification":
                    fn = jax.jit(
                        lambda tt: score_tuples_overlap(prob.cstats, tt)
                    )
                elif prob.method == "gram":
                    fn = jax.jit(lambda tt: score_tuples_gram(prob.stats, tt))
                else:
                    xs = jnp.asarray(prob.x, prob.dtype)
                    ys = jnp.asarray(prob.y, prob.dtype)
                    fn = jax.jit(
                        lambda tt: score_tuples_qr(
                            xs, ys, prob.layout, tt, prob.dtype
                        )
                    )
                prob.cache["jnp_l0"] = fn
        return fn

    def l0_scores(self, prob: L0Problem, tuples: np.ndarray) -> np.ndarray:
        return np.asarray(self._score_fn(prob)(jnp.asarray(tuples)))
