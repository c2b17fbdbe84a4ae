"""Reference backend: host numpy, literal formulas — the parity oracle.

Every other backend is validated against this one (tests/test_engine_parity).
The *operator math* still goes through ``core.operators.apply_op`` (the
single source of truth for what each op computes); everything downstream —
value rules, Pearson screening, least squares — is deliberately the naive
two-pass textbook form in float64, independent of the moment-form shortcuts
the device backends use.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..core.operators import apply_op
from ..core.sis import ScoreContext
from ..core.validity import value_rules_host
from .base import L0Problem, ScoringBackend


class ReferenceBackend(ScoringBackend):
    name = "reference"

    def eval_block(self, op_id, a, b, l_bound, u_bound):
        a = np.asarray(a, np.float64)
        b = np.asarray(b, np.float64)
        with np.errstate(all="ignore"):
            v = np.asarray(apply_op(op_id, jnp.asarray(a), jnp.asarray(b)))
        return v, value_rules_host(v, l_bound, u_bound)

    def sis_scores(self, values, ctx: ScoreContext) -> np.ndarray:
        """Literal screening score for the tagged problem.

        Regression: Eq. 1 — per-task two-pass Pearson r, mean over tasks,
        max over residuals.  Classification: negated 1D class-domain
        overlap count (+ tie term), max over state masks."""
        if ctx.problem == "classification":
            from ..core.problem import overlap_scores_host

            return overlap_scores_host(values, ctx)
        v = np.asarray(values, np.float64)[:, : ctx.s]
        yt = np.asarray(ctx.y_tilde, np.float64)  # (R*T, s_pad) unit-norm
        t = ctx.membership.shape[0]
        r_abs = np.zeros((len(v), ctx.n_residuals, t))
        for ti in range(t):
            mask = ctx.membership[ti, : ctx.s] > 0
            seg = v[:, mask]
            seg = seg - seg.mean(axis=1, keepdims=True)
            nrm = np.linalg.norm(seg, axis=1)
            with np.errstate(all="ignore"):
                segn = seg / nrm[:, None]
            for ri in range(ctx.n_residuals):
                y_seg = yt[ri * t + ti, : ctx.s][mask]
                corr = np.abs(segn @ y_seg)
                # zero-variance segments contribute r = 0 (matches the
                # eps-regularized rsqrt on the device backends)
                r_abs[:, ri, ti] = np.where(nrm > 0, corr, 0.0)
        scores = r_abs.mean(axis=2).max(axis=1)
        return np.where(np.isfinite(scores), scores, -np.inf)

    def l0_scores(self, prob: L0Problem, tuples: np.ndarray) -> np.ndarray:
        """Per-tuple oracle objective for the tagged problem.

        Regression: per-task ``np.linalg.lstsq`` with intercept — O(B·T)
        host solves, the paper-faithful oracle, not a fast path; use on
        reduced cases only.  Classification: literal numpy domain-overlap
        count over the tuple's subspace.
        """
        if prob.problem == "classification":
            from ..core.problem import score_tuples_overlap_host

            return score_tuples_overlap_host(prob.cstats, tuples)
        tuples = np.asarray(tuples)
        out = np.zeros(len(tuples))
        for k, tup in enumerate(tuples):
            total = 0.0
            for lo, hi in prob.layout.slices:
                a = np.concatenate(
                    [prob.x[list(tup), lo:hi].T, np.ones((hi - lo, 1))], axis=1
                )
                c, *_ = np.linalg.lstsq(a, prob.y[lo:hi], rcond=None)
                r = prob.y[lo:hi] - a @ c
                total += float(r @ r)
            out[k] = total if np.isfinite(total) else np.inf
        return out
