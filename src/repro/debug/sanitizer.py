"""Runtime contract sanitizer: a delegating :class:`Backend` wrapper.

reprolint (tools/reprolint) enforces the *static* halves of the protocol
invariants; this module enforces the dynamic halves at every Backend
call boundary:

* shape/dtype conformance of each method's returns (``eval_block``'s
  ``(values (B, S), valid (B,) bool)``, score vectors of length B, ...);
  a full score vector is checked wherever one is formed: when the wrapped
  backend reduces on the host through ``ScoringBackend``'s default, the
  sanitizer forms that vector itself and reduces it the same way;
* :class:`ReducedBlock` well-formedness — at most ``n_keep`` winners,
  unique in-range indices, **finite** scores (no ±inf sentinel lane may
  cross the host boundary — the dynamic half of RL007), best-first
  ordering, ``n_source`` equal to the submitted block length;
* NaN/Inf in non-masked entries: NaN never, +inf never in
  largest-is-better SIS scores, -inf never in ascending ℓ0 objectives.
  Device-resident outputs are checked *inside jit* via
  ``jax.experimental.checkify`` so the check itself stays on the jit
  path; host arrays use plain numpy.
* at verify level, a cross-check of every device-reduced top-k against
  a host reduction of a full vector — from the wrapped backend when it is
  a ``ScoringBackend``, else from the reference oracle on the same
  operands — which is exactly the ``k_epi >= min(n_keep, block)``
  coverage invariant plus stable-tie winner parity.

Enablement (``maybe_wrap_engine``): ``SissoConfig.debug_checks`` wins
when set; otherwise the ``REPRO_DEBUG`` environment variable — ``1`` for
structural checks, ``2``/``verify`` to add the full-vector cross-check.
A failed contract raises :class:`ContractViolation` at the offending
call, not thousands of selection steps later.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Optional

import numpy as np

from ..core.sis import ReducedBlock
from ..engine.base import Backend, Engine, ScoringBackend

LEVEL_OFF = 0
LEVEL_STRUCTURAL = 1
LEVEL_VERIFY = 2

_ENV_VAR = "REPRO_DEBUG"


class ContractViolation(AssertionError):
    """A Backend protocol contract failed at a call boundary."""


def env_level() -> int:
    """Sanitizer level requested by the REPRO_DEBUG environment variable."""
    raw = os.environ.get(_ENV_VAR, "").strip().lower()
    if raw in ("", "0", "false", "off"):
        return LEVEL_OFF
    if raw in ("2", "verify", "full"):
        return LEVEL_VERIFY
    return LEVEL_STRUCTURAL


def _is_jax_array(x: Any) -> bool:
    import jax

    return isinstance(x, jax.Array)


@functools.lru_cache(maxsize=None)
def _checkify_nan_probe():
    """jit-compiled checkify probe: errors iff the operand contains NaN.

    Built once (shape-polymorphic via jit retrace); keeping the check
    *inside* jit is the point — the sanitizer must not force an early
    device sync that would mask async-dispatch bugs.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import checkify

    def probe(x):
        checkify.check(
            jnp.logical_not(jnp.any(jnp.isnan(x))), "NaN in checked operand"
        )
        return x

    return jax.jit(checkify.checkify(probe, errors=checkify.user_checks))


def _assert_scores(name: str, arr: Any, n_expected: int, *,
                   allow_pos_inf: bool, allow_neg_inf: bool) -> None:
    """Shape + NaN/Inf policy for a (B,)-score return."""
    if _is_jax_array(arr):
        err, _ = _checkify_nan_probe()(arr)
        try:
            err.throw()
        except Exception as exc:  # checkify.JaxRuntimeError
            raise ContractViolation(f"{name}: {exc}") from exc
    host = np.asarray(arr)
    if host.shape != (n_expected,):
        raise ContractViolation(
            f"{name}: expected shape ({n_expected},), got {host.shape}"
        )
    if np.isnan(host).any():
        raise ContractViolation(f"{name}: NaN in scores")
    if not allow_pos_inf and np.any(host == np.inf):
        raise ContractViolation(
            f"{name}: +inf score (sentinel leaked into a "
            "largest-is-better score vector)"
        )
    if not allow_neg_inf and np.any(host == -np.inf):
        raise ContractViolation(
            f"{name}: -inf score (sentinel leaked into an "
            "ascending-is-better objective vector)"
        )


def _assert_reduced_block(name: str, rb: Any, n_keep: int, n_source: int,
                          *, largest: bool) -> None:
    if not isinstance(rb, ReducedBlock):
        raise ContractViolation(
            f"{name}: expected a ReducedBlock, got {type(rb).__name__}"
        )
    idx = np.asarray(rb.indices)
    sc = np.asarray(rb.scores)
    if idx.ndim != 1 or sc.shape != idx.shape:
        raise ContractViolation(
            f"{name}: indices/scores must be matching 1-d arrays, got "
            f"{idx.shape} / {sc.shape}"
        )
    if len(idx) > n_keep:
        raise ContractViolation(
            f"{name}: {len(idx)} winners exceed n_keep={n_keep}"
        )
    if int(rb.n_source) != int(n_source):
        raise ContractViolation(
            f"{name}: n_source={rb.n_source} but the submitted block has "
            f"{n_source} rows"
        )
    if not np.issubdtype(idx.dtype, np.integer):
        raise ContractViolation(f"{name}: indices dtype {idx.dtype} not integer")
    if len(idx):
        if idx.min() < 0 or idx.max() >= n_source:
            raise ContractViolation(
                f"{name}: winner index outside [0, {n_source}) — padding "
                "sentinel (-1) or out-of-block index crossed the boundary"
            )
        if len(np.unique(idx)) != len(idx):
            raise ContractViolation(f"{name}: duplicate winner indices")
        if not np.isfinite(sc).all():
            raise ContractViolation(
                f"{name}: non-finite winner score — ±inf sentinel lanes "
                "must be filtered before the block crosses the host "
                "boundary (RL007's dynamic half)"
            )
        ordered = np.all(np.diff(sc) <= 0) if largest else np.all(np.diff(sc) >= 0)
        if not ordered:
            raise ContractViolation(
                f"{name}: winner scores not sorted "
                f"{'descending' if largest else 'ascending'} (best-first)"
            )


def _assert_topk_matches(name: str, actual: ReducedBlock,
                         full_scores: np.ndarray, n_keep: int, *,
                         largest: bool,
                         mask: Optional[np.ndarray] = None) -> None:
    """Verify-level cross-check against the full-vector host reduction.

    Equal winner *count* is the coverage invariant (a fused epilogue with
    ``k_epi < min(n_keep, n_valid)`` under-fills the panel); equal scores
    within fp32-rescore tolerance is winner parity modulo exact ties.
    """
    expected = ReducedBlock.reduce_host(
        np.asarray(full_scores, np.float64), n_keep, mask=mask,
        largest=largest,
    )
    if len(actual.indices) != len(expected.indices):
        raise ContractViolation(
            f"{name}: coverage violation — reduced block carries "
            f"{len(actual.indices)} winners, full-vector reduction finds "
            f"{len(expected.indices)} (k_epi >= min(n_keep, n_valid) "
            "broken?)"
        )
    if len(expected.indices) and not np.allclose(
        np.asarray(actual.scores, np.float64), expected.scores,
        rtol=1e-3, atol=1e-6,
    ):
        raise ContractViolation(
            f"{name}: reduced winner scores diverge from the full-vector "
            f"reduction: {np.asarray(actual.scores)[:4]} vs "
            f"{expected.scores[:4]} ..."
        )
    # self-consistency: each winner's reported score must be the full
    # vector's score at its reported index (right scores attached to the
    # wrong candidates is the nastiest variant of this bug class)
    full = np.asarray(full_scores, np.float64)
    idx = np.asarray(actual.indices)
    if len(idx) and not np.allclose(
        full[idx], np.asarray(actual.scores, np.float64),
        rtol=1e-3, atol=1e-6,
    ):
        raise ContractViolation(
            f"{name}: winner (index, score) pairs diverge from full-vector "
            "rescoring — scores are attached to the wrong candidates"
        )


class DebugBackend(Backend):
    """Sanitizing proxy: delegates to ``inner``, checking every contract.

    Transparent by construction — capability flags and backend-specific
    attributes (autotune hooks, kernel config) read through to the
    wrapped backend, so a fit takes the same paths with or without the
    sanitizer.
    """

    def __init__(self, inner: Backend, level: int = LEVEL_STRUCTURAL):
        self._inner = inner
        self._level = int(level)

    # -- transparency --------------------------------------------------
    @property
    def name(self) -> str:  # type: ignore[override]
        return f"debug[{self._inner.name}]"

    @property
    def fused_deferred(self):  # type: ignore[override]
        return self._inner.fused_deferred

    @property
    def l0_widths(self):  # type: ignore[override]
        return self._inner.l0_widths

    @property
    def kernel_problems(self):  # type: ignore[override]
        return self._inner.kernel_problems

    @property
    def compute_dtype(self):  # type: ignore[override]
        return self._inner.compute_dtype

    @compute_dtype.setter
    def compute_dtype(self, value):
        self._inner.compute_dtype = value

    @property
    def score_ctx_dtype(self):  # type: ignore[override]
        return self._inner.score_ctx_dtype

    def set_precision(self, precision: str) -> "DebugBackend":
        self._inner.set_precision(precision)
        return self

    def __getattr__(self, attr):
        # backend-specific surface (autotune hooks, interpret flags, jit
        # caches) — only reached when normal lookup fails
        return getattr(self._inner, attr)

    def __repr__(self) -> str:
        return f"DebugBackend({self._inner!r}, level={self._level})"

    # -- phase 1 -------------------------------------------------------
    def eval_block(self, op_id, a, b, l_bound, u_bound):
        n_b, n_s = np.shape(a)
        values, valid = self._inner.eval_block(op_id, a, b, l_bound, u_bound)
        v = np.asarray(values)
        ok = np.asarray(valid)
        if v.shape != (n_b, n_s):
            raise ContractViolation(
                f"eval_block: values shape {v.shape} != ({n_b}, {n_s})"
            )
        if ok.shape != (n_b,) or ok.dtype != np.bool_:
            raise ContractViolation(
                f"eval_block: valid must be ({n_b},) bool, got "
                f"{ok.shape} {ok.dtype}"
            )
        if ok.any() and not np.isfinite(v[ok]).all():
            raise ContractViolation(
                "eval_block: non-finite values in rows flagged valid — the "
                "value rules must reject or the flag must be False"
            )
        return values, valid

    # -- reduced entries -----------------------------------------------
    def _reduce(self, entry: str, n_source: int, n_keep: int, *,
                largest: bool, topk, full, mask=None) -> ReducedBlock:
        """One reduced entry with its checks.

        ``topk()`` runs the inner backend's ``entry``; ``full(be)`` forms
        the block's full vector on the :class:`ScoringBackend` ``be``.
        When the inner backend reduces on the host through
        ``ScoringBackend``'s default (its class does not override
        ``entry``), the vector is formed here, its NaN/Inf policy checked,
        and reduced the same way — bit-identical to the inner's answer.
        Otherwise the inner's winners are checked structurally and, at
        verify level, against a host reduction of a full vector from the
        inner backend if it scores whole blocks, else from the reference
        oracle on the same operands (a distribution wrapper, say).
        """
        inner = self._inner
        scoring = isinstance(inner, ScoringBackend)
        if scoring and getattr(type(inner), entry) is getattr(ScoringBackend,
                                                              entry):
            scores = full(inner)
            _assert_scores(entry.replace("_topk", "_scores"), scores,
                           n_source, allow_pos_inf=not largest,
                           allow_neg_inf=largest)
            return ReducedBlock.reduce_host(scores, n_keep, mask=mask,
                                            largest=largest)
        rb = topk()
        _assert_reduced_block(entry, rb, n_keep, n_source, largest=largest)
        if self._level >= LEVEL_VERIFY:
            from ..engine.reference import ReferenceBackend

            _assert_topk_matches(
                entry, rb, full(inner if scoring else ReferenceBackend()),
                n_keep, largest=largest, mask=mask)
        return rb

    # -- phase 2 -------------------------------------------------------
    def sis_topk(self, values, ctx, n_keep, mask=None):
        return self._reduce(
            "sis_topk", np.shape(values)[0], n_keep, largest=True, mask=mask,
            topk=lambda: self._inner.sis_topk(values, ctx, n_keep,
                                              mask=mask),
            full=lambda be: be.sis_scores(values, ctx),
        )

    def sis_topk_deferred(self, op_id, a, b, ctx, l_bound, u_bound, n_keep):
        return self._reduce(
            "sis_topk_deferred", np.shape(a)[0], n_keep, largest=True,
            topk=lambda: self._inner.sis_topk_deferred(
                op_id, a, b, ctx, l_bound, u_bound, n_keep),
            full=lambda be: be.sis_scores_deferred(
                op_id, a, b, ctx, l_bound, u_bound),
        )

    # -- phase 3 -------------------------------------------------------
    def prepare_l0(self, x, y, layout, method="gram", dtype=np.float64,
                   problem="regression"):
        prob = self._inner.prepare_l0(
            x, y, layout, method=method, dtype=dtype, problem=problem
        )
        if np.asarray(prob.x).ndim != 2:
            raise ContractViolation(
                f"prepare_l0: x must be (m, S), got {np.shape(prob.x)}"
            )
        if prob.problem != problem:
            raise ContractViolation(
                f"prepare_l0: problem tag {prob.problem!r} != requested "
                f"{problem!r}"
            )
        return prob

    def l0_topk(self, prob, tuples, n_keep):
        def full(be):
            p = prob if be is self._inner else be.prepare_l0(
                prob.x, prob.y, prob.layout, method=prob.method,
                dtype=prob.dtype, problem=prob.problem)
            return be.l0_scores(p, tuples)

        return self._reduce(
            "l0_topk", np.shape(tuples)[0], n_keep, largest=False,
            topk=lambda: self._inner.l0_topk(prob, tuples, n_keep),
            full=full,
        )

    def l0_device_reducer(self, prob, width, k_local):
        # traceable closure: wrapping its returns would break shard_map
        # tracing, so it passes through unchecked (the merged panels are
        # re-checked at the l0_topk/ReducedBlock boundary above)
        return self._inner.l0_device_reducer(prob, width, k_local)

    # -- prediction ----------------------------------------------------
    def eval_program(self, program, x):
        out = self._inner.eval_program(program, x)
        host = np.asarray(out)
        if host.ndim != 2 or host.shape[1] != np.shape(x)[1]:
            raise ContractViolation(
                f"eval_program: expected (n_outputs, {np.shape(x)[1]}), "
                f"got {host.shape}"
            )
        if np.isnan(host).any():
            raise ContractViolation("eval_program: NaN in descriptor values")
        return out


def wrap_backend(backend: Backend, level: Optional[int] = None) -> Backend:
    """Wrap ``backend`` in a :class:`DebugBackend` (idempotent)."""
    if isinstance(backend, DebugBackend):
        return backend
    return DebugBackend(backend, level=env_level() if level is None else level)


def maybe_wrap_engine(engine: Engine,
                      debug_checks: Optional[bool] = None) -> Engine:
    """Sanitize ``engine`` when requested.

    ``debug_checks`` (from :class:`SissoConfig`) wins when not None;
    otherwise the REPRO_DEBUG environment variable decides.  Returns the
    engine unchanged when checks are off.
    """
    if debug_checks is None:
        level = env_level()
    elif debug_checks:
        level = max(env_level(), LEVEL_STRUCTURAL)
    else:
        return engine
    if level == LEVEL_OFF:
        return engine
    if isinstance(engine.backend, DebugBackend):
        return engine
    return Engine(wrap_backend(engine.backend, level))
