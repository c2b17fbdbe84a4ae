"""Graceful degradation: retry transient faults, demote persistent ones.

:class:`ResilientExecution` is a delegating :class:`Backend` proxy (the
``debug/sanitizer.py`` ``DebugBackend`` composition pattern) that makes a
long fit survive the failure modes real fleets exhibit:

* **transient device errors** (preempted RPC, OOM-retryable allocator
  states, the injected :class:`~repro.runtime.faults.TransientDeviceError`)
  are retried with capped exponential backoff plus seeded jitter — the
  jitter is deterministic per wrapper instance, so tests replay exactly;
* **persistent kernel failures** (Mosaic lowering errors, ``XlaRuntimeError``,
  :class:`~repro.runtime.faults.KernelFailure`, ``NotImplementedError``)
  demote the failing *operation* down the backend chain
  ``pallas → jnp → reference`` with a logged warning.  Demotion is
  per-op: a broken ℓ0 gather kernel falls back to the jnp Gram path
  while fused SIS keeps running on the kernels that still work.

Programming errors (``ValueError``/``TypeError``/contract violations)
are neither retried nor demoted — they re-raise immediately; masking
them behind a slower backend would hide real bugs.

Demoted ℓ0 calls need a fallback-prepared :class:`L0Problem` (per-backend
jit caches and dtype policy don't transfer), so the proxy re-prepares
from the original operands once per (problem, fallback backend) and
caches it.

Wire-up: ``get_engine("resilient:pallas")`` or ``SissoConfig(
resilient=True)``; the solver surfaces :attr:`fault_stats` (retry and
per-op demotion counters) in ``SissoFit.stats``.
"""
from __future__ import annotations

import logging
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Union

import numpy as np

from ..runtime.faults import KernelFailure, TransientDeviceError
from .base import Backend, Engine, L0Problem

log = logging.getLogger(__name__)

#: substrings of transient XLA error payloads worth retrying
_TRANSIENT_TAGS = ("RESOURCE_EXHAUSTED", "UNAVAILABLE", "ABORTED",
                   "DEADLINE_EXCEEDED")
#: exception type names (matched without importing their homes) that mean
#: the backend's compiled path is broken for this op
_DEMOTABLE_TYPE_NAMES = ("XlaRuntimeError", "MosaicError")
_DEMOTABLE_MESSAGE_TAGS = ("Mosaic", "lowering", "INTERNAL")


def _fallback_names(inner_name: str) -> List[str]:
    """Degradation chain below ``inner_name``: jnp first (still compiled,
    still fast), the reference oracle last (host numpy always works)."""
    return [n for n in ("jnp", "reference") if n != inner_name]


class ResilientExecution(Backend):
    """Retry/degrade proxy over any inner backend."""

    def __init__(
        self,
        inner: Union[Backend, str, None] = None,
        max_attempts: int = 3,
        base_delay: float = 0.05,
        max_delay: float = 2.0,
        jitter: float = 0.5,
        seed: int = 0,
        **opts,
    ):
        from . import get_engine

        if inner is None or isinstance(inner, str):
            inner = get_engine(inner, **opts).backend
        if isinstance(inner, ResilientExecution):
            raise ValueError("nesting resilient: wrappers is redundant")
        self._inner = inner
        self.max_attempts = int(max_attempts)
        self.base_delay = float(base_delay)
        self.max_delay = float(max_delay)
        self.jitter = float(jitter)
        self._rng = random.Random(seed)
        self._lock = threading.RLock()
        # chain[0] is the inner backend; fallbacks instantiate lazily
        self._chain: List[Optional[Backend]] = [inner]
        self._chain_names = [inner.name] + _fallback_names(inner.name)
        self._level: Dict[str, int] = {}        # op -> active chain index
        self._retries = 0
        self._demotions: Dict[str, int] = {}    # op -> demotion count
        # (id(prob), backend name) -> re-prepared L0Problem; keeps the
        # source prob alive so id() can't be recycled
        self._prob_cache: Dict[tuple, tuple] = {}

    # -- transparency (DebugBackend pattern) ---------------------------
    @property
    def name(self) -> str:  # type: ignore[override]
        return f"resilient[{self._inner.name}]"

    @property
    def fused_deferred(self):  # type: ignore[override]
        return self._inner.fused_deferred

    @property
    def l0_widths(self):  # type: ignore[override]
        return self._inner.l0_widths

    @property
    def reduces_blocks(self):  # type: ignore[override]
        return self._inner.reduces_blocks

    @property
    def bit_exact_oracle(self):  # type: ignore[override]
        return self._inner.bit_exact_oracle

    @property
    def kernel_problems(self):  # type: ignore[override]
        return self._inner.kernel_problems

    @property
    def compute_dtype(self):  # type: ignore[override]
        return self._inner.compute_dtype

    @compute_dtype.setter
    def compute_dtype(self, value):
        self._inner.compute_dtype = value
        for backend in self._chain[1:]:
            if backend is not None and backend.name != "reference":
                backend.compute_dtype = value

    @property
    def score_ctx_dtype(self):  # type: ignore[override]
        return self._inner.score_ctx_dtype

    def set_precision(self, precision: str) -> "ResilientExecution":
        self._inner.set_precision(precision)
        for backend in self._chain[1:]:
            if backend is not None and backend.name != "reference":
                backend.set_precision(precision)
        return self

    def __getattr__(self, attr):
        # backend-specific surface (autotune hooks, interpret flags) —
        # only reached when normal lookup fails
        return getattr(self._inner, attr)

    def __repr__(self) -> str:
        return (
            f"ResilientExecution({self._inner!r}, "
            f"max_attempts={self.max_attempts})"
        )

    # -- stats surfaced in SissoFit ------------------------------------
    @property
    def fault_stats(self) -> dict:
        """Retry/demotion counters (solver copies this into fit stats)."""
        with self._lock:
            return {
                "retries": self._retries,
                "demotions": dict(self._demotions),
                "active_backend": {
                    op: self._chain_names[lvl]
                    for op, lvl in self._level.items() if lvl > 0
                },
            }

    # -- failure classification ----------------------------------------
    def _is_transient(self, exc: BaseException) -> bool:
        if isinstance(exc, TransientDeviceError):
            return True
        if type(exc).__name__ == "XlaRuntimeError":
            return any(tag in str(exc) for tag in _TRANSIENT_TAGS)
        return False

    def _is_demotable(self, exc: BaseException) -> bool:
        if isinstance(exc, (KernelFailure, TransientDeviceError,
                            NotImplementedError)):
            return True
        if type(exc).__name__ in _DEMOTABLE_TYPE_NAMES:
            return True
        return any(tag in str(exc) for tag in _DEMOTABLE_MESSAGE_TAGS)

    def _backoff(self, attempt: int) -> float:
        base = min(self.max_delay, self.base_delay * (2 ** (attempt - 1)))
        with self._lock:
            scale = 1.0 + self.jitter * self._rng.random()
        return base * scale

    # -- chain management ----------------------------------------------
    def _backend_at(self, level: int) -> Optional[Backend]:
        if level >= len(self._chain_names):
            return None
        with self._lock:
            while len(self._chain) <= level:
                self._chain.append(None)
            if self._chain[level] is None:
                from . import BACKENDS

                backend = BACKENDS[self._chain_names[level]]()
                if backend.name != "reference":
                    backend.compute_dtype = self._inner.compute_dtype
                self._chain[level] = backend
            return self._chain[level]

    def _prob_for(self, prob: L0Problem, backend: Backend) -> L0Problem:
        """A prob prepared *by the chain backend* from the same operands.

        Chain level 0 uses the caller's prob untouched; fallbacks get
        their own preparation (jit caches, Gram dtype policy are
        per-backend) cached per (source prob, fallback backend)."""
        if backend is self._inner:
            return prob
        key = (id(prob), backend.name)
        with self._lock:
            hit = self._prob_cache.get(key)
            if hit is not None:
                return hit[1]
        fb_prob = backend.prepare_l0(
            prob.x, prob.y, prob.layout, method=prob.method,
            dtype=prob.dtype, problem=prob.problem,
        )
        with self._lock:
            self._prob_cache[key] = (prob, fb_prob)
        return fb_prob

    def _dispatch(self, op: str, call: Callable[[Backend], Any]):
        """Run ``call`` at the op's current chain level with retry on
        transient errors; demote persistent failures down the chain."""
        level = self._level.get(op, 0)
        while True:
            backend = self._backend_at(level)
            attempt = 1
            while True:
                try:
                    return call(backend)
                except Exception as exc:
                    if (
                        self._is_transient(exc)
                        and attempt < self.max_attempts
                    ):
                        delay = self._backoff(attempt)
                        attempt += 1
                        with self._lock:
                            self._retries += 1
                        log.warning(
                            "%s on %s: transient %s — retry %d/%d in "
                            "%.3fs", op, backend.name,
                            type(exc).__name__, attempt,
                            self.max_attempts, delay,
                        )
                        time.sleep(delay)
                        continue
                    nxt = (
                        self._backend_at(level + 1)
                        if self._is_demotable(exc) else None
                    )
                    if nxt is None:
                        raise
                    with self._lock:
                        level += 1
                        self._level[op] = level
                        self._demotions[op] = self._demotions.get(op, 0) + 1
                    log.warning(
                        "%s: persistent failure on %s (%s: %s) — "
                        "demoting to %s", op, backend.name,
                        type(exc).__name__, exc, nxt.name,
                    )
                    break  # re-run the op one level down

    # -- phase 1 -------------------------------------------------------
    def eval_block(self, op_id, a, b, l_bound, u_bound):
        return self._dispatch(
            "eval_block",
            lambda be: be.eval_block(op_id, a, b, l_bound, u_bound),
        )

    # -- phase 2 -------------------------------------------------------
    def sis_scores(self, values, ctx):
        return self._dispatch(
            "sis_scores", lambda be: be.sis_scores(values, ctx)
        )

    def sis_scores_deferred(self, op_id, a, b, ctx, l_bound, u_bound):
        return self._dispatch(
            "sis_scores_deferred",
            lambda be: be.sis_scores_deferred(
                op_id, a, b, ctx, l_bound, u_bound
            ),
        )

    def sis_topk(self, values, ctx, n_keep, mask=None):
        return self._dispatch(
            "sis_topk",
            lambda be: be.sis_topk(values, ctx, n_keep, mask=mask),
        )

    def sis_topk_deferred(self, op_id, a, b, ctx, l_bound, u_bound, n_keep):
        return self._dispatch(
            "sis_topk_deferred",
            lambda be: be.sis_topk_deferred(
                op_id, a, b, ctx, l_bound, u_bound, n_keep
            ),
        )

    # -- phase 3 -------------------------------------------------------
    def prepare_l0(self, x, y, layout, method="gram", dtype=np.float64,
                   problem="regression"):
        # host-side bookkeeping, no kernels: failure here is a bug, not
        # a fault — delegate without retry/demotion
        return self._inner.prepare_l0(
            x, y, layout, method=method, dtype=dtype, problem=problem
        )

    def l0_scores(self, prob, tuples):
        return self._dispatch(
            "l0_scores",
            lambda be: be.l0_scores(self._prob_for(prob, be), tuples),
        )

    def l0_topk(self, prob, tuples, n_keep):
        return self._dispatch(
            "l0_topk",
            lambda be: be.l0_topk(self._prob_for(prob, be), tuples, n_keep),
        )

    def l0_device_reducer(self, prob, width, k_local):
        # traceable closure for composed distribution: retry semantics
        # can't wrap a shard_map trace — pass through to the inner
        return self._inner.l0_device_reducer(prob, width, k_local)

    # -- prediction ----------------------------------------------------
    def eval_program(self, program, x):
        return self._dispatch(
            "eval_program", lambda be: be.eval_program(program, x)
        )


def wrap_engine_resilient(engine: Engine, **opts) -> Engine:
    """Wrap an engine's backend in :class:`ResilientExecution`
    (idempotent)."""
    if isinstance(engine.backend, ResilientExecution):
        return engine
    return Engine(ResilientExecution(engine.backend, **opts))
