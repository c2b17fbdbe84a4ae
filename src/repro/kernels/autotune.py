"""Launch-configuration auto-tuning (paper P6 / §II.D launch parameters).

The paper times a predefined set of Kokkos team sizes on the first batch and
reuses the winner (warp 32 vs 64 across vendors).  The TPU analogue tunes
Pallas *block shapes* — candidate feature-block sizes for the fused SIS
kernel, panel rows per grid step for the ℓ0 kernel — and, for the fused
SIS reduced epilogue, the per-block top-k width.  Cost is one extra evaluation of the
first batch per candidate — "a few seconds ... negligible compared to the
total runtime" (§II.D).

:func:`pick_config` measures each candidate on the *actual first batch* —
the caller passes a ``run(candidate)`` closure over real operands — and
caches the winner per ``(kernel, device_kind, padded shape, dtype)`` key.
Timing protocol, in order of the bugs it avoids:

* one untimed warmup call per candidate (compilation is not launch cost);
* the timed region holds the result and calls ``jax.block_until_ready`` on
  it — JAX dispatch is async, so without the barrier every candidate would
  time as dispatch overhead (``jax.effects_barrier()`` does **not** block
  on the computation);
* candidates whose ``run`` raises (unsupported shape / VMEM overflow) are
  skipped; if every candidate fails, the first is returned unchanged so the
  caller's real invocation surfaces the underlying error.

Winners persist as a JSON sidecar next to the fit's work journal
(:func:`set_cache_path`, wired by ``SissoSolver.fit``) so repeated fits
skip retuning; writes are atomic (tmp + ``os.replace``) and the in-memory
cache is lock-guarded because streaming prefetch workers may tune
concurrently.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

_CACHE: Dict[tuple, object] = {}
_LOCK = threading.RLock()
_PATH: Optional[str] = None

#: candidate block shapes (candidate axis) for the fused SIS kernel
FUSED_SIS_BLOCKS: Tuple[int, ...] = (128, 256, 512, 1024)
#: candidate panel rows (runs of tuples) per grid step for the ℓ0
#: Gram-gather kernel
L0_TILE_BLOCKS: Tuple[int, ...] = (64, 128, 256)
#: candidate per-block epilogue widths for the fused SIS reduced top-k
EPILOGUE_KS: Tuple[int, ...] = (32, 64, 128)


def device_kind() -> str:
    try:
        return jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no initialized backend
        return "unknown"


def _freeze(v):
    return tuple(_freeze(x) for x in v) if isinstance(v, (list, tuple)) else v


def _jsonable(v):
    return [_jsonable(x) for x in v] if isinstance(v, tuple) else v


def set_cache_path(path: Optional[str]) -> None:
    """Point the tuner at a persistence file and load any recorded winners.

    Entries already in memory win over the file (they were measured in this
    process); ``None`` disables persistence.
    """
    global _PATH
    with _LOCK:
        _PATH = path
        if path is None or not os.path.exists(path):
            return
        try:
            with open(path) as f:
                entries = json.load(f)
            for k, v in entries:
                _CACHE.setdefault(_freeze(k), _freeze(v))
        except (OSError, ValueError):  # corrupt sidecar: retune, overwrite
            pass


def _save_locked() -> None:
    if _PATH is None:
        return
    tmp = _PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump([[_jsonable(k), _jsonable(v)] for k, v in _CACHE.items()], f)
        f.flush()
        os.fsync(f.fileno())  # the rename must never publish a torn sidecar
    os.replace(tmp, _PATH)


def pick_config(
    key: Tuple,
    candidates: Sequence,
    run: Callable,
    repeats: int = 2,
):
    """Time ``run(candidate)`` on the first batch; cache + persist winner.

    ``key`` should be ``(kernel_name, device_kind(), padded_shape, dtype)``
    so a tuned value never leaks across devices, shapes or compute dtypes.
    """
    key = _freeze(key)
    with _LOCK:
        if key in _CACHE:
            return _CACHE[key]
    best, best_t = None, float("inf")
    for cand in candidates:
        try:
            jax.block_until_ready(run(cand))  # warmup: compile, not launch
            t0 = time.perf_counter()
            for _ in range(repeats):
                jax.block_until_ready(run(cand))
            dt = (time.perf_counter() - t0) / repeats
        except Exception:  # shape not supported for this input -> skip
            continue
        if dt < best_t:
            best, best_t = cand, dt
    if best is None:
        # nothing ran: return the first candidate so the caller's real
        # invocation raises the underlying error with full context
        best = candidates[0]
    with _LOCK:
        _CACHE[key] = best
        try:
            _save_locked()
        except OSError:  # read-only FS: tuning still works, just untracked
            pass
    return best


def pick_block(
    key: Tuple,
    candidates: Sequence[int],
    run: Callable[[int], object],
    repeats: int = 2,
) -> int:
    """Back-compat shim: block-size-only search via :func:`pick_config`."""
    return pick_config(key, candidates, run, repeats=repeats)


def clear_cache() -> None:
    with _LOCK:
        _CACHE.clear()
