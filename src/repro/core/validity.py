"""Canonical value-rule semantics for candidate features (paper P2, GPU side).

Exactly one definition of "valid candidate", shared by every execution
backend (engine/) and by the Pallas kernels, so a candidate can never pass
screening on one backend and fail on another:

* all entries over real samples are finite,
* ``l_bound <= max |v| <= u_bound`` (non-finite entries zeroed for the max),
* the variance over *all* samples exceeds ``MIN_STD**2``.

Historically the host oracle used the whole-sample standard deviation while
the fused Pallas kernel used the max *per-task* centered sum of squares; a
candidate constant within each task but varying across tasks (or with
variance between the two thresholds) passed one path and failed the other,
changing SIS selections between backends.  The moment-form rule below is the
single reconciled semantics; :func:`value_rules_from_moments` expresses it in
terms of the per-task reductions the kernels already compute, so the fused
path applies bit-for-bit the same formula without a second pass over values.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: minimum whole-sample standard deviation for a candidate to be screenable.
MIN_STD = 1e-10

#: relative quantization tolerance of the value-duplicate projection keys.
DEDUP_TOL = 1e-5


def value_rules_host(
    values: np.ndarray,  # (B, S)
    l_bound: float,
    u_bound: float,
) -> np.ndarray:
    """Validity mask (B,) — host-numpy form of the canonical rule."""
    v = np.asarray(values, np.float64)
    finite_entries = np.isfinite(v)
    finite = finite_entries.all(axis=1)
    vm = np.where(finite_entries, v, 0.0)
    max_abs = np.abs(vm).max(axis=1)
    n = v.shape[1]
    sums = vm.sum(axis=1)
    sumsq = (vm * vm).sum(axis=1)
    var = np.maximum(sumsq - sums * sums / n, 0.0) / n
    return (
        finite
        & (max_abs <= u_bound)
        & (max_abs >= l_bound)
        & (var > MIN_STD * MIN_STD)
    )


def value_rules_jnp(
    values: jnp.ndarray,  # (B, S)
    l_bound: float,
    u_bound: float,
) -> jnp.ndarray:
    """Validity mask (B,) — same rule, traceable (jnp) form."""
    finite_entries = jnp.isfinite(values)
    finite = finite_entries.all(axis=1)
    vm = jnp.where(finite_entries, values, 0.0)
    max_abs = jnp.abs(vm).max(axis=1)
    n = values.shape[1]
    sums = vm.sum(axis=1)
    sumsq = (vm * vm).sum(axis=1)
    var = jnp.maximum(sumsq - sums * sums / n, 0.0) / n
    return (
        finite
        & (max_abs <= u_bound)
        & (max_abs >= l_bound)
        & (var > MIN_STD * MIN_STD)
    )


def value_rules_from_moments(
    finite: jnp.ndarray,   # (B,) all real-sample entries finite
    max_abs: jnp.ndarray,  # (B,) max |v| over real samples (non-finite -> 0)
    sums: jnp.ndarray,     # (B, T) per-task sums over real samples
    sumsq: jnp.ndarray,    # (B, T) per-task sums of squares
    counts: jnp.ndarray,   # (T,) or (1, T) true samples per task
    l_bound: float,
    u_bound: float,
) -> jnp.ndarray:
    """Canonical rule from per-task reductions (fused-kernel epilogue form).

    The whole-sample variance is recovered from the per-task first/second
    moments (:func:`variance_ok_from_moments`).
    """
    return (
        finite
        & (max_abs <= u_bound)
        & (max_abs >= l_bound)
        & variance_ok_from_moments(sums, sumsq, counts)
    )


def variance_ok_from_moments(
    sums: jnp.ndarray,    # per-task sums over real samples, tasks on ``axis``
    sumsq: jnp.ndarray,   # per-task sums of squares, same layout
    counts: jnp.ndarray,  # true samples per task (any shape)
    axis: int = -1,
    keepdims: bool = False,
) -> jnp.ndarray:
    """The rule's variance clause: ``var = (sum_t sumsq_t - (sum_t
    sums_t)^2 / N) / N > MIN_STD^2``, reducing the task ``axis``."""
    n = counts.sum()
    total = sums.sum(axis=axis, keepdims=keepdims)
    ss = jnp.maximum(
        sumsq.sum(axis=axis, keepdims=keepdims) - total * total / n, 0.0
    )
    return ss / n > MIN_STD * MIN_STD
