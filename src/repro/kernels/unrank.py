"""Device-side combinatorial unranking — the ℓ0 tuple enumerator.

The exhaustive ℓ0 sweep walks all C(m, n) index tuples in lexicographic
order (the order ``itertools.combinations(range(m), n)`` yields, which is
what the work journal's "block index ⇒ tuples" contract is defined over).
For n ≥ 3 the seed implementation enumerated tuples with a *host-side
Python generator* — single-core work that serializes against device
scoring.  Here a block of tuples is identified by its rank range alone and
materializes directly on device:

    ranks r, r+1, …, r+B-1  ──unrank──►  (B, n) int32 index tuples

so enumeration is a jitted, vectorized XLA computation (a few int64 ops ×
log₂(m) binary-search steps per element) that overlaps with scoring via
the block prefetcher (engine/streaming.py).

Math: lexicographic rank over ascending tuples is the *colexicographic*
rank of the reversed complement.  With ``b_i = m-1-a_{n+1-i}`` (so ``b`` is
an ascending combination iff ``a`` is),

    lex_rank(a) = C(m, n) - 1 - Σ_i C(b_i, i)

Colex unranking is greedy: for i = n…1, ``b_i`` is the largest c with
C(c, i) ≤ r' — found here by a vectorized binary search over a table of
exact binomials C(c, i), c < m, built on the host in Python integers.  The
table keeps the device side to int64 compares, subtractions and gathers:
the TPU emulates int64, and an in-graph binomial by stepwise int64
division took minutes to compile for a v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.l0 import n_models


def comb_exact(n: int, k: int) -> int:
    """Host-exact C(n, k) (Python ints — rank arithmetic never rounds).

    One implementation with the block accounting: this *is*
    ``core.l0.n_models`` (guarded for n < k), so rank arithmetic and
    sweep bookkeeping can never diverge."""
    return n_models(n, k) if 0 <= k <= n else 0


def device_unrank_ok(m: int, n: int) -> bool:
    """True when device unranking is exact for this (m, n) space.

    Every intermediate must fit the widest integer the device computes in:
    int64 under jax x64, int32 otherwise.  The rank arithmetic is bounded
    by C(m, n) and the binomial table by max_i C(m-1, i), which for
    n > m/2 exceeds C(m, n), so both are checked.  Rejected spaces use the
    host-exact fallback in ``core/l0.py`` (slower, never wrong).
    """
    bound = 2**62 if jax.config.jax_enable_x64 else 2**30
    if comb_exact(m, n) >= bound:
        return False
    return max(comb_exact(m - 1, i) for i in range(n + 1)) < bound


def unrank_lex_host(rank: int, m: int, n: int) -> list:
    """Host-exact single-tuple unranking (Python ints, any space size)."""
    r = comb_exact(m, n) - 1 - rank
    out = []
    for i in range(n, 0, -1):
        lo, hi = i - 1, m - 1
        while lo < hi:  # largest c with C(c, i) <= r
            mid = (lo + hi + 1) // 2
            if comb_exact(mid, i) <= r:
                lo = mid
            else:
                hi = mid - 1
        r -= comb_exact(lo, i)
        out.append(m - 1 - lo)
    return out


@functools.partial(jax.jit, static_argnames=("m", "n"))
def unrank_lex(ranks: jnp.ndarray, m: int, n: int) -> jnp.ndarray:
    """Lexicographic combinations of ``range(m)`` at ``ranks`` → (B, n) int32.

    Matches ``itertools.combinations(range(m), n)`` element-for-element
    (tests/test_l0.py asserts the full bijection).  ``ranks`` may be any
    integer dtype; arithmetic runs in int64 (requires jax x64, which the
    fp64 precision policy already enables).
    """
    total = comb_exact(m, n)
    r = (total - 1) - ranks.astype(jnp.int64)  # colex rank of the dual
    # binom[i, c] = C(c, i), exact host integers
    binom = jnp.asarray(
        [[comb_exact(c, i) for c in range(m)] for i in range(n + 1)],
        dtype=r.dtype,
    )
    cols = []
    n_steps = max(1, int(np.ceil(np.log2(max(m, 2)))) + 1)
    for i in range(n, 0, -1):
        # largest c in [i-1, m-1] with C(c, i) <= r  (binary search)
        lo = jnp.full_like(r, i - 1)
        hi = jnp.full_like(r, m - 1)
        for _ in range(n_steps):
            mid = (lo + hi + 1) >> 1
            take = binom[i][mid] <= r
            lo = jnp.where(take, mid, lo)
            hi = jnp.where(take, hi, mid - 1)
        r = r - binom[i][lo]
        cols.append((m - 1 - lo).astype(jnp.int32))
    return jnp.stack(cols, axis=1)


def unrank_block(start: int, count: int, m: int, n: int) -> jnp.ndarray:
    """Device (count, n) int32 tuple block covering ranks [start, start+count).

    ``start``/``count`` are host Python ints (exact); the result is a device
    array — callers that stream blocks into a scoring kernel never pay a
    host↔device round-trip for enumeration.
    """
    ranks = jnp.arange(start, start + count, dtype=jnp.int64)
    return unrank_lex(ranks, m, n)
