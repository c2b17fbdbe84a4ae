"""Device-side combinatorial unranking — the ℓ0 tuple enumerator.

The exhaustive ℓ0 sweep walks all C(m, n) index tuples in lexicographic
order (the order ``itertools.combinations(range(m), n)`` yields, which is
what the work journal's "block index ⇒ tuples" contract is defined over).
A block of tuples is identified by its rank range alone and materializes
directly on device:

    ranks r, r+1, …, r+B-1  ──unrank──►  (B, n) int32 index tuples

so enumeration is a jitted XLA computation that overlaps with scoring via
the block prefetcher (engine/streaming.py).

Math: lexicographic rank over ascending tuples is the *colexicographic*
rank of the reversed complement.  With ``b_i = m-1-a_{n+1-i}`` (so ``b`` is
an ascending combination iff ``a`` is),

    lex_rank(a) = C(m, n) - 1 - Σ_i C(b_i, i)

Colex unranking is greedy: for i = n…1, ``b_i`` is the largest c with
C(c, i) ≤ r', and r' drops by C(b_i, i).  The row ``C(c, i)``, c < m, is
non-decreasing in c (zero below c = i), so the c with ``C(c, i) ≤ r'`` are
a prefix of the row, and

    b_i = #{c : C(c, i) ≤ r'} - 1,     C(b_i, i) = max {C(c, i) ≤ r'}.

Each column is therefore one broadcast compare of the (B,) residual ranks
against the exact row, built on the host in Python integers and held as a
(1, m) constant, followed by a count and a masked max over m.  There is no
gather: XLA fuses the compare and both reductions into one pass, so the
(B, m) comparison is never written out.  (A per-rank binary search over the
same table gathers one entry per step; on a TPU that gather traffic, not
arithmetic, set the enumerator's time.)

Integer width is chosen from (m, n) (:func:`rank_dtype`): int32 ranks and
table when the top rank and every table entry fit it (thermal at rung 1,
C(600, 3)), else the same decode in int64 under jax x64 (C(6000, 3)), which
the TPU emulates; spaces past 2⁶² use the host-exact fallback of
``core/l0.py`` (slower, never wrong).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.l0 import n_models

#: exclusive bound on every integer the decode holds, per rank dtype
_DTYPE_BOUND = {np.dtype(np.int32): 2**31, np.dtype(np.int64): 2**62}


def comb_exact(n: int, k: int) -> int:
    """Host-exact C(n, k) (Python ints — rank arithmetic never rounds).

    One implementation with the block accounting: this *is*
    ``core.l0.n_models`` (guarded for n < k), so rank arithmetic and
    sweep bookkeeping can never diverge."""
    return n_models(n, k) if 0 <= k <= n else 0


def _largest_value(m: int, n: int) -> int:
    """The largest integer the decode holds for (m, n): the rank bound
    C(m, n) or a table entry, max_i C(m-1, i) (above C(m, n) for n > m/2)."""
    return max(comb_exact(m, n),
               *(comb_exact(m - 1, i) for i in range(n + 1)))


def rank_dtype(m: int, n: int) -> Optional[np.dtype]:
    """The integer width device unranking of the (m, n) space runs in.

    int32 where every value fits it; int64 where it does not but fits
    2⁶² and jax x64 is on; None where neither holds, and the caller
    enumerates on the host.
    """
    top = _largest_value(m, n)
    if top < _DTYPE_BOUND[np.dtype(np.int32)]:
        return np.dtype(np.int32)
    if top < _DTYPE_BOUND[np.dtype(np.int64)] and jax.config.jax_enable_x64:
        return np.dtype(np.int64)
    return None


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
    (tests/test_l0.py asserts the full bijection).  The decode runs in the
    dtype of ``ranks``, int32 or int64, which must hold every value of the
    space (:func:`rank_dtype` gives the narrowest that does).
    """
    dtype = np.dtype(ranks.dtype)
    if dtype not in _DTYPE_BOUND or _largest_value(m, n) >= _DTYPE_BOUND[dtype]:
        raise ValueError(
            f"{dtype} ranks cannot unrank C({m}, {n}) exactly; "
            f"use rank_dtype({m}, {n})")
    r = (comb_exact(m, n) - 1) - ranks  # colex rank of the dual
    cols = []
    for i in range(n, 0, -1):
        row = np.array([[comb_exact(c, i) for c in range(m)]], dtype)
        fits = row <= r[:, None]                     # a prefix of each row
        b = jnp.sum(fits, axis=1, dtype=jnp.int32) - 1
        r = r - jnp.max(jnp.where(fits, row, 0), axis=1)
        cols.append(m - 1 - b)
    return jnp.stack(cols, axis=1)


def unrank_block(start: int, count: int, m: int, n: int) -> jnp.ndarray:
    """Device (count, n) int32 tuple block covering ranks [start, start+count).

    ``start``/``count`` are host Python ints (exact); the ranks are made
    in :func:`rank_dtype`'s width, and the result is a device array —
    callers that stream blocks into a scoring kernel never pay a
    host↔device round-trip for enumeration.
    """
    dtype = rank_dtype(m, n)
    if dtype is None:
        raise ValueError(f"C({m}, {n}) is too large to unrank on device")
    return unrank_lex(jnp.arange(start, start + count, dtype=dtype), m, n)
