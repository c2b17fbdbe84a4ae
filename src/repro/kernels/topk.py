"""In-kernel block top-k epilogue + device-side merge (selection fusion).

The paper's central result is that keeping intermediate data out of global
memory is what unlocks accelerator speedups; returning a full ``(B,)`` score
vector from a selection kernel reintroduces exactly the traffic the fused
evaluation eliminated.  The epilogue here reduces each grid step's block to
its top-``k`` (score, global index) pairs *before* anything leaves VMEM:

    HBM writes per block:   O(block)  ->  O(k_pad)
    host transfer per call: O(B)      ->  O(k)   (after the device merge)

Two pieces, shared by the fused-SIS kernel (largest=True) and the ℓ0
Gram-gather kernel (largest=False):

* :func:`block_topk` — runs *inside* a Pallas kernel.  Iterative extraction
  (k rounds of masked max/min + first-occurrence argpos) instead of
  ``jax.lax.top_k``: the loop is k VPU reductions over a (1, B) row, every
  op Mosaic-lowerable, and the tie rule is explicit — first occurrence, i.e.
  the lowest block position — which is exactly the order a stable sort of
  the full vector yields (``TopK.push`` / ``ReducedBlock.reduce_host``).
* :func:`merge_block_topk` — jitted tree merge of the per-block ``(nb,
  k_pad)`` winner panels: one ``jax.lax.top_k`` over the flattened winners
  (XLA lowers it to a log-depth sort network, O(k·log nb) effective depth).
  Flat position order is (block, extraction rank), so equal scores resolve
  to the lowest global index here too — the reduced path and the
  full-vector stable sort pick identical tied winners.

Sentinels: lanes past the k-th real winner hold ±inf scores and position
``-1``; they survive the merge only when fewer than ``k_merge`` finite
winners exist, and every consumer filters by finiteness before the block
crosses the host boundary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_INT_MAX = np.iinfo(np.int32).max


def block_topk(scores: jnp.ndarray, k: int, k_pad: int,
               largest: bool = True):
    """Top-``k`` of a (1, B) score row by iterative extraction (in-kernel).

    Returns ``(vals (1, k_pad) f32 best-first, pos (1, k_pad) i32)`` where
    ``pos`` is the block-local position of each winner (caller adds the
    grid-step base for global indices).  Lanes ``>= k`` (and extractions
    past the last finite score) hold the ±inf sentinel and ``pos`` is the
    first remaining position — consumers must filter on finite ``vals``,
    never on ``pos``.
    """
    b = scores.shape[1]
    pos_iota = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, k_pad), 1)
    sentinel = jnp.float32(-jnp.inf) if largest else jnp.float32(jnp.inf)

    def extract(j, carry):
        vals, pos, work = carry
        m = work.max() if largest else work.min()
        # first occurrence among exact ties -> lowest block position, the
        # stable-sort tie order the host merge (TopK.push) produces
        p = jnp.where(work == m, pos_iota, _INT_MAX).min()
        vals = jnp.where(lane == j, m, vals)
        pos = jnp.where(lane == j, p, pos)
        work = jnp.where(pos_iota == p, sentinel, work)
        return vals, pos, work

    # a rolled loop: k unrolled rounds make the kernel's compile time grow
    # with k (hundreds of rounds at SIS-sized k)
    vals, pos, _ = jax.lax.fori_loop(0, k, extract, (
        jnp.full((1, k_pad), sentinel, jnp.float32),
        jnp.full((1, k_pad), -1, jnp.int32),
        scores.astype(jnp.float32),
    ))
    return vals, pos


@functools.partial(jax.jit, static_argnames=("k", "largest"))
def merge_block_topk(vals: jnp.ndarray, idx: jnp.ndarray, k: int,
                     largest: bool = True):
    """Merge per-block winner panels ``(nb, k_pad)`` to a global top-``k``.

    One device ``top_k`` over the flattened winners; ties pick the lowest
    flat position = (lowest block, earliest extraction) = lowest global
    index.  Returns ``(scores (k,) f32 best-first, indices (k,) i32)``;
    sentinel lanes (±inf) can only appear when fewer than ``k`` finite
    winners exist.
    """
    flat_v = vals.reshape(-1)
    flat_i = idx.reshape(-1)
    if largest:
        v, sel = jax.lax.top_k(flat_v, k)
    else:
        neg, sel = jax.lax.top_k(-flat_v, k)
        v = -neg
    return v, flat_i[sel]
