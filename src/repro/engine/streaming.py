"""Double-buffered block streaming for the sweep phases (SIS, ℓ0).

The SISSO hot loops are all the same shape: a deterministic generator of
work blocks, a device scoring call per block, and a cheap host-side merge
(top-k, journal).  Run serially, the host work — enumerating or gathering
block *k+1* and merging block *k-1* — sits on the device's critical path.

:class:`BlockPrefetcher` pipelines them: up to ``depth`` blocks are
enumerated + dispatched on worker threads while the consumer merges earlier
results, so block *k+1*'s enumeration/transfer overlaps block *k*'s device
scoring and the host top-k merge moves off the critical path entirely.
Results are always yielded **in submission order**, which is what keeps the
work journal's "block index ⇒ tuples" resume contract intact — streaming
changes *when* work happens, never *what* a block means.  The prefetcher is
shape-agnostic by design: a scoring ``fn`` may return full score vectors or
pre-reduced :class:`~repro.core.sis.ReducedBlock` winners (a device-merging
backend behind the Engine's ``n_keep`` routing) — reduced blocks are
forwarded unchanged, and only the consumer's merge branch differs.

This lives in ``engine/`` (not ``core/``) deliberately: it is cross-phase
execution policy, the kind of thing the Engine façade exists to own
(ARCHITECTURE.md), and both ``core/l0.py`` and ``core/sis.py`` share this
one implementation.

Thread-safety notes: JAX dispatch is thread-safe, and with the default
``depth=2`` at most ``depth`` worker calls are in flight, so device memory
pressure is bounded by ``depth`` blocks.  Exceptions from workers re-raise
at the consumer in block order; pending blocks are cancelled.

Spans (runtime/trace.py): each block runs on its worker inside
``<span>.block``, and the consumer's wait for a block is ``<span>.wait``,
where ``span`` is the caller's base name (``sisso.l0``, ``sisso.sis``).  A
block runs in a copy of the submitting context, so its spans and counters
land in the fit that submitted it.  The workers' threads are named
``block-prefetch``, which is also their line's name in a profiler trace.
"""
from __future__ import annotations

import contextvars
from concurrent.futures import ThreadPoolExecutor
from collections import deque
from typing import Callable, Generic, Iterable, Iterator, Tuple, TypeVar

from ..runtime import trace

TItem = TypeVar("TItem")
TOut = TypeVar("TOut")


class BlockPrefetcher(Generic[TItem, TOut]):
    """Ordered prefetching map: ``fn`` over ``items``, ``depth`` in flight.

    Iterating yields ``(item, fn(item))`` pairs in the order ``items``
    produced them.  ``depth=1`` degenerates to eager single-buffering
    (still off-main-thread); ``depth=2`` is classic double buffering.
    ``span`` is the base name of the block and wait spans.
    """

    def __init__(
        self,
        fn: Callable[[TItem], TOut],
        items: Iterable[TItem],
        depth: int = 2,
        span: str = "prefetch",
    ):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.fn = fn
        self.items = iter(items)
        self.depth = depth
        self.span = span

    def _fetch(self, item: TItem) -> TOut:
        # fault site ``prefetch.fetch``: a worker-thread dispatch failure
        # (device error raised off-main-thread).  The exception is held in
        # the future and re-raises at the consumer in block order — which
        # is exactly the ordering contract this site exists to test.
        from ..runtime import faults

        with trace.span(self.span + ".block"):
            faults.check("prefetch.fetch")
            return self.fn(item)

    def _result(self, fut) -> TOut:
        with trace.span(self.span + ".wait"):
            return fut.result()

    def __iter__(self) -> Iterator[Tuple[TItem, TOut]]:
        pool = ThreadPoolExecutor(
            max_workers=self.depth, thread_name_prefix="block-prefetch",
            initializer=trace.name_os_thread, initargs=("block-prefetch",),
        )
        inflight: deque = deque()
        try:
            for item in self.items:
                ctx = contextvars.copy_context()
                inflight.append((item, pool.submit(ctx.run, self._fetch, item)))
                if len(inflight) < self.depth:
                    continue
                item0, fut = inflight.popleft()
                yield item0, self._result(fut)
            while inflight:
                item0, fut = inflight.popleft()
                yield item0, self._result(fut)
        finally:
            for _, fut in inflight:
                fut.cancel()
            pool.shutdown(wait=True, cancel_futures=True)


def prefetch(fn, items, depth: int = 2):
    """Functional alias: ``for item, out in prefetch(fn, items): ...``"""
    return BlockPrefetcher(fn, items, depth=depth)
