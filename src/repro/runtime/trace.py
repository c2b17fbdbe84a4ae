"""Spans and counters of one SISSO fit.

A fit records where its time goes as a tree of named spans, plus a few
counters, in a :class:`FitTrace` that it hands out when it ends
(``SissoFit.trace``); ``SissoFit.timings`` and ``SissoFit.stats`` are read
from it.

* ``span(name)`` opens a ``jax.profiler.TraceAnnotation`` of that name, so
  that a profiler trace shows the span on the host plane, on the clock of
  the device planes, and adds the span's host-clock interval to the active
  fit's record.  With no profiler attached a span costs the annotation and
  two clock reads.
* ``count(key, n)`` adds ``n`` to a counter of the active fit; a key
  ``("l0_paths", 3, "Gram-gather kernel")`` reads back as
  ``stats["l0_paths"][3]["Gram-gather kernel"]``.
* ``collecting()`` opens a fit's record and its ``sisso.fit`` span, or
  joins the record already open in this context: a solver run by an
  estimator records into the estimator's fit.
* ``recent_fits()`` returns the records of the process's last fits that
  ran to their end, for a monitor that did not hold the fits' results.

The active record is found through a ``ContextVar``.  Work run through
``contextvars.copy_context().run`` (the block workers of
engine/streaming.py) lands in the fit that submitted it, and fits run in
different threads never mix.  Outside a fit, spans still annotate a
profiler trace and counters are dropped.

Program lowering is counted per span: a ``jax.monitoring`` listener adds
each lowering of a program to MLIR (``lowered``), each program loaded from
the persistent compilation cache (``cache_loads``) and each backend
compilation that was not such a load (``compiled``) to the innermost span
open in the calling thread, as ``stats["programs"][span][kind]``.

The spans of a fit (PERF.md lists them):

=======================  ================================================
span                     interval
=======================  ================================================
``sisso.fit``            the estimator's whole ``fit`` (or the solver's)
``sisso.fc``             ``FeatureSpace`` construction and ``generate()``
``sisso.fc.enumerate``   one operator's child sets at one rung (host rules)
``sisso.fc.eval``        one ``eval_candidates`` call (device + read-back)
``sisso.fc.admit``       one ``admit_block`` call
``sisso.sis``            one dimension's screen
``sisso.sis.block``      one deferred-candidate block, on a block worker
``sisso.sis.wait``       the screen waiting on a block worker
``sisso.l0``             one dimension's ℓ0 search
``sisso.l0.prepare``     the Gram statistics (``engine.prepare_l0``)
``sisso.l0.block``       one tuple block's scoring, on a block worker
``sisso.l0.rescore``     one exact fp64 rescore (pallas backend)
``sisso.l0.wait``        the merge loop waiting on a block worker
``sisso.l0.merge``       one block's host top-k merge
``sisso.models``         one dimension's models and next residuals
``sisso.descriptor``     compiling and replaying every model's descriptor
=======================  ================================================

The counters of a fit: ``programs`` (above); ``l0_paths`` and ``l0_enum``
(ℓ0 blocks per width and scoring or enumeration path); ``l0_rows`` (per
width, the Gram-gather kernel's ``blocks``, its panel ``rows`` in use —
one per run of tuples sharing a prefix —, the ``tuples`` they hold and
the ``lanes`` they span: ``tuples / lanes`` is the panels' occupancy);
``sis_paths``
(deferred SIS blocks per scoring path: ``fused kernel``, ``fused kernel
in shard_map`` or ``evaluate then screen``); ``sharded`` (a distributed
engine: ``devices``, and per phase the k-sized ``merges`` and the winner
rows they all-gathered, ``gathered_rows``).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import ctypes
import sys
import threading
import time
from typing import Dict, Hashable, List, NamedTuple, Optional, Tuple

import jax

#: ``SissoFit.timings`` key -> the span whose durations it sums
TIMED_SPANS = {
    "fit": "sisso.fit",
    "fc": "sisso.fc",
    "sis": "sisso.sis",
    "l0": "sisso.l0",
    "models": "sisso.models",
    "descriptor": "sisso.descriptor",
    "l0_wait": "sisso.l0.wait",
    "fc_enumerate": "sisso.fc.enumerate",
    "sis_wait": "sisso.sis.wait",
}
PROGRAM_KINDS = ("lowered", "cache_loads", "compiled")
#: how many finished fits ``recent_fits()`` keeps
RECENT_FITS = 64

_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    name: str
    start_ns: int       # host clock (time.perf_counter_ns)
    end_ns: int
    parent: Optional[str]
    thread: str

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


class FitTrace:
    """The spans and counters of one fit (thread-safe)."""

    def __init__(self):
        self.spans: List[Span] = []
        self.counters: Dict[Tuple[Hashable, ...], int] = {}
        self._lock = threading.Lock()

    def add(self, span: Span) -> None:
        with self._lock:
            self.spans.append(span)

    def count(self, key: Tuple[Hashable, ...], n: int = 1) -> None:
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def seconds(self, name: str) -> float:
        """Summed duration of the closed spans called ``name``."""
        with self._lock:
            return sum(s.seconds for s in self.spans if s.name == name)

    def timings(self) -> Dict[str, float]:
        """``TIMED_SPANS`` keys whose span closed at least once."""
        with self._lock:
            closed = {s.name for s in self.spans}
        return {key: self.seconds(name) for key, name in TIMED_SPANS.items()
                if name in closed}

    def stats(self) -> Dict[str, dict]:
        """Counters as nested dicts: a key ``(group, a, b)`` reads as
        ``stats[group][a][b]``; ``programs`` and ``l0_paths`` are always
        there."""
        out: Dict[str, dict] = {"programs": {}, "l0_paths": {}}
        with self._lock:
            items = list(self.counters.items())
        for (*path, last), n in items:
            node = out
            for k in path:
                node = node.setdefault(k, {})
            node[last] = n
        for kinds in out["programs"].values():
            for kind in PROGRAM_KINDS:
                kinds.setdefault(kind, 0)
        return out

    def report(self, fit) -> None:
        """Write the record into a ``SissoFit``: ``timings`` and ``stats``
        are updated in place, ``trace`` is this record."""
        fit.timings.update(self.timings())
        fit.stats.update(self.stats())
        fit.trace = self


#: (the active fit's record, the innermost open span's name) or None
_active: contextvars.ContextVar[Optional[Tuple[FitTrace, Optional[str]]]] = \
    contextvars.ContextVar("repro_fit_trace", default=None)
#: the records of the process's last fits that ran to their end
_finished: collections.deque = collections.deque(maxlen=RECENT_FITS)


class span(contextlib.ContextDecorator):
    """``with span("sisso.l0"): ...`` — a named interval of the active fit,
    annotated for the profiler; ``@span(name)`` spans each call of a
    function."""

    def __init__(self, name: str):
        self.name = name

    def _recreate_cm(self) -> "span":
        # each decorated call (from any thread) gets its own interval
        return span(self.name)

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        active = _active.get()
        self._token = None
        if active is not None:
            self._parent = active[1]
            self._token = _active.set((active[0], self.name))
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        if self._token is not None:
            rec = _active.get()[0]
            _active.reset(self._token)
            rec.add(Span(self.name, self._start, end, self._parent,
                         threading.current_thread().name))
        self._annotation.__exit__(*exc)


def count(key: Tuple[Hashable, ...], n: int = 1) -> None:
    """Add ``n`` to the active fit's counter ``key``, a tuple
    ``(group, ..., name)`` that ``FitTrace.stats`` nests."""
    active = _active.get()
    if active is not None:
        active[0].count(key, n)


@contextlib.contextmanager
def collecting():
    """The active fit's record: opened here, with its ``sisso.fit`` span,
    unless this context already records a fit, which is then joined."""
    active = _active.get()
    if active is not None:
        yield active[0]
        return
    rec = FitTrace()
    token = _active.set((rec, None))
    try:
        with span("sisso.fit"):
            yield rec
    finally:
        _active.reset(token)
    _finished.append(rec)


def recent_fits() -> List[FitTrace]:
    """The records of the process's last ``RECENT_FITS`` fits that ran to
    their end, oldest first (a fit that raised is not among them)."""
    return list(_finished)


def name_os_thread(name: str) -> None:
    """Name the calling OS thread (its first 15 bytes), which is the name a
    profiler trace gives the thread's line; a no-op off Linux."""
    if not sys.platform.startswith("linux"):
        return
    libc = ctypes.CDLL(None)
    libc.pthread_self.restype = ctypes.c_ulong
    libc.pthread_setname_np(ctypes.c_ulong(libc.pthread_self()),
                            name.encode()[:15])


# ---------------------------------------------------------------------------
# program lowering, per span
# ---------------------------------------------------------------------------

#: persistent-cache loads of this thread not yet matched to the backend
#: compile event that encloses each of them
_pending_loads = threading.local()


def _count_program(kind: str) -> None:
    active = _active.get()
    if active is not None and active[1] is not None:
        active[0].count(("programs", active[1], kind))


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event == _LOWER_EVENT:
        _count_program("lowered")
    elif event == _COMPILE_EVENT:
        loads = getattr(_pending_loads, "n", 0)
        if loads:
            _pending_loads.n = loads - 1
        else:
            _count_program("compiled")


def _on_event(event: str, **kwargs) -> None:
    if event == _CACHE_HIT_EVENT:
        _pending_loads.n = getattr(_pending_loads, "n", 0) + 1
        _count_program("cache_loads")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
