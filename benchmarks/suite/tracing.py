"""Spans around the program's layers, and the reduction of a profiler trace
to busy time, idle share, device time inside spans and a breakdown.

The spans are the benchmark's own: ``instrument`` wraps the names that the
solver calls (``FeatureSpace.generate``, ``sis_screen``, ``l0_search``) in
``jax.profiler.TraceAnnotation`` and records what SIS selected; no file of
the program changes.

A trace is reduced from a flat list of events ``(plane, line, name,
start_ns, duration_ns)``, read from the profiler's ``.xplane.pb`` by
``load_events`` or from a recorded JSON list (the self-check), so the same
arithmetic serves both.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

PHASES = ("fc", "sis", "l0")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class Instrument:
    """Wraps the solver's calls into its layers for the life of a ``with``.

    ``selections`` collects, per fit, what each SIS call returned:
    ``(features, scores)`` in call order; ``annotate`` adds the profiler
    spans (off in an untraced run, so it costs nothing there)."""

    def __init__(self, annotate: bool):
        self.annotate = annotate
        self.selections: List[tuple] = []

    def _span(self, name: str):
        if not self.annotate:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)

    @contextlib.contextmanager
    def installed(self):
        import repro.core.solver as solver

        sis_screen, l0_search = solver.sis_screen, solver.l0_search
        generate = solver.FeatureSpace.generate
        inst = self

        def sis(*args, **kwargs):
            with inst._span("sis"):
                out = sis_screen(*args, **kwargs)
            inst.selections.append(out)
            return out

        def l0(*args, **kwargs):
            with inst._span("l0"):
                return l0_search(*args, **kwargs)

        def gen(self_):
            with inst._span("fc"):
                return generate(self_)

        solver.sis_screen, solver.l0_search = sis, l0
        solver.FeatureSpace.generate = gen
        try:
            yield self
        finally:
            solver.sis_screen, solver.l0_search = sis_screen, l0_search
            solver.FeatureSpace.generate = generate

    def window(self):
        return self._span("window")


# ---------------------------------------------------------------------------
# reading a trace
# ---------------------------------------------------------------------------

def load_events(trace_dir: str) -> List[Event]:
    """Device op and module events and host events of the profile written
    under ``trace_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[Event] = []
    for path in files:
        data = jax.profiler.ProfileData.from_file(path)
        for plane in data.planes:
            device = DEVICE_PLANE.match(plane.name) is not None
            if not device and not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                if device and line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    events.append(Event(plane.name, line.name, ev.name,
                                        float(ev.start_ns),
                                        float(ev.duration_ns)))
    return events


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return float(sum(hi - lo for lo, hi in intervals))


def intersect(a: Sequence[Tuple[float, float]],
              b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


_SUFFIX = re.compile(r"[.(]\d+\)?$")


def _kernel_label(module: str, op: str) -> str:
    """``module/op`` without instance numbers; a TPU op event is named by
    its whole HLO instruction (``%fusion.37 = u32[65536] fusion(...)``)."""
    op = op.split(" = ", 1)[0].lstrip("%")
    return f"{_SUFFIX.sub('', module)}/{_SUFFIX.sub('', op)}"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over the chips used
    phase_busy_s: Dict[str, float]      # device busy time inside each span
    phase_span_s: Dict[str, float]      # host time inside each span
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(events: Sequence[Event], n_chips: int, top: int = 10) -> Summary:
    """Reduce a traced window to the benchmark's device numbers.

    The window is the host span ``bench.window``; busy time is the union of
    device op intervals inside it, per chip, averaged over the ``n_chips``
    chips the cell uses."""
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no window span")
    w0 = min(e.start_ns for e in windows)
    w1 = max(e.end_ns for e in windows)
    win = [(w0, w1)]
    # host events of the thread that holds the benchmark's spans
    lines = {(e.plane, e.line) for e in windows}
    host = [e for e in events if (e.plane, e.line) in lines]
    spans = {p: union((e.start_ns, e.end_ns) for e in host
                      if e.name == SPAN_PREFIX + p) for p in PHASES}
    planes = sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)},
                    key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
    planes = planes[:n_chips]
    busy_total, phase_busy = 0.0, {p: 0.0 for p in PHASES}
    per_op: Dict[str, float] = {}
    busy_first: List[Tuple[float, float]] = []
    for k, plane in enumerate(planes):
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE]
        mods = sorted((e for e in events
                       if e.plane == plane and e.line == MODULES_LINE),
                      key=lambda e: e.start_ns)
        busy = intersect(union((e.start_ns, e.end_ns) for e in ops), win)
        busy_total += length(busy)
        for p in PHASES:
            phase_busy[p] += length(intersect(busy, spans[p]))
        if k == 0:
            busy_first = busy
        starts = [m.start_ns for m in mods]
        for e in ops:
            clipped = min(e.end_ns, w1) - max(e.start_ns, w0)
            if clipped <= 0:
                continue
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = mods[i].name if i >= 0 and mods[i].end_ns >= e.start_ns \
                else "?"
            label = _kernel_label(module, e.name)
            per_op[label] = per_op.get(label, 0.0) + clipped
    n = max(len(planes), 1)
    gaps = _idle_gaps(busy_first, (w0, w1), host, spans, top)
    ranked = sorted(per_op.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
    return Summary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / n * 1e-9,
        phase_busy_s={p: v / n * 1e-9 for p, v in phase_busy.items()},
        phase_span_s={p: length(intersect(spans[p], win)) * 1e-9
                      for p in PHASES},
        device_ops=[(k, v / n * 1e-9) for k, v in ranked],
        idle_gaps=gaps,
    )


def _idle_gaps(busy, window, host: Sequence[Event], spans, top: int):
    """Longest gaps between device work inside the window, each labelled by
    the benchmark phase and the innermost other host event at its middle."""
    w0, w1 = window
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: (-(g[1] - g[0]), g[0]))
    out = []
    for lo, hi in gaps[:top]:
        mid = 0.5 * (lo + hi)
        phase = next((p for p in PHASES
                      if any(a <= mid <= b for a, b in spans[p])), "between")
        inner: Optional[Event] = None
        for e in host:
            if e.name.startswith(SPAN_PREFIX) or not (
                    e.start_ns <= mid <= e.end_ns):
                continue
            if inner is None or e.dur_ns < inner.dur_ns:
                inner = e
        what = inner.name if inner is not None else "python"
        out.append((f"{phase}|{what}", (hi - lo) * 1e-9))
    return out
