"""One run of one cell: set-up, the measured window, the comparison.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) joins a
configuration (``configs/<config>.json``: the case's data and its SISSO
settings as published) with a campaign (``traffic/<traffic>.json``: the
settings this cell cuts, and the backend); its limits are
``limits/<cell>.json`` and each metric is read by ``metrics/<name>.py``.
Adding a configuration, a campaign or a metric adds files; the harness
finds them by the names in ``BENCHMARK.json``.

The window is a closed loop: one campaign, fitted through the public
estimator (``repro.api.SissoRegressor.from_config(...).fit``), back to
back; a new fit starts only inside ``--seconds``, and each runs to its end.
Set-up makes the data from the seed and runs one fit of the same campaign
on it, so that the window compiles nothing.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import compare, reference, tracing, work
from .data import Data, make_data

SUITE = Path(__file__).resolve().parent
CHECKOUT = SUITE.parents[1]
#: keys of a configuration that set how the reference runs the campaign
REFERENCE_KEYS = ("op_names", "max_rung", "l_bound", "u_bound", "n_sis",
                  "n_dim", "n_residual")


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    settings: dict          # the configuration with the campaign's cuts
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = _json(root / conf["file"])
    traffic = _json(SUITE / "traffic" / f"{w['traffic']}.json")
    limits_file = SUITE / "limits" / f"{name}.json"
    limits = _json(limits_file) if limits_file.exists() else {}

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name, chips=int(w["chips"]),
        settings={**config, **traffic}, config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
    )


def load_reader(metric: str) -> Callable:
    path = SUITE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.suite.metrics.{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_device(chips: int) -> dict:
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{info['count']} {info['platform']} device(s)")
    return info


def program_config(settings: dict):
    from repro.core.solver import SissoConfig

    fields = {f.name for f in dataclasses.fields(SissoConfig)}
    return SissoConfig(**{k: v for k, v in settings.items() if k in fields})


def program_units(data: Data):
    if data.units is None:
        return None
    from repro.core.units import Unit

    return [Unit(tuple(u), data.basis) for u in data.units]


@dataclasses.dataclass
class FitRecord:
    seconds: float
    timings: Dict[str, float]
    shape: dict
    answers: compare.Answers


def fit_once(settings: dict, data: Data, inst: tracing.Instrument,
             units) -> FitRecord:
    """One campaign through the public estimator; the clock stops when the
    fit has returned its models to the host."""
    from repro.api import SissoRegressor

    est = SissoRegressor.from_config(program_config(settings))
    inst.selections = []
    # fit() returns its models as host (NumPy) values: nothing is in flight
    t0 = time.perf_counter()  # reprolint: disable=RL002
    est.fit(data.x.T, data.y, names=data.names, units=units,
            tasks=data.tasks)
    seconds = time.perf_counter() - t0
    return record(est, seconds, inst.selections, settings, data)


def record(est, seconds: float, selections, settings, data) -> FitRecord:
    fit = est.fit_result_
    fs = fit.fspace
    xmat = fs.values_matrix()
    selected = {d + 1: [compare.Selected(f.expr, np.array(xmat[f.row]))
                        for f in feats]
                for d, (feats, _scores) in enumerate(selections)}
    models = {d: [compare.Model([f.expr for f in m.features],
                                np.array(xmat[[f.row for f in m.features]]),
                                float(m.sse)) for m in ms]
              for d, ms in fit.models_by_dim.items()}
    otf = settings.get("on_the_fly_last_rung", False)
    n_fc = sum(1 for f in fs.features
               if not (otf and f.rung == settings["max_rung"]))
    itemsize = {"bf16": 2, "fp32": 4, "fp64": 8}[settings["precision"]]
    dims, before = {}, 0
    for d, sel in selected.items():
        residuals = 1 if d == 1 else min(settings["n_residual"],
                                         len(models.get(d - 1, [])))
        dims[d] = {"screened": n_fc + fs.n_candidates_deferred - before,
                   "residuals": residuals, "subspace": before + len(sel)}
        before += len(sel)
    shape = {"samples": len(data.y), "tasks": len(data.task_slices),
             "itemsize": itemsize, "dims": dims}
    return FitRecord(seconds, dict(fit.timings), shape,
                     compare.Answers(selected, models))


class CompileCounter:
    """Compilations, and programs loaded from the persistent cache, while
    ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.compiles = self.loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if self.active and event.endswith("backend_compile_duration"):
            self.compiles += 1

    def _event(self, event: str, **kw) -> None:
        if self.active and event.endswith("cache_hits"):
            self.loads += 1


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed place in
    the checkout (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping
    every program so that only a cell's first run compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    setup_s: float
    fits: List[FitRecord]
    trace: Optional[tracing.Summary]
    peak: Dict[str, float]


def reference_campaign(cell: Cell, data: Data, store="fp64", compute="fp64"):
    settings = {k: cell.settings[k] for k in REFERENCE_KEYS}
    return reference.run(data.x, data.y, data.names, data.units,
                         data.task_slices, settings, store, compute)


def judge(cell: Cell, data: Data, fits: List[FitRecord]):
    """(readings: worst over the fits, correct, checks)."""
    ref = reference_campaign(cell, data)
    j = compare.Judge(ref, data.x, data.y, data.names, data.task_slices)
    readings = compare.worst([j.readings(f.answers) for f in fits]) if fits \
        else {k: compare.WRONG for k in cell.limits}
    checks = {k: {"value": readings[k], "limit": lim}
              for k, lim in sorted(cell.limits.items())}
    correct = bool(fits) and bool(cell.limits) and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values())
    return readings, correct, checks


def peak_memory(n: int) -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n])


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """One run of a cell of ``BENCHMARK.json``; returns the result line's
    object."""
    return run_cell(load_cell(cell_name), seed, seconds, trace, t_start)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, need_chip: bool = True, log=sys.stderr) -> dict:
    """One run of ``cell``.  ``need_chip=False`` lets a test drive it on
    the CPU."""
    import jax

    device = check_device(cell.chips) if need_chip else device_info()
    enable_cache()
    peak = work.peaks(device["kind"]) if trace else {}
    counter = CompileCounter()
    data = make_data(cell.config, seed)
    units = program_units(data)
    inst = tracing.Instrument(annotate=trace)
    fits: List[FitRecord] = []
    attempted = failed = 0
    summary = None
    with inst.installed():
        fit_once(cell.settings, data, inst, units)          # warm-up
        setup_s = time.perf_counter() - t_start
        trace_dir = tempfile.mkdtemp(prefix="sisso-trace-") if trace else None
        if trace:
            # host spans and runtime events, but no per-call Python events:
            # those cost the traced fits a third of their speed
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        counter.active = True
        with inst.window():
            # the window's clock: each fit inside it is timed by fit_once
            t0 = time.perf_counter()  # reprolint: disable=RL002
            while time.perf_counter() - t0 < seconds:
                attempted += 1
                try:
                    fits.append(fit_once(cell.settings, data, inst, units))
                except Exception as exc:  # a failed fit is counted, not fatal
                    failed += 1
                    print(f"fit failed: {exc!r}", file=log, flush=True)
            window_s = time.perf_counter() - t0
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
    print(f"set-up {setup_s:.3f} s; window {window_s:.3f} s, "
          f"{len(fits)} fits; in the window: {counter.compiles} "
          f"compilations, {counter.loads} programs loaded from the "
          f"persistent cache", file=log, flush=True)
    device["memory_peak_bytes"] = peak_memory(cell.chips)
    if trace:
        t_trace = time.perf_counter()  # reprolint: disable=RL002 (host)
        events = tracing.load_events(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        summary = tracing.summarize(events, cell.chips)
        print(f"trace: {len(events)} events read in "
              f"{time.perf_counter() - t_trace:.3f} s", file=log, flush=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    t_ref = time.perf_counter()  # reprolint: disable=RL002 (host NumPy)
    readings, correct, checks = judge(cell, data, fits)
    print(f"reference and comparison {time.perf_counter() - t_ref:.3f} s",
          file=log, flush=True)
    correct = correct and failed == 0
    r = Run(setup_s, fits, summary, peak)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = load_reader(m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": [list(x) for x in summary.device_ops],
                            "idle_gaps": [list(x) for x in summary.idle_gaps]}
    out["readings"] = readings
    out["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=log,
              flush=True)
    return out
