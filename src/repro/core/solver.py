"""SISSO driver: feature creation → (SIS → ℓ0)* over dimensions.

Mirrors the descriptor-identification flowchart of paper Fig. 1b:

    S = ∅;  Δ_0 = P (the target property)
    for dim d = 1..D:
        S += top-n_sis features by projection score against Δ_{d-1}
        model_d = argmin over all d-tuples of S of the LSQ error  (ℓ0)
        Δ_d = residuals of the best n_residual models of dim d

The 1-dimensional model is the exact ℓ0 solution over the full space; higher
dims search the accumulated SIS subspace (paper §II).
"""
from __future__ import annotations

import dataclasses
import logging
import warnings
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..precision import set_precision
from ..runtime import trace
from .feature_space import FeatureSpace
from .l0 import l0_search
from .problem import get_problem
from .sis import TaskLayout, sis_screen
from .units import Unit

log = logging.getLogger(__name__)


@dataclasses.dataclass
class SissoConfig:
    max_rung: int = 2
    n_dim: int = 2
    n_sis: int = 50
    n_residual: int = 10  # paper: "ten residuals per SIS iteration"
    l_bound: float = 1e-5
    u_bound: float = 1e8
    op_names: Sequence[str] = ("add", "sub", "mul", "div", "sq", "sqrt", "inv")
    on_the_fly_last_rung: bool = False  # paper P3
    l0_block: int = 65536               # paper: ℓ0 batches ≥ 65536
    sis_batch: int = 1 << 16
    l0_method: str = "gram"             # 'gram' (TPU-native) | 'qr' (paper-faithful)
    problem: str = "regression"         # regression | classification
    #                                     (core/problem.py: the objective —
    #                                     screening score, ℓ0 tuple
    #                                     objective, state update)
    backend: str = "jnp"                # reference | jnp | pallas | sharded
    #                                     | 'sharded:<inner>' (distribution
    #                                     wrapper over jnp/pallas/reference)
    precision: str = "fp64"             # bf16 | fp32 | fp64 (precision.py);
    #                                     threaded into the engine's compute
    #                                     dtype (SIS matmuls, ℓ0 solves)
    max_pairs_per_op: Optional[int] = None
    seed: int = 0
    debug_checks: Optional[bool] = None  # None: honor REPRO_DEBUG env;
    #                                      True/False: force the runtime
    #                                      contract sanitizer (repro.debug)
    #                                      on/off for this solver
    resilient: bool = False             # wrap the engine in
    #                                     ResilientExecution
    #                                     (engine/resilient.py): retry
    #                                     transient device errors, demote
    #                                     persistent kernel failures
    #                                     pallas→jnp→reference per-op;
    #                                     counters land in SissoFit.stats
    # deprecated aliases (pre-engine-layer configs)
    l0_engine: Optional[str] = None     # -> l0_method
    use_kernels: Optional[bool] = None  # True -> backend='pallas'

    def __post_init__(self):
        # apply-and-clear: dataclasses.replace() re-runs this, and a stale
        # alias must not override an explicitly replaced backend/method
        # (clearing also means each alias warns once, not per replace()).
        if self.l0_engine is not None:
            warnings.warn(
                "SissoConfig.l0_engine is deprecated; use l0_method",
                DeprecationWarning, stacklevel=3,
            )
            self.l0_method = self.l0_engine
            self.l0_engine = None
        if self.use_kernels is not None:
            warnings.warn(
                "SissoConfig.use_kernels is deprecated; use backend='pallas'",
                DeprecationWarning, stacklevel=3,
            )
            if self.use_kernels:
                self.backend = "pallas"
        self.use_kernels = None


@dataclasses.dataclass
class SissoFit:
    models_by_dim: Dict[int, List]  # SissoModel / SissoClassificationModel
    fspace: FeatureSpace
    #: seconds per phase, summed from the fit's spans
    #: (``runtime.trace.TIMED_SPANS``)
    timings: Dict[str, float]
    problem: str = "regression"
    #: runtime counters: ``programs`` lowered/loaded/compiled per span,
    #: ``l0_paths`` ℓ0 blocks per (width, scoring path), ``l0_enum``
    #: width ≥ 3 blocks per (width, enumeration path), ``sis_paths``
    #: deferred SIS blocks per scoring path, ``sharded`` (distributed
    #: engines: ``devices``, k-sized ``merges`` and ``gathered_rows`` per
    #: phase), and ``resilience`` retry/demotion accounting when
    #: SissoConfig.resilient is on
    stats: Dict[str, dict] = dataclasses.field(default_factory=dict)
    #: the fit's spans and counters (runtime/trace.py)
    trace: Optional[trace.FitTrace] = None

    def best(self, dim: Optional[int] = None):
        if not self.models_by_dim:
            raise RuntimeError("SissoFit holds no models (empty fit)")
        if dim is None:
            # highest dimension that actually produced a finite model
            finite = [d for d, ms in self.models_by_dim.items() if ms]
            if not finite:
                raise RuntimeError(
                    "no dimension produced a finite model "
                    f"(searched dims {sorted(self.models_by_dim)})"
                )
            dim = max(finite)
        models = self.models_by_dim.get(dim)
        if not models:
            raise RuntimeError(
                f"dimension {dim} produced no finite models; "
                f"dims with models: "
                f"{sorted(d for d, ms in self.models_by_dim.items() if ms)}"
            )
        return models[0]


class SissoSolver:
    """End-to-end SISSO core driver (single- and multi-task).

    Array-major convention: ``primary_values`` is ``(P, S)`` (features on
    rows), mirroring the paper's value-matrix layout.  The sklearn-style
    user surface with ``(n_samples, n_features)`` inputs, out-of-sample
    prediction and persistence is :class:`repro.api.SissoRegressor`.

    All three hot phases run on one execution engine selected by
    ``config.backend`` (see engine/ and ARCHITECTURE.md).
    """

    def __init__(self, config: SissoConfig, engine=None):
        from ..engine import get_engine

        self.cfg = config
        self.dtype = set_precision(config.precision)
        self.engine = get_engine(engine or config.backend)
        # thread the configured precision into the engine: backends run
        # their screening matmuls / ℓ0 solves at this dtype (the reference
        # oracle stays literal fp64)
        self.engine.set_precision(config.precision)
        # fault-tolerance wrapper (engine/resilient.py): retry transient
        # failures, demote persistent kernel failures down the backend
        # chain.  Wrapped *inside* the sanitizer so debug checks see the
        # final (post-retry, post-demotion) results.
        if config.resilient:
            from ..engine.resilient import wrap_engine_resilient

            self.engine = wrap_engine_resilient(self.engine)
        # runtime contract sanitizer (repro.debug): config.debug_checks
        # wins; otherwise REPRO_DEBUG=1/2 enables it
        from ..debug import maybe_wrap_engine

        self.engine = maybe_wrap_engine(self.engine, config.debug_checks)

    def fit(
        self,
        primary_values: np.ndarray,   # (P, S)
        y: np.ndarray,                # (S,)
        names: Sequence[str],
        units: Optional[Sequence[Unit]] = None,
        task_ids: Optional[np.ndarray] = None,
        journal=None,
    ) -> SissoFit:
        with trace.collecting() as rec:
            fit = self._fit(primary_values, y, names, units, task_ids,
                            journal)
        rec.report(fit)
        return fit

    def _fit(self, primary_values, y, names, units, task_ids,
             journal) -> SissoFit:
        cfg = self.cfg
        if journal is not None and getattr(journal, "path", None):
            # tuned launch configs persist next to the work journal so a
            # resumed / repeated fit skips the first-batch timing sweep
            from ..kernels import autotune

            autotune.set_cache_path(journal.path + ".autotune")
        y = np.asarray(y, np.float64)
        s = y.shape[0]
        layout = (
            TaskLayout.from_task_ids(task_ids)
            if task_ids is not None
            else TaskLayout.single(s)
        )
        # the devices a distributed engine shards candidates over
        shards = getattr(self.engine.backend, "n_shards", None)
        if shards:
            trace.count(("sharded", "devices"), shards)
        # ---- phase 1: feature creation -------------------------------
        with trace.span("sisso.fc"):
            fspace = FeatureSpace(
                primary_values, names, units,
                op_names=cfg.op_names, max_rung=cfg.max_rung,
                l_bound=cfg.l_bound, u_bound=cfg.u_bound,
                on_the_fly_last_rung=cfg.on_the_fly_last_rung,
                max_pairs_per_op=cfg.max_pairs_per_op, seed=cfg.seed,
                engine=self.engine,
            ).generate()
        log.info(
            "FC: %d materialized + %d deferred candidates",
            len(fspace.features), fspace.n_candidates_deferred,
        )

        # ---- phases 2+3: SIS / ℓ0 over dimensions ---------------------
        # The objective is owned by the Problem (core/problem.py): it
        # builds the screening context, defines the ℓ0 tuple objective,
        # turns winners into model objects, and produces the next state
        # (residuals / ambiguity masks).  This loop owns only phase
        # sequencing, the subspace bookkeeping and the phase spans.
        problem = get_problem(cfg.problem)
        subspace: List[int] = []  # fids, in selection order
        selected: set = set()
        models_by_dim: Dict[int, List] = {}
        state = problem.initial_state(y, layout)  # Δ_0

        for dim in range(1, cfg.n_dim + 1):
            with trace.span("sisso.sis"):
                feats, scores = sis_screen(
                    fspace, state, layout, cfg.n_sis, selected,
                    batch=cfg.sis_batch, engine=self.engine,
                    problem=problem, y=y,
                )
            for f in feats:
                subspace.append(f.fid)
                selected.add(f.fid)
            log.info(
                "dim %d SIS: +%d features (best score %.4f), subspace=%d",
                dim, len(feats), scores[0] if len(scores) else float("nan"),
                len(subspace),
            )

            # ℓ0 over the accumulated subspace
            with trace.span("sisso.l0"):
                xmat = fspace.values_matrix()
                xs = xmat[[fspace.features[fid].row for fid in subspace]]
                res = l0_search(
                    xs, y, layout, n_dim=dim, n_keep=cfg.n_residual,
                    block=cfg.l0_block, method=cfg.l0_method,
                    engine=self.engine, journal=journal,
                    dtype=self.dtype, problem=problem,
                )
                if journal is not None:
                    # this dim's sweep is complete; stale state would
                    # otherwise be "restored" by the next dim's search
                    # (different tuple width)
                    journal.clear()

            with trace.span("sisso.models"):
                models = problem.make_models(
                    xs, y, layout, res,
                    feature_of=lambda j: fspace.features[subspace[j]],
                    n_keep=cfg.n_residual, dtype=self.dtype,
                )
                # the best n_residual models feed the next SIS pass
                # (residuals for regression, still-ambiguous sample masks
                # for classification)
                state = problem.update_state(
                    y, layout, models[: cfg.n_residual],
                    values_of=lambda mdl: xmat[
                        [fspace.features[f.fid].row for f in mdl.features]
                    ],
                )
            models_by_dim[dim] = models
            if not models:
                log.warning(
                    "dim %d ℓ0: no finite models out of %d evaluated — "
                    "SissoFit.best(%d) will raise; check bounds/validity "
                    "rules and the SIS subspace",
                    dim, res.n_evaluated, dim,
                )
            log.info(
                "dim %d ℓ0: %d models evaluated, best objective %.6g",
                dim, res.n_evaluated, res.sses[0],
            )

        stats: Dict[str, dict] = {}
        # resilience accounting (reads through the DebugBackend proxy's
        # __getattr__ when the sanitizer wraps the resilient wrapper)
        fault_stats = getattr(self.engine.backend, "fault_stats", None)
        if fault_stats is not None:
            stats["resilience"] = dict(fault_stats)
        return SissoFit(models_by_dim=models_by_dim, fspace=fspace,
                        timings={}, problem=problem.kind, stats=stats)


class SissoRegressor(SissoSolver):
    """Deprecated alias of :class:`SissoSolver`.

    The name now belongs to the sklearn-convention estimator
    :class:`repro.api.SissoRegressor` (``(n_samples, n_features)`` inputs,
    ``predict``/``transform``/``save``); this shim keeps old array-major
    call sites working.
    """

    def __init__(self, config: SissoConfig, engine=None):
        warnings.warn(
            "repro.core.SissoRegressor is deprecated: use "
            "repro.api.SissoRegressor (sklearn-style estimator) or "
            "repro.core.SissoSolver (array-major core driver)",
            DeprecationWarning, stacklevel=2,
        )
        super().__init__(config, engine=engine)
