"""sklearn-convention SISSO estimators — the canonical user-facing surface.

One shared base (:class:`_BaseSisso`) owns the estimator plumbing —
parameter handling, task encoding, the core-solver handoff, descriptor
compilation and artifact persistence — and one subclass per *problem*
(core/problem.py) owns the target encoding and the prediction surface:

* :class:`SissoRegressor` — continuous targets, SSE objective,
  ``predict`` returns values, ``score`` is r².
* :class:`SissoClassifier` — categorical targets, domain-overlap
  objective with an LDA separating refit; ``predict`` returns labels,
  ``predict_proba`` softmax class probabilities over the per-task
  discriminants, ``score`` is accuracy.

``fit(X, y)`` takes ``(n_samples, n_features)`` tabular input (transposed
internally to the core's ``(P, S)`` value-matrix layout), learns the usual
SISSO model ladder, then *compiles* every selected descriptor's lineage DAG
into a standalone evaluation program (core/descriptor.py) validated exactly
against the training value matrix — which is what makes ``predict`` on
unseen samples possible at all.  ``get_params``/``set_params`` follow the
scikit-learn contract (``sklearn.base.clone`` works without importing
sklearn here), ``transform`` exposes descriptor values in the
``FunctionTransformer`` role pysisso calls ``SISTransformer``, and
``save``/``load_artifact`` round-trip a fitted model through a versioned
JSON artifact (api/artifact.py) without the training data.

    from repro.api import SissoRegressor, SissoClassifier

    est = SissoRegressor(max_rung=1, n_dim=2, n_sis=20)
    est.fit(X_train, y_train, names=["radius", "charge", ...])
    y_hat = est.predict(X_test)          # compiled descriptor, any backend

    clf = SissoClassifier(max_rung=1, n_dim=2, n_sis=20)
    clf.fit(X_train, labels_train, names=[...])
    clf.predict(X_test); clf.predict_proba(X_test)
    clf.save("phases.json")              # versioned, data-free artifact
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Optional, Sequence

import numpy as np

from ..core.descriptor import compile_features
from ..core.solver import SissoConfig, SissoSolver
from ..core.units import Unit
from ..runtime import trace
from .artifact import DescriptorModel, FittedSisso, _py

try:  # optional: inherit sklearn's estimator plumbing (tags, HTML repr)
    from sklearn.base import BaseEstimator as _SkBase
    from sklearn.base import ClassifierMixin as _SkClassifier
    from sklearn.base import RegressorMixin as _SkRegressor
except ImportError:  # sklearn absent: the manual contract below suffices
    _SkBase = object

    class _SkRegressor:  # type: ignore[no-redef]
        pass

    class _SkClassifier:  # type: ignore[no-redef]
        pass


#: largest difference, relative to a descriptor feature's largest training
#: value, between its compiled-program values and its training values
_REPLAY_RTOL = 1e-12


class NotFittedError(RuntimeError):
    """Raised when predict/transform/score is called before fit."""


class _BaseSisso(_SkBase):
    """Shared estimator plumbing; subclasses fix the problem kind.

    Constructor parameters mirror :class:`repro.core.SissoConfig` one-to-one
    (minus ``problem``, which the subclass owns) and are stored verbatim
    (the sklearn contract: no logic in ``__init__``, so ``clone`` and
    grid-search parameter sweeps behave).
    """

    #: problem kind this estimator class drives (core/problem.py)
    _problem = "regression"

    def __init__(
        self,
        max_rung: int = 2,
        n_dim: int = 2,
        n_sis: int = 50,
        n_residual: int = 10,
        l_bound: float = 1e-5,
        u_bound: float = 1e8,
        op_names: Sequence[str] = ("add", "sub", "mul", "div", "sq", "sqrt", "inv"),
        on_the_fly_last_rung: bool = False,
        l0_block: int = 65536,
        sis_batch: int = 1 << 16,
        l0_method: str = "gram",
        backend: str = "jnp",
        precision: str = "fp64",
        max_pairs_per_op: Optional[int] = None,
        seed: int = 0,
        debug_checks: Optional[bool] = None,
        resilient: bool = False,
    ):
        self.max_rung = max_rung
        self.n_dim = n_dim
        self.n_sis = n_sis
        self.n_residual = n_residual
        self.l_bound = l_bound
        self.u_bound = u_bound
        self.op_names = op_names
        self.on_the_fly_last_rung = on_the_fly_last_rung
        self.l0_block = l0_block
        self.sis_batch = sis_batch
        self.l0_method = l0_method
        self.backend = backend
        self.precision = precision
        self.max_pairs_per_op = max_pairs_per_op
        self.seed = seed
        # runtime contract sanitizer (repro.debug); None defers to the
        # REPRO_DEBUG environment variable
        self.debug_checks = debug_checks
        # fault-tolerance wrapper (engine/resilient.py): retry transient
        # device errors, demote persistent kernel failures per-op
        self.resilient = resilient

    # ------------------------------------------------------------------
    # sklearn parameter plumbing
    # ------------------------------------------------------------------
    @classmethod
    def _get_param_names(cls):
        sig = inspect.signature(cls.__init__)
        return sorted(p for p in sig.parameters if p != "self")

    def get_params(self, deep: bool = True) -> dict:
        return {name: getattr(self, name) for name in self._get_param_names()}

    def set_params(self, **params) -> "_BaseSisso":
        valid = set(self._get_param_names())
        for name, value in params.items():
            if name not in valid:
                raise ValueError(
                    f"invalid parameter {name!r} for {type(self).__name__}; "
                    f"valid: {sorted(valid)}"
                )
            setattr(self, name, value)
        return self

    @classmethod
    def from_config(cls, config: SissoConfig) -> "_BaseSisso":
        """Build an estimator from a core :class:`SissoConfig`."""
        names = set(cls._get_param_names())
        return cls(**{
            f.name: getattr(config, f.name)
            for f in dataclasses.fields(config) if f.name in names
        })

    def _config(self) -> SissoConfig:
        return SissoConfig(problem=self._problem, **{
            name: getattr(self, name) for name in self._get_param_names()
        })

    # ------------------------------------------------------------------
    # target encoding (the problem-specific half of fit)
    # ------------------------------------------------------------------
    def _encode_target(self, y: np.ndarray):
        """(core-facing y (S,) float, class labels or None)."""
        return np.asarray(y, np.float64), None

    # ------------------------------------------------------------------
    # fit
    # ------------------------------------------------------------------
    def fit(
        self,
        X,                      # (n_samples, n_features)
        y,                      # (n_samples,) targets / class labels
        *,
        names: Optional[Sequence[str]] = None,
        units: Optional[Sequence[Unit]] = None,
        tasks=None,             # (n_samples,) task labels, any hashables
        journal=None,
    ) -> "_BaseSisso":
        # the whole fit is one record (runtime/trace.py): the solver joins
        # it, and its timings and stats are final once it closes
        with trace.collecting() as rec:
            self._fit(X, y, names, units, tasks, journal)
        rec.report(self.fit_result_)
        return self

    def _fit(self, X, y, names, units, tasks, journal) -> None:
        X = np.asarray(X, np.float64)
        y = np.asarray(y)
        if X.ndim != 2:
            raise ValueError("X must be (n_samples, n_features)")
        if y.shape != (X.shape[0],):
            raise ValueError("y must be (n_samples,) matching X")
        s, p = X.shape
        names = (
            [f"feat{i}" for i in range(p)] if names is None else list(names)
        )
        if len(names) != p:
            raise ValueError("names must have one entry per X column")

        y_core, class_labels = self._encode_target(y)

        # task labels -> contiguous codes; core wants samples grouped by task
        if tasks is None:
            labels, codes = [0], np.zeros(s, np.intp)
            order = np.arange(s)
        else:
            tasks = np.asarray(tasks)
            if tasks.shape != (s,):
                raise ValueError("tasks must be (n_samples,)")
            uniq, codes = np.unique(tasks, return_inverse=True)
            labels = [_py(u) for u in uniq]
            order = np.argsort(codes, kind="stable")

        xp = np.ascontiguousarray(X[order].T)   # (P, S) core layout
        ys = y_core[order]
        task_ids = codes[order] if len(labels) > 1 else None

        solver = SissoSolver(self._config())
        fit = solver.fit(
            xp, ys, names, units=units, task_ids=task_ids, journal=journal
        )

        # compile every model's descriptor and validate it reproduces the
        # training value matrix (core/descriptor.py contract): exactly on
        # a CPU; a TPU emulates fp64, and its compiled program may round
        # the last bit differently from the per-op training path (7e-15 at
        # values ~50 on a v5e), while a wrong lineage is off by O(1)
        with trace.span("sisso.descriptor"):
            models_by_dim = self._compile_models(fit, solver.engine, xp,
                                                 class_labels)

        self.fitted_ = FittedSisso(
            names=names,
            config=solver.cfg,
            models_by_dim=models_by_dim,
            task_labels=labels,
            units=list(units) if units is not None else None,
            timings=fit.timings,
            class_labels=(
                None if class_labels is None
                else [_py(c) for c in class_labels]
            ),
        )
        self.fit_result_ = fit          # core SissoFit (fspace, raw models)
        self.n_features_in_ = p
        self.feature_names_in_ = np.asarray(names, object)

    def _compile_models(self, fit, engine, xp, class_labels) -> dict:
        """dim -> [DescriptorModel]: every model's descriptor compiled and
        replayed on the training primaries ``xp`` (P, S)."""
        xmat = fit.fspace.values_matrix()
        models_by_dim = {}
        for dim, models in fit.models_by_dim.items():
            compiled = []
            for mdl in models:
                program = compile_features(mdl.features, fit.fspace)
                got = engine.eval_program(program, xp)
                want = xmat[[f.row for f in mdl.features]]
                scale = np.abs(want).max(axis=1, keepdims=True)
                if not np.all(np.abs(got - want) <= _REPLAY_RTOL * scale):
                    raise RuntimeError(
                        f"compiled descriptor diverged from training values "
                        f"for dim-{dim} model {list(program.exprs)} "
                        f"(max |Δ| = {np.abs(got - want).max():g})"
                    )
                compiled.append(self._descriptor_model(
                    mdl, program, class_labels
                ))
            models_by_dim[dim] = compiled
        return models_by_dim

    def _descriptor_model(self, mdl, program, class_labels) -> DescriptorModel:
        """Core model -> serializable compiled model (problem-specific)."""
        return DescriptorModel(
            program=program,
            coefs=np.asarray(mdl.coefs, np.float64),
            intercepts=np.asarray(mdl.intercepts, np.float64),
            sse=float(mdl.sse),
            exprs=tuple(f.expr for f in mdl.features),
            units=tuple(str(f.unit) for f in mdl.features),
            problem=self._problem,
        )

    # ------------------------------------------------------------------
    # fitted surface
    # ------------------------------------------------------------------
    def _fitted(self) -> FittedSisso:
        fitted = getattr(self, "fitted_", None)
        if fitted is None:
            raise NotFittedError(
                f"{type(self).__name__} is not fitted yet; call fit(X, y)"
            )
        return fitted

    @property
    def models_by_dim(self):
        """dim -> [DescriptorModel], best first (compiled, serializable)."""
        return self._fitted().models_by_dim

    def model(self, dim: Optional[int] = None) -> DescriptorModel:
        """Best fitted model of dimension ``dim`` (default: highest)."""
        return self._fitted().model(dim)

    def transform(self, X, *, dim: Optional[int] = None,
                  backend: Optional[str] = None) -> np.ndarray:
        """Descriptor values (n_samples, dim) — the SISTransformer role."""
        return self._fitted().transform(X, dim=dim, backend=backend)

    def save(self, path: str) -> str:
        """Persist the fitted model as a versioned JSON artifact."""
        return self._fitted().save(path)

    @classmethod
    def from_artifact(cls, path: str) -> "_BaseSisso":
        """Reconstruct a fitted estimator from a saved artifact.

        The artifact records its problem kind; loading it into the wrong
        estimator class fails with a clear error rather than silently
        producing the wrong prediction surface.
        """
        fitted = FittedSisso.load(path)
        kind = getattr(fitted.config, "problem", "regression")
        if kind != cls._problem:
            other = ("SissoClassifier" if kind == "classification"
                     else "SissoRegressor")
            raise ValueError(
                f"artifact at {path!r} holds a {kind} model; load it with "
                f"repro.api.{other}.from_artifact (or the problem-agnostic "
                f"repro.api.load_artifact)"
            )
        est = cls.from_config(fitted.config)
        est.fitted_ = fitted
        est.n_features_in_ = fitted.n_features_in
        est.feature_names_in_ = np.asarray(fitted.names, object)
        if kind == "classification":
            est.classes_ = np.asarray(fitted.class_labels)
        return est

    def __repr__(self) -> str:
        params = ", ".join(
            f"{k}={getattr(self, k)!r}" for k in self._get_param_names()
        )
        return f"{type(self).__name__}({params})"


class SissoRegressor(_SkRegressor, _BaseSisso):
    """SISSO regressor with the scikit-learn estimator conventions."""

    _estimator_type = "regressor"
    _problem = "regression"

    def predict(self, X, *, dim: Optional[int] = None, tasks=None,
                backend: Optional[str] = None) -> np.ndarray:
        return self._fitted().predict(X, dim=dim, tasks=tasks, backend=backend)

    def score(self, X, y, *, dim: Optional[int] = None, tasks=None) -> float:
        """Coefficient of determination r² (sklearn regressor convention).

        Multi-task fits center ``y`` **per task** — the null model is the
        per-task mean (one intercept per task), so global centering would
        count the between-task spread in ss_tot and inflate R²; matches
        :meth:`repro.core.SissoModel.r2`.
        """
        y = np.asarray(y, np.float64)
        r = y - self.predict(X, dim=dim, tasks=tasks)
        if tasks is None:
            ss_tot = float(((y - y.mean()) ** 2).sum())
        else:
            ss_tot = sum(
                float(((y[g] - y[g].mean()) ** 2).sum())
                for g in (np.asarray(tasks) == t
                          for t in np.unique(np.asarray(tasks)))
            )
        return 1.0 - float((r * r).sum()) / max(ss_tot, 1e-300)


class SissoClassifier(_SkClassifier, _BaseSisso):
    """SISSO classifier: domain-overlap descriptors + LDA read-out.

    The search minimizes the class-domain overlap of the descriptor space
    (core/problem.py); the fitted surface is the per-task linear
    discriminants of the ℓ0 winners.  ``classes_`` holds the label set in
    sorted order (sklearn classifier convention).
    """

    _estimator_type = "classifier"
    _problem = "classification"

    def _encode_target(self, y):
        classes, codes = np.unique(y, return_inverse=True)
        if len(classes) < 2:
            raise ValueError(
                f"classification needs >= 2 classes, got {classes!r}"
            )
        self.classes_ = classes
        return codes.astype(np.float64), classes

    def _descriptor_model(self, mdl, program, class_labels):
        return DescriptorModel(
            program=program,
            coefs=np.asarray(mdl.coefs, np.float64),        # (T, C, n)
            intercepts=np.asarray(mdl.intercepts, np.float64),  # (T, C)
            sse=float(mdl.score),
            exprs=tuple(f.expr for f in mdl.features),
            units=tuple(str(f.unit) for f in mdl.features),
            problem="classification",
            classes=tuple(_py(c) for c in class_labels),
            n_overlap=int(mdl.n_overlap),
        )

    def decision_function(self, X, *, dim: Optional[int] = None, tasks=None,
                          backend: Optional[str] = None) -> np.ndarray:
        """Per-class discriminant values (n_samples, n_classes)."""
        return self._fitted().decision_function(
            X, dim=dim, tasks=tasks, backend=backend)

    def predict(self, X, *, dim: Optional[int] = None, tasks=None,
                backend: Optional[str] = None) -> np.ndarray:
        """Predicted class labels (n_samples,)."""
        return self._fitted().predict(X, dim=dim, tasks=tasks, backend=backend)

    def predict_proba(self, X, *, dim: Optional[int] = None, tasks=None,
                      backend: Optional[str] = None) -> np.ndarray:
        """Softmax class probabilities (n_samples, n_classes)."""
        return self._fitted().predict_proba(
            X, dim=dim, tasks=tasks, backend=backend)

    def score(self, X, y, *, dim: Optional[int] = None, tasks=None) -> float:
        """Mean accuracy (sklearn classifier convention)."""
        pred = self.predict(X, dim=dim, tasks=tasks)
        return float(np.mean(pred == np.asarray(y)))
