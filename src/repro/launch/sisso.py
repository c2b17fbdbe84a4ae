"""SISSO launcher: run a test case end-to-end with a restartable journal.

    PYTHONPATH=src python -m repro.launch.sisso --case thermal [--full] \
        [--problem regression|classification] \
        [--backend reference|jnp|pallas|sharded|sharded:pallas] \
        [--l0-method gram|qr] \
        [--journal /tmp/l0.json] [--save /tmp/model.json]

Fits through the canonical :mod:`repro.api` estimator, so the reported r²
comes from the *compiled descriptor* ``predict`` path (the one serving
uses), and ``--save`` writes a versioned artifact that
``repro.launch.serve_sisso`` can load on another machine.

``--problem classification`` runs the domain-overlap classification
problem (core/problem.py) on a synthetic separable case
(``repro.data.classification_dataset``; the named ``--case`` datasets
are regression tables) through :class:`repro.api.SissoClassifier` —
same backends, same artifact pipeline, accuracy instead of r².

The work journal is owned by the solver (cleared after each dimension's
sweep completes); this launcher only creates it.
"""
from __future__ import annotations

import argparse
import dataclasses
import warnings

import numpy as np

from ..api import SissoClassifier, SissoRegressor
from ..configs.sisso_kaggle import kaggle_bandgap_case
from ..configs.sisso_thermal import thermal_conductivity_case
from ..data import classification_dataset
from ..runtime import WorkJournal
from .compile_cache import enable_compile_cache


def _run_classification(args) -> None:
    x, labels, names = classification_dataset(n_samples=160)
    n_train = 120
    X = x.T
    clf = SissoClassifier(
        max_rung=1, n_dim=2, n_sis=10, n_residual=5,
        op_names=("add", "sub", "mul", "div"),
        backend=args.backend or "jnp", l0_method=args.l0_method,
    )
    journal = WorkJournal(args.journal) if args.journal else None
    clf.fit(X[:n_train], labels[:n_train], names=names, journal=journal)
    best = clf.model()
    print(best)
    acc_train = clf.score(X[:n_train], labels[:n_train])
    acc_test = clf.score(X[n_train:], labels[n_train:])
    print(f"[sisso] classify: backend={clf.backend} "
          f"train_acc={acc_train:.4f} test_acc={acc_test:.4f} "
          f"dim={best.dim} n_overlap={best.n_overlap}")
    print(f"[sisso] phases: {clf.fitted_.timings}")
    if args.save:
        print(f"[sisso] artifact -> {clf.save(args.save)}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", default="thermal", choices=("thermal", "kaggle"))
    ap.add_argument("--problem", default="regression",
                    choices=("regression", "classification"),
                    help="objective (core/problem.py); classification "
                         "fits the synthetic separable case")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--backend", default=None,
                    choices=("reference", "jnp", "pallas", "sharded",
                             "sharded:jnp", "sharded:pallas",
                             "sharded:reference"),
                    help="execution engine for all phases incl. predict; "
                         "'sharded:<inner>' composes the distribution "
                         "wrapper over the named inner backend")
    ap.add_argument("--l0-method", "--engine", dest="l0_method",
                    default="gram", choices=("gram", "qr"),
                    help="l0 math: Gram closed form or paper-faithful QR "
                         "(--engine is the deprecated spelling)")
    ap.add_argument("--kernels", action="store_true",
                    help="deprecated alias for --backend pallas")
    ap.add_argument("--journal", default=None,
                    help="work-journal path (restartable ℓ0 sweeps)")
    ap.add_argument("--save", default=None,
                    help="write the fitted model artifact (JSON) here")
    args = ap.parse_args()
    enable_compile_cache()

    if args.problem == "classification":
        if args.kernels:
            warnings.warn("--kernels is deprecated; use --backend pallas",
                          DeprecationWarning, stacklevel=2)
            args.backend = args.backend or "pallas"
        _run_classification(args)
        return

    case = (thermal_conductivity_case if args.case == "thermal"
            else kaggle_bandgap_case)(reduced=not args.full)

    cfg = case.config
    backend = args.backend or cfg.backend
    if args.kernels:
        warnings.warn("--kernels is deprecated; use --backend pallas",
                      DeprecationWarning, stacklevel=2)
        backend = args.backend or "pallas"
    cfg = dataclasses.replace(cfg, l0_method=args.l0_method, backend=backend)

    journal = WorkJournal(args.journal) if args.journal else None
    est = SissoRegressor.from_config(cfg)
    est.fit(case.x.T, case.y, names=case.names, units=case.units,
            tasks=case.task_ids, journal=journal)
    best = est.model()
    print(best)
    pred = est.predict(case.x.T, tasks=case.task_ids)
    r2 = est.score(case.x.T, case.y, tasks=case.task_ids)
    rmse = float(np.sqrt(np.mean((case.y - pred) ** 2)))
    print(f"[sisso] {case.name}: backend={backend} r2={r2:.6f} "
          f"rmse={rmse:.4g} dim={best.dim}")
    print(f"[sisso] phases: {est.fitted_.timings}")
    if args.save:
        print(f"[sisso] artifact -> {est.save(args.save)}")


if __name__ == "__main__":
    main()
