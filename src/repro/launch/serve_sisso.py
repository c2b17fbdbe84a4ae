"""Descriptor-serving launcher: resident artifacts behind the serving tier.

    # single artifact (unchanged invocation)
    PYTHONPATH=src python -m repro.launch.serve_sisso \
        --artifact /tmp/model.json [--batches 16] [--batch-size 32] \
        [--backend jnp] [--dim 2] [--vary-batch]

    # multi-model routing, replicas, row budget
    PYTHONPATH=src python -m repro.launch.serve_sisso \
        --artifact alpha=/tmp/a.json --artifact beta=/tmp/b.json \
        --replicas 2 --budget 128

Loads one or more saved artifacts (``repro.launch.sisso --save`` /
``SissoRegressor.save``) into a :class:`repro.serve.ModelRegistry`,
stands up a :class:`repro.serve.ServingTier` (``--replicas`` worker
replicas, each with its own bounded jit cache; ``--budget`` rows per
formed batch) and drives it with synthetic request batches routed by
model id — a throughput exercise, not a physics one.  Reports cold
latency, warm p50/p99, throughput, and the tier's stats snapshot.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Tuple

import numpy as np

from ..api import load_artifact
from ..serve import ServingTier
from .compile_cache import enable_compile_cache


def parse_artifact_specs(specs: List[str]) -> List[Tuple[str, str]]:
    """``["alpha=/p/a.json", "/p/b.json"]`` -> [(id, path), ...].

    A bare path (no ``=``) keeps the legacy single-artifact spelling and
    gets the id ``default``.  Ids must be unique.
    """
    out: List[Tuple[str, str]] = []
    for spec in specs:
        if "=" in spec:
            model_id, path = spec.split("=", 1)
            model_id = model_id.strip()
            if not model_id or not path:
                raise ValueError(f"--artifact {spec!r}: expected id=path")
        else:
            model_id, path = "default", spec
        if model_id in {m for m, _ in out}:
            raise ValueError(f"--artifact: duplicate model id {model_id!r}")
        out.append((model_id, path))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--artifact", required=True, action="append",
                    help="saved model JSON: 'path' (served as id "
                         "'default') or 'id=path'; repeat to serve "
                         "several models routed by id")
    ap.add_argument("--batches", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--dim", type=int, default=None)
    ap.add_argument("--backend", default=None,
                    choices=(None, "reference", "jnp", "pallas", "sharded",
                             "sharded:jnp", "sharded:pallas"))
    ap.add_argument("--replicas", type=int, default=1,
                    help="worker replicas, each owning a bounded jit cache")
    ap.add_argument("--budget", type=int, default=256,
                    help="row budget per formed batch (admission rejects "
                         "oversize requests)")
    ap.add_argument("--vary-batch", action="store_true",
                    help="randomize batch sizes to exercise shape bucketing")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    artifacts = parse_artifact_specs(args.artifact)
    tier = ServingTier(n_replicas=args.replicas, row_budget=args.budget,
                       backend=args.backend, default_slo=30.0)
    fitted_by_id = {}
    for model_id, path in artifacts:
        fitted = load_artifact(path)
        resident = tier.register(model_id, fitted, dim=args.dim)
        fitted_by_id[model_id] = fitted
        print(f"[serve_sisso] {model_id}: {len(fitted.names)} features, "
              f"{fitted.n_tasks} task(s), lib {fitted.library_version}")
        print(f"[serve_sisso] {model_id} v{resident.version} "
              f"dim={resident.dim}: {' ; '.join(resident.mdl.exprs)}")
    print(f"[serve_sisso] tier: {args.replicas} replica(s), "
          f"row budget {args.budget}, "
          f"models {sorted(fitted_by_id)}")

    rng = np.random.default_rng(args.seed)

    def make_batch(model_id, b):
        fitted = fitted_by_id[model_id]
        x = rng.uniform(0.5, 5.0, size=(b, fitted.n_features_in))
        tasks = (rng.choice(fitted.task_labels, size=b)
                 if fitted.n_tasks > 1 else None)
        return x, tasks

    ids = sorted(fitted_by_id)
    # cold request per model: includes program-compile for its bucket
    for model_id in ids:
        x, tasks = make_batch(model_id, args.batch_size)
        t0 = time.perf_counter()
        tier.predict(model_id, x, tasks)
        cold = time.perf_counter() - t0
        print(f"[serve_sisso] {model_id} cold first batch: "
              f"{cold * 1e3:.2f} ms")

    lat = []
    total = 0
    t_warm = time.perf_counter()
    for i in range(args.batches):
        model_id = ids[i % len(ids)]     # route round-robin across models
        b = (int(rng.integers(1, args.batch_size + 1)) if args.vary_batch
             else args.batch_size)
        x, tasks = make_batch(model_id, b)
        t0 = time.perf_counter()
        tier.predict(model_id, x, tasks)
        lat.append(time.perf_counter() - t0)
        total += b
    wall = time.perf_counter() - t_warm

    lat = np.asarray(lat)
    print(f"[serve_sisso] {args.batches} warm batches, {total} samples: "
          f"p50={np.median(lat) * 1e3:.3f} ms  "
          f"p99={np.quantile(lat, 0.99) * 1e3:.3f} ms  "
          f"{total / max(wall, 1e-9):.0f} samples/s")
    stats = tier.stats()
    print(f"[serve_sisso] scheduler: {stats['scheduler']}")
    for rep in stats["replicas"]:
        print(f"[serve_sisso] replica {rep['replica']}: "
              f"batches={rep['batches']} rows={rep['rows']} "
              f"occupancy={rep['batch_occupancy_mean']:.2f} "
              f"jit_cache={rep['jit_cache']}")
    print(f"[serve_sisso] models: {stats['models']}")
    tier.close()


if __name__ == "__main__":
    main()
