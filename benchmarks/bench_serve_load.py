"""Serving-tier load harness: synthetic traffic end-to-end.

Drives a 2-replica :class:`repro.serve.ServingTier` holding two resident
models with a mixed Poisson + bursty request trace (repro/serve/traffic.py),
performs one **mid-load hot-swap** of a model, and prints latency
percentiles, throughput, batch occupancy and per-status request
accounting.

Hard invariants asserted on every run (the serving tier's contract, not
just numbers): zero ``status="error"`` responses across the run — in
particular across the hot-swap — and no formed batch ever exceeding the
configured row budget.

    PYTHONPATH=src:. python -m benchmarks.bench_serve_load [--smoke]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.api import SissoRegressor
from repro.serve import (
    STATUS_ERROR, STATUS_OK, ServingTier, bursty_trace, merge_traces,
    poisson_trace,
)

#: primary-feature count shared by both synthetic models
N_FEATURES = 5


def _fit(target_fn, seed: int) -> "SissoRegressor":
    rng = np.random.default_rng(seed)
    X = rng.uniform(0.5, 3.0, size=(120, N_FEATURES))
    est = SissoRegressor(
        max_rung=1, n_dim=1, n_sis=10,
        op_names=("add", "sub", "mul", "sq"),
    )
    return est.fit(X, target_fn(X))


def _drive(tier: ServingTier, events, swap_at: int, swap_fn, rng):
    """Open-loop replay: submit each event at its trace time.

    ``swap_fn`` runs once, after ``swap_at`` submissions — the mid-load
    hot-swap whose in-flight requests must all still succeed.
    """
    pending = []
    t_start = time.monotonic()
    swapped = False
    for i, ev in enumerate(events):
        if not swapped and i >= swap_at:
            swap_fn()
            swapped = True
        lag = ev.t - (time.monotonic() - t_start)
        if lag > 0:
            time.sleep(lag)
        x = rng.uniform(0.5, 3.0, size=(ev.rows, N_FEATURES))
        pending.append((ev, tier.submit(ev.model_id, x, slo=2.0)))
    return [(ev, p.result(timeout=30.0)) for ev, p in pending]


def emit(name: str, value: float, derived: str = "") -> None:
    print(f"{name},{value:.1f},{derived}")


def main(quick: bool = False) -> None:
    rng = np.random.default_rng(7)

    alpha = _fit(lambda X: 2.5 * X[:, 0] * X[:, 1] + 0.7, seed=1)
    beta = _fit(lambda X: -1.3 * X[:, 2] ** 2 + 4.0, seed=2)
    # the re-fit swapped in mid-load: same request surface, new program
    alpha_v2 = _fit(lambda X: 0.5 * X[:, 0] + 3.0 * X[:, 3], seed=3)

    budget = 64
    horizon = 1.5 if quick else 5.0
    rate = 120.0 if quick else 200.0
    burst_rate = 400.0 if quick else 700.0

    trace_rng = np.random.default_rng(11)
    ids = ("alpha", "beta")
    events = merge_traces(
        poisson_trace(rate, horizon, ids, trace_rng, mean_rows=4, max_rows=24),
        bursty_trace(burst_rate, burst_len=0.15, idle=0.35, horizon=horizon,
                     model_ids=ids, rng=trace_rng, mean_rows=4, max_rows=24),
    )

    tier = ServingTier(n_replicas=2, row_budget=budget,
                       max_queued_rows=64 * budget, default_slo=2.0)
    tier.register("alpha", alpha.fitted_)
    tier.register("beta", beta.fitted_)

    swap_at = len(events) // 2
    t0 = time.perf_counter()
    results = _drive(
        tier, events, swap_at,
        swap_fn=lambda: tier.register("alpha", alpha_v2.fitted_), rng=rng,
    )
    # responses are host arrays; blocking on the last one keeps the timed
    # span honest about any straggling device work (RL002)
    jax.block_until_ready(results[-1][1].y if results[-1][1].ok else None)
    wall = time.perf_counter() - t0

    by_status = {}
    for _, resp in results:
        by_status[resp.status] = by_status.get(resp.status, 0) + 1
    ok = [(ev, r) for ev, r in results if r.status == STATUS_OK]
    lat = np.asarray([r.latency for _, r in ok])
    rows_ok = sum(ev.rows for ev, _ in ok)
    stats = tier.stats()
    tier.close()

    # contract, not just numbers: a hot-swap must fail nothing, and the
    # row budget is a hard cap on every formed batch
    n_errors = by_status.get(STATUS_ERROR, 0)
    assert n_errors == 0, f"{n_errors} failed requests (statuses {by_status})"
    max_batch = max(rep["max_batch_rows"] for rep in stats["replicas"])
    assert max_batch <= budget, \
        f"batch of {max_batch} rows exceeded the {budget}-row budget"
    versions = stats["models"]["alpha"]["by_version"]
    assert sorted(versions) == [1, 2], \
        f"hot-swap never split traffic across versions: {versions}"

    emit("serve_load_requests", len(results),
         f"statuses={by_status} over {horizon:.1f}s trace")
    emit("serve_load_p50_ms", float(np.quantile(lat, 0.50) * 1e3),
         f"{len(ok)} ok requests, 2 replicas, budget {budget}")
    emit("serve_load_p99_ms", float(np.quantile(lat, 0.99) * 1e3),
         f"p90={np.quantile(lat, 0.90) * 1e3:.3f} ms")
    emit("serve_load_throughput", rows_ok / max(wall, 1e-9),
         "rows/s sustained (Poisson + bursty mix)")
    emit("serve_load_swap_versions",
         float(versions.get(2, 0)),
         f"alpha requests on v2 after mid-load swap "
         f"(v1={versions.get(1, 0)}); zero failures")
    emit("serve_load_max_batch_rows", float(max_batch),
         f"row budget {budget} never exceeded")
    occ = [rep["batch_occupancy_mean"] for rep in stats["replicas"]]
    emit("serve_load_occupancy", float(np.mean(occ)),
         f"per-replica mean batch fill {[round(o, 3) for o in occ]}")
    evict = sum(rep["jit_cache"]["evictions"] for rep in stats["replicas"])
    emit("serve_load_jit_evictions", float(evict),
         f"bounded bucket caches: "
         f"{[rep['jit_cache']['resident'] for rep in stats['replicas']]} "
         f"resident")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short trace (CI: 2 replicas, Poisson + bursty, "
                         "one mid-load hot-swap)")
    main(quick=ap.parse_args().smoke)
