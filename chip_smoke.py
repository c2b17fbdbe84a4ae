"""Smoke test of the SISSO fit and serve path on a TPU.

    python chip_smoke.py             # one chip: device, thermal, kaggle, serve
    python chip_smoke.py --chips 4   # four chips: thermal on sharded:pallas
                                     # against the one-device pallas fit

Run from the root of a checkout.  It drives the public entry points
(``repro.api.SissoRegressor.from_config``, ``repro.serve.ServingTier``) at
the paper cases' published widths, cut only in depth, and checks what comes
out against the ``reference`` backend on the host and the ``jnp`` backend
on the chip.

Each phase prints one JSON line: its name, wall seconds (compilation
included), its cuts of the published configuration (``reduced``) and its
checks.  The first phase with a failed check ends the run with a non-zero
exit code.  When every phase passed, the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
There is no fallback: on a machine whose JAX finds no TPU the device phase
fails, and no kernel runs in interpret mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.api import SissoRegressor, load_artifact  # noqa: E402
from repro.configs.sisso_kaggle import kaggle_bandgap_case  # noqa: E402
from repro.configs.sisso_thermal import thermal_conductivity_case  # noqa: E402
from repro.core.descriptor import eval_program_host  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.runtime import FaultPlan, faults  # noqa: E402

#: depth cuts of the published configurations.  Thermal keeps its width-3
#: subspace (3 x 200 features, 2 tasks) under the Gram-gather VMEM budget,
#: so ℓ0 at width 3 runs the kernel; Kaggle stops at rung 1 because the
#: fp64 host store of its rung 2 would not fit.
THERMAL_CUT = {"max_rung": 2, "n_sis": 200, "n_dim": 3}
KAGGLE_CUT = {"max_rung": 1, "n_sis": 300, "n_dim": 2}
#: deepest thermal fit compared with the host reference backend (about a
#: minute on one host core); dimension 3 is compared with jnp on the chip
THERMAL_REFERENCE_DIM = 2
#: ℓ0 SSE agreement of fp64 fits, as in the backend parity suite
SSE_RTOL, SSE_ATOL = 1e-6, 1e-8
#: an fp32 fit's ℓ0 SSEs are differences of centered moments of the size
#: of the target's centered sum of squares, so they agree with fp64 to a
#: multiple of fp32 rounding of that sum: within 1e-5 of it (the Kaggle
#: cut differs by 1.2e-6 of it on a CPU)
FP32_SSE_SCALE = 1e-5
#: largest residual of a unit feature column projected on the other
#: descriptor's span that still counts as lying in it (fp64 values)
SPAN_TOL = 1e-9
#: request sizes the serve phase sends (rows)
SERVE_SIZES = (1, 3, 8, 17, 64)


def emit(phase: str, t0: float, checks: dict, **info) -> None:
    """Print the phase's JSON line; exit non-zero if a check failed."""
    ok = all(checks.values())
    print(json.dumps({"phase": phase, "ok": ok,
                      "seconds": time.perf_counter() - t0,
                      **info, "checks": checks}), flush=True)
    if not ok:
        failed = sorted(k for k, v in checks.items() if not v)
        sys.exit(f"chip smoke: phase {phase!r} failed: {failed}")


def cuts(published, cut: dict) -> dict:
    return {k: [getattr(published, k), v] for k, v in cut.items()
            if getattr(published, k) != v}


def fit(case, cut: dict, backend: str):
    """Fit ``case`` through the estimator; returns (estimator, seconds,
    fused-SIS kernel calls counted at the ops layer's fault site)."""
    cfg = dataclasses.replace(case.config, backend=backend, **cut)
    est = SissoRegressor.from_config(cfg)
    plan = faults.install(FaultPlan())   # counts site hits, injects nothing
    t0 = time.perf_counter()
    try:
        est.fit(case.x.T, case.y, names=case.names, units=case.units,
                tasks=case.task_ids)
    finally:
        faults.install(None)
    return est, time.perf_counter() - t0, plan.occurrences("kernel.sis")


def l0_paths(est) -> dict:
    """{width: {ℓ0 path: blocks}} as the backend recorded them."""
    return est.fit_result_.stats["l0_paths"]


def models(est, case, dims) -> dict:
    """dim -> [(exprs, SSE, descriptor values (n, S))] for every kept
    model, best first."""
    return {d: [(list(m.exprs), m.sse, eval_program_host(m.program, case.x))
                for m in est.models_by_dim[d]]
            for d in dims}


def same_descriptor(u: np.ndarray, v: np.ndarray) -> bool:
    """Same linear model: the features of ``u`` and of ``v`` (rows of
    values), each with an intercept, span the same column space.

    Values, not expression strings.  The SSE is a function of that span
    alone, so descriptors that share it tie exactly, and which of them a
    ranking lists first is rounding: ``sqrt((f2)^3)`` and ``(sqrt(f2))^3``
    are one feature, and ``{x + ecn_al, a1 + ecn_al}``,
    ``{x + ecn_al, a1 - x}`` and ``{a1 - x, a1 + ecn_al}`` are one model."""
    if len(u) != len(v):
        return False
    ones = np.ones((u.shape[1], 1))
    a = np.hstack([ones, np.asarray(u, np.float64).T])
    b = np.hstack([ones, np.asarray(v, np.float64).T])
    a, b = a / np.linalg.norm(a, axis=0), b / np.linalg.norm(b, axis=0)
    return all(np.linalg.norm(q - p @ np.linalg.lstsq(p, q, rcond=None)[0],
                              axis=0).max() <= SPAN_TOL
               for p, q in ((a, b), (b, a)))


def agree(a: dict, b: dict, rtol=SSE_RTOL, atol=SSE_ATOL) -> bool:
    """Same models in the same order, SSEs within the bounds."""
    for d in a:
        if len(a[d]) != len(b[d]) or not all(
                same_descriptor(u, v)
                for (_, _, u), (_, _, v) in zip(a[d], b[d])):
            return False
        if not np.allclose([m[1] for m in a[d]], [m[1] for m in b[d]],
                           rtol=rtol, atol=atol):
            return False
    return True


def best(found: dict) -> dict:
    return {str(d): {"descriptor": ms[0][0], "sse": ms[0][1]}
            for d, ms in found.items()}


def ranking(found: dict) -> dict:
    return {str(d): [[m[0], m[1]] for m in ms] for d, ms in found.items()}


def r2(est, case) -> float:
    return est.score(case.x.T, case.y, tasks=case.task_ids)


def phase_device(chips: int) -> dict:
    import jax
    from repro.engine import PallasBackend

    t0 = time.perf_counter()
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    emit("device", t0, {
        "platform_is_tpu": device["platform"] == "tpu",
        "kernels_compiled": PallasBackend().resolved_interpret is False,
        "enough_chips": device["count"] >= chips,
    }, reduced={}, device=device, compile_cache=cache_dir)
    return device


def phase_thermal():
    t0 = time.perf_counter()
    case = thermal_conductivity_case(reduced=False)
    dims = range(1, THERMAL_CUT["n_dim"] + 1)
    ref_cut = dict(THERMAL_CUT, n_dim=THERMAL_REFERENCE_DIM)
    ref_dims = range(1, THERMAL_REFERENCE_DIM + 1)
    est, t_pallas, sis_calls = fit(case, THERMAL_CUT, "pallas")
    ref, t_ref, _ = fit(case, ref_cut, "reference")
    jnp_est, t_jnp, _ = fit(case, THERMAL_CUT, "jnp")
    found = models(est, case, dims)
    score = r2(est, case)
    paths = l0_paths(est)
    emit("thermal", t0, {
        "planted_dim2_found":
            set(found[2][0][0]) == {"(f0 * f4)", "(f2)^2"},
        "r2_at_least_0.9999": score >= 0.9999,
        "matches_reference_dims_1_2":
            agree({d: found[d] for d in ref_dims}, models(ref, case, ref_dims)),
        "matches_jnp_dims_1_3": agree(found, models(jnp_est, case, dims)),
        "fused_sis_ran": sis_calls > 0,
        "gram_gather_ran": paths.get(3, {}).get("Gram-gather kernel", 0) > 0,
    }, reduced=cuts(case.config, THERMAL_CUT), r2=score, best=best(found),
        l0_paths=paths, fused_sis_calls=sis_calls,
        fit_seconds={"pallas": t_pallas, "reference": t_ref, "jnp": t_jnp})
    return est, case


def phase_kaggle() -> None:
    t0 = time.perf_counter()
    case = kaggle_bandgap_case(reduced=False)
    dims = range(1, KAGGLE_CUT["n_dim"] + 1)
    est, t_pallas, sis_calls = fit(case, KAGGLE_CUT, "pallas")
    ref, t_ref, _ = fit(case, KAGGLE_CUT, "reference")
    found = models(est, case, dims)
    ref_found = models(ref, case, dims)
    score = r2(est, case)
    # the configuration runs fp32: SSEs agree to fp32 rounding of the
    # target's centered sum of squares
    y = np.asarray(case.y, np.float64)
    syy = sum(float(np.sum((y[case.task_ids == t]
                            - y[case.task_ids == t].mean()) ** 2))
              for t in np.unique(case.task_ids))
    atol = FP32_SSE_SCALE * syy
    emit("kaggle", t0, {
        "planted_dim2_found":
            set(found[2][0][0]) == {"(x / a1)", "sqrt(ecn_al)"},
        "r2_at_least_0.999": score >= 0.999,
        "matches_reference": agree(found, ref_found, rtol=0.0, atol=atol),
    }, reduced=cuts(case.config, KAGGLE_CUT), r2=score,
        ranking={"pallas": ranking(found), "reference": ranking(ref_found)},
        sse_atol=atol, precision=case.config.precision,
        l0_paths=l0_paths(est), fused_sis_calls=sis_calls,
        fit_seconds={"pallas": t_pallas, "reference": t_ref})


def phase_serve(est, case) -> None:
    from repro.serve import ServingTier

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmp:
        fitted = load_artifact(est.save(os.path.join(tmp, "thermal.json")))
    rng = np.random.default_rng(0)
    requests = []
    for n in SERVE_SIZES:
        rows = rng.integers(0, len(case.y), n)
        requests.append((case.x.T[rows], case.task_ids[rows]))
    tier = ServingTier(n_replicas=1, default_slo=300.0)
    try:
        tier.register("thermal", fitted)
        pending = [tier.submit("thermal", x, t) for x, t in requests]
        responses = [p.result(timeout=300.0) for p in pending]
    finally:
        tier.close()
    statuses = [r.status for r in responses]
    emit("serve", t0, {
        "all_ok": all(r.ok for r in responses),
        "equal_to_predict": all(
            r.ok and np.array_equal(r.y, est.predict(x, tasks=t))
            for r, (x, t) in zip(responses, requests)),
    }, reduced={}, request_rows=list(SERVE_SIZES), statuses=statuses)


def phase_sharded() -> None:
    """thermal on ``sharded:pallas`` over every chip vs one-device pallas."""
    import jax

    t0 = time.perf_counter()
    case = thermal_conductivity_case(reduced=False)
    dims = range(1, THERMAL_CUT["n_dim"] + 1)
    one, t_one, _ = fit(case, THERMAL_CUT, "pallas")
    # sharded:pallas calls the fused-SIS kernel inside shard_map, past the
    # ops layer's fault site, so its kernel calls are not counted here
    sharded, t_sharded, _ = fit(case, THERMAL_CUT, "sharded:pallas")
    found = models(sharded, case, dims)
    memory = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        memory[str(d.id)] = {k: stats.get(k) for k in
                             ("bytes_in_use", "peak_bytes_in_use")}
    emit("sharded", t0, {
        "matches_one_device_pallas": agree(found, models(one, case, dims)),
        "planted_dim2_found":
            set(found[2][0][0]) == {"(f0 * f4)", "(f2)^2"},
        "every_device_used": all(
            (m["peak_bytes_in_use"] or 0) > 0 for m in memory.values()),
    }, reduced=cuts(case.config, THERMAL_CUT), best=best(found),
        r2=r2(sharded, case), device_memory=memory,
        l0_paths=l0_paths(sharded),
        fit_seconds={"pallas": t_one, "sharded:pallas": t_sharded})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded:pallas phase over four "
                         "chips and the one-device fit it is compared with")
    args = ap.parse_args(argv)
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_sharded()
    else:
        est, case = phase_thermal()
        phase_kaggle()
        phase_serve(est, case)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
