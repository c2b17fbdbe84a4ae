"""Subprocess program: a whole SISSO fit on ``sharded:pallas`` (interpret
mode) over a forced 4-device CPU mesh, rung 2 with the last rung on the
fly, against the plain reference (benchmarks/suite/reference.py).

Runs standalone or under tests/test_distributed.py.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import sys
from pathlib import Path

import jax

jax.config.update("jax_enable_x64", True)
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import _reference_case as case  # noqa: E402
from repro.core import n_models  # noqa: E402


def main() -> int:
    assert jax.device_count() == 4, jax.device_count()
    settings = dict(case.CASE, backend="sharded:pallas")
    d = case.data()
    record, fit_trace = case.fit(settings, d)
    readings = case.readings(settings, d, record.answers)
    kept = {dim: len(s) for dim, s in record.answers.selected.items()}
    print("readings", readings, "kept", kept, flush=True)
    assert kept == {dim: settings["n_sis"]
                    for dim in range(1, settings["n_dim"] + 1)}
    # fp64 values and models; the fp32 kernel scores only order the
    # screen, whose cut lies far from any tie at this size
    assert readings["fc_gap"] <= 1e-12 and readings["sis_gap"] <= 1e-12
    assert readings["l0_gap"] <= 1e-9
    print("sharded:pallas fit (4) == reference models: OK", flush=True)

    stats = fit_trace.stats()
    n_blocks = sum(1 for s in fit_trace.spans if s.name == "sisso.sis.block")
    assert stats["sis_paths"] == {"fused kernel in shard_map": n_blocks}
    l0_blocks = sum(1 for s in fit_trace.spans if s.name == "sisso.l0.block")
    assert stats["sharded"]["devices"] == 4
    assert stats["sharded"]["merges"]["l0"] == l0_blocks
    m = settings["n_dim"] * settings["n_sis"]
    width3 = -(-n_models(m, 3) // settings["l0_block"])
    assert stats["l0_paths"][3] == {"sharded Gram-gather kernel": width3}
    # every tuple in the kernel's panels once, each block counted once
    # (its four shards' runs together)
    rows = stats["l0_rows"][3]
    assert rows["blocks"] == width3 and rows["tuples"] == n_models(m, 3)
    assert rows["lanes"] == 128 * rows["rows"] >= rows["tuples"]
    print("sharded counters (4 devices): OK", stats["sharded"], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
