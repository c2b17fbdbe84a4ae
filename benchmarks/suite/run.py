"""Benchmark of SISSO campaigns on TPU chips: one run of one cell.

    python3 benchmarks/suite/run.py --workload thermal.d3-r1 --seed 1 \
        --seconds 30 --trace 0

Run from the root of a checkout, on a machine whose JAX finds the chips
the cell asks for (``chips`` in ``BENCHMARK.json``); elsewhere it exits
with code 2 and prints no result.  The last line of standard output is one
JSON object: ``correct``, ``attempted`` and ``failed`` fits, the cell's
end-to-end metrics (``--trace 0``) or per-layer metrics (``--trace 1``,
with the device's busy time and a breakdown), the device, and last the
numbers compared with their limits, which also end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.suite import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START)
    except harness.NoChip as exc:
        print(f"no result: {exc}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
