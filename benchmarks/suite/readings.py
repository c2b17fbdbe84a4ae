"""Readings that a cell's limits are set from, in one process.

    python3 benchmarks/suite/readings.py --workload thermal.d3-r1 \
        --seeds 11 12 13 --control-seeds 11 12 13

For each seed: one fit of the cell's campaign on the chip, at the cell's
own size, through the same entry as a run's window, and its readings
against the reference (``program``); for each control seed, the readings
of the control: the reference computed one precision lower than the
configuration states (``control`` in the configuration's file), put in the
program's place.  One JSON line per reading; the last line holds, per
number, the largest program reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.suite import compare, harness, tracing
    from benchmarks.suite.data import make_data

    cell = harness.load_cell(args.workload)
    harness.check_device(cell.chips)
    harness.enable_cache()
    inst = tracing.Instrument(annotate=False)
    worst: dict = {"program": {}, "control": {}}
    with inst.installed():
        for seed in sorted(set(args.seeds) | set(args.control_seeds)):
            data = make_data(cell.config, seed)
            ref = harness.reference_campaign(cell, data)
            judge = compare.Judge(ref, data.x, data.y, data.names,
                                  data.task_slices)
            runs = []
            if seed in args.seeds:
                fit = harness.fit_once(cell.settings, data, inst,
                                       harness.program_units(data))
                runs.append(("program", fit.answers, {"fit_s": fit.seconds}))
            if seed in args.control_seeds:
                ctl = harness.reference_campaign(
                    cell, data, cell.config["control"]["store"],
                    cell.config["control"]["compute"])
                runs.append(("control", compare.control_answers(ctl), {}))
            for kind, answers, extra in runs:
                r = judge.readings(answers)
                print(json.dumps({"kind": kind, "seed": seed, **r, **extra}),
                      flush=True)
                pick = max if kind == "program" else min
                for k, v in r.items():
                    worst[kind][k] = pick(worst[kind].get(k, v), v)
    print(json.dumps({"workload": args.workload,
                      "program_max": worst["program"],
                      "control_min": worst["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
