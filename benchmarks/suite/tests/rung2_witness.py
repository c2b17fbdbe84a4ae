"""Witness of the program's rung-2 enumeration fault, on the CPU.

    JAX_PLATFORMS=cpu python benchmarks/suite/tests/rung2_witness.py \
        --config thermal --seeds 1 2 3

Rung ``r`` of a SISSO feature space applies each binary operator to every
pair of features whose higher rung is ``r - 1`` (paper §II.C; the
program's own ``FeatureSpace._host_valid_children`` says "max(rung_a,
rung_b) == rung - 1").  The program skips, for commutative operators
(``add``, ``mul``, ``abs_diff``), every pair of a rung ``r - 1`` feature
with a lower-rung one: the commutative-order test ``fb.fid < fa.fid`` is
true for all of them.  For each seed this prints how many such pairs the
program never enumerates, and how many of them, with values no feature or
candidate of the program has, score above the program's ``n_sis``-th best
candidate at dimension 1 (so a correct SIS would select them).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.suite import reference  # noqa: E402
from benchmarks.suite.data import make_data  # noqa: E402
from benchmarks.suite.expr import OPS  # noqa: E402

CHUNK = 16384


def score(values, y, slices):
    return reference.sis_scores(values, y[None, :], slices)


def witness(config: dict, seed: int, n_sis: int) -> dict:
    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.core.feature_space import FeatureSpace
    from repro.core.units import Unit

    data = make_data(config, seed)
    units = None if data.units is None else [
        Unit(tuple(u), data.basis) for u in data.units]
    fs = FeatureSpace(data.x, data.names, units, op_names=config["op_names"],
                      max_rung=2, l_bound=config["l_bound"],
                      u_bound=config["u_bound"], on_the_fly_last_rung=True,
                      max_pairs_per_op=config["max_pairs_per_op"],
                      engine="reference").generate()
    x = fs.values_matrix()
    y, slices = data.y, data.task_slices
    scores = [score(x, y, slices)]
    enumerated = set()
    for blk in fs.candidates:
        op = next(o for o in OPS.values()
                  if o.fmt == _fmt_of(blk.op_id))
        enumerated.update((blk.op_id, int(a), int(b))
                          for a, b in zip(blk.child_a, blk.child_b))
        for lo in range(0, len(blk), CHUNK):
            a, b = x[blk.child_a[lo:lo + CHUNK]], x[blk.child_b[lo:lo + CHUNK]]
            with np.errstate(all="ignore"):
                v = op.fn(a, b) if op.arity == 2 else op.fn(a)
            ok = reference._value_ok(v, config["l_bound"], config["u_bound"])
            s = score(np.where(np.isfinite(v), v, 0), y, slices)
            scores.append(np.where(ok, s, -np.inf))
    allscores = np.sort(np.concatenate(scores))[::-1]
    threshold = float(allscores[n_sis - 1])
    prev = [f for f in fs.features if f.rung == 1]
    lower = [f for f in fs.features if f.rung == 0]
    missing, above, examples = 0, 0, []
    for f_op in fs.ops:
        if f_op.arity != 2 or not f_op.commutative:
            continue
        op = OPS[f_op.name]
        pairs = [(fa, fb) for fa in prev for fb in lower
                 if f_op.unit_rule(fa.unit, fb.unit) is not None
                 and f_op.domain_rule(fa.meta, fb.meta)
                 and (f_op.op_id, fa.row, fb.row) not in enumerated
                 and (f_op.op_id, fb.row, fa.row) not in enumerated]
        missing += len(pairs)
        if not pairs:
            continue
        v = op.fn(x[[p[0].row for p in pairs]], x[[p[1].row for p in pairs]])
        ok = reference._value_ok(v, config["l_bound"], config["u_bound"])
        s = np.where(ok, score(np.where(np.isfinite(v), v, 0), y, slices),
                     -np.inf)
        for k in np.nonzero(s > threshold)[0]:
            # a value-duplicate of something the program has ties its score
            if np.any(np.abs(allscores - s[k]) <= 1e-12 * s[k]):
                continue
            above += 1
            if len(examples) < 3:
                fa, fb = pairs[k]
                examples.append([op.fmt.format(fa.expr, fb.expr),
                                 float(s[k])])
    return {"seed": seed, "program_candidates": fs.n_candidates_deferred,
            "missing_pairs": missing, "n_sis": n_sis,
            "program_threshold": threshold,
            "missing_above_threshold": above, "examples": examples}


def _fmt_of(op_id: int) -> str:
    from repro.core import operators

    return operators.OPS[op_id].fmt


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--n-sis", type=int, default=None,
                    help="SIS size (default: the on-the-fly campaigns', 200 for "
                         "thermal, 2000 for kaggle)")
    args = ap.parse_args(argv)
    config = json.loads((ROOT / "benchmarks/suite/configs"
                         / f"{args.config}.json").read_text())
    n_sis = args.n_sis or (200 if args.config == "thermal" else 2000)
    for seed in args.seeds:
        print(json.dumps(witness(config, seed, n_sis)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
