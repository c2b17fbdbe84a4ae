"""Witness of the program's on-the-fly SIS shortfall, on the CPU.

    JAX_PLATFORMS=cpu python benchmarks/suite/tests/otf_witness.py \
        --config thermal --seeds 1 2 3

With the last rung on the fly, ``sis_screen`` keeps the best ``2 n_sis``
candidates and materializes them in order until ``n_sis`` are new.  A
candidate selected at an earlier dimension is still a deferred candidate,
and is screened again; it and its value-duplicates (``a - b`` next to ``b -
a``, each enumerated twice) fill the window, and the dimension keeps fewer
than ``n_sis`` features.  The same campaign with the rung stored keeps
``n_sis`` at every dimension.  This prints, per seed, the features each
dimension's SIS kept on both paths (program's ``jnp`` engine, fp64).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.suite.data import make_data  # noqa: E402


def kept_per_dimension(config: dict, seed: int, on_the_fly: bool,
                       n_sis: int, n_dim: int) -> list:
    import jax

    jax.config.update("jax_enable_x64", True)
    from repro.core.feature_space import FeatureSpace
    from repro.core.l0 import l0_search
    from repro.core.problem import get_problem
    from repro.core.sis import TaskLayout, sis_screen
    from repro.core.units import Unit

    data = make_data(config, seed)
    units = None if data.units is None else [
        Unit(tuple(u), data.basis) for u in data.units]
    fs = FeatureSpace(data.x, data.names, units, op_names=config["op_names"],
                      max_rung=1, l_bound=config["l_bound"],
                      u_bound=config["u_bound"],
                      on_the_fly_last_rung=on_the_fly,
                      engine="jnp").generate()
    y = data.y
    layout = TaskLayout(tuple(data.task_slices))
    problem = get_problem("regression")
    state, chosen, subspace, kept = problem.initial_state(y, layout), set(), [], []
    for dim in range(1, n_dim + 1):
        feats, _ = sis_screen(fs, state, layout, n_sis, chosen, engine="jnp",
                              problem=problem, y=y)
        kept.append(len(feats))
        for f in feats:
            chosen.add(f.fid)
            subspace.append(f.fid)
        if dim == n_dim:
            break
        xs = fs.values_matrix()[[fs.features[f].row for f in subspace]]
        res = l0_search(xs, y, layout, n_dim=dim, n_keep=10, engine="jnp",
                        problem=problem)
        models = problem.make_models(
            xs, y, layout, res, feature_of=lambda j: fs.features[subspace[j]],
            n_keep=10, dtype=np.float64)
        state = problem.update_state(
            y, layout, models, values_of=lambda m: fs.values_matrix()[
                [fs.features[f.fid].row for f in m.features]])
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--n-sis", type=int, default=200)
    args = ap.parse_args(argv)
    config = json.loads((ROOT / "benchmarks/suite/configs"
                         / f"{args.config}.json").read_text())
    for seed in args.seeds:
        out = {"seed": seed, "n_sis": args.n_sis}
        for otf in (True, False):
            out["on_the_fly" if otf else "stored"] = kept_per_dimension(
                config, seed, otf, args.n_sis, config["n_dim"])
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
