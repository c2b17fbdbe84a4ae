"""Inputs of a campaign, made from a configuration file and ``--seed``.

A configuration names its primary features in blocks, each drawn from one
distribution (with the unit of each feature where the case has units), and
a planted law: terms in the SISSO expression language with one coefficient
per task, an intercept per task and Gaussian noise.  The draws come in the
file's order from one ``numpy`` generator seeded with the configuration's
``data_seed``: one data set per configuration.

The run's seed orders the samples inside each task.  So every seed gives a
campaign the same work (the same features, subspaces and models, on
samples in another order), and a run's time measures the program, not the
data set that a seed happened to draw; the same seed gives the same inputs.
"""
from __future__ import annotations

import dataclasses
from fractions import Fraction
from typing import List, Optional, Tuple

import numpy as np

from .expr import evaluate_expr


@dataclasses.dataclass
class Data:
    x: np.ndarray                 # (P, S) primaries, samples grouped by task
    y: np.ndarray                 # (S,)
    names: List[str]
    units: Optional[List[Tuple[Fraction, ...]]]   # exponents over ``basis``
    basis: Tuple[str, ...]
    tasks: Optional[np.ndarray]   # (S,) task of each sample, or None
    task_slices: List[Tuple[int, int]]


def _draw(rng: np.random.Generator, block: dict, s: int) -> np.ndarray:
    n = len(block["names"])
    if block["dist"] == "uniform":
        return rng.uniform(block["low"], block["high"], size=(n, s))
    if block["dist"] == "dirichlet":
        v = rng.dirichlet(np.asarray(block["alpha"], float), size=s).T
        return np.clip(v, block.get("clip_min", -np.inf), None)
    raise ValueError(f"unknown distribution {block['dist']!r}")


def make_data(config: dict, seed: int) -> Data:
    rng = np.random.default_rng(config["data_seed"])
    per_task = config["samples_per_task"]
    s = sum(per_task)
    blocks = config["primaries"]
    names = [n for b in blocks for n in b["names"]]
    x = np.concatenate([_draw(rng, b, s) for b in blocks])
    basis = tuple(config.get("unit_basis", ()))
    units = None
    if basis:
        units = [tuple(Fraction(u.get(d, 0)) for d in basis)
                 for b in blocks for u in b["units"]]
    bounds = np.cumsum([0] + list(per_task))
    slices = [(int(lo), int(hi)) for lo, hi in zip(bounds[:-1], bounds[1:])]
    task = np.repeat(np.arange(len(per_task)), per_task)
    law = config["law"]
    y = np.asarray(law["intercept"], float)[task]
    for term in law["terms"]:
        y = y + np.asarray(term["coef"], float)[task] * evaluate_expr(
            term["expr"], names, x)
    y = y + law["noise"] * rng.normal(size=s)
    order = np.random.default_rng(seed)
    perm = np.concatenate([lo + order.permutation(hi - lo)
                           for lo, hi in slices])
    x, y = x[:, perm], y[perm]
    return Data(x=x, y=y, names=names, units=units, basis=basis,
                tasks=task if len(per_task) > 1 else None,
                task_slices=slices)
