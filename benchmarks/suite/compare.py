"""The comparison that decides ``correct``: what a fit produced against the
reference campaign (``reference.run``) on the same data.

A fit's answers are, per dimension, the features SIS selected (expression,
stored values) and the models ℓ0 kept (expressions, SSE).  Three
readings, each the worst over dimensions:

``fc_gap``
    feature creation: the largest difference between a selected or model
    feature's stored values and the reference's evaluation of its
    expression from the primaries, relative to the feature's largest value;
``sis_gap``
    SIS: how far the lowest reference score of a dimension's selection lies
    below the score of the reference's last selected feature (1.0 when the
    selection has another size, repeats a feature, or holds an expression
    that does not parse);
``l0_gap``
    ℓ0: at each rank, the larger of the gap between the fit's SSE and the
    reference's SSE at that rank, and between the reference's SSE of the
    fit's descriptor and the reference's SSE at that rank, relative to the
    target's centered sum of squares (1.0 when the fit kept another number
    of models).  Descriptors of one span are one model (``same_descriptor``,
    as in ``chip_smoke.py``), so a tie listed in another order reads 0.

Which readings a cell compares, and each limit, is data: the cell's file
under ``limits/``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np

from . import reference
from .expr import ParseError, evaluate_expr

#: largest residual of a unit feature column projected on the other
#: descriptor's span that still counts as lying in it (fp64 values)
SPAN_TOL = 1e-9
#: reading of an answer of the wrong shape
WRONG = 1.0


@dataclasses.dataclass
class Selected:
    expr: str
    values: np.ndarray   # (S,) as the fit stored them


@dataclasses.dataclass
class Model:
    exprs: List[str]
    values: np.ndarray   # (n, S) as the fit stored them
    sse: float


@dataclasses.dataclass
class Answers:
    """What one fit produced, per dimension."""

    selected: Dict[int, List[Selected]]
    models: Dict[int, List[Model]]


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


def centered_ss(y: np.ndarray, task_slices) -> float:
    return float(sum(np.sum((y[lo:hi] - y[lo:hi].mean()) ** 2)
                     for lo, hi in task_slices))


class Judge:
    """Readings of fits against one reference campaign."""

    def __init__(self, ref: reference.Campaign, x, y, names, task_slices):
        self.ref = ref
        self.x = np.asarray(x, np.float64)
        self.y = np.asarray(y, np.float64)
        self.names = list(names)
        self.slices = task_slices
        self.syy = centered_ss(self.y, task_slices)
        self._values: Dict[str, np.ndarray] = {}

    def values(self, expr: str) -> np.ndarray:
        """Reference values of an expression (cached; raises ParseError)."""
        v = self._values.get(expr)
        if v is None:
            v = self._values[expr] = evaluate_expr(expr, self.names, self.x)
        return v

    def fc_gap(self, ans: Answers) -> float:
        worst = 0.0
        stored = [(s.expr, s.values) for sel in ans.selected.values()
                  for s in sel]
        stored += [(e, m.values[i]) for ms in ans.models.values()
                   for m in ms for i, e in enumerate(m.exprs)]
        for expr, got in stored:
            try:
                want = self.values(expr)
            except ParseError:
                return WRONG
            with np.errstate(all="ignore"):
                gap = np.abs(np.asarray(got, np.float64) - want).max() \
                    / np.abs(want).max()
            worst = max(worst, float(gap) if np.isfinite(gap) else WRONG)
        return worst

    def sis_gap(self, ans: Answers) -> float:
        worst = 0.0
        seen = reference.Registry(len(self.y), np.float64)
        for d, dim in self.ref.dims.items():
            sel = ans.selected.get(d, [])
            if len(sel) != len(dim.selected):
                return WRONG
            if not sel:
                continue
            try:
                vals = np.stack([self.values(s.expr) for s in sel])
            except ParseError:
                return WRONG
            if not seen.admit(vals).all():
                return WRONG
            scores = reference.sis_scores(vals, dim.residuals, self.slices)
            worst = max(worst, dim.threshold - float(scores.min()))
        return worst

    def l0_gap(self, ans: Answers) -> float:
        worst = 0.0
        for d, dim in self.ref.dims.items():
            models = ans.models.get(d, [])
            if len(models) != len(dim.sses):
                return WRONG
            for m, rows, want in zip(models, dim.tuples, dim.sses):
                try:
                    vals = np.stack([self.values(e) for e in m.exprs])
                except ParseError:
                    return WRONG
                ref_vals = self.ref.space.values[rows]
                if same_descriptor(vals, ref_vals):
                    own = want
                else:
                    own = reference.tuple_sse(vals, self.y, self.slices)
                gap = max(abs(m.sse - want), abs(own - want)) / self.syy
                worst = max(worst, float(gap) if np.isfinite(gap) else WRONG)
        return worst

    def readings(self, ans: Answers) -> Dict[str, float]:
        return {"fc_gap": self.fc_gap(ans), "sis_gap": self.sis_gap(ans),
                "l0_gap": self.l0_gap(ans)}


def control_answers(ref: reference.Campaign) -> Answers:
    """A reference campaign (at a lower precision) as the answers of a fit:
    the control the comparison has to find wrong."""
    sp = ref.space
    selected, models = {}, {}
    for d, dim in ref.dims.items():
        selected[d] = [Selected(sp.exprs[i], sp.values[i].astype(np.float64))
                       for i in dim.selected]
        models[d] = [Model([sp.exprs[i] for i in rows],
                           sp.values[rows].astype(np.float64), float(sse))
                     for rows, sse in zip(dim.tuples, dim.sses)]
    return Answers(selected, models)


def worst(readings: Sequence[Dict[str, float]]) -> Dict[str, float]:
    """Largest reading of each number over several fits."""
    keys: Tuple[str, ...] = tuple(readings[0]) if readings else ()
    return {k: max(r[k] for r in readings) for k in keys}
