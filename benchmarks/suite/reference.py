"""Plain SISSO, written for the comparison that decides ``correct``.

It imports nothing of the program and takes nothing that the program made:
it builds the feature space from the primaries, screens it and searches it
exhaustively, one straightforward step after another:

* feature creation: rung ``r`` applies every operator to features whose
  highest child rung is ``r - 1``: unary operators to each rung ``r - 1``
  feature, commutative binary operators to each unordered pair, the others
  to each ordered pair.  A candidate is kept when its units agree, its
  children's range suits the operator, it does not simplify away
  (``expr.SIMPLIFIES``), its values are finite with ``l_bound <= max|v| <=
  u_bound`` and a standard deviation above ``MIN_STD``, and it is not an
  affine image of a feature kept before it (same values up to scale, sign
  and offset: a model with an intercept cannot tell them apart);
* SIS: a feature's score is the largest, over the residuals, of the mean
  over tasks of its |Pearson correlation| with the residual inside the
  task; each dimension adds the ``n_sis`` best features not yet selected;
* ℓ0: every ``n``-tuple of the selected features is fitted by least
  squares with one intercept and one coefficient per task; the tuples are
  ranked by their total SSE, and the residuals of the best ``n_residual``
  feed the next dimension.

``precision`` is the arithmetic: ``fp64``; ``fp32``; or ``fp32_high``, fp32
whose matrix products carry only the three bf16 passes of a TPU's ``high``
precision (the high half of each factor times both halves of the other).
The two lower ones are the controls: the comparison has to find them
wrong.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .expr import OPS, SIMPLIFIES, Unit

#: smallest whole-sample standard deviation of a feature
MIN_STD = 1e-10
#: largest gap, after scaling both to unit norm and a common sign, between
#: the centered values of two features that are one feature
SAME_TOL = 1e-8
#: smallest pivot, relative to its diagonal entry, of a tuple's normal
#: equations: below it the tuple's features are linearly dependent
PIVOT_TOL = 1e-10

DTYPES = {"fp64": np.float64, "fp32": np.float32, "fp32_high": np.float32}


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _bf16(a: np.ndarray) -> np.ndarray:
    """fp32 -> nearest bf16 (round half to even), kept in fp32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    u = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return u.view(np.float32)


def matmul(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    if precision != "fp32_high":
        return a @ b
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)
    return a_hi @ b_hi + (a_hi @ b_lo + a_lo @ b_hi)


# ---------------------------------------------------------------------------
# feature creation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Space:
    exprs: List[str]
    rungs: np.ndarray          # (F,)
    values: np.ndarray         # (F, S) in the reference dtype
    units: List[Unit]
    roots: List[Optional[str]]  # operator at the root, None for a primary


class Registry:
    """Kept features and the test for "an affine image of one of them"."""

    def __init__(self, n_samples: int, dtype):
        rng = np.random.default_rng(0)
        self.proj = rng.normal(size=(n_samples, 2))
        self.dtype = dtype
        self.rows: List[np.ndarray] = []
        self.z: List[np.ndarray] = []
        self.buckets: Dict[Tuple[int, int], List[int]] = {}

    @staticmethod
    def standardize(v: np.ndarray) -> np.ndarray:
        c = v - v.mean(axis=1, keepdims=True)
        z = c / np.linalg.norm(c, axis=1, keepdims=True)
        # a common sign: the first sample that is clearly non-zero is > 0
        lead = np.argmax(np.abs(z) > 1e-3, axis=1)
        sign = np.sign(z[np.arange(len(z)), lead])
        return z * sign[:, None]

    def admit(self, values: np.ndarray) -> np.ndarray:
        """Keep the rows that are no image of a kept row (or of an earlier
        row of this block); returns the mask of kept rows."""
        v64 = values.astype(np.float64)
        z = self.standardize(v64)
        keys = np.floor(z @ self.proj / (4 * SAME_TOL)).astype(np.int64)
        kept = np.zeros(len(values), bool)
        for i in range(len(values)):
            k0, k1 = int(keys[i, 0]), int(keys[i, 1])
            dup = False
            for d0 in (-1, 0, 1):
                for d1 in (-1, 0, 1):
                    for j in self.buckets.get((k0 + d0, k1 + d1), ()):
                        if np.abs(self.z[j] - z[i]).max() <= SAME_TOL:
                            dup = True
                            break
                    if dup:
                        break
                if dup:
                    break
            if dup:
                continue
            self.buckets.setdefault((k0, k1), []).append(len(self.z))
            self.z.append(z[i])
            self.rows.append(values[i])
            kept[i] = True
        return kept


def _value_ok(v: np.ndarray, l_bound: float, u_bound: float) -> np.ndarray:
    v64 = v.astype(np.float64)
    finite = np.isfinite(v64).all(axis=1)
    with np.errstate(all="ignore"):
        big = np.abs(np.where(np.isfinite(v64), v64, 0.0)).max(axis=1)
        std = np.where(finite[:, None], v64, 0.0).std(axis=1)
    return finite & (big >= l_bound) & (big <= u_bound) & (std > MIN_STD)


def _candidates(op, prev: np.ndarray, lower: np.ndarray):
    """Child index tuples of operator ``op`` at a rung whose previous rung
    holds the features ``prev`` and the rungs below it ``lower``."""
    if op.arity == 1:
        return [(int(i),) for i in prev]
    out = []
    for i in prev:
        for j in prev:
            if i != j and (not op.commutative or i < j):
                out.append((int(i), int(j)))
        for j in lower:
            out.append((int(i), int(j)))
            if not op.commutative:
                out.append((int(j), int(i)))
    return out


def build_space(x: np.ndarray, names: Sequence[str],
                units: Optional[Sequence[Unit]], ops: Sequence[str],
                max_rung: int, l_bound: float, u_bound: float,
                precision: str = "fp64") -> Space:
    dtype = DTYPES[precision]
    x = np.asarray(x, np.float64).astype(dtype)
    p, s = x.shape
    if units is None:
        units = [()] * p
    reg = Registry(s, dtype)
    kept = reg.admit(x)
    exprs = [str(n) for n, k in zip(names, kept) if k]
    fu = [tuple(u) for u, k in zip(units, kept) if k]
    roots: List[Optional[str]] = [None] * len(exprs)
    rungs = [0] * len(exprs)
    for rung in range(1, max_rung + 1):
        r = np.asarray(rungs)
        prev, lower = np.nonzero(r == rung - 1)[0], np.nonzero(r < rung - 1)[0]
        vals = np.asarray(reg.rows)
        lo = vals.astype(np.float64).min(axis=1)
        hi = vals.astype(np.float64).max(axis=1)
        for name in ops:
            op = OPS[name]
            kids, kid_units = [], []
            for c in _candidates(op, prev, lower):
                if op.arity == 1 and (name, roots[c[0]]) in SIMPLIFIES:
                    continue
                u = op.unit(*(fu[i] for i in c))
                if u is None or not op.domain(*((lo[i], hi[i]) for i in c)):
                    continue
                kids.append(c)
                kid_units.append(u)
            if not kids:
                continue
            idx = np.asarray(kids)
            with np.errstate(all="ignore"):
                v = op.fn(*(vals[idx[:, k]] for k in range(op.arity)))
            v = v.astype(dtype)
            ok = _value_ok(v, l_bound, u_bound)
            sel = np.nonzero(ok)[0]
            admitted = reg.admit(v[sel])
            for k in sel[admitted]:
                exprs.append(op.fmt.format(*(exprs[i] for i in kids[k])))
                fu.append(kid_units[k])
                roots.append(name)
                rungs.append(rung)
    return Space(exprs=exprs, rungs=np.asarray(rungs),
                 values=np.asarray(reg.rows), units=fu, roots=roots)


# ---------------------------------------------------------------------------
# SIS
# ---------------------------------------------------------------------------

def sis_scores(values: np.ndarray, residuals: np.ndarray,
               task_slices: Sequence[Tuple[int, int]],
               precision: str = "fp64") -> np.ndarray:
    """Score (F,) of each feature: max over residuals of the mean over
    tasks of |Pearson r| inside the task."""
    dtype = DTYPES[precision]
    v = np.asarray(values).astype(dtype)
    res = np.atleast_2d(residuals).astype(dtype)
    total = np.zeros((len(v), len(res)), dtype)
    for lo, hi in task_slices:
        c = v[:, lo:hi] - v[:, lo:hi].mean(axis=1, keepdims=True)
        rc = res[:, lo:hi] - res[:, lo:hi].mean(axis=1, keepdims=True)
        rn = np.linalg.norm(rc, axis=1)
        rc = rc / np.where(rn > 0, rn, 1)[:, None]
        norm = np.sqrt(matmul(c * c, np.ones((hi - lo, 1), dtype),
                              precision))[:, 0]
        dots = matmul(c, rc.T, precision)
        with np.errstate(all="ignore"):
            r = np.where(norm[:, None] > 0, dots / norm[:, None], 0)
        total += np.abs(r)
    return (total / len(task_slices)).max(axis=1).astype(np.float64)


def top_indices(scores: np.ndarray, k: int, exclude=()) -> np.ndarray:
    """The ``k`` highest scores, first index first among equals."""
    s = np.asarray(scores, np.float64).copy()
    s[list(exclude)] = -np.inf
    order = np.argsort(-s, kind="stable")
    order = order[np.isfinite(s[order])]
    return order[:k]


# ---------------------------------------------------------------------------
# ℓ0
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Gram:
    g: np.ndarray    # (T, m, m) task-centered Gram
    b: np.ndarray    # (T, m)    task-centered X y
    yy: np.ndarray   # (T,)      task-centered y y


def gram(x: np.ndarray, y: np.ndarray, task_slices, precision="fp64") -> Gram:
    dtype = DTYPES[precision]
    x = np.asarray(x).astype(dtype)
    y = np.asarray(y, np.float64).astype(dtype)
    gs, bs, yys = [], [], []
    for lo, hi in task_slices:
        c = x[:, lo:hi] - x[:, lo:hi].mean(axis=1, keepdims=True)
        yc = y[lo:hi] - y[lo:hi].mean()
        gs.append(matmul(c, c.T, precision))
        bs.append(matmul(c, yc[:, None], precision)[:, 0])
        yys.append(matmul(yc[None, :], yc[:, None], precision)[0, 0])
    return Gram(np.stack(gs), np.stack(bs), np.asarray(yys, dtype))


def _eliminate(gr: Gram, p: int, diag0: np.ndarray) -> Tuple[Gram, bool]:
    """Project feature ``p`` out of every other feature and of y; returns
    the reduced statistics and whether ``p`` is independent, in every
    task, of the features projected out before it."""
    piv = gr.g[:, p, p]
    ok = bool((piv > PIVOT_TOL * diag0[:, p]).all())
    col = gr.g[:, :, p]
    safe = np.where(piv > 0, piv, 1)
    g = gr.g - col[:, :, None] * col[:, None, :] / safe[:, None, None]
    b = gr.b - col * (gr.b[:, p] / safe)[:, None]
    yy = gr.yy - gr.b[:, p] ** 2 / safe
    return Gram(g, b, yy), ok


def _pair_sse(gr: Gram, first: int, diag0: np.ndarray) -> np.ndarray:
    """(n, n) total SSE of the pairs j < k of features ``first + j`` and
    ``first + k``; inf on and below the diagonal and where a pair is
    dependent (``diag0``: the features' own squared norms, against which a
    pivot is judged)."""
    g, b = gr.g[:, first:, first:], gr.b[:, first:]
    d0 = diag0[:, first:]
    n = g.shape[1]
    gjj = np.einsum("tii->ti", g)
    with np.errstate(all="ignore"):
        det = gjj[:, :, None] * gjj[:, None, :] - g ** 2
        quad = (gjj[:, None, :] * b[:, :, None] ** 2
                - 2 * g * b[:, :, None] * b[:, None, :]
                + gjj[:, :, None] * b[:, None, :] ** 2)
        sse = gr.yy[:, None, None] - quad / det
    ok_j = gjj > PIVOT_TOL * d0
    ok = (det > PIVOT_TOL * gjj[:, :, None] * d0[:, None, :]) \
        & ok_j[:, :, None] & ok_j[:, None, :]
    sse = np.where(ok, np.maximum(sse, 0), np.inf).sum(axis=0)
    return np.where(np.triu(np.ones((n, n), bool), 1), sse, np.inf)


def l0_search(x: np.ndarray, y: np.ndarray, task_slices, width: int,
              n_keep: int, precision="fp64"):
    """Best ``n_keep`` ``width``-tuples of the rows of ``x``: (tuples (k,
    width) int, total SSE (k,)), lowest SSE first, earlier tuple first
    among equals."""
    gr = gram(x, y, task_slices, precision)
    m = gr.g.shape[1]
    diag0 = np.einsum("tii->ti", gr.g).astype(np.float64)
    best_t: List[Tuple[int, ...]] = []
    best_s = np.zeros(0)

    def push(tuples, sses):
        nonlocal best_t, best_s
        s = np.concatenate([best_s, sses])
        t = best_t + tuples
        order = np.argsort(s, kind="stable")[:n_keep]
        best_s, best_t = s[order], [t[i] for i in order]

    def bound():
        return best_s[-1] if len(best_s) == n_keep else np.inf

    if width == 1:
        with np.errstate(all="ignore"):
            sse = gr.yy[:, None] - gr.b ** 2 / np.einsum("tii->ti", gr.g)
        dep = ~(diag0 > 0)
        sse = np.where(dep, np.inf, np.maximum(sse, 0)).sum(axis=0)
        ok = np.nonzero(np.isfinite(sse))[0]
        push([(int(i),) for i in ok], sse[ok].astype(np.float64))
        return np.asarray(best_t, int).reshape(-1, 1), best_s

    def rec(prefix: Tuple[int, ...], gr_red: Gram):
        if len(prefix) == width - 2:
            first = prefix[-1] + 1 if prefix else 0
            flat = _pair_sse(gr_red, first, diag0).astype(np.float64).ravel()
            cand = np.nonzero(flat <= bound())[0]
            if len(cand) > n_keep:
                cand = cand[np.argsort(flat[cand], kind="stable")[:n_keep]]
            if len(cand):
                j, k = np.divmod(cand, m - first)
                push([prefix + (first + int(a), first + int(c))
                      for a, c in zip(j, k)], flat[cand])
            return
        start = prefix[-1] + 1 if prefix else 0
        for p in range(start, m - (width - len(prefix)) + 1):
            reduced, ok = _eliminate(gr_red, p, diag0)
            if ok:
                rec(prefix + (p,), reduced)

    rec((), gr)
    return np.asarray(best_t, int).reshape(-1, width), best_s


def tuple_sse(x: np.ndarray, y: np.ndarray, task_slices,
              precision="fp64") -> float:
    """Total SSE of one descriptor (rows of ``x``), by least squares."""
    dtype = DTYPES[precision]
    total = 0.0
    for lo, hi in task_slices:
        a = np.concatenate([np.ones((1, hi - lo)), x[:, lo:hi]]).T.astype(dtype)
        yt = np.asarray(y[lo:hi]).astype(dtype)
        coef = np.linalg.lstsq(a, yt, rcond=None)[0]
        total += float(np.sum((yt - a @ coef).astype(np.float64) ** 2))
    return total


def residuals(x: np.ndarray, y: np.ndarray, tuples, task_slices,
              precision="fp64") -> np.ndarray:
    """(k, S) least-squares residuals of each descriptor tuple."""
    dtype = DTYPES[precision]
    out = np.zeros((len(tuples), len(y)), dtype)
    for i, tup in enumerate(tuples):
        for lo, hi in task_slices:
            a = np.concatenate([np.ones((1, hi - lo)),
                                x[list(tup), lo:hi]]).T.astype(dtype)
            yt = np.asarray(y[lo:hi]).astype(dtype)
            coef = np.linalg.lstsq(a, yt, rcond=None)[0]
            out[i, lo:hi] = yt - a @ coef
    return out


# ---------------------------------------------------------------------------
# the whole campaign
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Dimension:
    selected: List[int]     # space rows SIS added at this dimension
    threshold: float        # score of the last feature selected
    residuals: np.ndarray   # (R, S) the residuals it screened against
    tuples: np.ndarray      # (k, dim) rows of the space
    sses: np.ndarray        # (k,)


@dataclasses.dataclass
class Campaign:
    space: Space
    dims: Dict[int, Dimension]


def run(x, y, names, units, task_slices, settings: dict,
        store="fp64", compute="fp64") -> Campaign:
    """The reference campaign.  ``settings`` holds ``op_names``,
    ``max_rung``, ``l_bound``, ``u_bound``, ``n_sis``, ``n_dim`` and
    ``n_residual``; ``store`` is the precision of feature values, and
    ``compute`` that of screening and least squares."""
    space = build_space(x, names, units, settings["op_names"],
                        settings["max_rung"], settings["l_bound"],
                        settings["u_bound"], store)
    state = np.asarray(y, np.float64)[None, :]
    subspace: List[int] = []
    dims: Dict[int, Dimension] = {}
    for dim in range(1, settings["n_dim"] + 1):
        scores = sis_scores(space.values, state, task_slices, compute)
        new = top_indices(scores, settings["n_sis"], exclude=subspace)
        subspace.extend(int(i) for i in new)
        tuples, sses = l0_search(space.values[subspace], y, task_slices, dim,
                                 settings["n_residual"], compute)
        rows = np.asarray(subspace)[tuples]
        dims[dim] = Dimension(
            selected=[int(i) for i in new],
            threshold=float(scores[new[-1]]) if len(new) else np.inf,
            residuals=state, tuples=rows, sses=sses)
        state = residuals(space.values, y, rows, task_slices,
                          compute).astype(np.float64)
    return Campaign(space=space, dims=dims)
