"""The SISSO operator set and its expression language, written from the
paper's description (arXiv 2502.20072 §II.C, Table II) for the reference.

Nothing here imports the program.  An expression string is what a user of
the program reads back from a fitted model (``"((f0 * f4) / f2)"``); the
reference parses it and evaluates it from the primary features, and builds
its own feature space in the same language.

Rules an operator carries:

* ``unit``: dimensional analysis on the children's unit exponents;
* ``domain``: a check on the children's value range that prevents
  evaluating a feature with no meaning (a divisor that crosses zero);
* ``simplifies``: unary chains that undo or repeat their child, so the
  outer operator adds nothing (``sqrt`` of a square, ``exp`` of ``ln``).
"""
from __future__ import annotations

import dataclasses
from fractions import Fraction
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

#: largest |x| an exponential may take (its value then stays finite)
EXP_ARG_MAX = 80.0

Unit = Tuple[Fraction, ...]


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    arity: int
    fmt: str
    commutative: bool
    fn: Callable
    unit: Callable[..., Optional[Unit]]
    domain: Callable[..., bool]


def _same(a: Unit, b: Unit) -> Optional[Unit]:
    return a if a == b else None


def _dimensionless(a: Unit) -> Optional[Unit]:
    return a if all(e == 0 for e in a) else None


def _power(p) -> Callable[[Unit], Unit]:
    p = Fraction(p)
    return lambda a: tuple(e * p for e in a)


def _any(*ranges) -> bool:
    return True


def _not_crossing_zero(a, b=None) -> bool:
    lo, hi = (b if b is not None else a)
    return not (lo <= 0.0 <= hi)


def _cbrt(a):
    return np.cbrt(a)


def _six(a):
    a2 = a * a
    return a2 * a2 * a2


OPS: Dict[str, Op] = {op.name: op for op in (
    Op("add", 2, "({0} + {1})", True, lambda a, b: a + b, _same, _any),
    Op("sub", 2, "({0} - {1})", False, lambda a, b: a - b, _same, _any),
    Op("mul", 2, "({0} * {1})", True, lambda a, b: a * b,
       lambda a, b: tuple(x + y for x, y in zip(a, b)), _any),
    Op("div", 2, "({0} / {1})", False, lambda a, b: a / b,
       lambda a, b: tuple(x - y for x, y in zip(a, b)), _not_crossing_zero),
    Op("abs_diff", 2, "|{0} - {1}|", True, lambda a, b: np.abs(a - b),
       _same, _any),
    Op("exp", 1, "exp({0})", False, np.exp, _dimensionless,
       lambda a: -EXP_ARG_MAX < a[0] and a[1] < EXP_ARG_MAX),
    Op("neg_exp", 1, "exp(-{0})", False, lambda a: np.exp(-a), _dimensionless,
       lambda a: -EXP_ARG_MAX < a[0] and a[1] < EXP_ARG_MAX),
    Op("log", 1, "ln({0})", False, np.log, _dimensionless,
       lambda a: a[0] > 0.0),
    Op("abs", 1, "|{0}|", False, np.abs, lambda a: a, _any),
    Op("sqrt", 1, "sqrt({0})", False, np.sqrt, _power("1/2"),
       lambda a: a[0] >= 0.0),
    Op("cbrt", 1, "cbrt({0})", False, _cbrt, _power("1/3"), _any),
    Op("sq", 1, "({0})^2", False, lambda a: a * a, _power(2), _any),
    Op("cb", 1, "({0})^3", False, lambda a: a * a * a, _power(3), _any),
    Op("inv", 1, "({0})^-1", False, lambda a: 1.0 / a, _power(-1),
       _not_crossing_zero),
    Op("sin", 1, "sin({0})", False, np.sin, _dimensionless, _any),
    Op("cos", 1, "cos({0})", False, np.cos, _dimensionless, _any),
    Op("six_pow", 1, "({0})^6", False, _six, _power(6), _any),
)}

#: (outer, root operator of the child) pairs whose result simplifies away
SIMPLIFIES = frozenset({
    ("exp", "log"), ("log", "exp"), ("neg_exp", "log"),
    ("sq", "sqrt"), ("sqrt", "sq"), ("cb", "cbrt"), ("cbrt", "cb"),
    ("inv", "inv"), ("abs", "abs"), ("abs", "abs_diff"),
    ("exp", "neg_exp"), ("neg_exp", "exp"),
})

_PREFIX = (("exp(-", "neg_exp"), ("exp(", "exp"), ("ln(", "log"),
           ("sqrt(", "sqrt"), ("cbrt(", "cbrt"), ("sin(", "sin"),
           ("cos(", "cos"))
_INFIX = {" + ": "add", " - ": "sub", " * ": "mul", " / ": "div"}
_POWER = (("^-1", "inv"), ("^2", "sq"), ("^3", "cb"), ("^6", "six_pow"))


class ParseError(ValueError):
    pass


def parse(expr: str, names: Sequence[str]):
    """Expression string -> tree: a primary index, or ``(op, child...)``."""
    index = {n: i for i, n in enumerate(names)}
    tree, end = _parse(expr, 0, index)
    if end != len(expr):
        raise ParseError(f"trailing text at {end} in {expr!r}")
    return tree


def _expect(s: str, i: int, tok: str) -> int:
    if not s.startswith(tok, i):
        raise ParseError(f"expected {tok!r} at {i} in {s!r}")
    return i + len(tok)


def _parse(s: str, i: int, index):
    for tok, name in _PREFIX:
        if s.startswith(tok, i):
            child, i = _parse(s, i + len(tok), index)
            return (name, child), _expect(s, i, ")")
    if s.startswith("|", i):
        a, i = _parse(s, i + 1, index)
        if s.startswith(" - ", i):
            b, i = _parse(s, i + 3, index)
            return ("abs_diff", a, b), _expect(s, i, "|")
        return ("abs", a), _expect(s, i, "|")
    if s.startswith("(", i):
        a, i = _parse(s, i + 1, index)
        for tok, name in _INFIX.items():
            if s.startswith(tok, i):
                b, i = _parse(s, i + len(tok), index)
                return (name, a, b), _expect(s, i, ")")
        i = _expect(s, i, ")")
        for tok, name in _POWER:
            if s.startswith(tok, i):
                return (name, a), i + len(tok)
        raise ParseError(f"bare parentheses at {i} in {s!r}")
    j = i
    while j < len(s) and (s[j].isalnum() or s[j] == "_"):
        j += 1
    if s[i:j] not in index:
        raise ParseError(f"unknown primary {s[i:j]!r} in {s!r}")
    return index[s[i:j]], j


def evaluate(tree, x: np.ndarray) -> np.ndarray:
    """Values of a parsed expression over primaries ``x`` (P, S), in the
    dtype of ``x``."""
    if isinstance(tree, int):
        return x[tree]
    op = OPS[tree[0]]
    with np.errstate(all="ignore"):
        return op.fn(*(evaluate(c, x) for c in tree[1:])).astype(x.dtype)


def evaluate_expr(expr: str, names: Sequence[str], x: np.ndarray):
    return evaluate(parse(expr, names), x)
