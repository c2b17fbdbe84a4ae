"""Feature creation (FC) — the first SISSO phase.

Implements the paper's GPU algorithm (Fig. 2, right) adapted to TPU/JAX:

* **operator-outer-loop** (paper P1): for each operator, all candidate child
  combinations are evaluated as one batched device sweep over an SoA value
  matrix ``X: (n_features, n_samples)``.
* **host/device rule split** (paper P2): unit-, domain- and structural-dedup
  rules run on host metadata and *prevent* evaluation; value rules (bounds,
  NaN, variance, duplicate values) are applied on device to the evaluated
  block and produce a validity mask — exactly the paper's "validity list".
  Which device runs them is the execution engine's concern (engine/): the
  FeatureSpace only asks its :class:`~repro.engine.Engine` to
  ``eval_block``.
* **on-the-fly last rung** (paper P3): the highest rung is optionally never
  materialized; candidates are kept as ``(op_id, child_a, child_b)`` integer
  triples and (re-)evaluated inside SIS (see kernels/fused_sis.py).

Value-based duplicate elimination uses two fixed random projections of the
standardized feature values (sign-canonicalized, so ``x`` and ``-x`` — which
span the same model space — collide), quantized to a relative tolerance.
Projection keys are computed for whole candidate blocks at once (one
matmul), and admitted rows append into a geometrically-grown SoA value
matrix — ``values_matrix()`` is a view, never a re-stack.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..runtime import trace
from . import operators as ops_mod
from .operators import ChildMeta, Operator
from .units import Unit
from .validity import DEDUP_TOL, MIN_STD

log = logging.getLogger(__name__)


@dataclasses.dataclass
class Feature:
    fid: int
    rung: int
    unit: Unit
    expr: str
    complexity: int
    op_id: Optional[int] = None  # None => primary feature
    child_a: Optional[int] = None  # fid
    child_b: Optional[int] = None  # fid
    row: Optional[int] = None  # row in the materialized value matrix
    vmin: float = 0.0
    vmax: float = 0.0

    @property
    def meta(self) -> ChildMeta:
        return ChildMeta(self.vmin, self.vmax)


@dataclasses.dataclass
class CandidateBlock:
    """A batch of same-operator last-rung candidates (never materialized)."""

    op_id: int
    child_a: np.ndarray  # (B,) rows into the materialized value matrix
    child_b: np.ndarray  # (B,) rows; == child_a for unary ops

    def __len__(self) -> int:
        return len(self.child_a)


class FeatureSpace:
    """Rung-wise combinatorial feature generation with validity rules."""

    def __init__(
        self,
        primary_values: np.ndarray,  # (P, S)
        names: Sequence[str],
        units: Optional[Sequence[Unit]] = None,
        op_names: Sequence[str] = ops_mod.THERMAL_OPS,
        max_rung: int = 2,
        l_bound: float = 1e-5,
        u_bound: float = 1e8,
        on_the_fly_last_rung: bool = False,
        eval_batch: int = 8192,
        max_pairs_per_op: Optional[int] = None,
        seed: int = 0,
        dtype=jnp.float32,
        engine=None,
    ) -> None:
        primary_values = np.asarray(primary_values, dtype=np.float64)
        if primary_values.ndim != 2:
            raise ValueError("primary_values must be (n_features, n_samples)")
        p, s = primary_values.shape
        if len(names) != p:
            raise ValueError("names must match primary feature count")
        units = list(units) if units else [Unit.dimensionless() for _ in range(p)]

        from ..engine import get_engine  # deferred: engine builds on core

        self.engine = get_engine(engine or "reference")
        self.dtype = dtype
        self.n_samples = s
        self.ops: Tuple[Operator, ...] = ops_mod.op_pool(op_names)
        self.max_rung = max_rung
        self.l_bound = float(l_bound)
        self.u_bound = float(u_bound)
        self.on_the_fly = bool(on_the_fly_last_rung)
        self.eval_batch = int(eval_batch)
        self.max_pairs_per_op = max_pairs_per_op
        self._rng = np.random.default_rng(seed)

        # Two fixed dedup projection vectors (host side, float64 for stability).
        proj_rng = np.random.default_rng(1234)
        self._proj = proj_rng.normal(size=(2, s))
        self._proj /= np.linalg.norm(self._proj, axis=1, keepdims=True)
        self._dedup: Dict[Tuple[int, int], int] = {}

        self.features: List[Feature] = []
        # SoA value store: geometrically grown, values_matrix() is a view.
        self._values = np.empty((0, s), np.float64)
        self._n_rows = 0
        self._row_fids: List[int] = []  # row -> fid (O(1) feature_by_row)
        self.candidates: List[CandidateBlock] = []  # last rung, on-the-fly only
        self.n_rejected = {"unit": 0, "domain": 0, "value": 0, "dup": 0, "redundant": 0}

        # Descriptor compilation (core/descriptor.py) rebuilds selected
        # features from the *user's input columns*, so record which column
        # each admitted primary came from (dedup may reject some primaries,
        # making fid != column) and the full input-name row.
        self.n_primary_inputs = p
        self.primary_names: List[str] = [str(n) for n in names]
        admitted = self.admit_block(
            rung=0, values=primary_values, units=units,
            exprs=[str(n) for n in names], complexities=[0] * p,
        )
        self.primary_columns: Dict[int, int] = {
            f.fid: col for col, f in enumerate(admitted) if f is not None
        }

    # ------------------------------------------------------------------
    # materialized storage
    # ------------------------------------------------------------------
    @property
    def n_materialized(self) -> int:
        return self._n_rows

    def values_matrix(self) -> np.ndarray:
        """(n_materialized, n_samples) float64 host matrix.

        A view into the incrementally-maintained store — O(1), not a
        re-stack.  Treat as read-only; it may be detached from the live
        store by a later growth reallocation.
        """
        return self._values[: self._n_rows]

    def values_device(self) -> jnp.ndarray:
        return jnp.asarray(self.values_matrix(), dtype=self.dtype)

    def _append_rows(self, rows: np.ndarray) -> None:
        need = self._n_rows + len(rows)
        if need > len(self._values):
            cap = max(need, 2 * len(self._values), 64)
            grown = np.empty((cap, self.n_samples), np.float64)
            grown[: self._n_rows] = self._values[: self._n_rows]
            self._values = grown
        self._values[self._n_rows : need] = rows
        self._n_rows = need

    # ------------------------------------------------------------------
    # value-duplicate elimination (vectorized over candidate blocks)
    # ------------------------------------------------------------------
    def _block_keys(
        self, values: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Projection dedup keys for a whole block: (keys (B, 2), ok (B,))."""
        v = values - values.mean(axis=1, keepdims=True)
        nrm = np.linalg.norm(v, axis=1)
        ok = nrm >= MIN_STD
        with np.errstate(all="ignore"):
            vn = v / nrm[:, None]
        p = vn @ self._proj.T  # (B, 2) — the whole block in one matmul
        flip = (p[:, 0] < 0) | ((p[:, 0] == 0) & (p[:, 1] < 0))
        p = np.where(flip[:, None], -p, p)
        with np.errstate(all="ignore"):
            keys = np.round(p / DEDUP_TOL)
        keys = np.where(np.isfinite(keys), keys, 0).astype(np.int64)
        return keys, ok

    def _is_dup(self, key: Tuple[int, int]) -> bool:
        # check neighbor buckets too: quantization can split equal values
        # across adjacent buckets at bucket boundaries
        k0, k1 = key
        for d1 in (-1, 0, 1):
            for d2 in (-1, 0, 1):
                if (k0 + d1, k1 + d2) in self._dedup:
                    return True
        return False

    @trace.span("sisso.fc.admit")
    def admit_block(
        self,
        rung: int,
        values: np.ndarray,  # (B, S) candidate values (already value-valid)
        units: Sequence[Unit],
        exprs: Sequence[str],
        complexities: Sequence[int],
        op_id: Optional[int] = None,
        child_a: Optional[Sequence[int]] = None,
        child_b: Optional[Sequence[int]] = None,
        check_dup: bool = True,
    ) -> List[Optional[Feature]]:
        """Dedup + register a block of candidates; returns per-candidate
        Feature or None (rejected).  Projection keys are computed for the
        whole block at once; accepted rows append in one bulk copy."""
        values = np.asarray(values, np.float64)
        keys, ok = self._block_keys(values)
        out: List[Optional[Feature]] = []
        new_rows: List[np.ndarray] = []
        for k in range(len(values)):
            if not ok[k]:
                self.n_rejected["value"] += 1
                out.append(None)
                continue
            key = (int(keys[k, 0]), int(keys[k, 1]))
            if check_dup and self._is_dup(key):
                self.n_rejected["dup"] += 1
                out.append(None)
                continue
            fid = len(self.features)
            feat = Feature(
                fid=fid, rung=rung, unit=units[k], expr=exprs[k],
                complexity=complexities[k], op_id=op_id,
                child_a=None if child_a is None else int(child_a[k]),
                child_b=None if child_b is None else int(child_b[k]),
                row=self._n_rows + len(new_rows),
                vmin=float(values[k].min()), vmax=float(values[k].max()),
            )
            self._dedup[key] = fid
            self.features.append(feat)
            self._row_fids.append(fid)
            new_rows.append(values[k])
            out.append(feat)
        if new_rows:
            self._append_rows(np.stack(new_rows))
        return out

    def _add_feature(
        self, rung: int, unit: Unit, expr: str, complexity: int,
        values: np.ndarray, op_id: Optional[int] = None,
        child_a: Optional[int] = None, child_b: Optional[int] = None,
        check_dup: bool = True,
    ) -> Optional[Feature]:
        return self.admit_block(
            rung=rung, values=np.asarray(values, np.float64)[None, :],
            units=[unit], exprs=[expr], complexities=[complexity],
            op_id=op_id,
            child_a=None if child_a is None else [child_a],
            child_b=None if child_b is None else [child_b],
            check_dup=check_dup,
        )[0]

    # ------------------------------------------------------------------
    # candidate enumeration (host rules only — paper P2 "CPU side")
    # ------------------------------------------------------------------
    @trace.span("sisso.fc.enumerate")
    def _host_valid_children(
        self, op: Operator, rung: int
    ) -> Tuple[np.ndarray, np.ndarray, List[Unit]]:
        """Enumerate child index pairs passing unit/domain/structural rules.

        Each child set of rung ``rung`` comes once: a unary operator takes
        each rung ``rung - 1`` feature; a binary operator pairs each rung
        ``rung - 1`` feature ``fa`` with itself (where the operator allows
        it), with each later rung ``rung - 1`` feature and with each
        lower-rung feature, as ``(fa, fb)`` and, for a non-commutative
        operator, also ``(fb, fa)``.  Unit rules are evaluated once per
        pair of child units.
        """
        feats = self.features
        prev = [f for f in feats if f.rung == rung - 1]
        lower = [f for f in feats if f.rung < rung - 1]
        ia: List[int] = []
        ib: List[int] = []
        units: List[Unit] = []
        # the unit rule, once per pair of distinct child units
        distinct: Dict[Unit, int] = {}
        unit_id = {f.fid: distinct.setdefault(f.unit, len(distinct))
                   for f in prev + lower}
        unit_of: Dict[Tuple[int, int], Optional[Unit]] = {}
        meta = {f.fid: f.meta for f in prev + lower}

        def add(fa: Feature, fb: Feature) -> None:
            key = (unit_id[fa.fid], unit_id[fb.fid])
            if key not in unit_of:
                unit_of[key] = op.unit_rule(fa.unit) if op.arity == 1 \
                    else op.unit_rule(fa.unit, fb.unit)
            u = unit_of[key]
            if u is None:
                self.n_rejected["unit"] += 1
            elif not (op.domain_rule(meta[fa.fid]) if op.arity == 1
                      else op.domain_rule(meta[fa.fid], meta[fb.fid])):
                self.n_rejected["domain"] += 1
            else:
                ia.append(fa.fid)
                ib.append(fb.fid)
                units.append(u)

        if op.arity == 1:
            for f in prev:
                if ops_mod.is_redundant_unary(op.op_id, f.op_id):
                    self.n_rejected["redundant"] += 1
                    continue
                add(f, f)
        else:
            # max(rung_a, rung_b) == rung - 1  =>  at least one child in prev
            for i, fa in enumerate(prev):
                for fb in prev[i:] + lower:
                    if fb is fa:
                        if op.allow_same_child:
                            add(fa, fa)
                        continue
                    add(fa, fb)
                    if not op.commutative:
                        add(fb, fa)
        ia_arr = np.asarray(ia, dtype=np.int32)
        ib_arr = np.asarray(ib, dtype=np.int32)
        if self.max_pairs_per_op is not None and len(ia_arr) > self.max_pairs_per_op:
            sel = self._rng.choice(len(ia_arr), self.max_pairs_per_op, replace=False)
            sel.sort()
            ia_arr, ib_arr = ia_arr[sel], ib_arr[sel]
            units = [units[i] for i in sel]
        return ia_arr, ib_arr, units

    # ------------------------------------------------------------------
    # device evaluation + value rules (paper P2 "GPU side")
    # ------------------------------------------------------------------
    @trace.span("sisso.fc.eval")
    def eval_candidates(
        self, op_id: int, rows_a: np.ndarray, rows_b: np.ndarray,
        values: Optional[np.ndarray] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Evaluate op over child *rows*; returns (values (B,S), valid (B,)).

        Routed through the execution engine — the canonical value rules
        (core/validity.py) apply identically on every backend.
        """
        x = self.values_matrix() if values is None else values
        return self.engine.eval_block(
            op_id, x[rows_a], x[rows_b], self.l_bound, self.u_bound
        )

    # ------------------------------------------------------------------
    # generation driver
    # ------------------------------------------------------------------
    def generate(self) -> "FeatureSpace":
        for rung in range(1, self.max_rung + 1):
            last = rung == self.max_rung
            n_before = len(self.features)
            for op in self.ops:  # operator outer loop (paper P1)
                ia, ib, units = self._host_valid_children(op, rung)
                if len(ia) == 0:
                    continue
                rows_a = np.asarray([self.features[i].row for i in ia], np.int32)
                rows_b = np.asarray([self.features[i].row for i in ib], np.int32)
                if last and self.on_the_fly:
                    # paper P3: defer evaluation to SIS; store integer triples.
                    self.candidates.append(CandidateBlock(op.op_id, rows_a, rows_b))
                    continue
                for lo in range(0, len(ia), self.eval_batch):
                    hi = min(lo + self.eval_batch, len(ia))
                    vals, valid = self.eval_candidates(
                        op.op_id, rows_a[lo:hi], rows_b[lo:hi]
                    )
                    self.n_rejected["value"] += int((~valid).sum())
                    keep = np.nonzero(valid)[0]
                    if len(keep) == 0:
                        continue
                    blk_units, blk_exprs, blk_cx = [], [], []
                    blk_a, blk_b = [], []
                    for k in keep:
                        fa = self.features[int(ia[lo + k])]
                        fb = self.features[int(ib[lo + k])]
                        children = (fa.expr,) if op.arity == 1 else (fa.expr, fb.expr)
                        blk_units.append(units[lo + k])
                        blk_exprs.append(ops_mod.expr_string(op, *children))
                        blk_cx.append(ops_mod.complexity_of(
                            op, fa.complexity, fb.complexity))
                        blk_a.append(fa.fid)
                        blk_b.append(fb.fid)
                    self.admit_block(
                        rung=rung, values=vals[keep], units=blk_units,
                        exprs=blk_exprs, complexities=blk_cx,
                        op_id=op.op_id, child_a=blk_a, child_b=blk_b,
                    )
            log.info(
                "rung %d: +%d materialized features (%d candidates deferred)",
                rung, len(self.features) - n_before, self.n_candidates_deferred,
            )
        return self

    # ------------------------------------------------------------------
    # SIS-facing API
    # ------------------------------------------------------------------
    @property
    def n_candidates_deferred(self) -> int:
        return sum(len(c) for c in self.candidates)

    @property
    def n_total(self) -> int:
        return len(self.features) + self.n_candidates_deferred

    def iter_candidate_batches(self, batch: int) -> Iterator[CandidateBlock]:
        """Yield deferred candidates in same-operator blocks of <= batch."""
        for blk in self.candidates:
            for lo in range(0, len(blk), batch):
                hi = min(lo + batch, len(blk))
                yield CandidateBlock(blk.op_id, blk.child_a[lo:hi], blk.child_b[lo:hi])

    def feature_by_row(self, row: int) -> Feature:
        if 0 <= row < len(self._row_fids):
            return self.features[self._row_fids[row]]
        raise KeyError(row)

    def materialize_candidate(
        self, op_id: int, row_a: int, row_b: int
    ) -> Optional[Feature]:
        """Turn a SIS-selected deferred candidate into a real Feature."""
        op = ops_mod.OPS[op_id]
        fa = self.feature_by_row(int(row_a))
        fb = self.feature_by_row(int(row_b))
        vals, valid = self.eval_candidates(
            op_id, np.asarray([row_a]), np.asarray([row_b])
        )
        if not bool(valid[0]):
            return None
        u = op.unit_rule(fa.unit) if op.arity == 1 else op.unit_rule(fa.unit, fb.unit)
        if u is None:
            return None
        children = (fa.expr,) if op.arity == 1 else (fa.expr, fb.expr)
        return self._add_feature(
            rung=self.max_rung, unit=u,
            expr=ops_mod.expr_string(op, *children),
            complexity=ops_mod.complexity_of(op, fa.complexity, fb.complexity),
            values=vals[0], op_id=op_id, child_a=fa.fid, child_b=fb.fid,
        )
