"""Pluggable execution-engine layer for the three SISSO hot phases.

The paper's central claim is *portability*: one expression of the
time-dominating phases (feature creation, SIS screening, ℓ0 regression)
dispatched to whatever hardware is available — the Kokkos single-source
discipline.  Here that translates to a :class:`Backend` contract with one
implementation of the screening math per execution strategy:

========== =============================================================
backend     execution strategy
========== =============================================================
reference   host numpy, literal textbook formulas — the bit-exact oracle
jnp         jit-cached XLA (MXU matmuls + vmapped solves)
pallas      jnp + Pallas kernels on the hot paths (fused gen+SIS,
            ℓ0 pair tiles); interpret mode on CPU, Mosaic on TPU
sharded     composable distribution wrapper over any inner backend
            (``sharded:pallas`` etc.): shard_map + device top-k merges
========== =============================================================

Core code (``core/sis.py``, ``core/l0.py``, ``core/feature_space.py``)
never branches on *how* a phase executes; it calls the :class:`Engine` it
was handed and gets each block's winners back (:class:`Backend`).
Capability flags let a backend decline a (phase, shape) combo — the class
hierarchy then falls back to the jnp path, so every backend accepts every
request.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..core.sis import ReducedBlock, ScoreContext, TaskLayout
from ..core.l0 import GramStats
from ..runtime import trace

#: ``stats["sis_paths"]`` names of the paths a deferred block can take
SIS_FUSED = "fused kernel"
SIS_FUSED_SHARDED = "fused kernel in shard_map"
SIS_EVAL_SCREEN = "evaluate then screen"


@dataclasses.dataclass
class L0Problem:
    """One ℓ0 sweep's operands, prepared once and scored block-by-block.

    Problem-tagged (core/problem.py): ``problem`` names the tuple
    objective.  Regression fills ``stats`` (Gram sufficient statistics);
    classification fills ``cstats`` (per-task per-class domain boxes).
    Per-problem jit caches are filled in by the backend's
    :meth:`Backend.prepare_l0`.
    """

    x: np.ndarray            # (m, S) subspace feature values
    y: np.ndarray            # (S,) target (regression) or class labels
    layout: TaskLayout
    method: str              # 'gram' (closed form) | 'qr' (paper-faithful)
    dtype: Any
    stats: Optional[GramStats] = None
    cache: Dict[str, Any] = dataclasses.field(default_factory=dict)
    backend: str = ""        # name of the backend that prepared this problem
    problem: str = "regression"
    cstats: Any = None       # core.problem.ClassStats (classification only)

    @property
    def m(self) -> int:
        return int(self.x.shape[0])


class Backend(abc.ABC):
    """One execution strategy for the three hot phases.

    The contract between the engine and core is the *reduced block*: every
    screening and ℓ0 entry takes the number of winners the caller keeps
    (``n_keep``) and answers with a :class:`~repro.core.sis.ReducedBlock`
    of at most that many (index, score) winners, best-first, in the stable
    first-occurrence tie order of a sort of the full block.  No full score
    vector crosses this boundary; backends that form one reduce it
    themselves (:class:`ScoringBackend`).

    Capability flags:

    * ``fused_deferred`` — :meth:`sis_topk_deferred` generates, validates
      and scores candidate values without materializing them (paper P3).
    * ``l0_widths`` — tuple widths :meth:`l0_topk` accelerates with a
      backend-native kernel; other widths delegate to the generic (jnp)
      implementation.  ``None`` means the backend's one implementation
      covers every width (reference, jnp).
    * ``kernel_problems`` — problem kinds (core/problem.py) the backend's
      *native* fast paths cover; a problem-tagged context/L0Problem whose
      kind is outside this set routes to the generic jnp / compose
      implementations instead (e.g. the Pallas fused-SIS and Gram-gather
      kernels are regression-only, so ``PallasBackend`` declares
      ``("regression",)`` and classification falls through to its jnp
      parent — semantics stay canonical, only the acceleration differs).

    Precision: ``compute_dtype`` (set via :meth:`set_precision` from the
    ``precision.py`` registry) is the dtype device backends run the
    screening matmuls and ℓ0 solves in; the fp64 default preserves the
    historical pins.  The reference backend stays a literal fp64 oracle
    regardless.
    """

    name: str = "abstract"
    fused_deferred: bool = False
    l0_widths: Optional[Tuple[int, ...]] = None
    compute_dtype: Any = np.float64
    kernel_problems: Tuple[str, ...] = ("regression", "classification")

    def set_precision(self, precision: str) -> "Backend":
        """Select the compute dtype by registry name (bf16 | fp32 | fp64).

        Goes through :func:`repro.precision.set_precision`, the owner of
        the global x64 switch, so requesting fp64 works outside the solver
        too."""
        from ..precision import set_precision

        self.compute_dtype = set_precision(precision)
        return self

    @property
    def score_ctx_dtype(self):
        """Master dtype for screening-context operands (membership,
        normalized residuals).  Capped at fp32 — the historical storage
        format, per the paper's FP32 mode — unless the compute dtype is
        narrower (bf16); backends upcast at the matmul."""
        return (
            self.compute_dtype
            if np.dtype(self.compute_dtype).itemsize < 4
            else np.float32
        )

    # -- phase 1: candidate evaluation + value rules -------------------
    @abc.abstractmethod
    def eval_block(
        self,
        op_id: int,
        a: np.ndarray,  # (B, S) child-1 values
        b: np.ndarray,  # (B, S) child-2 values (== a for unary ops)
        l_bound: float,
        u_bound: float,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Evaluate one operator over child-value blocks.

        Returns ``(values (B, S) float64, valid (B,) bool)`` under the
        canonical value rules (core/validity.py).
        """

    # -- phase 2: SIS screening ----------------------------------------
    @abc.abstractmethod
    def sis_topk(
        self,
        values: np.ndarray,
        ctx: ScoreContext,
        n_keep: int,
        mask: Optional[np.ndarray] = None,
    ) -> ReducedBlock:
        """Top-``n_keep`` of a materialized block; ``mask`` rows excluded."""

    @abc.abstractmethod
    def sis_topk_deferred(
        self,
        op_id: int,
        a: np.ndarray,
        b: np.ndarray,
        ctx: ScoreContext,
        l_bound: float,
        u_bound: float,
        n_keep: int,
    ) -> ReducedBlock:
        """Top-``n_keep`` of a *deferred* candidate block; candidates the
        value rules reject never win."""

    def l0_device_reducer(self, prob: "L0Problem", width: int,
                          k_local: int):
        """Optional traceable per-shard reducer for composed distribution.

        A backend whose ℓ0 kernel has a reduced top-k epilogue returns
        ``(specialize, operands)``.  ``specialize(tuples, n_valid,
        n_shards)`` looks at one padded tuple block (the Gram-gather
        kernel reads back its per-shard run counts) and returns a
        ``reducer(tup_blk, vld_blk, *operands)``, jit/shard_map-traceable,
        that yields ``(sse (k_local,) ascending fp32 with +inf sentinels,
        local_idx (k_local,) int32, floor)``; it returns the same object
        for the same static shape, so the wrapper's compiled program (cached
        per reducer) is lowered once per shape.  The distribution wrapper (engine/sharded.py)
        merges the O(k) winner panels across shards without ever
        materializing a per-shard SSE vector on the host.  ``None`` (the
        default) means "no device reducer for this problem/width"; the
        wrapper falls back to a traceable exact scorer + per-shard
        ``top_k``.  Reducer outputs are a fp32 prescreen: the wrapper must
        rescore the merged survivors in fp64 before final ranking.
        """
        return None

    # -- phase 3: ℓ0 tuple search --------------------------------------
    def prepare_l0(
        self,
        x: np.ndarray,
        y: np.ndarray,
        layout: TaskLayout,
        method: str = "gram",
        dtype: Any = np.float64,
        problem: str = "regression",
    ) -> L0Problem:
        prob = L0Problem(
            x=np.asarray(x, np.float64), y=np.asarray(y, np.float64),
            layout=layout, method=method, dtype=dtype, backend=self.name,
            problem=problem,
        )
        if problem == "classification":
            from ..core.problem import compute_class_stats

            prob.cstats = compute_class_stats(prob.x, prob.y, layout)
        return prob

    @abc.abstractmethod
    def l0_topk(self, prob: "L0Problem", tuples: np.ndarray,
                n_keep: int) -> ReducedBlock:
        """Best-``n_keep`` (ascending objective) of one (B, n) tuple block.

        Regression: total SSE of the per-task LSQ fits; classification:
        domain-overlap count + tie term (core/problem.py)."""

    def rescore_window(self, n_keep: int) -> int:
        """Prescreened ℓ0 survivors per block that are rescored exactly
        for a top-``n_keep`` (backends whose ℓ0 kernel is an fp32 prescreen
        and the distribution wrapper composed over them)."""
        return 2 * int(n_keep)

    def record_sis_path(self, path: str) -> None:
        """Count one deferred-candidate block scored by ``path``, reported
        as ``SissoFit.stats["sis_paths"]`` (``{path: blocks}``)."""
        trace.count(("sis_paths", path))

    def record_l0_path(self, width: int, path: str) -> None:
        """Count one ℓ0 block of tuple ``width`` scored by ``path``.

        The count belongs to the active fit (runtime/trace.py), which
        reports it as ``SissoFit.stats["l0_paths"]`` (``{width: {path:
        blocks}}``), so a caller sees which path each width took rather
        than predicting it."""
        trace.count(("l0_paths", int(width), path))

    # -- prediction: compiled descriptor programs ----------------------
    def eval_program(self, program, x: np.ndarray) -> np.ndarray:
        """Descriptor values (n_outputs, S) for primary rows ``x (n_inputs, S)``.

        ``program`` is a :class:`~repro.core.descriptor.DescriptorProgram`
        (a fitted model's lineage DAG flattened into a tape).  The default
        replays the tape on host through the same ``apply_op`` math that
        ``eval_block`` ran during training, so predict-on-train reproduces
        the training value matrix exactly; the jnp family overrides this
        with one jit-cached whole-program closure per batch shape.
        """
        from ..core.descriptor import eval_program_host

        return eval_program_host(program, x)


class ScoringBackend(Backend):
    """A backend that does the screening math itself: reference, jnp and,
    through jnp, pallas.

    It scores whole blocks (:meth:`sis_scores`, :meth:`sis_scores_deferred`,
    :meth:`l0_scores`) and, unless it overrides a reduced entry with a
    device reduction, answers the :class:`Backend` contract by reducing
    its own full vector on the host (:meth:`ReducedBlock.reduce_host`).
    That reduction keeps the stable first-occurrence tie order of a sort
    of the full vector, so a host-reducing backend and a device-merging
    one (engine/sharded.py) are interchangeable winner for winner.
    """

    @abc.abstractmethod
    def sis_scores(self, values: np.ndarray, ctx: ScoreContext) -> np.ndarray:
        """Projection scores (B,) of materialized candidate values."""

    def sis_scores_deferred(
        self,
        op_id: int,
        a: np.ndarray,
        b: np.ndarray,
        ctx: ScoreContext,
        l_bound: float,
        u_bound: float,
    ) -> np.ndarray:
        """Scores (B,) of *deferred* candidates; invalid -> -inf.

        Default composition: evaluate, apply value rules, score.  Backends
        with ``fused_deferred`` overrule this with a fused kernel.
        """
        self.record_sis_path(SIS_EVAL_SCREEN)
        values, valid = self.eval_block(op_id, a, b, l_bound, u_bound)
        scores = self.sis_scores(values, ctx)
        return np.where(valid, scores, -np.inf)

    @abc.abstractmethod
    def l0_scores(self, prob: L0Problem, tuples: np.ndarray) -> np.ndarray:
        """Tuple objectives (B,), ascending-is-better, for (B, n) tuples."""

    def sis_topk(self, values, ctx, n_keep, mask=None) -> ReducedBlock:
        return ReducedBlock.reduce_host(
            self.sis_scores(values, ctx), n_keep, mask=mask, largest=True
        )

    def sis_topk_deferred(self, op_id, a, b, ctx, l_bound, u_bound,
                          n_keep) -> ReducedBlock:
        return ReducedBlock.reduce_host(
            self.sis_scores_deferred(op_id, a, b, ctx, l_bound, u_bound),
            n_keep, largest=True,
        )

    def l0_topk(self, prob, tuples, n_keep) -> ReducedBlock:
        return ReducedBlock.reduce_host(
            self.l0_scores(prob, tuples), n_keep, largest=False
        )


class Engine:
    """Phase→backend dispatcher threaded through the whole SISSO pipeline.

    A thin façade over one :class:`Backend`: the solver, feature space, SIS
    screen and ℓ0 search all hold the same ``Engine`` and never ask *how*
    their math runs.  Every screening and ℓ0 call states how many winners
    the caller keeps and gets a :class:`~repro.core.sis.ReducedBlock` of
    at most that many back — the host boundary carries k-sized payloads,
    never full scores.
    """

    def __init__(self, backend: Backend):
        self.backend = backend

    @property
    def name(self) -> str:
        return self.backend.name

    def set_precision(self, precision: str) -> "Engine":
        self.backend.set_precision(precision)
        return self

    def __repr__(self) -> str:
        return f"Engine({self.backend.name})"

    def eval_block(self, op_id, a, b, l_bound, u_bound):
        return self.backend.eval_block(op_id, a, b, l_bound, u_bound)

    def sis_topk(self, values, ctx, n_keep, mask=None) -> ReducedBlock:
        return self.backend.sis_topk(values, ctx, n_keep, mask=mask)

    def sis_topk_deferred(self, op_id, a, b, ctx, l_bound, u_bound,
                          n_keep) -> ReducedBlock:
        return self.backend.sis_topk_deferred(
            op_id, a, b, ctx, l_bound, u_bound, n_keep
        )

    def prepare_l0(self, x, y, layout, method="gram", dtype=None,
                   problem="regression"):
        dtype = self.backend.compute_dtype if dtype is None else dtype
        return self.backend.prepare_l0(x, y, layout, method=method,
                                       dtype=dtype, problem=problem)

    def l0_topk(self, prob, tuples, n_keep) -> ReducedBlock:
        return self.backend.l0_topk(prob, tuples, n_keep)

    def eval_program(self, program, x):
        return self.backend.eval_program(program, x)
