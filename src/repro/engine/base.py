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
was handed.  Capability flags let a backend decline a (phase, shape) combo
— the class hierarchy then falls back to the jnp path, so every backend
accepts every request.
"""
from __future__ import annotations

import abc
import dataclasses
import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..core.sis import ReducedBlock, ScoreContext, TaskLayout
from ..core.l0 import GramStats


#: guards :meth:`Backend.record_l0_path` (prefetch threads score blocks)
_PATH_LOCK = threading.Lock()


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

    Capability flags:

    * ``fused_deferred`` — :meth:`sis_scores_deferred` generates, validates
      and scores candidate values without materializing them (paper P3); if
      False the default eval→score→mask composition is used.
    * ``l0_widths`` — tuple widths :meth:`l0_scores` accelerates with a
      backend-native kernel; other widths delegate to the generic (jnp)
      implementation.  ``None`` means the backend's one implementation
      covers every width (reference, jnp).  Replaces the former boolean
      ``l0_pairs_only`` flag now that the Pallas path covers widths 2–4.
    * ``reduces_blocks`` — the backend merges score blocks *on device*:
      when a caller passes ``n_keep`` through the :class:`Engine`, the
      ``*_topk`` entry points return a
      :class:`~repro.core.sis.ReducedBlock` of O(k) winners instead of a
      full block-length vector (engine/sharded.py).
    * ``kernel_problems`` — problem kinds (core/problem.py) the backend's
      *native* fast paths cover; a problem-tagged context/L0Problem whose
      kind is outside this set routes to the generic jnp / compose
      implementations instead (e.g. the Pallas fused-SIS and Gram-gather
      kernels are regression-only, so ``PallasBackend`` declares
      ``("regression",)`` and classification falls through to its jnp
      parent — semantics stay canonical, only the acceleration differs).
    * ``bit_exact_oracle`` — results define the parity baseline.

    Precision: ``compute_dtype`` (set via :meth:`set_precision` from the
    ``precision.py`` registry) is the dtype device backends run the
    screening matmuls and ℓ0 solves in; the fp64 default preserves the
    historical pins.  The reference backend stays a literal fp64 oracle
    regardless.
    """

    name: str = "abstract"
    fused_deferred: bool = False
    l0_widths: Optional[Tuple[int, ...]] = None
    reduces_blocks: bool = False
    bit_exact_oracle: bool = False
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
        values, valid = self.eval_block(op_id, a, b, l_bound, u_bound)
        scores = self.sis_scores(values, ctx)
        return np.where(valid, scores, -np.inf)

    # -- pre-reduced blocks: device-merged top-k entry points ----------
    #
    # The Engine routes through these (instead of the full-vector methods
    # above) when the caller supplies ``n_keep`` and the backend declares
    # ``reduces_blocks``.  The defaults reduce on host with the stable
    # tie order the full-vector TopK merge would produce, so a reducing
    # wrapper (engine/sharded.py) and a plain backend are interchangeable
    # winner-for-winner.

    def sis_topk(
        self,
        values: np.ndarray,
        ctx: ScoreContext,
        n_keep: int,
        mask: Optional[np.ndarray] = None,
    ) -> ReducedBlock:
        """Top-``n_keep`` of a materialized block; ``mask`` rows excluded."""
        return ReducedBlock.reduce_host(
            self.sis_scores(values, ctx), n_keep, mask=mask, largest=True
        )

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
        """Top-``n_keep`` of a deferred candidate block."""
        return ReducedBlock.reduce_host(
            self.sis_scores_deferred(op_id, a, b, ctx, l_bound, u_bound),
            n_keep, largest=True,
        )

    def l0_topk(self, prob: "L0Problem", tuples: np.ndarray,
                n_keep: int) -> ReducedBlock:
        """Best-``n_keep`` (ascending SSE) of one tuple block."""
        return ReducedBlock.reduce_host(
            self.l0_scores(prob, tuples), n_keep, largest=False
        )

    def l0_device_reducer(self, prob: "L0Problem", width: int,
                          k_local: int):
        """Optional traceable per-shard reducer for composed distribution.

        A backend whose ℓ0 kernel has a reduced top-k epilogue returns
        ``(reducer, operands)`` where ``reducer(tup_blk, vld_blk,
        *operands)`` is jit/shard_map-traceable and yields ``(sse
        (k_local,) ascending fp32 with +inf sentinels, local_idx (k_local,)
        int32)`` — the distribution wrapper (engine/sharded.py) then merges
        the O(k) winner panels across shards without ever materializing a
        per-shard SSE vector.  ``None`` (the default) means "no device
        reducer for this problem/width"; the wrapper falls back to its
        full-vector scorer + per-shard ``top_k``.  Reducer outputs are a
        fp32 prescreen: the wrapper must rescore the merged survivors in
        fp64 before final ranking.
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
    def l0_scores(self, prob: L0Problem, tuples: np.ndarray) -> np.ndarray:
        """Tuple objectives (B,), ascending-is-better, for (B, n) tuples.

        Regression: total SSE of the per-task LSQ fits; classification:
        domain-overlap count + tie term (core/problem.py)."""

    def rescore_window(self, n_keep: int) -> int:
        """Prescreened ℓ0 survivors per block that are rescored exactly
        for a top-``n_keep`` (backends whose ℓ0 kernel is an fp32 prescreen
        and the distribution wrapper composed over them)."""
        return 2 * int(n_keep)

    def record_l0_path(self, width: int, path: str) -> None:
        """Count one ℓ0 block of tuple ``width`` scored by ``path``.

        The solver reports the counts as ``SissoFit.stats["l0_paths"]``
        (``{width: {path: blocks}}``), so a caller sees which path each
        width took rather than predicting it."""
        with _PATH_LOCK:
            paths = self.__dict__.setdefault("l0_paths", {})
            by_path = paths.setdefault(int(width), {})
            by_path[path] = by_path.get(path, 0) + 1

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


class Engine:
    """Phase→backend dispatcher threaded through the whole SISSO pipeline.

    A thin façade over one :class:`Backend`: the solver, feature space, SIS
    screen and ℓ0 search all hold the same ``Engine`` and never ask *how*
    their math runs.  Exists as its own object (rather than passing the
    backend around) so cross-phase policy — streaming, async double
    buffering, multi-host merges — lands here without touching core code.

    The ``n_keep`` keywords are how distribution composes in: when the
    caller states how many winners it will keep *and* the backend merges
    on device (``reduces_blocks``), the call returns a
    :class:`~repro.core.sis.ReducedBlock` of O(n_keep) winners instead of
    a block-length score vector — the host boundary carries k-sized
    payloads, never full scores.  Callers that omit ``n_keep`` always get
    the classic full vectors.
    """

    def __init__(self, backend: Backend):
        self.backend = backend

    @property
    def name(self) -> str:
        return self.backend.name

    @property
    def reduces_blocks(self) -> bool:
        return self.backend.reduces_blocks

    def set_precision(self, precision: str) -> "Engine":
        self.backend.set_precision(precision)
        return self

    def __repr__(self) -> str:
        return f"Engine({self.backend.name})"

    def eval_block(self, op_id, a, b, l_bound, u_bound):
        return self.backend.eval_block(op_id, a, b, l_bound, u_bound)

    def sis_scores(self, values, ctx, n_keep=None, mask=None):
        if n_keep is not None and self.backend.reduces_blocks:
            return self.backend.sis_topk(values, ctx, n_keep, mask=mask)
        scores = self.backend.sis_scores(values, ctx)
        if mask is not None:
            # honor the exclusion mask on the full-vector path too — the
            # kwarg must mean the same thing on every backend
            scores = np.where(np.asarray(mask, bool), scores, -np.inf)
        return scores

    def sis_scores_deferred(self, op_id, a, b, ctx, l_bound, u_bound,
                            n_keep=None):
        if n_keep is not None and self.backend.reduces_blocks:
            return self.backend.sis_topk_deferred(
                op_id, a, b, ctx, l_bound, u_bound, n_keep
            )
        return self.backend.sis_scores_deferred(
            op_id, a, b, ctx, l_bound, u_bound
        )

    def prepare_l0(self, x, y, layout, method="gram", dtype=None,
                   problem="regression"):
        dtype = self.backend.compute_dtype if dtype is None else dtype
        return self.backend.prepare_l0(x, y, layout, method=method,
                                       dtype=dtype, problem=problem)

    def l0_scores(self, prob, tuples, n_keep=None):
        if n_keep is not None and self.backend.reduces_blocks:
            return self.backend.l0_topk(prob, tuples, n_keep)
        return self.backend.l0_scores(prob, tuples)

    def eval_program(self, program, x):
        return self.backend.eval_program(program, x)
