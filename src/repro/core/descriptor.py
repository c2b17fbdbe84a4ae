"""Compiled descriptor programs: out-of-sample evaluation of fitted models.

A fitted SISSO model is a handful of :class:`~repro.core.feature_space.Feature`
records whose values were materialized *for the training samples only* — the
solver never needed anything else.  To predict on new samples the selected
features' lineage DAGs (``op_id``/``child_a``/``child_b`` down to the primary
inputs) are compiled here into a :class:`DescriptorProgram`: a flat,
topologically-ordered instruction tape over input slots, independent of the
:class:`~repro.core.feature_space.FeatureSpace` that produced it, and therefore
serializable into a model artifact (api/artifact.py).

Evaluation is dispatched through the execution-engine layer
(``Engine.eval_program``): the default host path replays the tape through
``apply_op`` — the single source of truth for the operator math, which is what
every backend's ``eval_block`` used during training — so *predict-on-train
reproduces the training value matrix bit-for-bit*.  The jnp backend compiles
the whole tape into one jit-cached closure (one executable per batch shape,
reused across serving requests).  Each tape step sits behind an
optimization barrier: XLA would otherwise rewrite across steps (``x /
sqrt(y)`` becomes ``x * rsqrt(y)``, one ulp off the per-op training value),
so the compiled program runs exactly the per-op HLO that training ran.  That
makes predict-on-train bit-exact on a CPU; a TPU emulates fp64, and there
the compiled program can still round the last bit differently from the
per-op training path, which round-trips every value through the host.

Pure numpy is deliberately *not* used for the math: host libm and XLA disagree
in the last ulp on transcendentals (exp/log/cbrt), which would break the exact
predict-on-train == ``values_matrix()`` gather contract.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .operators import apply_op


@dataclasses.dataclass(frozen=True)
class Instruction:
    """One tape step: ``tape[out] = op(tape[a], tape[b])`` (b == a for unary)."""

    op_id: int
    a: int
    b: int


@dataclasses.dataclass(frozen=True)
class DescriptorProgram:
    """A standalone evaluation program for one model's descriptor.

    Tape slots ``0..n_inputs-1`` are the primary-input rows (one per column
    of the user's ``X``, in training order); each instruction appends one
    slot.  ``outputs`` name the slots holding the descriptor components.
    Frozen + tuple-typed so programs are hashable — backends key their
    compiled-closure caches on the program itself.
    """

    n_inputs: int
    input_names: Tuple[str, ...]
    instructions: Tuple[Instruction, ...]
    outputs: Tuple[int, ...]
    exprs: Tuple[str, ...]

    @property
    def n_outputs(self) -> int:
        return len(self.outputs)

    # -- artifact (de)serialization ------------------------------------
    def to_dict(self) -> dict:
        return {
            "n_inputs": self.n_inputs,
            "input_names": list(self.input_names),
            "instructions": [[i.op_id, i.a, i.b] for i in self.instructions],
            "outputs": list(self.outputs),
            "exprs": list(self.exprs),
        }

    @staticmethod
    def from_dict(d: dict) -> "DescriptorProgram":
        return DescriptorProgram(
            n_inputs=int(d["n_inputs"]),
            input_names=tuple(d["input_names"]),
            instructions=tuple(
                Instruction(int(op), int(a), int(b))
                for op, a, b in d["instructions"]
            ),
            outputs=tuple(int(o) for o in d["outputs"]),
            exprs=tuple(d["exprs"]),
        )


def compile_features(features: Sequence, fspace) -> DescriptorProgram:
    """Compile selected features' lineage DAGs into one shared-tape program.

    Shared subexpressions (a child feeding several selected features) are
    emitted once.  ``fspace`` supplies the fid -> Feature table and the
    primary fid -> input-column mapping.
    """
    slot: Dict[int, int] = {}
    instructions: List[Instruction] = []
    n_inputs = fspace.n_primary_inputs

    def visit(fid: int) -> int:
        if fid in slot:
            return slot[fid]
        f = fspace.features[fid]
        if f.op_id is None:  # primary input
            s = fspace.primary_columns[f.fid]
        else:
            a = visit(f.child_a)
            b = visit(f.child_b if f.child_b is not None else f.child_a)
            s = n_inputs + len(instructions)
            instructions.append(Instruction(int(f.op_id), a, b))
        slot[fid] = s
        return s

    outputs = tuple(visit(f.fid) for f in features)
    return DescriptorProgram(
        n_inputs=n_inputs,
        input_names=tuple(fspace.primary_names),
        instructions=tuple(instructions),
        outputs=outputs,
        exprs=tuple(f.expr for f in features),
    )


def eval_program_host(program: DescriptorProgram, x: np.ndarray) -> np.ndarray:
    """Replay the tape eagerly on host; returns (n_outputs, S) float64.

    The default ``Backend.eval_program`` — same ``apply_op`` math the
    backend's ``eval_block`` ran during training, so results match the
    training value matrix exactly.
    """
    x = np.asarray(x, np.float64)
    if x.ndim != 2 or x.shape[0] != program.n_inputs:
        raise ValueError(
            f"program expects ({program.n_inputs}, S) primary rows, "
            f"got {x.shape}"
        )
    tape: List = [jnp.asarray(x[i]) for i in range(program.n_inputs)]
    with np.errstate(all="ignore"):
        for ins in program.instructions:
            tape.append(apply_op(ins.op_id, tape[ins.a], tape[ins.b]))
    return np.stack([np.asarray(tape[o], np.float64) for o in program.outputs])


def program_evaluator_jnp(program: DescriptorProgram):
    """One jit-compiled closure for the whole tape (jnp/pallas/sharded path).

    ``jax.jit`` caches one executable per input shape, which is exactly the
    per-batch-shape compile cache the serving layer relies on.
    """

    def run(x: jnp.ndarray) -> jnp.ndarray:  # (n_inputs, S) -> (n_outputs, S)
        tape = [x[i] for i in range(program.n_inputs)]
        for ins in program.instructions:
            tape.append(jax.lax.optimization_barrier(
                apply_op(ins.op_id, tape[ins.a], tape[ins.b])))
        return jnp.stack([tape[o] for o in program.outputs])

    return jax.jit(run)
