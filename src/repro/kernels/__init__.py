"""Pallas TPU kernels for the SISSO hot spots.

fused_sis.py and l0_gather.py (with topk.py's in-kernel epilogue) compile
for a TPU v5e: tests/test_tpu_compile.py compiles them for a described v5e,
and chip_smoke.py runs them on the chip.  l0_tile.py has only run in
interpret mode.  Off a TPU every kernel runs with ``interpret=True``.

fused_sis.py — P1+P2+P3: generate candidate values, validate, project against
              residuals entirely in VMEM (never materializes the last rung).
l0_tile.py   — P4: blocked Gram-tile pair scorer (MXU matmul + VPU closed-form
              solve + tile argmin), scalar-prefetched upper-triangle tiles.
l0_gather.py — P4 for any width ≥ 3: Gram-gather scorer over
              VMEM-resident Gram statistics, one run of prefix-sharing
              tuples per panel row (prefix columns by one-hot MXU matmul,
              unrolled elimination along the lanes), fp32 phase of the
              two-phase exact top-k.
topk.py      — in-kernel per-block top-k epilogue (iterative extraction) +
              the device-side tree merge across block panels (fused SIS).
unrank.py    — device-side combinatorial unranking: ℓ0 tuple blocks
              materialize from rank ranges, no host enumeration.
autotune.py  — P6: launch-config auto-tuning (block shapes, epilogue k).
ops.py       — jit'd wrappers, padding/layout policy, two-phase exact top-k.
ref.py       — pure-jnp oracles for every kernel.
"""
from . import ops, ref, autotune, unrank  # noqa: F401
