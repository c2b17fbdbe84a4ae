"""The work a campaign's algorithm needs, counted from the fit's own shapes,
and the chip's published peaks.

The counts are of the algorithm, never of an implementation: a kernel that
gathers by one-hot matrix products, or screens padded rows, does more, and
a later kernel that does less still reads the same work against its own
time.

* SIS, per dimension: every candidate not yet selected is correlated with
  every residual over the samples: ``2 S R`` operations for the products
  and ``3 S`` for its sum and sum of squares; its values are read once.
* ℓ0, per dimension ``n``: every ``n``-tuple of the subspace, in every
  task, costs one elimination of its ``(n+1) x (n+1)`` normal equations
  bordered by the target, ``sum_{k=1..n} (2 (n+1-k)^2 + (n+1-k))``
  operations; the Gram statistics are read once.
"""
from __future__ import annotations

import json
from math import comb
from pathlib import Path
from typing import Dict, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak operations/s and bytes/s of one chip of ``device_kind``."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(table)})")
    return table[device_kind]


def sis_work(shape: dict) -> Tuple[float, float]:
    """(operations, bytes) of one fit's SIS screens."""
    s, item = shape["samples"], shape["itemsize"]
    ops = nbytes = 0.0
    for dim in shape["dims"].values():
        n = dim["screened"]
        ops += n * (2.0 * s * dim["residuals"] + 3.0 * s)
        nbytes += n * s * item
    return ops, nbytes


def elimination_ops(width: int) -> int:
    return sum(2 * (width + 1 - k) ** 2 + (width + 1 - k)
               for k in range(1, width + 1))


def l0_work(shape: dict) -> Tuple[float, float]:
    """(operations, bytes) of one fit's ℓ0 searches."""
    t, item = shape["tasks"], shape["itemsize"]
    ops = nbytes = 0.0
    for width, dim in shape["dims"].items():
        m = dim["subspace"]
        ops += float(comb(m, int(width))) * t * elimination_ops(int(width))
        nbytes += t * (m * m + m + 1) * item
    return ops, nbytes


def roofline_seconds(ops: float, nbytes: float, peak: Dict[str, float]):
    """(least seconds the chip could take, which bound sets it)."""
    compute = ops / peak["flops_per_s"]
    memory = nbytes / peak["bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
