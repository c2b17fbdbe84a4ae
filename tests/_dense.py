"""Test helper: spread a reduced block back over its source block.

Backends answer every block with its winners (``ReducedBlock``); a test
that wants every score asks for ``n_keep`` equal to the block length and
densifies the answer here.
"""
import numpy as np


def densify(rb, largest=True):
    """``(rb.n_source,)`` vector: each winner's score at its index, the
    losing sentinel elsewhere (``-inf`` for SIS scores, ``+inf`` for ℓ0
    objectives) — the value a rejected candidate carries."""
    v = np.full(rb.n_source, -np.inf if largest else np.inf)
    v[np.asarray(rb.indices)] = np.asarray(rb.scores, np.float64)
    return v
