"""Helpers that keep the Pallas kernels 32-bit for Mosaic.

The solver runs with ``jax_enable_x64`` on (fp64 host statistics).  Under
it a Python scalar in a kernel body (``jnp.where(m, v, 0.0)``, a
``fori_loop`` bound) or in an index map traces as a 64-bit value, which
the TPU compiler refuses: Mosaic has no 64-bit vectors and its index maps
return int32.  :func:`x32_kernel` traces a kernel body with x64 off and
:func:`block_spec` casts block indices to int32.  Interpret mode is
unaffected: the kernels' refs are 32-bit either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def x32_kernel(body):
    """Trace the kernel ``body`` with ``jax_enable_x64`` off."""

    @functools.wraps(body)
    def wrapped(*refs, **kwargs):
        with jax.enable_x64(False):
            return body(*refs, **kwargs)

    return wrapped


def block_spec(block_shape, index_map) -> pl.BlockSpec:
    """``pl.BlockSpec(block_shape, index_map)`` with int32 block indices."""

    def i32_map(*grid_idx):
        return tuple(jnp.asarray(b, jnp.int32) for b in index_map(*grid_idx))

    return pl.BlockSpec(block_shape, i32_map)
