"""Distribution as a composable execution layer.

:class:`ShardedExecution` is a *wrapper*, not an inheritance leaf: it
composes over any inner backend (jnp, pallas, even reference) and owns
exactly one concern — how blocks shard over a device mesh and how their
winners merge.  Candidate features (SIS) and tuple blocks (ℓ0) shard over
the mesh's ``data`` (+``pod``) axes; samples shard over ``model`` when the
mesh has one (Gram/projection partial sums are psum'ed —
core/distributed.py).  On a single-device container this degenerates to a
1-shard mesh: the same code path, exercised end-to-end, which is exactly
what the parity suite needs before a multi-host run is attempted.

The merge discipline is the paper's: each shard keeps only its local top
candidates and a k-sized all-gather combines them (SISSO++ never ships
full score vectors off-device), so ``sis_topk``/``l0_topk`` return
:class:`~repro.core.sis.ReducedBlock` winners — O(k) payloads across the
host boundary.  When the inner backend brings the fused Pallas deferred
kernel (pallas), the wrapper runs it *inside* ``shard_map``
(core/distributed.py:fused_sis_topk_sharded): the deferred SIS screen is
fused and distributed at once.

``ShardedBackend`` (the old ``JnpBackend`` subclass) survives as a
deprecated constructor shim over ``ShardedExecution(JnpBackend(), ...)``.
"""
from __future__ import annotations

import threading
import warnings
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.distributed import (
    _dp_axes, _sample_axis, count_merge, fused_sis_topk_sharded,
    gram_operands, gram_topk_scorer, make_l0_topk_fn,
    make_l0_topk_reduced_fn, overlap_operands, overlap_sis_topk_sharded,
    overlap_topk_scorer, qr_topk_scorer, sis_topk_sharded,
)
from ..core.l0 import compute_gram_stats
from ..core.sis import ReducedBlock, ScoreContext
from .base import (
    SIS_EVAL_SCREEN, SIS_FUSED_SHARDED, Backend, Engine, L0Problem,
)


def default_mesh() -> Mesh:
    """1-D data mesh over every visible device."""
    return Mesh(np.asarray(jax.devices()), ("data",))


class ShardedExecution(Backend):
    """Cross-cutting distribution layer over an inner execution backend.

    Everything the mesh does not change — operator evaluation, value
    rules, compiled-descriptor prediction, ℓ0 problem preparation — is
    delegated to ``inner`` untouched, so the wrapper composes with the
    Pallas kernels exactly as it does with plain jnp.  What the wrapper
    owns:

    * ``sis_topk`` / ``l0_topk`` — per-shard scoring, in-shard padding
      masks, local top-k, k-sized all-gather merge on device.
    * ``sis_topk_deferred`` — the shard_map-wrapped fused Pallas kernel
      when ``inner.fused_deferred`` (and samples are replicated);
      eval-compose otherwise.
    """

    def __init__(self, inner: Union[Backend, Engine, str, None] = None,
                 mesh: Optional[Mesh] = None, **inner_opts):
        if isinstance(inner, Engine):
            inner = inner.backend
        if inner is None or isinstance(inner, str):
            from . import BACKENDS  # deferred: package imports this module

            name = inner or "jnp"
            if name == "sharded" or name.startswith("sharded:"):
                raise ValueError("cannot nest ShardedExecution in itself")
            inner = BACKENDS[name](**inner_opts)
        elif inner_opts:
            raise ValueError(
                "inner_opts only apply when the inner backend is built "
                "from a name"
            )
        if isinstance(inner, ShardedExecution):
            raise ValueError("cannot nest ShardedExecution in itself")
        self.inner = inner
        self.name = "sharded" if inner.name == "jnp" else f"sharded:{inner.name}"
        self.fused_deferred = inner.fused_deferred
        self.l0_widths = inner.l0_widths if inner.l0_widths is None \
            else tuple(sorted(set(inner.l0_widths) | {2}))
        # the wrapper shards both problems natively (regression matmul
        # screen, classification overlap screen + the generic ℓ0 reducer)
        self.kernel_problems = ("regression", "classification")
        self.mesh = mesh if mesh is not None else default_mesh()
        dp = _dp_axes(self.mesh)
        if not dp:
            raise ValueError("sharded backend needs a 'data' or 'pod' mesh axis")
        self._nd = int(np.prod([self.mesh.shape[a] for a in dp]))
        # guards per-problem compiled-reducer fills (prefetch worker threads)
        self._cache_lock = threading.Lock()

    @property
    def n_shards(self) -> int:
        """Devices the candidate axes shard over."""
        return self._nd

    def set_precision(self, precision: str) -> "ShardedExecution":
        super().set_precision(precision)
        self.inner.set_precision(precision)
        return self

    def _pad(self, n: int) -> int:
        return ((n + self._nd - 1) // self._nd) * self._nd

    # -- delegated phases ----------------------------------------------
    def eval_block(self, op_id, a, b, l_bound, u_bound):
        return self.inner.eval_block(op_id, a, b, l_bound, u_bound)

    def eval_program(self, program, x):
        return self.inner.eval_program(program, x)

    def prepare_l0(self, x, y, layout, method="gram", dtype=np.float64,
                   problem="regression"):
        prob = self.inner.prepare_l0(x, y, layout, method=method, dtype=dtype,
                                     problem=problem)
        if problem == "regression" and method == "gram" \
                and prob.stats is None:
            # inner backends without a Gram cache (reference) still shard
            # through the closed-form scorer
            prob.stats = compute_gram_stats(
                jnp.asarray(prob.x), jnp.asarray(prob.y), layout, dtype
            )
        prob.backend = self.name
        return prob

    # -- SIS: sharded scoring ------------------------------------------
    def _padded_values(self, values, mask):
        v = np.asarray(values, np.float64)
        f = len(v)
        fp = self._pad(f)
        vp = np.zeros((fp, v.shape[1]))
        vp[:f] = v
        row_mask = np.zeros((fp,), bool)
        row_mask[:f] = True if mask is None else np.asarray(mask, bool)
        return jnp.asarray(vp, self.compute_dtype), jnp.asarray(row_mask), f

    def sis_topk(self, values, ctx: ScoreContext, n_keep: int,
                 mask=None) -> ReducedBlock:
        if len(values) == 0:
            return ReducedBlock(
                indices=np.zeros((0,), np.int64), scores=np.zeros((0,)),
                n_source=0,
            )
        if ctx.problem == "classification" \
                and _sample_axis(self.mesh) is not None:
            # the overlap score needs whole sample rows; sample-sharded
            # meshes leave the screen to the inner backend
            return self.inner.sis_topk(values, ctx, n_keep, mask=mask)
        vp, row_mask, f = self._padded_values(values, mask)
        if ctx.problem == "classification":
            vals, idx = overlap_sis_topk_sharded(
                self.mesh, vp, ctx, row_mask, n_keep)
        else:
            vals, idx = sis_topk_sharded(self.mesh, vp, ctx, row_mask, n_keep)
        keep = vals > -np.inf
        return ReducedBlock(
            indices=idx[keep].astype(np.int64), scores=vals[keep], n_source=f
        )

    def sis_topk_deferred(self, op_id, a, b, ctx, l_bound, u_bound,
                          n_keep) -> ReducedBlock:
        if self.inner.fused_deferred and _sample_axis(self.mesh) is None \
                and ctx.problem in self.inner.kernel_problems:
            self.record_sis_path(SIS_FUSED_SHARDED)
            vals, idx = fused_sis_topk_sharded(
                self.mesh, op_id, jnp.asarray(a), jnp.asarray(b), ctx,
                n_keep, l_bound, u_bound,
                block_b=getattr(self.inner, "block_b", 256),
                interpret=self.inner.resolved_interpret,
                epilogue_k=getattr(self.inner, "epilogue_k", 64),
                dtype=getattr(self.inner, "kernel_dtype", None),
            )
            keep = vals > -np.inf
            return ReducedBlock(
                indices=idx[keep].astype(np.int64), scores=vals[keep],
                n_source=len(a),
            )
        self.record_sis_path(SIS_EVAL_SCREEN)
        values, valid = self.inner.eval_block(op_id, a, b, l_bound, u_bound)
        return self.sis_topk(values, ctx, n_keep, mask=valid)

    # -- ℓ0: sharded scoring -------------------------------------------
    def _l0_reducer(self, prob: L0Problem, width: int, n_keep: int,
                    b_shard: int):
        """Compiled sharded ℓ0 reducer for one (width, n_keep, shard) shape.

        Prefers the inner backend's device-side reduced epilogue
        (``Backend.l0_device_reducer``, e.g. the Pallas Gram-gather top-k),
        keeping as many survivors as the inner backend rescores per block
        (``Backend.rescore_window``) — the kernel screen is an fp32 bound,
        so the wrapper rescores merged survivors in fp64 before the final
        ranking.  Falls back to the exact traceable scorers (overlap /
        Gram closed form / QR) when the inner backend has none.
        Returns ``(build, operands, prescreen, k_local)``; ``build(tuples,
        b)`` gives the compiled reducer for one padded block of ``b`` live
        tuples (the inner backend specializes its device reducer to what it
        reads off the block; the program is cached per reducer, across
        fits).
        """
        key = ("sharded_l0_topk", width, int(n_keep), int(b_shard))
        with self._cache_lock:
            entry = prob.cache.get(key)
            if entry is None:
                survivors = self.inner.rescore_window(n_keep)
                k_local = min(survivors, b_shard)
                dev = self.inner.l0_device_reducer(prob, width, k_local)
                if dev is not None:
                    specialize, operands = dev
                    k_merge = min(survivors, self._nd * k_local)

                    def build(tuples, b):
                        return make_l0_topk_reduced_fn(
                            self.mesh, specialize(tuples, b, self._nd),
                            k_local, k_merge, len(operands))

                    entry = prob.cache[key] = (build, operands, True, k_local)
                else:
                    if prob.problem == "classification":
                        scorer = overlap_topk_scorer()
                        operands = overlap_operands(prob.cstats)
                    elif prob.method == "gram":
                        scorer = gram_topk_scorer(prob.m)
                        operands = gram_operands(prob.stats)
                    else:
                        scorer = qr_topk_scorer(prob.layout, prob.dtype)
                        operands = (jnp.asarray(prob.x, prob.dtype),
                                    jnp.asarray(prob.y, prob.dtype))
                    k_local = min(int(n_keep), b_shard)
                    k_merge = min(int(n_keep), self._nd * k_local)
                    fn = make_l0_topk_fn(self.mesh, scorer, k_local, k_merge,
                                         len(operands))
                    entry = prob.cache[key] = (
                        lambda tuples, b: fn, operands, False, k_local)
        return entry

    def l0_topk(self, prob: L0Problem, tuples, n_keep: int) -> ReducedBlock:
        tuples = jnp.asarray(tuples, jnp.int32)
        b, width = int(tuples.shape[0]), int(tuples.shape[1])
        if b == 0:
            return ReducedBlock(
                indices=np.zeros((0,), np.int64), scores=np.zeros((0,)),
                n_source=0,
            )
        bp = self._pad(b)
        if bp != b:
            fill = jnp.broadcast_to(
                jnp.arange(width, dtype=jnp.int32)[None, :], (bp - b, width)
            )
            tuples = jnp.concatenate([tuples, fill], axis=0)
        valid = np.zeros((bp,), bool)
        valid[:b] = True
        build, operands, prescreen, k_local = self._l0_reducer(
            prob, width, int(n_keep), bp // self._nd)
        fn = build(tuples, b)
        count_merge("l0", self.mesh, k_local)
        out = fn(tuples, jnp.asarray(valid), *operands)
        sses, idx = np.asarray(out[0], np.float64), np.asarray(out[1])
        keep = np.isfinite(sses)
        sses, idx = sses[keep], idx[keep]
        if not prescreen:
            self.record_l0_path(width, "sharded exact scorer")
        elif len(idx):
            # the device screen is an fp32 bound; rescore the O(k)
            # survivors in fp64 and re-rank.  Candidates sort by global
            # index first so exact-SSE ties resolve to the lowest index
            # (stable-merge semantics).
            gidx = np.sort(np.unique(idx))
            exact = self.inner._exact_rescore(prob, tuples[jnp.asarray(gidx)])
            order = np.argsort(exact, kind="stable")[: int(n_keep)]
            order = order[np.isfinite(exact[order])]
            sses, idx = exact[order], gidx[order]
        if prescreen:
            # certified only when every tuple left out bounds above the
            # n_keep-th rescored SSE (PallasBackend.l0_topk); otherwise
            # the inner backend settles this block on one device
            floor = float(out[2])
            kth = sses[-1] if len(sses) == int(n_keep) else np.inf
            if floor != np.inf and not kth < floor:
                self.record_l0_path(width, "one-device fallback")
                return self.inner.l0_topk(prob, tuples[:b], n_keep)
            self.record_l0_path(width, "sharded Gram-gather kernel")
        return ReducedBlock(
            indices=idx.astype(np.int64), scores=sses, n_source=b
        )


class ShardedBackend(ShardedExecution):
    """Deprecated constructor shim: the pre-refactor inheritance leaf.

    ``ShardedBackend(mesh)`` behaves like
    ``ShardedExecution(JnpBackend(), mesh=mesh)``; distribution is a
    wrapper now, so it can also compose over the Pallas backend —
    construct ``ShardedExecution(inner, mesh=...)`` or spell the config
    backend ``"sharded:pallas"``.
    """

    def __init__(self, mesh: Optional[Mesh] = None):
        warnings.warn(
            "ShardedBackend is deprecated; use ShardedExecution(inner, "
            "mesh=...) — distribution now composes over any inner backend",
            DeprecationWarning, stacklevel=2,
        )
        super().__init__(inner=None, mesh=mesh)
