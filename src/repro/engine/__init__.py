"""Execution-engine layer: one screening-math expression per backend.

Select with :func:`get_engine` (``SissoConfig.backend`` / ``--backend``)::

    engine = get_engine("pallas")             # or reference | jnp | sharded
    engine = get_engine("pallas", interpret=True)
    engine = get_engine("sharded:pallas")     # distribution over any inner
    engine = get_engine(existing_engine)      # pass-through

``"sharded"`` is the :class:`~.sharded.ShardedExecution` *wrapper* —
distribution is a composable layer, not a leaf backend — and the
``"sharded:<inner>"`` spelling picks the backend it wraps (default jnp).

See engine/base.py for the Backend contract and ARCHITECTURE.md for the
phase→backend dispatch table.
"""
from __future__ import annotations

from typing import Union

from .base import Backend, Engine, L0Problem, ReducedBlock, ScoringBackend
from .streaming import BlockPrefetcher
from .reference import ReferenceBackend
from .jnp_backend import JnpBackend
from .pallas_backend import PallasBackend
from .sharded import ShardedBackend, ShardedExecution
from .resilient import ResilientExecution

BACKENDS = {
    "reference": ReferenceBackend,
    "jnp": JnpBackend,
    "pallas": PallasBackend,
    "sharded": ShardedExecution,
    "resilient": ResilientExecution,
}

#: default execution backend (jit-cached XLA) when none is configured.
DEFAULT_BACKEND = "jnp"


def get_engine(spec: Union[str, Engine, Backend, None] = None, **opts) -> Engine:
    """Resolve a backend name / instance into an :class:`Engine`.

    String specs accept the composed form ``"sharded:<inner>"`` (e.g.
    ``"sharded:pallas"``): the distribution wrapper over the named inner
    backend, with ``**opts`` forwarded to the wrapper (``mesh=...``) /
    inner construction.
    """
    if spec is None:
        spec = DEFAULT_BACKEND
    if isinstance(spec, Engine):
        return spec
    if isinstance(spec, Backend):
        return Engine(spec)
    if isinstance(spec, str) and spec.startswith("sharded:"):
        inner = spec.split(":", 1)[1]
        if inner not in BACKENDS or inner in ("sharded", "resilient"):
            raise ValueError(
                f"unknown inner backend {inner!r} in {spec!r}; expected "
                f"one of {sorted(set(BACKENDS) - {'sharded', 'resilient'})}"
            )
        return Engine(ShardedExecution(inner=inner, **opts))
    if isinstance(spec, str) and spec.startswith("resilient:"):
        # fault-tolerance wrapper (engine/resilient.py) over any inner
        # spec — including composed ones ("resilient:sharded:pallas")
        inner = spec.split(":", 1)[1]
        return Engine(ResilientExecution(inner=inner, **opts))
    try:
        cls = BACKENDS[spec]
    except KeyError:
        raise ValueError(
            f"unknown backend {spec!r}; expected one of {sorted(BACKENDS)} "
            f"or 'sharded:<inner>'"
        ) from None
    return Engine(cls(**opts))


__all__ = [
    "Backend", "Engine", "L0Problem", "ReducedBlock", "BACKENDS",
    "BlockPrefetcher", "DEFAULT_BACKEND", "get_engine", "ReferenceBackend",
    "JnpBackend", "PallasBackend", "ResilientExecution", "ScoringBackend",
    "ShardedBackend", "ShardedExecution",
]
