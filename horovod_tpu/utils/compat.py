"""The jax symbols this framework needs that jax 0.9.0 (the one installed
version; setup.py pins it) does not export from a stable public module —
one import site each, so a later jax moves one line."""

from __future__ import annotations

import jax
from jax._src import xla_bridge as _xla_bridge
# ``all_gather`` whose output is marked replicated (invariant) over the
# axis, so ``shard_map(..., out_specs=P())`` type-checks under VMA
# analysis. jax 0.9.0 has no public export of it.
from jax._src.lax.parallel import all_gather_invariant  # noqa: F401


def is_tracer(x) -> bool:
    """True inside a trace (jit / shard_map / grad) for a traced value."""
    return isinstance(x, jax.core.Tracer)


def backend_initialized() -> bool:
    """Whether this process has initialised a jax backend (and so holds
    the chip, if it has one) — asked WITHOUT initialising one."""
    return _xla_bridge.backends_are_initialized()
