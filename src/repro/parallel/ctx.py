"""Activation-sharding context.

Model code stays mesh-agnostic: it calls ``shard(name, x)`` at canonical
cut points (residual stream, logits, kv-cache, moe buffers).  The
launcher installs a sharder that maps names to
``jax.lax.with_sharding_constraint`` specs for the active mesh; outside
a mesh the hook is the identity, so smoke tests and single-host runs are
untouched.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Callable

_state = threading.local()


def auto_mesh(mesh):
    """``mesh`` with every axis of type Auto.

    The repo's shard_map bodies and sharding constraints are written for
    GSPMD-propagated (Auto) axes; ``jax.make_mesh`` makes Explicit axes
    by default, under which a gradient through those bodies asks for a
    ``jax.set_mesh`` context the callers do not hold.
    """
    from jax.sharding import AxisType, Mesh  # noqa: PLC0415

    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if tuple(mesh.axis_types) == auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


def _identity(name: str, x):
    return x


def shard(name: str, x):
    fn: Callable = getattr(_state, "sharder", _identity)
    return fn(name, x)


@contextlib.contextmanager
def activation_sharding(fn: Callable):
    prev = getattr(_state, "sharder", _identity)
    _state.sharder = fn
    try:
        yield
    finally:
        _state.sharder = prev


# --- expert-parallel execution context -------------------------------------
# When set, MoE layers run through the shard_map EP path (local dispatch
# per data shard, expert weights gathered over 'data', psum combine over
# 'model') instead of the pjit/GSPMD-propagated path.


def ep_context():
    return getattr(_state, "ep", None)


@contextlib.contextmanager
def expert_parallel(mesh, dp_axes: tuple[str, ...], model_axis: str):
    prev = getattr(_state, "ep", None)
    _state.ep = (mesh, tuple(dp_axes), model_axis)
    try:
        yield
    finally:
        _state.ep = prev
