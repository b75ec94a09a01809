"""The few jax names this tree reaches through one module.

Written for the one installation there is (jax 0.9 / jaxlib 0.9):
``shard_map`` is ``jax.shard_map`` (``check_vma=``), the mapped axis
size is ``jax.lax.axis_size``, and every backend exposes the
``device`` / ``pinned_host`` memory kinds, so offload code names them
directly.  What stays here is the TP-mesh constructor and the virtual
CPU device pin that tests and CPU-only helper processes use.
"""
from __future__ import annotations

import jax as _jax
# jax.export is a submodule that is not an attribute until imported;
# call sites write ``jax.export.symbolic_shape(...)``
import jax.export  # noqa: F401
from jax import shard_map
from jax.lax import axis_size

from .backend_guard import backend_initialized


def pin_cpu_devices(n: int) -> None:
    """Provision ``n`` virtual CPU devices; must run before the first
    backend touch of the process."""
    _jax.config.update("jax_num_cpu_devices", int(n))


def make_tp_mesh(n: int):
    """A 1-D tensor-parallel ``Mesh`` over ``n`` devices, axis name
    ``'tensor'`` — the mesh every TP serving program in this tree
    shards over.

    Prefers real devices.  When the backend is NOT yet initialized
    (first jax touch of the process) the CPU host platform is
    provisioned with ``n`` virtual devices first — the in-process
    equivalent of ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    — so tier-1 CI exercises TP=2 programs on one CPU.  Once a backend
    is live the visible device count is fixed; asking for more than it
    has is an error naming the pre-init escape hatch."""
    import numpy as _np
    n = int(n)
    if n < 1:
        raise ValueError(f"tp degree must be >= 1, got {n}")
    if n > 1 and not backend_initialized():
        pin_cpu_devices(max(n, 2))
    devs = _jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"make_tp_mesh({n}): only {len(devs)} device(s) visible. "
            f"On CPU, call before the first jax operation (or set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}) so "
            f"the host platform can be split into virtual devices.")
    return _jax.sharding.Mesh(_np.asarray(devs[:n]), ("tensor",))


__all__ = ["shard_map", "axis_size", "pin_cpu_devices", "make_tp_mesh"]
