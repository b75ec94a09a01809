"""Group sharded (ZeRO 1/2/3) training.

Capability parity: python/paddle/distributed/fleet/meta_parallel/sharding/
in the reference — group_sharded_parallel (group_sharded.py), stage2
optimizer/grad sharding (group_sharded_optimizer_stage2.py:53), stage3
parameter sharding (group_sharded_stage3.py:85).

TPU-native mapping (SURVEY §7): ZeRO stages are *sharding configs*, not
runtime machinery:
  os (stage 1):   optimizer states sharded on the sharding axis; the jitted
                  optimizer step computes shard-locally, XLA all-gathers the
                  fresh params (reference's broadcast).
  os_g (stage 2): + gradients land sharded: XLA turns the grad psum into
                  reduce-scatter when the consumer (optimizer state) is
                  sharded — the comm pattern stage2 implements by hand.
  p_g_os (3):     + parameters sharded dim0; XLA inserts per-op all-gathers
                  on use (the reference's param broadcast-on-demand).
"""
from __future__ import annotations

from typing import Optional

import numpy as np
import jax

from ...framework.tape import no_grad
from ..auto_parallel.placement import Shard, Replicate
from ..auto_parallel.process_mesh import ProcessMesh, get_mesh
from ..auto_parallel.api import shard_tensor, shard_optimizer
from .topology import get_hybrid_communicate_group


def _sharding_mesh(axis="sharding", degree=None):
    hcg = get_hybrid_communicate_group()
    if hcg is not None and hcg.get_sharding_parallel_world_size() > 1:
        return hcg.mesh, "sharding"
    m = get_mesh()
    if m is not None and axis in m.dim_names:
        return m, axis
    n = jax.device_count()
    if degree is not None and 1 < degree < n and n % degree == 0:
        # ZeRO over groups of `degree`, pure DP across groups (reference:
        # sharding_degree subdividing the world)
        return ProcessMesh(np.arange(n).reshape(n // degree, degree),
                           ["dp", axis]), axis
    return ProcessMesh(np.arange(n), [axis]), axis


def _offload_sharding(ns):
    """Host-memory variant of a NamedSharding (ZeRO-offload residency)."""
    return ns.with_memory_kind("pinned_host")


def _apply_offload(optimizer):
    """ZeRO offload (reference: group_sharded_stage3.py:85 cpu_offload,
    group_sharded_optimizer_stage2.py:53 offload=True): optimizer slot
    state and fp32 master weights live in HOST memory between steps —
    shardings carry memory_kind='pinned_host'.  jit.TrainStep streams
    them to device memory around the fused update (the XLA-native form of
    the reference's param.cpu() staging), and the eager ``opt.step()``
    path stages them at the call boundary.  On backends whose host and
    device memory coincide (CPU tests) the annotation is a no-op."""
    orig_init = optimizer._init_slot

    def offload_init(slot, p):
        arr = orig_init(slot, p)
        sh = getattr(arr, "sharding", None)
        if isinstance(sh, jax.sharding.NamedSharding):
            return jax.device_put(arr, _offload_sharding(sh))
        return arr

    optimizer._init_slot = offload_init

    orig_ensure = optimizer._ensure_state

    def ensure_and_offload(params):
        orig_ensure(params)
        for p in params:
            m = optimizer._master_weights.get(id(p))
            if m is None:
                continue
            sh = getattr(m, "sharding", None)
            if isinstance(sh, jax.sharding.NamedSharding) and \
                    getattr(sh, "memory_kind", None) != "pinned_host":
                optimizer._master_weights[id(p)] = jax.device_put(
                    m, _offload_sharding(sh))

    optimizer._ensure_state = ensure_and_offload
    optimizer._sharding_offload = True


def group_sharded_parallel(model, optimizer, level, scaler=None, group=None,
                           offload=False, sync_buffers=False, buffer_max_size=None,
                           segment_size=None, sync_comm=False,
                           dp_group=None, exclude_layer=None, degree=None):
    """reference: paddle.distributed.sharding.group_sharded_parallel.

    level: 'os' (stage1) | 'os_g' (stage2) | 'p_g_os' (stage3).
    degree: shard over groups of this many devices (replicated across
    groups); honored when it divides the device count and no mesh with a
    sharding axis is already installed, else the full world is used.
    offload: optimizer states + master weights live in host memory
    (memory_kind='pinned_host'); the compiled step streams them in/out.
    """
    if level not in ("os", "os_g", "p_g_os"):
        raise ValueError(f"level must be os|os_g|p_g_os, got {level}")
    if buffer_max_size is not None or segment_size is not None or sync_comm:
        import warnings
        warnings.warn(
            "buffer_max_size/segment_size/sync_comm are comm-fusion knobs "
            "of the reference's hand-written NCCL path; under XLA the "
            "compiler owns collective buffering and overlap, so these "
            "arguments have no effect here", stacklevel=2)
    mesh, axis = _sharding_mesh(degree=degree)
    degree = mesh.get_dim_size(axis)
    axis_idx = mesh.dim_names.index(axis)

    if level == "p_g_os":
        # stage 3: shard parameters along dim0 where divisible
        with no_grad():
            for p in model.parameters():
                placements = [Replicate()] * mesh.ndim
                if p.ndim > 0 and p.shape[0] % degree == 0:
                    placements[axis_idx] = Shard(0)
                shard_tensor(p, mesh, placements)
    else:
        with no_grad():
            for p in model.parameters():
                if p.dist_attr is None:
                    shard_tensor(p, mesh, [Replicate()] * mesh.ndim)

    def state_shard_fn(slot, p):
        placements = [Replicate()] * mesh.ndim
        if p.ndim > 0 and p.shape[0] % degree == 0:
            placements[axis_idx] = Shard(0)
        return placements, mesh

    optimizer = shard_optimizer(optimizer, state_shard_fn)
    if offload:
        _apply_offload(optimizer)
    # stamp the stage so whole-step compilation (jit.TrainStep) can apply
    # the stage's GRADIENT placement: os_g/p_g_os land grads sharded
    # (reduce-scatter pattern, group_sharded_optimizer_stage2.py:53) while
    # os keeps full grads — an observable compiled-memory difference
    optimizer._sharding_level = level
    optimizer._sharding_mesh = (mesh, axis)
    return model, optimizer, scaler


def save_group_sharded_model(model, output, optimizer=None):
    """reference: sharding save_group_sharded_model."""
    from ...framework.io import save
    save(model.state_dict(), output + ".pdmodel")
    if optimizer is not None:
        save(optimizer.state_dict(), output + ".pdopt")
