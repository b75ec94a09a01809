"""Eager collective communication API + groups.

Capability parity: python/paddle/distributed/communication/ in the reference
(all_reduce/all_gather/broadcast/reduce/scatter/all_to_all/send/recv/barrier,
group management in communication/group.py) over ProcessGroupNCCL
(paddle/fluid/distributed/collective/process_group_nccl.cc).

TPU-native semantics (SURVEY §5 "Distributed communication backend"): inside
a host, chips are SPMD lanes — a "rank" in a group is a position along a mesh
axis, and an eager collective is a shard_map over that axis (XLA lowers it to
the ICI collective).  Collectives on *dist tensors* transform their
placements (all_reduce: Partial→Replicate, all_gather: Shard→Replicate, ...).
On replicated/local tensors with world_size 1 they are no-ops, matching the
reference.  Cross-host eager collectives on host data go through
jax.experimental.multihost_utils.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from ..framework.jax_compat import shard_map
from jax.sharding import NamedSharding, PartitionSpec

from ..framework.tensor import Tensor, wrap_array
from ..framework.dispatch import call_op
from .. import monitor
from .auto_parallel.placement import Shard, Replicate, Partial
from .auto_parallel.process_mesh import ProcessMesh, get_mesh
from .auto_parallel.api import DistAttr, placements_to_spec, reshard
from .env import get_rank, get_world_size


# ---------------------------------------------------------- telemetry
# Per-kind collective telemetry (ISSUE 1; the measurement substrate the
# overlap work in arxiv 2401.16677 presupposes): every eager collective
# — including world-size-1 no-ops — records a call, its wall latency and
# its payload size, tagged by collective kind.
_coll_calls = monitor.counter(
    "collective_calls_total", "eager collective invocations", ("kind",))
_coll_latency = monitor.histogram(
    "collective_latency_seconds", "eager collective wall latency",
    ("kind",))
_coll_bytes = monitor.histogram(
    "collective_bytes", "eager collective payload size",
    ("kind",), buckets=monitor.BYTES_BUCKETS)


def _payload_nbytes(args) -> int:
    """Best-effort payload size from the first tensor-ish argument."""
    for a in args:
        seq = a if isinstance(a, (list, tuple)) else (a,)
        for t in seq:
            data = getattr(t, "_data", None)
            nbytes = getattr(data, "nbytes", None)
            if nbytes is not None:
                return int(nbytes)
    return 0


def _instrumented(kind: str):
    """Wrap a collective: count + latency histogram (span feeds the
    profiler timeline too) + payload bytes, tagged by kind."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            _coll_calls.inc(kind=kind)
            nb = _payload_nbytes(args)
            if nb:
                _coll_bytes.observe(nb, kind=kind)
            with monitor.span(f"collective/{kind}",
                              histogram=_coll_latency, kind=kind):
                return fn(*args, **kwargs)
        return wrapper
    return deco


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = one axis of a ProcessMesh
    (reference: communication/group.py Group over ProcessGroup ring ids)."""

    _groups: List["Group"] = []

    def __init__(self, mesh: Optional[ProcessMesh] = None,
                 axis: Optional[str] = None, ranks: Optional[List[int]] = None):
        self.mesh = mesh
        self.axis = axis
        self._explicit_ranks = ranks is not None
        self.ranks = ranks if ranks is not None else (
            list(range(mesh.get_dim_size(axis))) if mesh else
            list(range(get_world_size())))
        self.id = len(Group._groups)
        Group._groups.append(self)

    @property
    def nranks(self) -> int:
        if self.mesh is not None and self.axis is not None:
            return self.mesh.get_dim_size(self.axis)
        return len(self.ranks)

    @property
    def world_size(self) -> int:
        return self.nranks

    @property
    def rank(self) -> int:
        return get_rank() if self.mesh is None else 0

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(axis={self.axis}, nranks={self.nranks})"


_default_group: Optional[Group] = None


def new_group(ranks=None, backend=None, timeout=None, mesh=None, axis=None):
    """reference: paddle.distributed.new_group."""
    return Group(mesh=mesh, axis=axis, ranks=ranks)


def get_group(gid: int = 0) -> Optional[Group]:
    if 0 <= gid < len(Group._groups):
        return Group._groups[gid]
    return None


def _default_axis_group(tensor: Tensor) -> Optional[Group]:
    attr = tensor.dist_attr
    if attr is None:
        return None
    # first sharded/partial axis is the natural comm axis
    for i, p in enumerate(attr.placements):
        if not isinstance(p, Replicate):
            return Group(mesh=attr.process_mesh,
                         axis=attr.process_mesh.dim_names[i])
    return Group(mesh=attr.process_mesh,
                 axis=attr.process_mesh.dim_names[0])


def _shard_map_collective(tensor: Tensor, group: Group, body, out_spec_fn=None,
                          name="collective"):
    """Run a per-shard body over the group axis with shard_map."""
    mesh = group.mesh
    attr = tensor.dist_attr
    in_spec = placements_to_spec(
        [p if isinstance(p, Shard) else Replicate() for p in attr.placements],
        mesh, tensor.ndim)
    out_spec = out_spec_fn(in_spec) if out_spec_fn else in_spec
    fn = shard_map(body, mesh=mesh.jax_mesh, in_specs=in_spec,
                   out_specs=out_spec, check_vma=False)
    return call_op(name, fn, (tensor,), {})


def _is_noop(tensor: Tensor, group: Optional[Group]) -> bool:
    if tensor.dist_attr is not None:
        return False
    if group is not None and group.mesh is not None:
        return False
    # jax.process_count() covers multi-host SPMD; the launcher env contract
    # covers multi-process eager jobs (each process runs its own jax)
    return get_world_size() <= 1 and _host_world() <= 1


@_instrumented("all_reduce")
def all_reduce(tensor: Tensor, op=ReduceOp.SUM, group: Optional[Group] = None,
               sync_op=True):
    """reference: paddle.distributed.all_reduce.

    Dist tensor: reduces pending-partial/sharded values over the group axis
    (in-place on the wrapper, paddle semantics)."""
    if _is_noop(tensor, group):
        return tensor
    if _mp_eager(tensor, group):
        return _mp_all_reduce(tensor, op, group)
    group = group or _default_axis_group(tensor)
    axis = group.axis
    red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
           "avg": lambda x, a: jax.lax.pmean(x, a)}[op if isinstance(op, str) else ReduceOp.SUM]

    attr = tensor.dist_attr
    # Partial → Replicate on this axis; Shard stays (reduce over other axis)
    out = _shard_map_collective(tensor, group,
                                lambda x: red(x, axis), name="all_reduce")
    out.dist_attr = DistAttr(attr.process_mesh, [
        Replicate() if (attr.process_mesh.dim_names[i] == axis and
                        not isinstance(p, Shard)) else p
        for i, p in enumerate(attr.placements)])
    tensor._data = out._data
    tensor._grad_node = out._grad_node
    tensor._node_out_idx = out._node_out_idx
    tensor.stop_gradient = out.stop_gradient and tensor.stop_gradient
    tensor.dist_attr = out.dist_attr
    return tensor


@_instrumented("all_gather")
def all_gather(tensor_list: Optional[List[Tensor]], tensor: Tensor,
               group: Optional[Group] = None, sync_op=True, axis: int = 0):
    """reference: paddle.distributed.all_gather — gathers shards along the
    group axis; fills tensor_list with per-rank pieces."""
    if _is_noop(tensor, group):
        if tensor_list is not None:
            tensor_list.append(tensor.clone())
        return tensor_list
    if _mp_eager(tensor, group):
        return _mp_all_gather(tensor_list, tensor, group)
    group = group or _default_axis_group(tensor)
    attr = tensor.dist_attr
    mesh = attr.process_mesh
    # reshard to replicated on the group axis = all-gather
    new_placements = [
        Replicate() if mesh.dim_names[i] == group.axis else p
        for i, p in enumerate(attr.placements)]
    gathered = reshard(tensor, mesh, new_placements)
    if tensor_list is not None:
        n = group.nranks
        shard_dim = None
        for i, p in enumerate(attr.placements):
            if mesh.dim_names[i] == group.axis and isinstance(p, Shard):
                shard_dim = p.dim
        if shard_dim is None:
            tensor_list.extend(gathered.clone() for _ in range(n))
        else:
            from ..tensor.manipulation import split as t_split
            tensor_list.extend(t_split(gathered, n, axis=shard_dim))
    return gathered


def _host_world():
    """Cross-process world size from the launcher env contract — does NOT
    touch the jax backend (spawned helpers must not claim the chip)."""
    import os
    return int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))


def _host_rank():
    import os
    return int(os.environ.get("PADDLE_TRAINER_ID", "0"))


# string keys: world-wide object collectives (_obj_key); tuple keys
# (kind, peers): per-participant-set tensor collectives (_mp_tag)
_obj_gen = {"bcast": 0, "scatter": 0, "gather": 0, "a2a": 0}


# ----------------------------------------------- cross-process eager lane
# (reference: ProcessGroupGloo/NCCL eager collectives — plain tensors in a
# multi-process job, no mesh.  Transport is the store-brokered p2p
# substrate; rank 0 is the reduction root.)

def _mp_eager(tensor, group) -> bool:
    """True when this call must run on the cross-process eager lane."""
    return (tensor.dist_attr is None and _host_world() > 1
            and (group is None or group.mesh is None))


def _mp_peers(group):
    """The participating global ranks: a mesh-less group's explicit rank
    list, else the whole launcher world.  A default-constructed group
    (new_group() with no ranks) means the whole world too — its ranks
    default from jax.process_count(), which is 1 in every spawned eager
    process and would otherwise shrink the group to [0]."""
    if group is not None and group.mesh is None and group.ranks \
            and getattr(group, "_explicit_ranks", False):
        return list(group.ranks)
    return list(range(_host_world()))


def _clone(t):
    return t.clone()


def _mp_tag(kind, peers):
    """Per-(collective, participant-set) generation tag: members of a
    subgroup advance their own sequence, so a rank outside the group can
    run other collectives without desynchronizing the members' tags."""
    key = (kind, tuple(peers))
    _obj_gen[key] = _obj_gen.get(key, 0) + 1
    return f"objcoll/{kind}/{'-'.join(map(str, peers))}/{_obj_gen[key]}"


def _np_combine(acc, other, opname):
    if opname in ("sum", "avg"):
        return acc + other
    if opname == "max":
        return np.maximum(acc, other)
    if opname == "min":
        return np.minimum(acc, other)
    return acc * other


def _mp_all_reduce(tensor, op, group=None):
    from . import p2p
    peers = _mp_peers(group)
    rank = _host_rank()
    if rank not in peers:
        return tensor
    tag = _mp_tag("ar", peers)
    opname = str(op)
    root = peers[0]
    if rank == root:
        acc = np.asarray(tensor.numpy(), np.float64) \
            if opname == "avg" else np.asarray(tensor.numpy()).copy()
        buf = _clone(tensor)
        for src in peers[1:]:
            p2p.recv(buf, src=src, tag=tag)
            acc = _np_combine(acc, np.asarray(buf.numpy()), opname)
        if opname == "avg":
            acc = acc / len(peers)
        result = wrap_array(jnp.asarray(
            acc.astype(np.asarray(tensor.numpy()).dtype)))
        for dst in peers[1:]:
            p2p.send(result, dst=dst, tag=tag + "o")
        tensor._data = result._data
    else:
        p2p.send(tensor, dst=root, tag=tag)
        p2p.recv(tensor, src=root, tag=tag + "o")
    return tensor


def _mp_broadcast(tensor, src, group=None):
    from . import p2p
    peers = _mp_peers(group)
    rank = _host_rank()
    if rank not in peers:
        return tensor
    if src not in peers:
        raise ValueError(f"broadcast src {src} is not in the group "
                         f"{peers}")
    tag = _mp_tag("tbcast", peers)
    if rank == src:
        for dst in peers:
            if dst != src:
                p2p.send(tensor, dst=dst, tag=tag)
    else:
        p2p.recv(tensor, src=src, tag=tag)
    return tensor


def _mp_all_gather(tensor_list, tensor, group=None):
    from . import p2p
    peers = _mp_peers(group)
    rank = _host_rank()
    if rank not in peers:
        return []
    tag = _mp_tag("ag", peers)
    for dst in peers:
        if dst != rank:
            p2p.send(tensor, dst=dst, tag=tag)
    parts = []
    for src in peers:
        if src == rank:
            parts.append(_clone(tensor))
        else:
            parts.append(p2p.recv(_clone(tensor), src=src, tag=tag))
    if tensor_list is not None:
        tensor_list.extend(parts)
    return parts


def _mp_reduce(tensor, dst, op, group=None):
    from . import p2p
    peers = _mp_peers(group)
    rank = _host_rank()
    if rank not in peers:
        return tensor
    if dst not in peers:
        raise ValueError(f"reduce dst {dst} is not in the group {peers}")
    tag = _mp_tag("red", peers)
    opname = str(op)
    if rank == dst:
        acc = np.asarray(tensor.numpy()).copy()
        buf = _clone(tensor)
        for src in peers:
            if src == dst:
                continue
            p2p.recv(buf, src=src, tag=tag)
            acc = _np_combine(acc, np.asarray(buf.numpy()), opname)
        if opname == "avg":
            acc = acc / len(peers)
        tensor._data = jnp.asarray(acc).astype(tensor._data.dtype)
    else:
        p2p.send(tensor, dst=dst, tag=tag)
    return tensor


def _mp_scatter(tensor, tensor_list, src, group=None):
    from . import p2p
    peers = _mp_peers(group)
    rank = _host_rank()
    if rank not in peers:
        return tensor
    if src not in peers:
        raise ValueError(f"scatter src {src} is not in the group {peers}")
    tag = _mp_tag("tscatter", peers)
    if rank == src:
        if not tensor_list or len(tensor_list) != len(peers):
            raise ValueError(
                f"scatter on rank {src} needs tensor_list of length "
                f"{len(peers)}")
        for i, dst in enumerate(peers):
            if dst != src:
                p2p.send(tensor_list[i], dst=dst, tag=tag)
        tensor._data = tensor_list[peers.index(src)]._data
    else:
        p2p.recv(tensor, src=src, tag=tag)
    return tensor


def _mp_reduce_scatter(output, input, op, group=None):
    peers = _mp_peers(group)
    rank = _host_rank()
    if rank not in peers:
        return output
    if input.shape[0] % len(peers) != 0:
        raise ValueError(
            f"reduce_scatter: group size ({len(peers)}) must divide "
            f"dim 0 ({input.shape[0]})")
    reduced = _mp_all_reduce(_clone(input), op, group)
    n = input.shape[0] // len(peers)
    i = peers.index(rank)
    output._data = reduced._data[i * n:(i + 1) * n]
    return output


def _obj_key(kind):
    """Deterministic per-call key: every rank calls the object collective the
    same number of times (the same SPMD assumption the reference makes)."""
    _obj_gen[kind] += 1
    return f"objcoll/{kind}/{_obj_gen[kind]}"


def _release_when_all_read(key, readers):
    """Empty a consumed store payload once every reader has seen it, so
    long-running jobs don't grow rank 0's store without bound."""
    from . import p2p
    st = p2p._state
    with st.io_lock:
        if st.get_store().add(key + "/read", 1) >= readers:
            st.get_store().set(key, b"")


@_instrumented("all_gather_object")
def all_gather_object(object_list, obj, group=None):
    """reference: communication/all_gather.py all_gather_object — host
    objects gathered rank-major over the TCPStore substrate."""
    import pickle
    world = _host_world()
    if world <= 1:
        object_list.append(obj)
        return
    from . import p2p
    key = _obj_key("gather")
    rank = _host_rank()
    p2p.store_set(f"{key}/{rank}", pickle.dumps(obj))
    for r in range(world):
        object_list.append(pickle.loads(p2p.store_get(f"{key}/{r}")))
        _release_when_all_read(f"{key}/{r}", world)


@_instrumented("reduce_scatter")
def reduce_scatter(output: Tensor, input: Tensor, op=ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op=True):
    """reference: communication/reduce_scatter.py — Partial→Shard(0) on
    SPMD lanes; all-reduce + local slice across processes."""
    if _is_noop(input, group):
        output._data = input._data
        return output
    if _mp_eager(input, group):
        return _mp_reduce_scatter(output, input, op, group)
    group = group or _default_axis_group(input)
    attr = input.dist_attr
    mesh = attr.process_mesh
    axis_idx = mesh.dim_names.index(group.axis)
    reduced = all_reduce(input.clone() if hasattr(input, "clone") else input,
                         op, group)
    new_placements = list(reduced.dist_attr.placements)
    new_placements[axis_idx] = Shard(0)
    out = reshard(reduced, mesh, new_placements)
    output._data = out._data
    output.dist_attr = out.dist_attr
    return output


@_instrumented("broadcast")
def broadcast(tensor: Tensor, src: int = 0, group: Optional[Group] = None,
              sync_op=True):
    """reference: paddle.distributed.broadcast — on SPMD lanes this is a
    reshard to Replicate (XLA broadcasts from the owning shard); across
    processes, rank-to-rank p2p from src."""
    if _is_noop(tensor, group):
        return tensor
    if _mp_eager(tensor, group):
        return _mp_broadcast(tensor, src, group)
    attr = tensor.dist_attr
    if attr is not None:
        out = reshard(tensor, attr.process_mesh,
                      [Replicate()] * attr.process_mesh.ndim)
        tensor._data = out._data
        tensor.dist_attr = out.dist_attr
    return tensor


@_instrumented("reduce")
def reduce(tensor: Tensor, dst: int = 0, op=ReduceOp.SUM,
           group: Optional[Group] = None, sync_op=True):
    """reduce-to-root == all_reduce on SPMD lanes (root extraction is a
    local slice; XLA keeps one copy per device anyway); across processes
    only dst receives the reduced value."""
    if _mp_eager(tensor, group):
        return _mp_reduce(tensor, dst, op, group)
    return all_reduce(tensor, op, group)


@_instrumented("scatter")
def scatter(tensor: Tensor, tensor_list=None, src=0,
            group: Optional[Group] = None, sync_op=True):
    """reference: paddle.distributed.scatter — Replicate→Shard(0) on SPMD
    lanes; rank-to-rank p2p from src across processes."""
    if _mp_eager(tensor, group):
        return _mp_scatter(tensor, tensor_list, src, group)
    if tensor_list:
        from ..tensor.manipulation import concat
        full = concat(tensor_list, axis=0)
    else:
        full = tensor
    attr = full.dist_attr
    if attr is None:
        tensor._data = full._data
        return tensor
    mesh = attr.process_mesh
    group = group or Group(mesh=mesh, axis=mesh.dim_names[0])
    axis_idx = mesh.dim_names.index(group.axis)
    placements = list(attr.placements)
    placements[axis_idx] = Shard(0)
    out = reshard(full, mesh, placements)
    tensor._data = out._data
    tensor.dist_attr = out.dist_attr
    return tensor


@_instrumented("all_to_all")
def all_to_all(out_tensor_list, in_tensor_list,
               group: Optional[Group] = None, sync_op=True):
    """reference: communication/all_to_all.py — Shard(i)→Shard(j)."""
    if isinstance(in_tensor_list, Tensor):
        x = in_tensor_list
        attr = x.dist_attr
        if attr is None:
            return x
        mesh = attr.process_mesh
        group = group or _default_axis_group(x)
        axis_idx = mesh.dim_names.index(group.axis)
        placements = list(attr.placements)
        cur = placements[axis_idx]
        new_dim = 1 if (isinstance(cur, Shard) and cur.dim == 0) else 0
        placements[axis_idx] = Shard(new_dim)
        return reshard(x, mesh, placements)
    if _host_world() > 1:
        # real rank-to-rank exchange over the p2p substrate: group member
        # at slot i sends in_tensor_list[j] to the member at slot j and
        # receives slot i from every member.  Routed through _mp_peers so a
        # subgroup only exchanges among its members (non-members return
        # immediately instead of blocking in recv).
        from . import p2p
        peers = _mp_peers(group)
        rank = _host_rank()
        if rank not in peers:
            return []
        if len(in_tensor_list) != len(peers):
            raise ValueError(
                f"all_to_all needs one input tensor per group rank "
                f"({len(in_tensor_list)} != group size {len(peers)})")
        me = peers.index(rank)
        tag = _mp_tag("a2a", peers)
        for j, dst in enumerate(peers):
            if dst != rank:
                p2p.send(in_tensor_list[j], dst=dst, tag=tag)
        parts = []
        for i, src in enumerate(peers):
            if src == rank:
                parts.append(in_tensor_list[me])
            else:
                t = in_tensor_list[i].clone() if hasattr(
                    in_tensor_list[i], "clone") else in_tensor_list[i]
                parts.append(p2p.recv(t, src=src, tag=tag))
        if out_tensor_list is not None:
            out_tensor_list.extend(parts)
        return parts
    # world 1: identity exchange (each rank keeps its own slot)
    parts = list(in_tensor_list)
    if out_tensor_list is not None:
        out_tensor_list.extend(parts)
    return parts


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    return all_to_all(out_tensor_list, in_tensor_list, group, sync_op)


@_instrumented("send")
def send(tensor, dst=0, group=None, sync_op=True):
    """Eager p2p send (reference: communication/send.py).  Intra-process
    chips exchange via compiled ppermute (fleet/pipeline_parallel.py); eager
    send targets another *process* over the store substrate (p2p.py)."""
    from . import p2p
    return p2p.send(tensor, dst=dst, group=group, sync_op=sync_op)


@_instrumented("recv")
def recv(tensor, src=0, group=None, sync_op=True):
    """Eager p2p receive, in-place (reference: communication/recv.py)."""
    from . import p2p
    return p2p.recv(tensor, src=src, group=group, sync_op=sync_op)


def isend(tensor, dst=0, group=None):
    from . import p2p
    return p2p.isend(tensor, dst=dst, group=group)


def irecv(tensor, src=0, group=None):
    from . import p2p
    return p2p.irecv(tensor, src=src, group=group)


@_instrumented("barrier")
def barrier(group=None):
    """reference: paddle.distributed.barrier — multi-host SPMD syncs
    global devices; multi-process eager jobs rendezvous on the store."""
    if _host_world() > 1:
        from . import p2p
        from .store import barrier as _store_barrier
        _store_barrier(p2p._state.get_store(), "coll/barrier",
                       _host_world())
        return
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")
    else:
        (jax.device_put(0) + 0).block_until_ready()


def destroy_process_group(group=None):
    Group._groups.clear()


def get_backend(group=None) -> str:
    return "xla"


# ------------------------------------------------- host-object collectives
@_instrumented("broadcast_object_list")
def broadcast_object_list(object_list, src=0, group=None):
    """reference: communication/broadcast.py broadcast_object_list — replaces
    ``object_list`` contents in-place with ``src``'s list on every rank."""
    import pickle
    world = _host_world()
    if world <= 1:
        return object_list
    from . import p2p
    key = _obj_key("bcast")
    if _host_rank() == src:
        p2p.store_set(key, pickle.dumps(list(object_list)))
        return object_list
    object_list[:] = pickle.loads(p2p.store_get(key))
    _release_when_all_read(key, world - 1)   # src doesn't read
    return object_list


@_instrumented("scatter_object_list")
def scatter_object_list(out_list, in_list, src=0, group=None):
    """reference: communication/scatter.py scatter_object_list — rank r gets
    in_list[r] from ``src``."""
    import pickle
    world = _host_world()
    if world <= 1:
        out_list.extend(in_list[:1] if in_list else [])
        return out_list
    from . import p2p
    key = _obj_key("scatter")
    rank = _host_rank()
    if rank == src:
        if len(in_list) != world:
            raise ValueError(
                f"scatter_object_list needs one object per rank "
                f"({len(in_list)} != world {world})")
        for r in range(world):
            p2p.store_set(f"{key}/{r}", pickle.dumps(in_list[r]))
    out_list.append(pickle.loads(p2p.store_get(f"{key}/{rank}")))
    _release_when_all_read(f"{key}/{rank}", 1)   # each slot has one reader
    return out_list


@_instrumented("alltoall_single")
def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """reference: communication/all_to_all.py alltoall_single — one tensor
    split along dim 0 across ranks.  SPMD lane: a Shard(0)->Shard(1)
    reshard (the compiled all-to-all); multi-process: p2p exchange of the
    row blocks."""
    world = _host_world()
    if world == 1:
        if isinstance(in_tensor, Tensor) and in_tensor.dist_attr is not None:
            res = all_to_all(None, in_tensor, group, sync_op)
            if out_tensor is not None:
                out_tensor._data = res._data
                out_tensor.dist_attr = res.dist_attr
            return res
        if out_tensor is not None:
            out_tensor._data = in_tensor._data
        return in_tensor
    peers = _mp_peers(group)
    if _host_rank() not in peers:
        return in_tensor
    nparts = len(peers)
    n = in_tensor.shape[0]
    if in_split_sizes is None:
        if n % nparts != 0:
            raise ValueError(
                f"alltoall_single: dim 0 ({n}) not divisible by group "
                f"size ({nparts}); pass in_split_sizes explicitly")
        in_split_sizes = [n // nparts] * nparts
    offs = np.cumsum([0] + list(in_split_sizes))
    blocks = [in_tensor[int(offs[i]):int(offs[i + 1])]
              for i in range(nparts)]
    got = all_to_all(None, blocks, group, sync_op)
    from ..tensor.manipulation import concat as _concat
    res = _concat(got, axis=0)
    if out_tensor is not None:
        out_tensor._data = res._data
    return res


@_instrumented("gather")
def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """reference: communication/gather.py — collect tensors on rank dst.
    SPMD lane: all ranks see the full value (all_gather then keep);
    multi-process: p2p to dst."""
    world = _host_world()
    if world == 1:
        out = []
        all_gather(out, tensor, group, sync_op)
        if gather_list is not None and _host_rank() == dst:
            gather_list.extend(out)
        return out
    from . import p2p
    peers = _mp_peers(group)
    rank = _host_rank()
    if rank not in peers:
        return None
    if dst not in peers:
        raise ValueError(f"gather dst {dst} is not in the group {peers}")
    tag = _mp_tag("gath", peers)
    if rank == dst:
        parts = []
        for src in peers:
            if src == rank:
                parts.append(tensor)
            else:
                t = tensor.clone() if hasattr(tensor, "clone") else tensor
                parts.append(p2p.recv(t, src=src, tag=tag))
        if gather_list is not None:
            gather_list.extend(parts)
        return parts
    p2p.send(tensor, dst=dst, tag=tag)
    return None


def wait(tensor, group=None, use_calc_stream=True):
    """reference: communication/wait.py — block until the tensor's value
    is materialized (XLA async dispatch barrier)."""
    import jax
    jax.block_until_ready(tensor._data)
    return tensor


def is_available() -> bool:
    """reference: paddle.distributed.is_available."""
    import jax
    try:
        return len(jax.devices()) > 0
    except Exception:
        return False


# ------------------------------------------------------- gloo CPU barrier
_gloo_state = {"store": None, "rank": 0, "world": 1, "gen": 0}


def gloo_init_parallel_env(rank_id: int, rank_num: int,
                           server_endpoint: str):
    """reference: pybind gloo_init_parallel_env — CPU-side barrier fabric.
    The TCPStore plays gloo's role on this stack."""
    from .store import TCPStore
    host, port = server_endpoint.rsplit(":", 1)
    _gloo_state["store"] = TCPStore(host, int(port),
                                    is_master=(rank_id == 0),
                                    world_size=rank_num)
    _gloo_state["rank"] = rank_id
    _gloo_state["world"] = rank_num


def gloo_barrier():
    """reference: pybind gloo_barrier."""
    from .store import barrier as _store_barrier
    st = _gloo_state["store"]
    if st is None:
        return
    _gloo_state["gen"] += 1
    _store_barrier(st, f"gloo/barrier/{_gloo_state['gen']}",
                   _gloo_state["world"])


def gloo_release():
    """reference: pybind gloo_release."""
    st = _gloo_state["store"]
    if st is not None:
        st.close()
        _gloo_state["store"] = None
