"""Op dispatch: the single chokepoint every eager op goes through.

Capability parity with the reference's generated ``*_ad_func`` + phi API
dispatch (reference: paddle/fluid/eager/auto_code_generator/generator/
eager_gen.py:365 forward template, paddle/phi/api/generator/api_gen.py,
paddle/phi/core/kernel_factory.cc:267 SelectKernelOrThrowError).

TPU-native design: there is no KernelKey registry — XLA is the only backend.
``call_op``:
  1. flattens (args, kwargs), unwraps Tensor leaves to jax.Arrays,
  2. applies AMP autocast if active (reference: eager_gen.py:675),
  3. if the tape is live and any floating input requires grad, runs
     ``jax.vjp`` over the pure function and records a GradNode,
  4. wraps outputs, stamping tape edges.
The op table (OP_REGISTRY) is data: name → OpDef{fn, spmd_rule, ...} — the
"op definitions are data, not code" lesson from SURVEY §1 (5 consumers of one
YAML schema); here the registry feeds dispatch, to_static, and the sharding
propagation rules.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import jax
import jax.tree_util as jtu

from ..monitor.span import span as _span
from . import dtype as dtypes
from . import tape as _tape
from .flags import get_flag
from .tensor import Tensor, wrap_array


@dataclass
class OpDef:
    name: str
    fn: Callable            # pure function over jax arrays
    wrapper: Callable       # user-facing tensor function
    spmd_rule: Optional[Callable] = None   # sharding propagation rule (SURVEY #15)
    meta: Dict[str, Any] = field(default_factory=dict)


OP_REGISTRY: Dict[str, OpDef] = {}


def register_spmd_rule(name: str, rule: Callable) -> None:
    """Attach a sharding-propagation rule to a registered op.  Raises on an
    unknown op name — a typo'd registration silently dropping a rule would
    degrade hybrid-parallel placement with no error."""
    if name not in OP_REGISTRY:
        raise ValueError(f"register_spmd_rule: no op named {name!r} "
                         f"(is the defining module imported yet?)")
    OP_REGISTRY[name].spmd_rule = rule


def _is_tensor(x):
    return isinstance(x, Tensor)


# AMP autocast hook, installed by paddle_tpu.amp (avoids circular import).
_amp_cast_hook: Optional[Callable] = None


def set_amp_cast_hook(hook: Optional[Callable]) -> None:
    global _amp_cast_hook
    _amp_cast_hook = hook


# Static Program recorder (static/program.py): while a program_guard is
# active every op ON THE GUARDING THREAD records into the Program instead
# of executing — the reference's Program-build mode (python/paddle/
# static/).  THREAD-LOCAL to match program_guard's thread-local stack:
# background threads doing eager work (e.g. the continuous-batching
# decode thread) must never record into another thread's Program.
import threading as _threading

_static_tls = _threading.local()


def set_static_recorder(rec: Optional[Callable]) -> None:
    _static_tls.rec = rec


def _get_static_recorder() -> Optional[Callable]:
    return getattr(_static_tls, "rec", None)


# Post-op observer hooks (numerical sanitizers, operator-stats collectors —
# SURVEY §5 "race/numerical sanitizers"; reference: the check_nan_inf plumbing
# of paddle/fluid/framework/details/nan_inf_utils_detail.cc and the low-
# precision op counters behind paddle/amp/debugging.py).  Each hook is called
# as ``hook(op_name, result)`` after every eager op; the empty-list fast path
# costs one truthiness check.
_post_op_hooks: list = []


def add_post_op_hook(hook: Callable) -> Callable:
    _post_op_hooks.append(hook)
    return hook


def remove_post_op_hook(hook: Callable) -> None:
    try:
        _post_op_hooks.remove(hook)
    except ValueError:
        pass


def _run_post_op_hooks(name, result):
    for h in list(_post_op_hooks):
        h(name, result)


# Set while a Profiler is in a RECORD state: one host span per eager op
# (reference: RecordEvent spans auto-inserted by eager_gen.py:322).  None
# when profiling is off, so the hot path pays one attribute read.
_prof_recorder = None


def set_profiler_recorder(rec) -> None:
    global _prof_recorder
    _prof_recorder = rec


def call_op(name: str, fn: Callable, args: tuple, kwargs: dict):
    """Execute ``fn`` (a pure jax-array function) with tape recording."""
    if _prof_recorder is not None:
        with _span("op::" + name):
            return _call_op_impl(name, fn, args, kwargs)
    return _call_op_impl(name, fn, args, kwargs)


def _call_op_impl(name: str, fn: Callable, args: tuple, kwargs: dict):
    _rec = _get_static_recorder()
    if _rec is not None:
        # AMP casts must be applied BEFORE recording: symbolic Variables
        # are Tensor subclasses, so the hook's .astype() re-enters
        # call_op and the cast lands in the Program — the replayed graph
        # then matches what the eager path would have executed
        if _amp_cast_hook is not None:
            args, kwargs = _amp_cast_hook(name, args, kwargs)
        return _rec(name, fn, args, kwargs)
    if _amp_cast_hook is not None:
        args, kwargs = _amp_cast_hook(name, args, kwargs)

    leaves, treedef = jtu.tree_flatten((args, kwargs), is_leaf=_is_tensor)
    tensor_idx = [i for i, l in enumerate(leaves) if _is_tensor(l)]
    arrays = [leaves[i]._data for i in tensor_idx]

    record = False
    diff_pos = []   # positions (within tensor_idx) that are differentiable
    if _tape.is_grad_enabled():
        for p, i in enumerate(tensor_idx):
            t = leaves[i]
            if not t.stop_gradient and dtypes.is_floating_point(t.dtype):
                diff_pos.append(p)
        record = bool(diff_pos)

    def _call_with(arrs):
        new_leaves = list(leaves)
        for i, a in zip(tensor_idx, arrs):
            new_leaves[i] = a
        a2, k2 = jtu.tree_unflatten(treedef, new_leaves)
        return fn(*a2, **k2)

    if not record:
        out = _call_with(arrays)
        result, _, _ = _wrap_outputs(out)
        _apply_spmd_rule(name, leaves, tensor_idx, treedef, result)
        _check_nan_inf(name, result)
        if _post_op_hooks:
            _run_post_op_hooks(name, result)
        return result

    # Differentiate w.r.t. the requires-grad floating inputs only; others are
    # baked into the closure as constants (reference: eager_gen.py records
    # TensorWrappers only for inputs needed by the grad node).
    diff_arrays = [arrays[p] for p in diff_pos]

    cached = _cached_grad_call(name, fn, leaves, treedef, tensor_idx,
                               diff_pos, arrays) \
        if (get_flag("eager_cached_grad")
            and name not in _PLACEMENT_OPS) else None
    if cached is not None:
        out_arrays, vjp_fn = cached
    else:
        def _pure(*diff_args):
            full = list(arrays)
            for p, a in zip(diff_pos, diff_args):
                full[p] = a
            return _call_with(full)

        out_arrays, vjp_fn = jax.vjp(_pure, *diff_arrays)

    edges = []
    for p in diff_pos:
        t = leaves[tensor_idx[p]]
        edges.append(_tape.Edge(t._grad_node, t._node_out_idx, t))

    result, flat_outs, out_treedef = _wrap_outputs(out_arrays)
    out_metas = [(tuple(a.shape), a.dtype) for a in flat_outs]
    node = _tape.GradNode(name, vjp_fn, edges, len(flat_outs), out_metas,
                          out_treedef)

    # Stamp tape metadata on floating outputs.
    _stamp_outputs(result, node)
    _apply_spmd_rule(name, leaves, tensor_idx, treedef, result)
    _check_nan_inf(name, result)
    if _post_op_hooks:
        _run_post_op_hooks(name, result)
    return result


def _apply_spmd_rule(name, leaves, tensor_idx, treedef, result):
    """Apply the op's SPMD rule when any input is a dist tensor (SURVEY row
    15; reference: the InferSPMD slot run by the dist API layer).

    Pins the output sharding the rule chose — ``with_sharding_constraint``
    under tracing, ``device_put`` eagerly — and stamps ``dist_attr`` so
    placements keep flowing through eager op chains.  Rules are advisory:
    any failure leaves GSPMD's default propagation in place.
    """
    opdef = OP_REGISTRY.get(name)
    if opdef is None or opdef.spmd_rule is None:
        return
    dist_in = [leaves[i] for i in tensor_idx
               if leaves[i].dist_attr is not None]
    if not dist_in:
        return
    try:
        from ..distributed.auto_parallel.api import (
            DistAttr, placements_to_spec,
        )
        from ..distributed.auto_parallel.placement import Replicate
        from ..distributed.auto_parallel.spmd_rules import ShardedArg
        from jax.sharding import NamedSharding

        mesh = dist_in[0].dist_attr.process_mesh
        n_axes = mesh.ndim

        def as_meta(leaf):
            if not _is_tensor(leaf):
                return leaf
            attr = leaf.dist_attr
            placements = (list(attr.placements) if attr is not None
                          else [Replicate() for _ in range(n_axes)])
            return ShardedArg(leaf._data.shape, placements, mesh)

        meta_leaves = [as_meta(l) for l in leaves]
        args2, kwargs2 = jtu.tree_unflatten(treedef, meta_leaves)
        out_pl = opdef.spmd_rule(*args2, **kwargs2)
        if out_pl is None:
            return
        flat_res, _ = jtu.tree_flatten(result, is_leaf=_is_tensor)
        out_tensors = [t for t in flat_res if _is_tensor(t)]
        if out_pl and isinstance(out_pl[0], (list, tuple)) and not isinstance(
                out_pl[0], str):
            per_out = list(out_pl)
        else:
            per_out = [out_pl] * len(out_tensors)
        # stage everything before mutating ANY output: a failure halfway
        # must not leave a mixed constrained/unconstrained state
        staged = []
        for t, placements in zip(out_tensors, per_out):
            spec = placements_to_spec(placements, mesh, t.ndim)
            sharding = NamedSharding(mesh.jax_mesh, spec)
            if isinstance(t._data, jax.core.Tracer):
                new_data = jax.lax.with_sharding_constraint(t._data, sharding)
            else:
                new_data = jax.device_put(t._data, sharding)
            staged.append((t, new_data, DistAttr(mesh, list(placements))))
        for t, new_data, attr in staged:
            t._data = new_data
            t.dist_attr = attr
    except Exception:   # advisory: never let a rule break dispatch
        if get_flag("spmd_rule_strict", 0):
            raise            # CI health mode: a rotted rule must FAIL
        if get_flag("spmd_rule_debug", 0):
            import traceback
            print(f"WARNING: spmd rule for op '{name}' failed:")
            traceback.print_exc()
        return


# --------------------------------------------------------------------------
# FLAGS_eager_cached_grad: compile-cached eager autograd.  The default
# record path runs jax.vjp per op call — two Python traces of the op every
# step (~0.5 ms for a small op).  With the flag on, forward and backward
# are jitted ONCE per (op, input signature) and replayed from the compile
# cache; the backward recomputes the forward inside its jit (op-level
# rematerialization — the TPU-native trade: FLOPs are cheap, Python
# dispatch is the eager bottleneck).  ON by default since round 4
# (measured 11-16x per-op dispatch with grad, lower live residual bytes —
# tools/eager_dispatch_measurement.json); FLAGS_eager_cached_grad=0
# restores the per-call jax.vjp record path.
# --------------------------------------------------------------------------
_GRAD_CACHE: "OrderedDict[Any, Any]" = OrderedDict()
_GRAD_CACHE_CAP = 1024

# Placement ops MUST execute their device_put eagerly: under the cached
# path the op fn runs inside jit, where the compiler decides output
# shardings and the explicit NamedSharding destination is discarded —
# shard_tensor on a requires-grad Parameter would silently leave it
# replicated (caught by tests/test_llama_moe.py EP sharding assert).
_PLACEMENT_OPS = frozenset({"shard_tensor", "reshard"})


def _cached_grad_call(name, fn, leaves, treedef, tensor_idx, diff_pos,
                      arrays):
    """(out_arrays, vjp_fn) via per-signature jitted fwd/bwd, or None when
    the call signature isn't hashable (fall back to plain jax.vjp)."""
    if _GRAD_CACHE_CAP <= 0:
        return None                    # caching disabled -> plain vjp path
    static_leaves = [None if _is_tensor(leaf) else leaf for leaf in leaves]
    try:
        # id(fn) distinguishes re-registrations of the same op name; the
        # entry's closures pin fn alive, so the id cannot be recycled
        # while its entry exists
        key = (name, id(fn), treedef, tuple(tensor_idx), tuple(diff_pos),
               tuple((a.shape, str(a.dtype)) for a in arrays),
               tuple((i, s) for i, s in enumerate(static_leaves)
                     if s is not None))
        hash(key)
    except TypeError:
        return None

    entry = _GRAD_CACHE.get(key)
    if entry is not None:
        _GRAD_CACHE.move_to_end(key)   # LRU touch
    else:
        # LRU eviction: drop only the single coldest signature.  A
        # wholesale clear() here caused a recompile thundering-herd for
        # workloads cycling through >CAP distinct signatures.
        while len(_GRAD_CACHE) >= _GRAD_CACHE_CAP:
            _GRAD_CACHE.popitem(last=False)
        # close over the BUILD-time static leaves/treedef — equal keys
        # guarantee they match this call's.  Tensor positions are blanked:
        # they are always overwritten by _apply, and keeping the first
        # call's Tensors would pin its activations for the cache lifetime.
        build_leaves = list(leaves)
        for i in tensor_idx:
            build_leaves[i] = None
        build_treedef = treedef
        build_tensor_idx = list(tensor_idx)
        build_diff_pos = list(diff_pos)

        def _apply(arrs):
            new_leaves = list(build_leaves)
            for i, a in zip(build_tensor_idx, arrs):
                new_leaves[i] = a
            a2, k2 = jtu.tree_unflatten(build_treedef, new_leaves)
            return fn(*a2, **k2)

        def _make_bwd(f0_meta, ct_tree):
            # f0_meta: ((leaf_index, shape), ...) of float0 cotangents
            # (integer outputs).  float0 arrays have no XLA buffer form,
            # so they are rebuilt INSIDE the trace as constants instead
            # of being passed as jit arguments.
            f0_idx = {i for i, _ in f0_meta}

            def _bwd(arrs, live_cts):
                full, it = [], iter(live_cts)
                n_leaves = len(f0_meta) + len(live_cts)
                shapes = dict(f0_meta)
                for i in range(n_leaves):
                    if i in f0_idx:
                        import numpy as _np
                        full.append(_np.zeros(shapes[i],
                                              jax.dtypes.float0))
                    else:
                        full.append(next(it))
                cts = jtu.tree_unflatten(ct_tree, full)

                def pure_diff(*diff_args):
                    fully = list(arrs)
                    for p, a in zip(build_diff_pos, diff_args):
                        fully[p] = a
                    return _apply(fully)

                diff = [arrs[p] for p in build_diff_pos]
                return jax.vjp(pure_diff, *diff)[1](cts)

            return jax.jit(_bwd)

        entry = (jax.jit(_apply), {}, _make_bwd)
        _GRAD_CACHE[key] = entry

    fwd_jit, bwd_cache, make_bwd = entry
    out_arrays = fwd_jit(arrays)

    def vjp_fn(cts):
        ct_leaves, ct_tree = jtu.tree_flatten(cts)
        f0_meta = tuple(
            (i, tuple(c.shape))
            for i, c in enumerate(ct_leaves)
            if getattr(c, "dtype", None) == jax.dtypes.float0)
        live = [c for i, c in enumerate(ct_leaves)
                if getattr(c, "dtype", None) != jax.dtypes.float0]
        bkey = (f0_meta, ct_tree)
        bwd = bwd_cache.get(bkey)
        if bwd is None:
            bwd = bwd_cache[bkey] = make_bwd(f0_meta, ct_tree)
        return bwd(arrays, live)

    return out_arrays, vjp_fn


def _wrap_outputs(out):
    """Wrap jax arrays (possibly nested in tuple/list/dict) into Tensors."""
    flat, treedef = jtu.tree_flatten(out)
    wrapped = []
    arrays = []
    for a in flat:
        arrays.append(a)
        wrapped.append(wrap_array(a))
    return jtu.tree_unflatten(treedef, wrapped), arrays, treedef


def _stamp_outputs(result, node):
    flat, _ = jtu.tree_flatten(result, is_leaf=_is_tensor)
    idx = 0
    for t in flat:
        if _is_tensor(t):
            if dtypes.is_floating_point(t.dtype):
                t.stop_gradient = False
                t._grad_node = node
                t._node_out_idx = idx
            idx += 1


_NAN_CHECK_WARNED = False


def _check_nan_inf(name, result):
    """Numerical sanitizer (FLAGS_check_nan_inf).

    COST WARNING: the bool() forces a device->host sync after EVERY op,
    destroying async dispatch while enabled — the reference's equivalent
    runs kernel-side (paddle/fluid/eager/nan_inf_utils.cc).  Debug tool
    only; a one-time warning states this at first use.
    """
    if not get_flag("check_nan_inf"):
        return
    global _NAN_CHECK_WARNED
    if not _NAN_CHECK_WARNED:
        _NAN_CHECK_WARNED = True
        import warnings
        warnings.warn(
            "FLAGS_check_nan_inf forces a device sync per op (async "
            "dispatch is disabled while it is on) — debug runs only")
    import jax.numpy as jnp
    flat, _ = jtu.tree_flatten(result, is_leaf=_is_tensor)
    for t in flat:
        if _is_tensor(t) and dtypes.is_floating_point(t.dtype):
            if bool(jnp.any(~jnp.isfinite(t._data))):
                msg = f"nan/inf detected in output of op '{name}'"
                if get_flag("check_nan_inf_level", 0) == 0:
                    raise FloatingPointError(msg)
                print("WARNING:", msg)


def def_op(name: str, spmd_rule: Optional[Callable] = None, **meta):
    """Define a user-facing op from a pure jax-array function.

    Usage::

        @def_op("matmul")
        def matmul(x, y, transpose_x=False, transpose_y=False): ...
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            return call_op(name, fn, args, kwargs)
        OP_REGISTRY[name] = OpDef(name, fn, wrapper, spmd_rule, meta)
        wrapper.raw_fn = fn
        return wrapper
    return deco
