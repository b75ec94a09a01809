"""Mixture-of-experts layer with expert parallelism.

Capability parity: python/paddle/incubate/distributed/models/moe/moe_layer.py
(MoELayer at :263, cross-rank dispatch via global_scatter/global_gather at
:119,:167) in the reference.

TPU-native: the reference scatters variable-length token buffers across ranks
with NCCL alltoall.  Here routing is dense and static-shaped (see gate.py):

    dispatch/combine : [tokens, experts, capacity]
    expert inputs    : einsum('tec,tm->ecm', dispatch, x)
    expert outputs   : expert FFN on the per-expert [capacity, d_model] slices
    output           : einsum('tec,ecm->tm', combine, y)

Unlike the reference (per-rank expert ownership, ``num_expert`` local experts
x ``world_size`` ranks), the single-controller SPMD model sees ALL experts:
``experts`` is the full expert set and expert parallelism is a *placement* of
the expert axis over an 'ep' mesh axis.  Use ``ExpertFFN`` (stacked weights)
+ ``shard_moe_layer`` for that; GSPMD then lowers the reshard between the
token-sharded einsum and the expert-sharded FFN into the same ICI all_to_all
the reference issues by hand.  A list of arbitrary per-expert Layers also
works (loop, replicated weights) for eager/single-host use.

The held-range contract (``held_experts=(first, count)``): the layer is one
expert-parallel rank's share.  The gate still scores the WHOLE expert set
(``gate.tot_expert``), the layer holds experts ``first .. first + count - 1``
(``experts`` is a ``SwiGLUExperts`` of ``count``), keeps the assignments that
fall on them, computes every one of those and returns the partial sum, plus
the shared expert if it has one.  Nothing can be dropped, by either of the
two products the layer chooses between from what it can observe (the count
it holds against ``top_k``; ``DENSE_SHARE``).  A share of about as many
experts as a token chooses sends every token through every held expert,
weighted 0 where it did not choose it: the rows any routing may ask for,
tokens x min(top_k, count), are that dense product or a fraction of it, and
its time does not follow the routing.  A wider share (all 256 of a layer
served whole on one chip) lays the (token, chosen expert) pairs out grouped
by expert in blocks of 16 rows (``ops/pallas/moe_grouped_ffn.py``): tokens x
top_k rows plus the blocks' padding, and the weights of the experts that
were chosen (16 experts of 2,048 x 2,048 at top-1 take it too: the kernel
holds a tile of an expert's width at a time).  Either product leaves the same counts (``ROUTING_FIELDS``).
What the other ranks' experts would add is NOT here: summing it across
ranks is the exchange a multi-chip deployment adds around this layer.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from .....framework.dispatch import def_op
from .....framework.tensor import Tensor
from .....nn.layer.layers import Layer, LayerList
from .....nn.initializer import XavierNormal, Constant
from .....distributed.auto_parallel.placement import Shard, Replicate
from .....distributed.auto_parallel.process_mesh import ProcessMesh
from .....distributed.auto_parallel.api import shard_tensor
from .gate import BaseGate, NaiveGate, GShardGate, SwitchGate


@def_op("moe_dispatch")
def _dispatch(dispatch, x):
    return jnp.einsum("tec,tm->ecm", dispatch, x)


@def_op("moe_combine")
def _combine(combine, y):
    return jnp.einsum("tec,ecm->tm", combine, y)


@def_op("moe_ragged_dispatch")
def _ragged_dispatch(x, expert_idx, slot_pos, keep, num_expert, capacity):
    """Scatter tokens into the [E, C, M] expert buffers by routing
    assignment — O(T*k) work and O(E*C*M) output, never materializing the
    [T, E, C] one-hot (the reference moves the same token payloads with
    global_scatter alltoall, moe_layer.py:119; under an 'ep' sharding of
    the expert axis GSPMD lowers this scatter into that all_to_all).

    x [T, M]; expert_idx/slot_pos/keep [k, T].  Dropped assignments
    (keep=False) land in a dump row that is sliced off."""
    k, T = expert_idx.shape
    M = x.shape[-1]
    dump = num_expert * capacity
    flat = jnp.where(keep, expert_idx * capacity + slot_pos, dump)
    buf = jnp.zeros((dump + 1, M), x.dtype)
    # round-major assignment order matches flat's [k, T] layout; kept
    # slots are unique by construction so .add == .set for them
    buf = buf.at[flat.reshape(-1)].add(jnp.tile(x, (k, 1)))
    return buf[:dump].reshape(num_expert, capacity, M)


@def_op("moe_ragged_combine")
def _ragged_combine(y, expert_idx, slot_pos, keep, weight):
    """Gather each assignment's expert output and weighted-sum per token:
    the inverse of _ragged_dispatch (reference: global_gather,
    moe_layer.py:167).  y [E, C, M] -> out [T, M]."""
    E, C, M = y.shape
    flat = jnp.where(keep, expert_idx * C + slot_pos, E * C)
    y_flat = jnp.concatenate(
        [y.reshape(E * C, M), jnp.zeros((1, M), y.dtype)])
    g = y_flat[flat.reshape(-1)].reshape(*expert_idx.shape, M)  # [k,T,M]
    return jnp.sum(weight[..., None].astype(y.dtype) * g, axis=0)


@def_op("expert_ffn")
def _expert_ffn(x, w1, b1, w2, b2, activation):
    """Stacked-expert FFN on [E, C, M] buffers (batched einsum -> MXU).
    Biases may be None (the fused_moe functional path shares this body)."""
    import jax
    h = jnp.einsum("ecm,emh->ech", x, w1)
    if b1 is not None:
        h = h + b1[:, None, :]
    if activation == "swiglu":
        u, g = jnp.split(h, 2, axis=-1)
        h = u * jax.nn.silu(g)
    else:
        h = getattr(jax.nn, activation)(h)
    y = jnp.einsum("ech,ehm->ecm", h, w2)
    if b2 is not None:
        y = y + b2[:, None, :]
    return y


#: what a share's forward leaves in ``MoELayer.last_routing``, in order, as
#: float32, from either product: the (token, chosen expert) pairs of the
#: real tokens; those that fell on a held expert; the rows computed for
#: them; the most pairs one held expert got; the held experts some token
#: chose (whose weights the grouped product reads)
ROUTING_FIELDS = ("slots", "held", "rows", "most", "touched")

#: a share of at most ``DENSE_SHARE x top_k`` experts takes the dense
#: product, a wider one the grouped.  Measured at 8 held of 256 at top_k 8
#: (``kimi-linear.train.seq8k``: dense), at 256 of 256 at top_k 8
#: (``laguna-xs2.serve.agent8``: grouped, 1.41 against 3.16 ms a layer at 8
#: tokens; PERF.md section 6, PR 33), and ON the line, 16 held of 256 at
#: top_k 8, experts of 4,096 x 2,048 (``mimo-v2-flash.serve.mixed32``; ms a
#: layer from the host's clock around 30 queued calls, dense against
#: grouped, ``benchmark/tests/chip_limits_mimo.py --experts``, PERF.md
#: section 6, PR 49): 160 tokens (5 pairs an expert, all 16 touched) 1.12
#: against 1.26; 288 tokens (9 pairs: the step that cell runs 97.6 % of the
#: time) 1.39 against 1.51; 32 tokens (1 pair an expert, 13 of 16 touched)
#: 1.17 against 0.99.  Both products are bound by the experts' bytes (805 MB
#: at 819 GB/s is 0.98 ms); once every held expert is touched the grouped one
#: only adds its layout, so the line stays where it was and a share ON it
#: takes the dense product at every step.  The grouped one's 0.18 ms at 32
#: tokens is not taken: no cell stands on that side (PERF.md section 7); its
#: readings at 160 and 288 came through a grid with the blocks outermost that
#: is not in the tree (``_ffn_pallas`` refuses a layout whose partial sums
#: pass ``ACC_BYTES``, as this one's do from 98 tokens)
DENSE_SHARE = 2


def _routing_counts(slots, per_expert, rows):
    """``ROUTING_FIELDS`` from the pairs a held expert got [count]."""
    return jnp.stack([jnp.asarray(slots), jnp.sum(per_expert),
                      jnp.asarray(rows), jnp.max(per_expert),
                      jnp.sum(per_expert > 0)]).astype(jnp.float32)


@def_op("moe_held_experts")
def _held_experts(x, idx, weight, first, w_gate, w_up, w_down,
                  token_mask=None):
    """Every token through every HELD expert's SwiGLU, each product
    weighted by the token's routing weight for that expert: 0 where the
    token did not choose it.  x [T, M]; idx, weight [T, k] (ids over the
    whole expert set); stacked weights [count, M, H] / [count, H, M];
    ``token_mask`` [T] bool or None: a False token has no pair (its row is
    computed with weight 0).  Returns (y [T, M], counts as
    ``ROUTING_FIELDS``).  A token picks an expert at most once, so the
    rows a share may have to compute under ANY routing are T x
    min(k, count); with count <= k that is this dense product, whose
    time does not depend on the routing."""
    count = w_gate.shape[0]
    chose = idx[:, :, None] == first + jnp.arange(count)        # [T, k, E]
    slots = idx.size
    if token_mask is not None:
        chose &= token_mask[:, None, None]
        slots = jnp.sum(token_mask) * idx.shape[1]
    w = jnp.sum(jnp.where(chose, weight[:, :, None], 0.0), axis=1)
    h = (jax.nn.silu(jnp.einsum("tm,emh->teh", x, w_gate))
         * jnp.einsum("tm,emh->teh", x, w_up))
    y = jnp.einsum("teh,ehm->tm", (h * w[:, :, None]).astype(x.dtype),
                   w_down)
    return y, _routing_counts(slots, jnp.sum(chose, axis=(0, 1)),
                              x.shape[0] * count)


@def_op("moe_grouped_experts")
def _grouped_experts(x, idx, weight, token_mask, first, w_gate, w_up,
                     w_down):
    """The (token, chosen expert) pairs that fall on the HELD experts,
    grouped by expert, each through its expert's SwiGLU and summed back
    a token with its routing weight (``ops/pallas/moe_grouped_ffn.py``;
    serving: the Pallas call has no gradient).  ``token_mask`` [T] bool
    or None: a False token (a pad position of a packed step) has no pair
    and touches no expert.  Returns (y [T, M], counts as
    ``ROUTING_FIELDS``)."""
    from .....ops.pallas.moe_grouped_ffn import grouped_swiglu
    count = w_gate.shape[0]
    local = idx - first
    real = (jnp.ones(idx.shape[:1], bool) if token_mask is None
            else token_mask)
    held = (local >= 0) & (local < count) & real[:, None]
    y, per_expert, rows = grouped_swiglu(x, local, weight, held, w_gate,
                                         w_up, w_down)
    return y, _routing_counts(jnp.sum(real) * idx.shape[1], per_expert, rows)


class SwiGLUExperts(Layer):
    """``num_expert`` bias-free SwiGLU experts, weights stacked on a
    leading expert axis (the container of the held-experts path)."""

    def __init__(self, num_expert, d_model, d_hidden, weight_attr=None):
        super().__init__()
        self.num_expert = num_expert
        attr = weight_attr if weight_attr is not None else XavierNormal()
        self.gate_proj = self.create_parameter(
            [num_expert, d_model, d_hidden], attr=attr)
        self.up_proj = self.create_parameter(
            [num_expert, d_model, d_hidden], attr=attr)
        self.down_proj = self.create_parameter(
            [num_expert, d_hidden, d_model], attr=attr)


class ExpertFFN(Layer):
    """All experts' FFN weights stacked on a leading expert axis — the
    TPU-native expert container (shardable over 'ep', batched on the MXU)."""

    def __init__(self, num_expert, d_model, d_hidden, activation="gelu"):
        super().__init__()
        self.num_expert = num_expert
        self.activation = activation
        w1_cols = 2 * d_hidden if activation == "swiglu" else d_hidden
        self.w1 = self.create_parameter([num_expert, d_model, w1_cols],
                                        attr=XavierNormal())
        self.b1 = self.create_parameter([num_expert, w1_cols],
                                        attr=Constant(0.0), is_bias=True)
        self.w2 = self.create_parameter([num_expert, d_hidden, d_model],
                                        attr=XavierNormal())
        self.b2 = self.create_parameter([num_expert, d_model],
                                        attr=Constant(0.0), is_bias=True)

    def forward(self, expert_in):
        return _expert_ffn(expert_in, self.w1, self.b1, self.w2, self.b2,
                           self.activation)


class MoELayer(Layer):
    """reference: moe_layer.py:263 MoELayer(d_model, experts, gate, ...).

    ``experts``: an ExpertFFN (stacked fast path), or a list of Layers (one
    per expert — the full global expert set).  ``gate``: a BaseGate instance
    or config dict {"type": "gshard"|"switch"|"naive", "top_k": k}.

    ``held_experts=(first, count)``: this layer is a share (module
    docstring): ``experts`` is a ``SwiGLUExperts`` of ``count``, ``gate`` a
    gate with ``route_no_drop`` over the whole set (``SigmoidTopKGate``).
    ``shared_expert``: a Layer every token also goes through, added once.
    After a forward of a share, ``last_routing`` holds its counts
    (``ROUTING_FIELDS``; ``routing_counts()`` gives them by name).
    ``forward(x, token_mask=)``: positions that are no tokens (the pad of a
    packed serving step) are routed nowhere.  ``forward(x, router_state=)``:
    for a gate whose router reads the previous layer's router
    (``DepthAveragedMLPGate``: ``carries_state``), that layer's state
    [tokens, hidden] or None for the first; the layer then returns
    ``(y, its own state)``, to be handed to the next.
    """

    def __init__(self, d_model: int,
                 experts: Union[ExpertFFN, "SwiGLUExperts", Sequence[Layer]],
                 gate=None, moe_group=None, mp_group=None,
                 recompute_interval=0, recompute_ctx=None,
                 held_experts=None, shared_expert=None):
        super().__init__()
        self.d_model = d_model
        self.held_experts = (None if held_experts is None
                             else (int(held_experts[0]), int(held_experts[1])))
        self.shared_expert = shared_expert
        self.last_routing = None
        if isinstance(experts, (ExpertFFN, SwiGLUExperts)):
            self.experts = experts
            self.num_expert = experts.num_expert
        else:
            self.experts = (experts if isinstance(experts, LayerList)
                            else LayerList(list(experts)))
            self.num_expert = len(self.experts)
        self.moe_group = moe_group
        self.recompute_interval = recompute_interval
        if gate is None:
            gate = {"type": "gshard", "top_k": 2}
        if isinstance(gate, dict):
            kind = gate.get("type", "gshard")
            topk = gate.get("top_k", 2 if kind != "switch" else 1)
            # a gate built from a dict routes over exactly the experts
            # this layer holds (world_size=1); a share of a wider gate
            # passes the gate itself and held_experts=(first, count)
            assert self.held_experts is None, (
                "held_experts needs the gate instance that routes over the "
                "whole expert set, not a config dict")
            if kind == "naive":
                gate = NaiveGate(d_model, self.num_expert, 1, topk=topk)
            elif kind == "switch":
                # switch routing is top-1 by definition; a config that
                # says otherwise is corrected with a warning instead of
                # tripping SwitchGate's assert (every dict caller would
                # otherwise need this special case)
                if topk != 1:
                    import warnings
                    warnings.warn(
                        f"switch gate is top-1 by definition; ignoring "
                        f"top_k={topk}")
                gate = SwitchGate(d_model, self.num_expert, 1, topk=1)
            else:
                gate = GShardGate(d_model, self.num_expert, 1, topk=topk)
        assert isinstance(gate, BaseGate)
        if self.held_experts is None:
            assert gate.tot_expert == self.num_expert, (
                f"the gate routes over {gate.tot_expert} experts and the "
                f"layer holds {self.num_expert}: a layer that holds a share "
                "of them says which with held_experts=(first, count)")
        else:
            first, count = self.held_experts
            assert isinstance(self.experts, SwiGLUExperts) \
                and hasattr(gate, "route_no_drop"), (
                    "a share of the experts is a SwiGLUExperts routed by a "
                    "gate with route_no_drop (SigmoidTopKGate, "
                    "DepthAveragedMLPGate)")
            assert count == self.num_expert and first >= 0 \
                and first + count <= gate.tot_expert, (
                    f"held_experts=({first}, {count}) must name the "
                    f"{self.num_expert} experts this layer holds, inside "
                    f"the {gate.tot_expert} the gate routes over")
        self.gate = gate

    @property
    def l_aux(self):
        return self.gate.get_loss(clear=False)

    def _run_experts(self, expert_in, use_recompute=False):
        if use_recompute:
            from .....distributed.fleet.recompute import recompute
        if isinstance(self.experts, ExpertFFN):
            if use_recompute:
                return recompute(self.experts, expert_in)
            return self.experts(expert_in)
        outs = []
        for i, expert in enumerate(self.experts):
            seg = (recompute(expert, expert_in[i]) if use_recompute
                   else expert(expert_in[i]))
            if isinstance(seg, (tuple, list)):
                seg = seg[0]
            outs.append(seg.unsqueeze(0))
        from .....tensor.manipulation import concat
        return concat(outs, axis=0)                      # [E, C, M]

    def _forward_share(self, tokens, token_mask=None, router_state=None):
        first, count = self.held_experts
        ex = self.experts
        state = None
        with jax.named_scope("moe/router"):
            if getattr(self.gate, "carries_state", False):
                idx, w, state = self.gate.route_no_drop(tokens, router_state)
            else:
                idx, w = self.gate.route_no_drop(tokens)
        with jax.named_scope("moe/experts"):
            if count <= DENSE_SHARE * self.gate.top_k:
                y, self.last_routing = _held_experts(
                    tokens, idx, w, first, ex.gate_proj, ex.up_proj,
                    ex.down_proj, token_mask)
            else:
                y, self.last_routing = _grouped_experts(
                    tokens, idx, w, token_mask, first, ex.gate_proj,
                    ex.up_proj, ex.down_proj)
            if self.shared_expert is not None:
                y = y + self.shared_expert(tokens)
        return y, state

    def routing_counts(self) -> dict:
        """The last forward's counts by name (``ROUTING_FIELDS``)."""
        return dict(zip(ROUTING_FIELDS, self.last_routing._data))

    def forward(self, x: Tensor, token_mask=None, router_state=None):
        orig_shape = x.shape
        tokens = x.reshape([-1, self.d_model])
        if self.held_experts is not None:
            y, state = self._forward_share(tokens, token_mask, router_state)
            y = y.reshape(orig_shape)
            # a gate that carries a state from layer to layer hands this
            # layer's out beside the output, for the caller to hand the
            # next layer's: it lives in the caller's program, never here
            return (y, state) if state is not None else y
        use_recompute = self.recompute_interval > 0 and self.training
        if (isinstance(self.gate, NaiveGate)
                and type(self.gate).forward is NaiveGate.forward):
            # ragged fast path: O(T) routing metadata + scatter/gather,
            # no [T, E, C] tensor.  A subclass that overrides forward()
            # (the documented combine/dispatch contract) keeps its
            # override — only stock gate routing is substituted.
            eidx, pos, keep, w, cap = self.gate.route(tokens)
            expert_in = _ragged_dispatch(tokens, eidx, pos, keep,
                                         self.num_expert, cap)
            expert_out = self._run_experts(expert_in, use_recompute)
            y = _ragged_combine(expert_out, eidx, pos, keep, w)
        else:
            # custom gates keep the dense combine/dispatch contract
            combine, dispatch = self.gate(tokens)
            expert_in = _dispatch(dispatch, tokens)      # [E, C, M]
            expert_out = self._run_experts(expert_in, use_recompute)
            y = _combine(combine, expert_out)            # [T, M]
        return y.reshape(orig_shape)


def shard_moe_layer(layer: MoELayer, mesh: ProcessMesh, axis: str = "ep"):
    """Place a MoELayer for expert parallelism: gate replicated, stacked
    expert weights Shard(0) over ``axis`` — GSPMD inserts the cross-rank
    all_to_all around the expert FFN (the compiled equivalent of the
    reference's global_scatter/global_gather).

    Requires the stacked ``ExpertFFN`` expert container; a Python list of
    arbitrary expert Layers has no shardable expert axis."""
    if layer.held_experts is not None:
        raise ValueError(
            f"this MoELayer already holds a share of the experts "
            f"(held_experts={layer.held_experts}): it is one rank's part, "
            "there is no whole expert axis left to shard")
    if not isinstance(layer.experts, ExpertFFN):
        raise NotImplementedError(
            "expert parallelism needs stacked expert weights: build the "
            "MoELayer with experts=ExpertFFN(...) (a list of per-expert "
            "Layers runs replicated)")
    axis_idx = mesh.dim_names.index(axis)
    repl = [Replicate()] * mesh.ndim

    def _place(p, placements):
        sharded = shard_tensor(p, mesh, placements)
        p._data = sharded._data
        p.dist_attr = sharded.dist_attr

    for p in layer.gate.parameters():
        _place(p, repl)
    ep = list(repl)
    ep[axis_idx] = Shard(0)
    for p in layer.experts.parameters():
        _place(p, ep)
    return layer
