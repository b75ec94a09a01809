"""MoE gates: naive top-k, GShard top-2, Switch top-1, and the sigmoid
top-k router with a selection bias (no capacity, no drops).

Capability parity: python/paddle/incubate/distributed/models/moe/gate/ in the
reference (base_gate.py BaseGate, naive_gate.py NaiveGate, gshard_gate.py
GShardGate, switch_gate.py SwitchGate).

TPU-native: the reference routes tokens with variable-length index buffers
(utils.py count_by_gate + global_scatter alltoall).  XLA needs static shapes,
so gates here emit dense *combine*/*dispatch* tensors over a fixed per-expert
capacity (GShard-style):

    dispatch [tokens, experts, capacity]  one-hot routing tensor
    combine  [tokens, experts, capacity]  dispatch * gate probability

MoE dispatch/combine then becomes two einsums that map straight onto the MXU,
and expert parallelism is just a sharding of the expert axis (GSPMD inserts
the all_to_all).  Tokens routed past an expert's capacity are dropped (their
combine weight is zero), matching GShard/Switch semantics.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .....framework.dispatch import def_op
from .....framework import random as _random
from .....nn.layer.layers import Layer, ParamAttr
from .....nn.initializer import Constant, XavierNormal


def moe_capacity(top_k, num_tokens, num_expert, factor):
    """Per-expert capacity C = ceil(top_k * T / E * factor), clamped to
    [1, T].  Single definition shared by the gates and fused_moe."""
    cap = int(math.ceil(top_k * num_tokens * factor / max(num_expert, 1)))
    return max(1, min(cap, num_tokens))


def _topk_routing(gates, top_k, capacity, normalize, random_keep=None):
    """Capacity-based top-k routing WITHOUT densification — the shared
    core of both the dense [T,E,C] oracle and the O(T) ragged dispatch.

    gates: [T, E] softmax probabilities.  ``random_keep``: optional [T]
    uniforms — when given, the second-choice expert is kept only where
    u < 2 * p2 (GShard random routing).

    Returns (expert_idx [k,T] int32, slot_pos [k,T] int32, keep [k,T]
    bool, weight [k,T] — capacity-masked, normalized if requested —
    l_aux scalar).  Slot positions count EVERY token that chose the
    expert (in round-major, token order), so dropped assignments leave
    holes in the capacity buffer — GShard semantics, and identical to
    what the dense path always did.  Largest intermediate is [T, E]
    (which the gate's softmax already materializes); nothing here is
    O(T*E*C)."""
    T, E = gates.shape
    remaining = gates
    fill = jnp.zeros((E,), jnp.int32)        # tokens already placed per expert
    eidx_l, pos_l, keep_l, w_l = [], [], [], []
    first_mask = None
    for k in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                    # [T]
        onehot = jax.nn.one_hot(idx, E, dtype=jnp.int32)        # [T, E]
        if first_mask is None:
            first_mask = onehot
        # Position of each token inside its expert's capacity buffer:
        # earlier tokens (and earlier rounds) get earlier slots.
        pos_grid = jnp.cumsum(onehot, axis=0) - onehot + fill[None, :]
        pos = jnp.sum(pos_grid * onehot, axis=1)                # [T]
        within = pos < capacity
        gate_val = jnp.take_along_axis(gates, idx[:, None], axis=1)[:, 0]
        if k == 1 and random_keep is not None:
            within = within & (random_keep < 2.0 * gate_val)
        eidx_l.append(idx.astype(jnp.int32))
        pos_l.append(pos.astype(jnp.int32))
        keep_l.append(within)
        w_l.append(gate_val * within.astype(gates.dtype))
        fill = fill + jnp.sum(onehot, axis=0)
        remaining = remaining * (1 - onehot).astype(gates.dtype)
    w = jnp.stack(w_l)                                          # [k, T]
    if normalize:
        w = w / jnp.maximum(jnp.sum(w, axis=0, keepdims=True), 1e-9)
    # GShard load-balance loss over the primary (top-1) assignment:
    # E * sum_e(mean_prob_e * fraction_tokens_e).
    me = jnp.mean(gates, axis=0)                                 # [E]
    ce = jnp.mean(first_mask.astype(gates.dtype), axis=0)        # [E]
    l_aux = jnp.sum(me * ce) * E
    return (jnp.stack(eidx_l), jnp.stack(pos_l), jnp.stack(keep_l), w,
            l_aux)


def _capacity_gating(gates, top_k, capacity, normalize, random_keep=None):
    """Dense capacity-based top-k routing — the numerics ORACLE.

    Densifies _topk_routing into (combine [T,E,C], dispatch [T,E,C]
    float 0/1, l_aux).  O(T*E*C) memory: use the ragged path
    (moe_ragged_dispatch/combine) at scale; this form remains for the
    einsum path and for checking the ragged path against."""
    E = gates.shape[1]
    eidx, pos, keep, w, l_aux = _topk_routing(
        gates, top_k, capacity, normalize, random_keep)
    oh_e = jax.nn.one_hot(eidx, E, dtype=gates.dtype)           # [k,T,E]
    oh_c = jax.nn.one_hot(pos, capacity, dtype=gates.dtype)     # [k,T,C]
    sel = (oh_e[..., :, None] * oh_c[..., None, :]
           * keep[..., None, None].astype(gates.dtype))         # [k,T,E,C]
    combine = jnp.sum(w[..., None, None] * sel, axis=0)
    dispatch = (combine > 0).astype(gates.dtype)
    return combine, dispatch, l_aux


@def_op("moe_gating")
def _moe_gating(logits, top_k, capacity, normalize, random_keep=None):
    gates = jax.nn.softmax(logits, axis=-1)
    return _capacity_gating(gates, top_k, capacity, normalize, random_keep)


@def_op("moe_topk_routing")
def _moe_topk_routing(logits, top_k, capacity, normalize,
                      random_keep=None):
    import jax.numpy as _jnp
    if random_keep is None and logits.dtype == _jnp.float32:
        # fused Pallas gating on TPU (per-shape measured dispatch, the
        # same policy the attention/rmsnorm/rope kernels use); the XLA
        # oracle everywhere else, for GShard random routing, and for
        # non-f32 logits (the kernel computes in f32, so low-precision
        # inputs could route differently than the same-dtype oracle —
        # argmax ties break differently after the upcast)
        from .....ops import autotune as _autotune
        from .....ops.pallas.moe_gating import topk_gating_pallas

        key = (f"moe_gating:{tuple(logits.shape)}:{top_k}:{capacity}:"
               f"{logits.dtype}")
        impl = _autotune.select(
            key, logits,
            {"xla": lambda: _topk_routing(
                jax.nn.softmax(logits, axis=-1), top_k, capacity,
                normalize),
             "pallas": lambda: topk_gating_pallas(
                 logits, top_k, capacity, normalize)},
            default="xla")
        if impl == "pallas":
            return topk_gating_pallas(logits, top_k, capacity, normalize)
    gates = jax.nn.softmax(logits, axis=-1)
    return _topk_routing(gates, top_k, capacity, normalize, random_keep)


class BaseGate(Layer):
    """reference: gate/base_gate.py BaseGate."""

    def __init__(self, num_expert, world_size):
        super().__init__()
        self.world_size = world_size
        self.num_expert = num_expert
        self.tot_expert = world_size * num_expert
        self.loss = None

    def capacity(self, num_tokens, training=True):
        factor = self.cap[0] if training else self.cap[1]
        return moe_capacity(self.top_k, num_tokens, self.tot_expert, factor)

    def set_loss(self, loss):
        self.loss = loss

    def get_loss(self, clear=True):
        loss = self.loss
        if clear:
            self.loss = None
        return loss

    def forward(self, x):
        raise NotImplementedError("Base gate cannot be called")


class NaiveGate(BaseGate):
    """Plain learned top-k gate, no balance loss
    (reference: gate/naive_gate.py).  Generous default capacity so token
    drop is rare."""

    use_balance_loss = False

    def __init__(self, d_model, num_expert, world_size, topk=2):
        super().__init__(num_expert, world_size)
        self.d_model = d_model
        self.top_k = topk
        self.cap = (2.0, 4.0)
        self.normalize = True
        self.gate_weight = self.create_parameter(
            [d_model, self.tot_expert], attr=XavierNormal())

    def gate_logits(self, x):
        return x.matmul(self.gate_weight)

    def _random_keep(self, num_tokens):
        return None

    def forward(self, x):
        """x: [tokens, d_model] -> (combine, dispatch) [T, E, C]."""
        logits = self.gate_logits(x)
        cap = self.capacity(x.shape[0], self.training)
        combine, dispatch, l_aux = _moe_gating(
            logits, self.top_k, cap, self.normalize,
            self._random_keep(x.shape[0]))
        self.set_loss(l_aux if self.use_balance_loss else None)
        return combine, dispatch

    def route(self, x):
        """Ragged routing: x [T, d_model] -> (expert_idx, slot_pos, keep,
        weight) each [top_k, T], plus capacity — O(T) memory, no [T,E,C]
        tensor.  Same selection math as forward(); MoELayer's fast path."""
        logits = self.gate_logits(x)
        cap = self.capacity(x.shape[0], self.training)
        eidx, pos, keep, w, l_aux = _moe_topk_routing(
            logits, self.top_k, cap, self.normalize,
            self._random_keep(x.shape[0]))
        self.set_loss(l_aux if self.use_balance_loss else None)
        return eidx, pos, keep, w, cap


class GShardGate(NaiveGate):
    """Top-2 gate with capacity, load-balance loss and random second-choice
    routing (reference: gate/gshard_gate.py)."""

    use_balance_loss = True

    def __init__(self, d_model, num_expert, world_size, topk=2,
                 capacity=(1.2, 2.4), random_routing=True, group=None):
        assert topk == 2, "GShard only supports top-2 gating"
        super().__init__(d_model, num_expert, world_size, topk=2)
        self.cap = capacity
        self.random_routing = random_routing
        self.normalize = True

    def _random_keep(self, num_tokens):
        if not (self.training and self.random_routing):
            return None
        from .....tensor.creation import rand
        return rand([num_tokens], dtype="float32")


class SwitchGate(NaiveGate):
    """Top-1 switch gate with jitter noise + balance loss
    (reference: gate/switch_gate.py)."""

    use_balance_loss = True

    def __init__(self, d_model, num_expert, world_size, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4), group=None):
        assert topk == 1, "Switch gate only supports top-1"
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = switch_eps
        self.cap = capacity
        self.normalize = False

    def gate_logits(self, x):
        logits = x.matmul(self.gate_weight)
        if self.training and self.switch_eps > 0:
            from .....tensor.creation import rand
            noise = rand(logits.shape, dtype=logits.dtype)
            noise = noise * (2 * self.switch_eps) + (1.0 - self.switch_eps)
            logits = logits * noise
        return logits


@def_op("moe_sigmoid_topk")
def _sigmoid_topk(logits, bias, top_k, renormalize, scaling):
    """Scores sigma(logits) in float32; the ``top_k`` largest of
    score + bias are chosen (the bias steers the choice only); the
    weights are the chosen SCORES, divided by their sum if
    ``renormalize``, times ``scaling``.  Returns (expert ids [T, k]
    int32, weights [T, k] float32).  Only the weights carry gradient."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scaling


@def_op("moe_router_logits_f32")
def _logits_f32(x, w):
    """x @ w accumulated AND kept in float32 whatever the operands'
    dtype: a bfloat16 logit's rounding (2^-9 of its size) is wider than
    the gap between the k-th and the (k+1)-th of 256 scores."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


class SigmoidTopKGate(BaseGate):
    """The DeepSeek-V3 / Kimi router: one sigmoid score an expert, top-k
    over score + ``e_score_correction_bias`` (float32, ``trainable=False``:
    the published models move it with a load-balancing rule outside the
    gradient), weights renormalised over the chosen and scaled.  No
    capacity and no balance loss: every assignment is kept, so it is
    routed by ``MoELayer``'s held-experts path (``route_no_drop``) and
    has no dense combine/dispatch form.  ``float32_logits``: the logits
    leave the product in float32 (the scores always are float32)."""

    def __init__(self, d_model, num_expert, world_size=1, topk=8,
                 renormalize=True, routed_scaling_factor=1.0,
                 float32_logits=False):
        super().__init__(num_expert, world_size)
        self.d_model = d_model
        self.top_k = topk
        self.float32_logits = bool(float32_logits)
        self.renormalize = renormalize
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.gate_weight = self.create_parameter(
            [d_model, self.tot_expert], attr=XavierNormal())
        # not trained, but a parameter and not a buffer: a compiled step
        # takes parameters as arguments and closes over buffers, and a
        # value that differs from seed to seed inside the program is a
        # compile-cache miss on every run
        self.e_score_correction_bias = self.create_parameter(
            [self.tot_expert], dtype="float32",
            attr=ParamAttr(initializer=Constant(0.0), trainable=False))

    def route_no_drop(self, x):
        """x [T, d_model] -> (expert ids [T, k], weights [T, k])."""
        logits = (_logits_f32(x, self.gate_weight) if self.float32_logits
                  else x.matmul(self.gate_weight))
        return _sigmoid_topk(logits, self.e_score_correction_bias,
                             self.top_k, self.renormalize,
                             self.routed_scaling_factor)

    def forward(self, x):
        raise NotImplementedError(
            "SigmoidTopKGate keeps every assignment and has no "
            "[tokens, experts, capacity] form: use it in a MoELayer "
            "built with held_experts=(first, count)")


@def_op("moe_mlp_router_top1")
def _mlp_router_top1(r, norm_w, w1, b1, w2, b2, w3, bias, eps):
    """The router's float32 path from its 256-wide state on: RMSNorm, a
    three-layer GELU MLP, softmax; the expert chosen is the argmax of
    probability + ``bias`` (the bias steers the choice only) and its
    weight the UNBIASED probability, not renormalised.  Every product at
    precision "highest": a float32 operand's default product on the TPU
    is one bfloat16 pass.  Returns (ids [T, 1] int32, weights [T, 1])."""
    hi = jax.lax.Precision.HIGHEST
    u = r * jax.lax.rsqrt(jnp.mean(r * r, axis=-1, keepdims=True) + eps) \
        * norm_w
    h = jax.nn.gelu(jnp.dot(u, w1, precision=hi) + b1, approximate=False)
    h = jax.nn.gelu(jnp.dot(h, w2, precision=hi) + b2, approximate=False)
    p = jax.nn.softmax(jnp.dot(h, w3, precision=hi), axis=-1)
    idx = jnp.argmax(p + bias, axis=-1)[:, None].astype(jnp.int32)
    return idx, jnp.take_along_axis(p, idx, axis=-1)


class DepthAveragedMLPGate(BaseGate):
    """The ZAYA router: more than one matrix, and a state handed from one
    layer's router to the next.  ``r = W_d x + b_d`` (d_model ->
    ``hidden``, accumulated and kept in float32); with the previous
    layer's state ``r_prev``: ``r += gamma * r_prev`` (depth averaging;
    ``r`` is handed on as it stands HERE, before the norm); then
    ``softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2))`` over the
    experts, top-1 over probability + ``balancing_bias`` (float32,
    ``trainable=False``: moved against the load outside the gradient, a
    parameter and not a buffer for ``SigmoidTopKGate``'s reason), the
    weight the unbiased probability.  Everything behind ``W_d`` is float32
    whatever the model's dtype.  No capacity: routed by ``MoELayer``'s
    held-experts path, which hands the state in and out
    (``carries_state``).  ``first`` (the stack's first layer) has no
    ``gamma``: there is no state before it."""

    carries_state = True
    top_k = 1

    def __init__(self, d_model, num_expert, hidden, first=False, eps=1e-5,
                 weight_attr=None):
        super().__init__(num_expert, 1)
        self.d_model, self.hidden, self.eps = d_model, hidden, float(eps)
        attr = weight_attr if weight_attr is not None else XavierNormal()
        f32 = dict(dtype="float32")
        self.down_weight = self.create_parameter([d_model, hidden], attr=attr)
        self.down_bias = self.create_parameter([hidden], is_bias=True, **f32)
        self.gamma = None if first else self.create_parameter(
            [hidden], default_initializer=Constant(1.0), **f32)
        self.norm_weight = self.create_parameter(
            [hidden], default_initializer=Constant(1.0), **f32)
        self.w1 = self.create_parameter([hidden, hidden], attr=attr, **f32)
        self.b1 = self.create_parameter([hidden], is_bias=True, **f32)
        self.w2 = self.create_parameter([hidden, hidden], attr=attr, **f32)
        self.b2 = self.create_parameter([hidden], is_bias=True, **f32)
        self.w3 = self.create_parameter([hidden, self.tot_expert], attr=attr,
                                        **f32)
        self.balancing_bias = self.create_parameter(
            [self.tot_expert], dtype="float32",
            attr=ParamAttr(initializer=Constant(0.0), trainable=False))

    def route_no_drop(self, x, state=None):
        """x [T, d_model], ``state`` [T, hidden] float32 the previous
        layer's or None -> (expert ids [T, 1], weights [T, 1], this
        layer's state [T, hidden])."""
        r = _logits_f32(x, self.down_weight) + self.down_bias
        if self.gamma is not None and state is not None:
            r = r + self.gamma * state
        idx, w = _mlp_router_top1(r, self.norm_weight, self.w1, self.b1,
                                  self.w2, self.b2, self.w3,
                                  self.balancing_bias, self.eps)
        return idx, w, r

    def forward(self, x):
        raise NotImplementedError(
            "DepthAveragedMLPGate keeps every assignment and has no "
            "[tokens, experts, capacity] form: use it in a MoELayer "
            "built with held_experts=(first, count)")
