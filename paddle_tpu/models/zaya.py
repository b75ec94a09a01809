"""ZAYA1: a decoder whose every layer is a compressed convolutional
attention (CCA) sublayer and then a top-1 mixture of wide experts chosen
by an MLP router that reads the previous layer's router.

Source of the architecture: the published ``config.json`` of
``Zyphra/ZAYA1-8B`` (``model_type`` ``zaya``; every ``layer_types`` entry
``"hybrid"``); ``ZayaConfig`` keeps its key names.  Write E the hidden
size, d the head width, H_q query heads over H_k KV heads (G = H_q / H_k
a group), L_q = H_q d, L_k = H_k d.  Layer l:

    x <- Merge_a(x, CCA(RMSNorm(x)))
    (y, r_l) = MoE(RMSNorm(x), r_{l-1});  x <- Merge_m(x, y)

then a final RMSNorm and the head tied to the embedding.

**CCA**: attention wholly inside the compressed width; nothing is
projected back up before the softmax.  For token t, ``x_t`` the normed
input, everything before position 0 zero:

1. ``z_t = [W_Q x_t; W_K x_t]`` (L_q + L_k channels = H_q + H_k heads of d);
2. a depthwise convolution over time of width ``cca_time0`` = 2:
   ``a_t = w0[0] . z_{t-1} + w0[1] . z_t + b0``;
3. a convolution of width ``cca_time1`` = 2 grouped a HEAD (d -> d):
   ``c_t = W1[0] a_{t-1} + W1[1] a_t + b1``, the sequence padded in front
   ONCE (of ``z``), so ``a_{-1} = b0``; ``c_t`` splits into q' and k';
4. the q-k mean of the values BEFORE the convolutions: query head h of KV
   group g: ``mq_h = (z^q_h + z^k_g) / 2``, ``mk_g = mean_{h in g} mq_h``;
   ``q = q' + mq``, ``k = k' + mk``;
5. the value shift: ``v_t = [W_V1 x_t; W_V2 x_{t-1}]`` cut into the H_k
   heads in that order: the first half of the KV heads hold the token's own
   value, the second half the token's before it;
6. ``q^ = q / rms(q)``, ``k^ = tau_g k / rms(k)`` a head at a time (rms over
   the head's d lanes, so the norm of ``q^`` is sqrt(d)); rope on the first
   ``partial_rotary_factor`` d lanes, rotate-half over those;
7. causal softmax(q^ k^T / sqrt(d)) v over the H_k heads, then ``W_O``
   (L_q -> E).  The cache holds ``k^`` and ``v``.

A sequence's state a layer BESIDE its pages is three one-token tails:
``[z_{t-1}; W_V2 x_{t-1}]`` and ``a_{t-1} - b0``, float32, two arrays of a
slot (``recurrent_state``; ``paged_ctx.shift_rows`` is the one primitive
that serves them: a token's predecessor).  What ``attend`` appends is a
function of the tails, so a chunk boundary, a row entering a used slot and
pause -> resume all change what the PAGES hold: the slot and the pages of a
sequence are taken, re-run and returned together (the engine's preemption
of a recurrent model, ``ContinuousBatchingEngine._preempt_locked``).

**The expert sublayer**: ``MoELayer`` with ``DepthAveragedMLPGate`` (the
router of ``router_hidden_size``, float32 behind its first product, its
state ``r_l`` an operand of layer l + 1's gate inside the same program)
over ``num_experts`` SwiGLU experts of ``moe_intermediate_size``, top-1,
the weight the chosen probability, no shared expert, all experts held.

**Merge**: ``x <- (a_r . x + b_r) + (a_y . y + b_y)``, four vectors a
sublayer.

What no key of the config gives is listed with its reason under
``assumed`` in the benchmark's configuration file
(``benchmark/configs/zaya1-8b.serve-pp2-d20.json``); none of it is an
option of ``ZayaConfig``.  Single chip: ``inference.paged._tp_plan``
refuses this model by what it lacks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..framework.tensor import Tensor, wrap_array
from ..incubate.distributed.models.moe import (DepthAveragedMLPGate,
                                               MoELayer, SwiGLUExperts)
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from .laguna import _masked_attention, rope_tables

F32 = jnp.float32
HYBRID = "hybrid"


def _published_rope():
    return {HYBRID: {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                     "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"}


@dataclass
class ZayaConfig:
    """The published keys at their published values (ZAYA1-8B)."""
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_parameters: dict = field(default_factory=_published_rope)
    layer_types: Optional[List[str]] = None
    sliding_window: Optional[int] = None
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    attention_bias: bool = False
    lm_head_bias: bool = False
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 131072
    model_type: str = "zaya"

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [HYBRID] * n
        if list(self.layer_types) != [HYBRID] * n \
                or self.sliding_window is not None:
            raise NotImplementedError(
                "the published model's layers are all 'hybrid' (CCA then "
                "experts) and none is windowed")
        if (self.cca_time0, self.cca_time1) != (2, 2):
            raise NotImplementedError(
                "both convolutions are two taps wide as published: a "
                "sequence's slot is ONE token's tails")
        if (not self.tie_word_embeddings or self.attention_bias
                or self.lm_head_bias or self.hidden_act != "silu"
                or self.num_experts_per_tok != 1):
            raise NotImplementedError(
                "the published model has a tied head, no attention or head "
                "bias, silu experts and one expert a token")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise NotImplementedError(
                "query heads in whole groups over an even number of KV "
                "heads (half hold the token's own value, half the "
                "shifted one), as published")

    @property
    def latent(self):
        """L_q + L_k: the channels the convolutions mix."""
        return (self.num_attention_heads
                + self.num_key_value_heads) * self.head_dim

    @property
    def value_half(self):
        """The width of W_V1 x and of W_V2 x: half of the KV heads each."""
        return self.num_key_value_heads * self.head_dim // 2


def _shift(x):
    """(b, s, D) -> each token's predecessor in its sequence, zeros before
    the first: the forward without a cache."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


class ZayaCCA(Layer):
    """Compressed convolutional attention (module docstring, steps 1-7)."""

    def __init__(self, config: ZayaConfig, weight_attr):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = d = c.head_dim
        self.latent, self.value_half = c.latent, c.value_half
        self.rot = int(d * c.partial_rotary_factor)
        self.eps = c.rms_norm_eps
        # [W_Q; W_K; W_V1; W_V2] as one product
        self.qkv_proj = Linear(c.hidden_size,
                               self.latent + 2 * self.value_half,
                               weight_attr=weight_attr, bias_attr=False)
        self.conv0_weight = self.create_parameter((2, self.latent),
                                                  attr=weight_attr)
        self.conv0_bias = self.create_parameter((self.latent,), is_bias=True)
        self.conv1_weight = self.create_parameter(
            (2, self.latent // d, d, d), attr=weight_attr)
        self.conv1_bias = self.create_parameter((self.latent,), is_bias=True)
        # tau, a KV head, stored as it multiplies, float32
        self.k_scale = self.create_parameter(
            (self.num_kv_heads,), dtype="float32",
            default_initializer=Constant(1.0))
        self.o_proj = Linear(self.num_heads * d, c.hidden_size,
                             weight_attr=weight_attr, bias_attr=False)

    def _rope(self, x, cos, sin, position_offset):
        """x (b, s, heads, d) float32: the first ``rot`` lanes rotated."""
        s, half = x.shape[1], self.rot // 2
        if getattr(position_offset, "ndim", 0) == 1:    # a position a row
            at = position_offset[:, None] + jnp.arange(s)[None]
        else:
            at = (position_offset + jnp.arange(s))[None]
        co, si = cos[at][:, :, None, :], sin[at][:, :, None, :]
        x1, x2 = x[..., :half], x[..., half:2 * half]
        return jnp.concatenate([x1 * co - x2 * si, x2 * co + x1 * si,
                                x[..., 2 * half:]], axis=-1)

    def forward(self, x, cos, sin, position_offset=0, paged_ctx=None):
        b, s = x.shape[0], x.shape[1]
        hq, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        lz, lq = self.latent, self.num_heads * self.head_dim
        with jax.named_scope("proj"):
            qkv = self.qkv_proj(x)._data.astype(F32)
        with jax.named_scope("mix"):
            z, v1 = qkv[..., :lz], qkv[..., lz:lz + self.value_half]
            zv = jnp.concatenate([z, qkv[..., lz + self.value_half:]], -1)
            if paged_ctx is not None:       # s == 1: the packed tokens
                prev = paged_ctx.shift_rows(zv[:, 0], 0, 2)[:, None]
            else:
                prev = _shift(zv)
            w0 = self.conv0_weight._data.astype(F32)
            a = w0[0] * prev[..., :lz] + w0[1] * z
            # the tail is kept WITHOUT the bias: before a sequence's first
            # token it reads zero, and a_{-1} = b0 (the one front pad)
            if paged_ctx is not None:
                a_prev = paged_ctx.shift_rows(a[:, 0], 1, 2)[:, None]
            else:
                a_prev = _shift(a)
            b0 = self.conv0_bias._data.astype(F32)
            w1 = self.conv1_weight._data.astype(F32)
            heads = (b, s, hq + hk, d)
            c = (jnp.einsum("bshi,hio->bsho", (a_prev + b0).reshape(heads),
                            w1[0])
                 + jnp.einsum("bshi,hio->bsho", (a + b0).reshape(heads),
                              w1[1])
                 + self.conv1_bias._data.astype(F32).reshape(hq + hk, d))
            zq = z[..., :lq].reshape(b, s, hk, hq // hk, d)
            mq = 0.5 * (zq + z[..., lq:].reshape(b, s, hk, 1, d))
            q = c[:, :, :hq] + mq.reshape(b, s, hq, d)
            k = c[:, :, hq:] + jnp.mean(mq, axis=3)
            v = jnp.concatenate([v1, prev[..., lz:]], -1).reshape(b, s, hk, d)
            q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True)
                                  + self.eps)
            k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True)
                                  + self.eps) \
                * self.k_scale._data.astype(F32)[:, None]
            q = self._rope(q, cos, sin, position_offset)
            k = self._rope(k, cos, sin, position_offset)
            q, k, v = (wrap_array(t.astype(x.dtype)) for t in (q, k, v))
        with jax.named_scope("attn"):
            if paged_ctx is not None:
                o = paged_ctx.attend(q, k, v)
            else:
                o = _masked_attention(q, k, v, None)
        with jax.named_scope("out"):
            return self.o_proj(o.reshape([b, s, hq * d]))


class ZayaMerge(Layer):
    """x <- (a_r . x + b_r) + (a_y . y + b_y): the scaled residual merge."""

    def __init__(self, width):
        super().__init__()
        one, zero = Constant(1.0), Constant(0.0)
        self.res_scale = self.create_parameter((width,),
                                               default_initializer=one)
        self.res_bias = self.create_parameter((width,),
                                              default_initializer=zero)
        self.out_scale = self.create_parameter((width,),
                                               default_initializer=one)
        self.out_bias = self.create_parameter((width,),
                                              default_initializer=zero)

    def forward(self, x, y):
        f = lambda p: p._data.astype(F32)                   # noqa: E731
        out = (f(self.res_scale) * x._data.astype(F32) + f(self.res_bias)
               + f(self.out_scale) * y._data.astype(F32) + f(self.out_bias))
        return wrap_array(out.astype(x.dtype))


def _moe_block(config: ZayaConfig, layer_idx: int, weight_attr) -> MoELayer:
    c = config
    gate = DepthAveragedMLPGate(
        c.hidden_size, c.num_experts, c.router_hidden_size,
        first=layer_idx == 0, eps=c.rms_norm_eps, weight_attr=weight_attr)
    return MoELayer(
        c.hidden_size,
        SwiGLUExperts(c.num_experts, c.hidden_size, c.moe_intermediate_size,
                      weight_attr=weight_attr),
        gate=gate, held_experts=(0, c.num_experts))


class ZayaDecoderLayer(Layer):
    def __init__(self, config: ZayaConfig, layer_idx: int, weight_attr):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = ZayaCCA(c, weight_attr)
        self.attn_merge = ZayaMerge(c.hidden_size)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                epsilon=c.rms_norm_eps)
        self.mlp = _moe_block(c, layer_idx, weight_attr)
        self.mlp_merge = ZayaMerge(c.hidden_size)

    def forward(self, x, router_state, cos, sin, position_offset=0,
                paged_ctx=None):
        """-> (x, this layer's router state, for the next layer's)."""
        with jax.named_scope("cca"):
            y = self.self_attn(self.input_layernorm(x), cos, sin,
                               position_offset, paged_ctx=paged_ctx)
        x = self.attn_merge(x, y)
        # MoELayer names moe/router and moe/experts; pads are no tokens
        y, router_state = self.mlp(
            self.post_attention_layernorm(x),
            token_mask=getattr(paged_ctx, "token_mask", None),
            router_state=router_state)
        if hasattr(paged_ctx, "count"):
            got = self.mlp.routing_counts()
            paged_ctx.count(moe_slots=got["slots"],
                            moe_rows_computed=got["rows"],
                            moe_experts_touched=got["touched"],
                            moe_max_expert_pairs=got["most"],
                            moe_expert_layers=self.mlp.num_expert)
        return self.mlp_merge(x, y), router_state


class ZayaModel(Layer):
    def __init__(self, config: ZayaConfig, weight_attr):
        super().__init__()
        self.config = c = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=weight_attr)
        self.layers = LayerList([ZayaDecoderLayer(c, i, weight_attr)
                                 for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        cos, sin = rope_tables(c.rope_parameters[HYBRID], c.head_dim,
                               c.max_position_embeddings)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        #: the scope the last forward ran under (``_logits_of`` joins it)
        self.scope = "model"

    def forward(self, input_ids, position_offset=0, paged_ctx=None):
        self.scope = "serve/model" if paged_ctx is not None else "model"
        with jax.named_scope(self.scope):
            x = self.embed_tokens(input_ids)
            # the router's state of the forward's own tokens: layer l's is
            # an operand of layer l + 1's gate and of nothing else
            state = None
            for i, layer in enumerate(self.layers):
                if paged_ctx is not None:
                    paged_ctx.layer_idx = i
                x, state = layer(x, state, self.rope_cos._data,
                                 self.rope_sin._data, position_offset,
                                 paged_ctx=paged_ctx)
            return self.norm(x)


class ZayaForCausalLM(Layer):
    """``weight_attr``: the initialiser of every matrix (embedding,
    projections, convolutions, router, experts), ``Normal(std=0.02)`` if
    None; whoever loads the values next passes one that draws nothing."""

    def __init__(self, config: ZayaConfig, weight_attr=None):
        super().__init__()
        self.config = config
        if weight_attr is None:
            weight_attr = Normal(std=0.02)
        self.model = ZayaModel(config, weight_attr)

    def forward(self, input_ids, labels=None):
        logits = self._logits_of(self.model(input_ids))
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]), ignore_index=-100)
            return loss, logits
        return logits

    def _logits_of(self, hidden):
        """The tied head: the embedding's rows against the hidden state."""
        with jax.named_scope(f"{self.model.scope}/head"):
            return F.linear(hidden, self.model.embed_tokens.weight.T)

    # ---- what the paged engine reads of the model
    def attention_kinds(self):
        """[(query heads, window or None)] a layer: every layer full, over
        a page pool of its own (``paged_layout``)."""
        c = self.config
        return [(c.num_attention_heads, None)] * c.num_hidden_layers

    def recurrent_state(self) -> dict:
        """The state a sequence EVERY layer carries beside its pages: a
        slot of two arrays, ``[z_{t-1}; W_V2 x_{t-1}]`` and ``a_{t-1} -
        b0``, float32 (``paged_ctx.shift_rows`` names them 0 and 1), and
        the bytes of both."""
        c = self.config
        shapes = [(c.latent + c.value_half,), (c.latent,)]
        return {"layers": c.num_hidden_layers, "shapes": shapes,
                "bytes": 4 * sum(s[0] for s in shapes)}
