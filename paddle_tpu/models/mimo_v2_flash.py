"""MiMo-V2-Flash: a decoder whose sliding and full attention layers differ
in their KV heads, whose K heads are wider than its V heads, whose sliding
softmax holds a learned sink a query head, and whose FFN is a dense SwiGLU
first and then a 256-way sigmoid mixture of wide experts with no shared one.

Source of the architecture: the published ``config.json`` of
``XiaomiMiMo/MiMo-V2-Flash`` (``model_type`` ``mimo_v2_flash``, 309B-A15B);
``MiMoV2FlashConfig`` keeps its key names.  Pre-norm residual blocks,
``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``, RMSNorm eps
``layernorm_epsilon``, a final RMSNorm, an untied head, no biases.

* Attention, layer ``l``: ``hybrid_layer_pattern[l]`` 0 is a full layer, 1 a
  sliding one.  ``num_attention_heads`` (``swa_num_attention_heads``) query
  heads in both; q and k heads ``head_dim`` (192) wide, v heads
  ``v_head_dim`` (128); ``num_key_value_heads`` (4) KV heads in a full
  layer, ``swa_num_key_value_heads`` (8) in a sliding one.  Rotary
  (rotate-half) on the first ``int(partial_rotary_factor * head_dim)`` (64)
  channels of every q and k head, theta ``rope_theta`` in a full layer and
  ``swa_rope_theta`` in a sliding one, the other channels unrotated.  Scores
  ``q . k * head_dim^-1/2``, causal; in a sliding layer query i sees key j
  iff ``0 <= i - j < sliding_window``.  The values are multiplied by
  ``attention_value_scale`` before they are cached.  Sliding layers only
  (``add_swa_attention_sink_bias``): a parameter ``b_h`` a query head,
  float32; ``p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))`` over the
  visible j', ``o_i = sum_j p_ij v_j``: the sink takes mass and gives no
  value.  Output ``W_o [o_h]_h``, ``W_o`` (heads x v_head_dim) x hidden.
* FFN: ``moe_layer_freq[l]`` 0 is a dense SwiGLU of ``intermediate_size``;
  1 is ``MoELayer`` with ``SigmoidTopKGate`` over ``n_routed_experts``
  (``num_experts_per_tok`` chosen over score + a selection bias held at
  zero, weights renormalised over the chosen, ``routed_scaling_factor``
  null = 1), bias-free SwiGLU experts of ``moe_intermediate_size``, NO
  shared expert, of which this rank HOLDS ``held_experts = (first, count)``
  (the whole set if None): the router keeps its published width and top-k,
  a token whose choices miss the held experts gets nothing from the layer.

What the config names without giving its form sits behind the key that names
it (the benchmark's configuration file lists each under ``assumed`` with the
reason): where the sink enters; that ``attention_value_scale`` multiplies V;
which channels are rotated; the score scale; no q/k norm; that
``attention_chunk_size`` is the window and nothing else; the selection bias
at zero.  The multi-token-prediction modules the model card speaks of are
sized by no key of the config and are NOT built.

Serving: ``model.model(ids, pos, paged_ctx=ctx)`` is the contract
``JittedPagedDecoder`` calls.  A layer tells the context what it is
(``paged_ctx.attend(q, k, v, window=, sinks=)``); what ``paged_layout``
needs of a model whose pools differ it reads in one place,
``kv_page_shape()``: the (KV heads, K width, V width) of each layer's pool.
An expert layer routes only the context's real tokens and counts what it
did (``paged_ctx.count``; ``moe_expert_layers`` is the HELD count).
Without a ``paged_ctx`` attention is a plain masked product (the full
forward of the CPU tests).  Single chip, the ragged unified step only:
``ContinuousBatchingEngine`` refuses ``tp``, an int8 KV cache, a draft
model and the whole-prompt prefill programs by what they lack.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.dispatch import def_op
from ..framework.tensor import Tensor
from ..incubate.distributed.models.moe import (MoELayer, SigmoidTopKGate,
                                               SwiGLUExperts)
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from .. import tensor as T
from .laguna import LagunaMLP
from .llama import apply_rope


def _published_pattern(n: int) -> List[int]:
    """Full at 0, 5, 11, 17, ...: the leading layer, then periods of five
    sliding layers and one full."""
    return [0 if i == 0 or i % 6 == 5 else 1 for i in range(n)]


@dataclass
class MiMoV2FlashConfig:
    """The published keys at their published values.  The two per-layer
    lists follow ``num_hidden_layers`` in the published pattern where they
    are left out, and are cut to it where they are longer (a configuration
    file keeps the published lists and fewer layers).  ``held_experts`` is
    not a published key: the (first, count) of the routed experts this rank
    holds, all of them if None."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    max_position_embeddings: int = 262144
    layernorm_epsilon: float = 1e-5
    rope_theta: float = 5000000
    swa_rope_theta: float = 10000
    partial_rotary_factor: float = 0.334
    sliding_window: int = 128
    sliding_window_size: int = 128
    attention_chunk_size: int = 128
    attention_value_scale: float = 0.707
    attention_bias: bool = False
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    hybrid_layer_pattern: Optional[List[int]] = None
    moe_layer_freq: Optional[List[int]] = None
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: Optional[int] = None
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    routed_scaling_factor: Optional[float] = None
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    held_experts: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.hybrid_layer_pattern is None:
            self.hybrid_layer_pattern = _published_pattern(n)
        if self.moe_layer_freq is None:
            self.moe_layer_freq = [0] + [1] * (n - 1)
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            got = list(getattr(self, name))
            if len(got) < n:
                raise ValueError(f"{name} has {len(got)} entries for {n} "
                                 "layers")
            setattr(self, name, got[:n])
        lacks = [
            (self.tie_word_embeddings, "a tied head"),
            (self.attention_bias, "attention biases"),
            (self.n_shared_experts, "a shared expert"),
            (self.scoring_func != "sigmoid", "a router that is no sigmoid"),
            (self.n_group != 1 or self.topk_group != 1,
             "a choice by groups of experts"),
            (not self.norm_topk_prob, "unnormalised router weights"),
            (self.hidden_act != "silu", "an activation that is not silu"),
            (self.sliding_window != self.sliding_window_size
             or self.attention_chunk_size != self.sliding_window,
             "a window, a window size and an attention chunk that differ"),
            ((self.swa_num_attention_heads, self.swa_head_dim,
              self.swa_v_head_dim) != (self.num_attention_heads,
                                       self.head_dim, self.v_head_dim),
             "sliding layers whose query heads or head widths are not the "
             "full layers'")]
        named = [what for bad, what in lacks if bad]
        if named:
            raise NotImplementedError(
                "the published model has none of: " + "; ".join(named))
        if self.held_experts is not None:
            self.held_experts = tuple(int(x) for x in self.held_experts)

    def sliding(self, i: int) -> bool:
        return bool(self.hybrid_layer_pattern[i])

    @property
    def rotary_dim(self) -> int:
        """Channels of a head that are rotated: ``int(0.334 x 192)`` = 64,
        an even count."""
        return int(self.partial_rotary_factor * self.head_dim) // 2 * 2


def rope_tables(theta: float, rot: int, max_pos: int):
    """(cos, sin) [max_pos, rot / 2] float32 over the ``rot`` rotated
    channels of a head."""
    inv = 1.0 / float(theta) ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    freqs = np.outer(np.arange(max_pos, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(freqs), jnp.float32),
            jnp.asarray(np.sin(freqs), jnp.float32))


@def_op("mimo_masked_attention")
def _masked_attention(q, k, v, window, sinks):
    """Plain causal attention of q, k (b, s, heads, d) and v (b, s, heads,
    dv) by an explicit mask, a ``window`` of keys a query if not None;
    float32 softmax, with ``sinks`` (q_heads,) one more column in it that is
    dropped afterwards."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) / math.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    sc = jnp.where(seen, sc, -jnp.inf)
    if sinks is not None:
        col = jnp.broadcast_to(sinks.astype(sc.dtype)[None, :, None, None],
                               (b, h, s, 1))
        p = jax.nn.softmax(jnp.concatenate([sc, col], axis=-1),
                           axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


class MiMoV2FlashAttention(Layer):
    def __init__(self, config: MiMoV2FlashConfig, layer_idx: int,
                 weight_attr):
        super().__init__()
        c = config
        self.sliding = c.sliding(layer_idx)
        self.window = c.sliding_window if self.sliding else None
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = (c.swa_num_key_value_heads if self.sliding
                             else c.num_key_value_heads)
        self.head_dim, self.v_head_dim = c.head_dim, c.v_head_dim
        self.rot = c.rotary_dim
        self.value_scale = float(c.attention_value_scale)
        h = c.hidden_size

        def proj(n_in, n_out):
            return Linear(n_in, n_out, weight_attr=weight_attr,
                          bias_attr=False)

        self.q_proj = proj(h, self.num_heads * self.head_dim)
        self.k_proj = proj(h, self.num_kv_heads * self.head_dim)
        self.v_proj = proj(h, self.num_kv_heads * self.v_head_dim)
        self.o_proj = proj(self.num_heads * self.v_head_dim, h)
        # the learned sink a query head, float32 whatever the model's dtype
        has_sink = (c.add_swa_attention_sink_bias if self.sliding
                    else c.add_full_attention_sink_bias)
        self.sinks = (self.create_parameter(
            [self.num_heads], dtype="float32", attr=Constant(0.0))
            if has_sink else None)

    def forward(self, x, cos, sin, position_offset=0, paged_ctx=None):
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        # ``attention_value_scale``: assumed to multiply V (module
        # docstring), before it is cached
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads,
                                    self.v_head_dim]) * self.value_scale
        qr, kr = apply_rope(q[..., :self.rot], k[..., :self.rot], cos, sin,
                            position_offset)
        q = T.concat([qr, q[..., self.rot:]], axis=-1)
        k = T.concat([kr, k[..., self.rot:]], axis=-1)
        if paged_ctx is not None:
            out = paged_ctx.attend(q, k, v, window=self.window,
                                   sinks=self.sinks)
        else:
            out = _masked_attention(q, k, v, self.window, self.sinks)
        return self.o_proj(out.reshape([b, s, self.num_heads
                                        * self.v_head_dim]))


def _moe_block(config: MiMoV2FlashConfig, weight_attr) -> MoELayer:
    c = config
    first, count = c.held_experts or (0, c.n_routed_experts)
    gate = SigmoidTopKGate(
        c.hidden_size, c.n_routed_experts, 1, topk=c.num_experts_per_tok,
        renormalize=True,
        routed_scaling_factor=(1.0 if c.routed_scaling_factor is None
                               else c.routed_scaling_factor),
        float32_logits=True)
    return MoELayer(
        c.hidden_size,
        SwiGLUExperts(count, c.hidden_size, c.moe_intermediate_size,
                      weight_attr=weight_attr),
        gate=gate, held_experts=(first, count))


class MiMoV2FlashDecoderLayer(Layer):
    """x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)), the attention and the
    FFN of the layer's kinds."""

    def __init__(self, config: MiMoV2FlashConfig, layer_idx: int,
                 weight_attr):
        super().__init__()
        c = config
        self.sparse = bool(c.moe_layer_freq[layer_idx])
        self.input_layernorm = RMSNorm(c.hidden_size,
                                       epsilon=c.layernorm_epsilon)
        self.self_attn = MiMoV2FlashAttention(c, layer_idx, weight_attr)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                epsilon=c.layernorm_epsilon)
        self.mlp = (_moe_block(c, weight_attr) if self.sparse else
                    LagunaMLP(c.hidden_size, c.intermediate_size,
                              weight_attr))

    def forward(self, x, cos, sin, position_offset=0, paged_ctx=None):
        attn = self.self_attn
        with jax.named_scope("attn_sliding" if attn.sliding
                             else "attn_full"):
            x = x + attn(self.input_layernorm(x), cos, sin, position_offset,
                         paged_ctx=paged_ctx)
        h = self.post_attention_layernorm(x)
        if not self.sparse:
            with jax.named_scope("dense_ffn"):
                return x + self.mlp(h)
        # MoELayer names moe/router and moe/experts; pads are no tokens
        y = self.mlp(h, token_mask=getattr(paged_ctx, "token_mask", None))
        if hasattr(paged_ctx, "count"):
            got = self.mlp.routing_counts()
            paged_ctx.count(moe_slots=got["slots"],
                            moe_rows_computed=got["rows"],
                            moe_experts_touched=got["touched"],
                            moe_max_expert_pairs=got["most"],
                            moe_expert_layers=self.mlp.num_expert)
        return x + y


class MiMoV2FlashModel(Layer):
    def __init__(self, config: MiMoV2FlashConfig, weight_attr):
        super().__init__()
        self.config = c = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=weight_attr)
        self.layers = LayerList([MiMoV2FlashDecoderLayer(c, i, weight_attr)
                                 for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.layernorm_epsilon)
        for theta, name in ((c.rope_theta, "full"),
                            (c.swa_rope_theta, "sliding")):
            cos, sin = rope_tables(theta, c.rotary_dim,
                                   c.max_position_embeddings)
            self.register_buffer(f"rope_cos_{name}", Tensor(cos),
                                 persistable=False)
            self.register_buffer(f"rope_sin_{name}", Tensor(sin),
                                 persistable=False)
        #: the scope the last forward ran under (``_logits_of`` joins it)
        self.scope = "model"

    def forward(self, input_ids, position_offset=0, paged_ctx=None):
        self.scope = "serve/model" if paged_ctx is not None else "model"
        with jax.named_scope(self.scope):
            x = self.embed_tokens(input_ids)
            for i, layer in enumerate(self.layers):
                if paged_ctx is not None:
                    paged_ctx.layer_idx = i
                slide = layer.self_attn.sliding
                x = layer(
                    x,
                    self.rope_cos_sliding if slide else self.rope_cos_full,
                    self.rope_sin_sliding if slide else self.rope_sin_full,
                    position_offset, paged_ctx=paged_ctx)
            return self.norm(x)


class MiMoV2FlashForCausalLM(Layer):
    """``weight_attr``: the initialiser of every matrix (embedding,
    projections, experts, head), ``Normal(std=0.02)`` if None; whoever
    loads the values next passes one that draws nothing."""

    def __init__(self, config: MiMoV2FlashConfig, weight_attr=None):
        super().__init__()
        self.config = config
        if weight_attr is None:
            weight_attr = Normal(std=0.02)
        self.model = MiMoV2FlashModel(config, weight_attr)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=weight_attr, bias_attr=False)

    def forward(self, input_ids, labels=None):
        logits = self._logits_of(self.model(input_ids))
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]), ignore_index=-100)
            return loss, logits
        return logits

    def _logits_of(self, hidden):
        with jax.named_scope(f"{self.model.scope}/head"):
            return self.lm_head(hidden)

    def attention_kinds(self):
        """[(query heads, window or None, None, sinks)] a layer: what each
        layer's paged call looks like (``paged_layout``): every call owns
        the pool of its own index (None), a sliding one hands a sink."""
        return [(a.num_heads, a.window, None, a.sinks is not None)
                for a in (layer.self_attn for layer in self.model.layers)]

    def kv_page_shape(self):
        """[(KV heads, K width, V width)] a pool, a pool a layer: the
        pools of a sliding and of a full layer differ, and K is wider
        than V in both."""
        return [(a.num_kv_heads, a.head_dim, a.v_head_dim)
                for a in (layer.self_attn for layer in self.model.layers)]
