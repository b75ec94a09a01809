"""Laguna: a decoder whose layers differ in kind — full and
sliding-window attention with different query-head counts over one set of
KV heads, a per-head output gate, partial rotary embeddings (yarn in the
full layers), a dense SwiGLU first and then a 256-way sigmoid mixture of
experts with one shared expert.

Source of the architecture: the published ``config.json`` of
``poolside/Laguna-XS.2`` (``model_type`` ``laguna``); ``LagunaConfig``
keeps its key names.  Pre-norm residual blocks, ``x += Attn(RMSNorm(x));
x += FFN(RMSNorm(x))``, a final RMSNorm, an untied head.

* Attention, layer ``l``: ``layer_types[l]`` is ``full_attention`` or
  ``sliding_attention``; ``num_attention_heads_per_layer[l]`` query heads
  over ``num_key_value_heads`` KV heads of ``head_dim`` (explicit: hidden
  / heads is not it); no biases.  Rotary on the first
  ``partial_rotary_factor * head_dim`` channels of every head, rotate-half
  over those channels, by the layer type's ``rope_parameters`` entry:
  ``default`` (theta) or ``yarn`` (theta, factor, original length,
  beta_fast / beta_slow, cos and sin times ``attention_factor``).  Causal
  softmax attention scaled by head_dim^-1/2; in a sliding layer query i
  sees key j only if 0 <= i - j < ``sliding_window``.
* FFN: ``mlp_layer_types[l]`` ``dense`` is a SwiGLU of
  ``intermediate_size``; ``sparse`` is ``MoELayer`` with
  ``SigmoidTopKGate`` over ``num_experts`` (``num_experts_per_tok``
  chosen, weights renormalised over the chosen, times
  ``moe_routed_scaling_factor``, applied to the experts' OUTPUT), every
  one of them held, plus the shared expert.

Three things the config names without giving their form; each sits
behind the key that names it (the benchmark's configuration file lists
them under ``assumed`` with the reasons):

1. ``gating: true`` — one sigmoid gate a HEAD on the attention output,
   g = sigmoid(W_g x') in R^heads, o = W_o [g_h a_h]_h;
2. the router's score function — sigmoid (no key says; the scale of 2.5
   on eight weights presumes weights that sum to one); the selection
   bias is held at zero;
3. no normalisation of q and k (no key names one).

Serving: ``model.model(ids, pos, paged_ctx=ctx)`` is the contract
``JittedPagedDecoder`` calls, so ``ContinuousBatchingEngine(model, ...)``
serves it as it serves ``LlamaForCausalLM``.  An attention layer tells
the context what it is (``paged_ctx.attend(q, k, v, window=...)``); the
per-layer head counts reach the kernels through q's shape and every
layer's pool keeps one shape.  An expert layer routes only the context's
real tokens (``paged_ctx.token_mask``) and counts what it did under the
step record's names (``paged_ctx.count(moe_slots=...)``).  Without a ``paged_ctx`` attention is a plain masked
product (the full forward of the CPU tests).  Single chip:
``inference.paged._tp_plan`` refuses this model by what it lacks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.dispatch import def_op
from ..framework.tensor import Tensor
from ..incubate.distributed.models.moe import (MoELayer, SigmoidTopKGate,
                                               SwiGLUExperts)
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from .. import tensor as T
from .llama import apply_rope

FULL, SLIDING = "full_attention", "sliding_attention"


def _published_rope():
    return {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}


@dataclass
class LagunaConfig:
    """The published keys at their published values (Laguna-XS.2).  The
    three per-layer lists follow
    ``num_hidden_layers`` in the published pattern where they are left
    out: a full layer every fourth, 48 query heads in a full layer and 64
    in a sliding one, a dense FFN first."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: bool = True
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=_published_rope)
    layer_types: Optional[List[str]] = None
    moe_apply_router_weight_on_input: bool = False
    partial_rotary_factor: float = 0.5
    mlp_layer_types: Optional[List[str]] = None
    moe_routed_scaling_factor: float = 2.5
    num_attention_heads_per_layer: Optional[List[int]] = None

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [FULL if i % 4 == 0 else SLIDING
                                for i in range(n)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["dense"] + ["sparse"] * (n - 1)
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = [
                self.num_attention_heads if t == FULL
                else self.num_attention_heads * 4 // 3
                for t in self.layer_types]
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {n} layers")
        if self.tie_word_embeddings or self.attention_bias \
                or self.moe_apply_router_weight_on_input:
            raise NotImplementedError(
                "the published model has an untied head, no attention "
                "bias and router weights on the experts' output")


def rope_tables(params: dict, head_dim: int, max_pos: int):
    """(cos, sin) [max_pos, rot / 2] float32 of one layer type's
    ``rope_parameters`` entry, ``rot = partial_rotary_factor * head_dim``
    the channels rotated.  ``yarn`` blends interpolated (1 / factor) and
    unchanged frequencies by a linear ramp between the channels that turn
    ``beta_fast`` and ``beta_slow`` times over the original length, and
    multiplies cos and sin by ``attention_factor``."""
    rot = int(head_dim * params.get("partial_rotary_factor", 1))
    base = float(params["rope_theta"])
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    inv, scale = 1.0 / pos_freqs, 1.0
    if params["rope_type"] == "yarn":
        factor = float(params["factor"])
        orig = params["original_max_position_embeddings"]

        def turns_dim(n_rot):
            return rot * math.log(orig / (n_rot * 2 * math.pi)) \
                / (2 * math.log(base))

        low = max(math.floor(turns_dim(params["beta_fast"])), 0)
        high = min(math.ceil(turns_dim(params["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                       / (high - low), 0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        scale = float(params["attention_factor"])
    elif params["rope_type"] != "default":
        raise NotImplementedError(f"rope_type {params['rope_type']!r}")
    freqs = np.outer(np.arange(max_pos, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(freqs) * scale, jnp.float32),
            jnp.asarray(np.sin(freqs) * scale, jnp.float32))


@def_op("laguna_masked_attention")
def _masked_attention(q, k, v, window):
    """Plain causal attention of (b, s, heads, d) by an explicit mask, a
    ``window`` of keys a query if not None; float32 softmax."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) / math.sqrt(d)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


class LagunaAttention(Layer):
    def __init__(self, config: LagunaConfig, layer_idx: int, weight_attr):
        super().__init__()
        c = config
        self.kind = c.layer_types[layer_idx]
        self.window = c.sliding_window if self.kind == SLIDING else None
        self.num_heads = c.num_attention_heads_per_layer[layer_idx]
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        self.rot = int(c.head_dim * c.rope_parameters[self.kind].get(
            "partial_rotary_factor", 1))
        init = weight_attr
        wide = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = Linear(c.hidden_size, wide, weight_attr=init,
                             bias_attr=False)
        self.k_proj = Linear(c.hidden_size, kv, weight_attr=init,
                             bias_attr=False)
        self.v_proj = Linear(c.hidden_size, kv, weight_attr=init,
                             bias_attr=False)
        self.o_proj = Linear(wide, c.hidden_size, weight_attr=init,
                             bias_attr=False)
        # ``gating``: assumed a sigmoid gate a head (module docstring)
        self.g_proj = (Linear(c.hidden_size, self.num_heads,
                              weight_attr=init, bias_attr=False)
                       if c.gating else None)

    def forward(self, x, cos, sin, position_offset=0, paged_ctx=None):
        b, s = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, s, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([b, s, self.num_kv_heads, self.head_dim])
        if self.rot == self.head_dim:
            q, k = apply_rope(q, k, cos, sin, position_offset)
        else:
            qr, kr = apply_rope(q[..., :self.rot], k[..., :self.rot],
                                cos, sin, position_offset)
            q = T.concat([qr, q[..., self.rot:]], axis=-1)
            k = T.concat([kr, k[..., self.rot:]], axis=-1)
        if paged_ctx is not None:
            out = paged_ctx.attend(q, k, v, window=self.window)
        else:
            out = _masked_attention(q, k, v, self.window)
        if self.g_proj is not None:
            out = out * F.sigmoid(self.g_proj(x)).unsqueeze(-1)
        return self.o_proj(out.reshape([b, s, self.num_heads
                                        * self.head_dim]))


class LagunaMLP(Layer):
    def __init__(self, hidden, width, weight_attr):
        super().__init__()
        init = weight_attr
        self.gate_proj = Linear(hidden, width, weight_attr=init,
                                bias_attr=False)
        self.up_proj = Linear(hidden, width, weight_attr=init,
                              bias_attr=False)
        self.down_proj = Linear(width, hidden, weight_attr=init,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def _moe_block(config: LagunaConfig, weight_attr) -> MoELayer:
    c = config
    # the router's scores: assumed sigmoid (module docstring), in float32
    gate = SigmoidTopKGate(
        c.hidden_size, c.num_experts, 1, topk=c.num_experts_per_tok,
        renormalize=True, routed_scaling_factor=c.moe_routed_scaling_factor,
        float32_logits=True)
    return MoELayer(
        c.hidden_size,
        SwiGLUExperts(c.num_experts, c.hidden_size, c.moe_intermediate_size,
                      weight_attr=weight_attr),
        gate=gate, held_experts=(0, c.num_experts),
        shared_expert=LagunaMLP(c.hidden_size,
                                c.shared_expert_intermediate_size,
                                weight_attr))


class LagunaDecoderLayer(Layer):
    """x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)), the attention and the
    FFN of the layer's kinds."""

    def __init__(self, config: LagunaConfig, layer_idx: int, weight_attr):
        super().__init__()
        c = config
        self.ffn_kind = c.mlp_layer_types[layer_idx]
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = LagunaAttention(c, layer_idx, weight_attr)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                epsilon=c.rms_norm_eps)
        self.mlp = (LagunaMLP(c.hidden_size, c.intermediate_size, weight_attr)
                    if self.ffn_kind == "dense"
                    else _moe_block(c, weight_attr))

    def forward(self, x, cos, sin, position_offset=0, paged_ctx=None):
        attn = self.self_attn
        with jax.named_scope("attn_full" if attn.kind == FULL
                             else "attn_sliding"):
            x = x + attn(self.input_layernorm(x), cos, sin, position_offset,
                         paged_ctx=paged_ctx)
        h = self.post_attention_layernorm(x)
        if self.ffn_kind == "dense":
            with jax.named_scope("dense_ffn"):
                return x + self.mlp(h)
        # MoELayer names moe/router and moe/experts; pads are no tokens
        y = self.mlp(h, token_mask=getattr(paged_ctx, "token_mask", None))
        if hasattr(paged_ctx, "count"):
            got = self.mlp.routing_counts()
            paged_ctx.count(moe_slots=got["slots"],
                            moe_rows_computed=got["rows"],
                            moe_experts_touched=got["touched"],
                            moe_expert_layers=self.mlp.num_expert)
        return x + y


class LagunaModel(Layer):
    def __init__(self, config: LagunaConfig, weight_attr):
        super().__init__()
        self.config = c = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=weight_attr)
        self.layers = LayerList([LagunaDecoderLayer(c, i, weight_attr)
                                 for i in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        for kind, name in ((FULL, "full"), (SLIDING, "sliding")):
            cos, sin = rope_tables(c.rope_parameters[kind], c.head_dim,
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
                full = layer.self_attn.kind == FULL
                x = layer(x,
                          self.rope_cos_full if full else self.rope_cos_sliding,
                          self.rope_sin_full if full else self.rope_sin_sliding,
                          position_offset, paged_ctx=paged_ctx)
            return self.norm(x)


class LagunaForCausalLM(Layer):
    """``weight_attr``: the initialiser of every matrix (embedding,
    projections, experts, head), ``Normal(std=0.02)`` if None; whoever
    loads the values next passes one that draws nothing."""

    def __init__(self, config: LagunaConfig, weight_attr=None):
        super().__init__()
        self.config = config
        if weight_attr is None:
            weight_attr = Normal(std=0.02)
        self.model = LagunaModel(config, weight_attr)
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
        """[(query heads, window or None)] a layer: what each layer's
        paged call looks like (``paged_layout`` in
        ``ops/pallas/paged_attention.py`` reads it: ``PagedKVCache`` opens
        a page pool an entry and ``JittedPagedDecoder`` counts the
        kernels' walk from it).  An entry may carry a third member, the
        index of the POOL the call walks: a call that names a pool an
        earlier call opened appends nothing and holds no pool of its own
        (``models/phi4_flash.py``); with two members, as here, every call
        owns the pool of its own index."""
        return [(layer.self_attn.num_heads, layer.self_attn.window)
                for layer in self.model.layers]
