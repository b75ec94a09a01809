"""Brumby: an attention-free decoder whose every mixer is a power-retention
layer (a gated linear attention whose kernel is the square of the dot
product), with a recurrent state of fixed size a sequence in place of a
KV cache.

Source of the architecture: the published ``config.json`` of
``manifestai/Brumby-14B-Base`` (``model_type`` ``brumby``; retrained from
Qwen3-14B-Base, whose projection shapes the config keeps) and Buckman,
Gelada, Zhang, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239.  ``BrumbyConfig`` keeps the config's key names.

Pre-norm residual blocks, ``x += Mix(RMSNorm(x)); x += SwiGLU(RMSNorm(x))``,
a final RMSNorm, an untied head.  With x' = RMSNorm(x), KV head h of
``num_key_value_heads``, query head i in h's group, d = ``head_dim``:

    q_i = rope(RMSNorm_d(W_q x')_i)   k_h = rope(RMSNorm_d(W_k x')_h)   v_h = (W_v x')_h
    log g_h,t = logsigmoid((W_g x'_t)_h)         W_g: hidden -> KV heads, float32 from here on
    a_i,t,j   = exp(sum_{m=j+1..t} log g_h,m) (s q_i,t . k_h,j)^p      j <= t,  p = 2,  s = d^-1/2
    y_i,t     = sum_j a_i,t,j v_h,j / (sum_j a_i,t,j + eps)            Mix = W_o [y_i]_i

and, the same numbers as a recurrence, with phi the symmetric power
embedding of degree 2, phi(x) . phi(y) = (x . y)^2, D = d (d + 1) / 2:

    S_h,t = g_h,t S_h,t-1 + phi(s^1/2 k_h,t) v_h,t^T  in R^{D x d}     z_h,t = g_h,t z_h,t-1 + phi(s^1/2 k_h,t)
    y_i,t = phi(s^1/2 q_i,t)^T S_h,t / (phi(s^1/2 q_i,t)^T z_h,t + eps)

(``ops/power_retention.py`` has both forms, the chunk form between them,
and how phi and the state are laid out.)  Served through the paged
engine the state is a SLOT of the cache a sequence a layer
(``recurrent_state``; ``PagedKVCache`` holds the pools, the ragged step
updates a row's slot in place through ``paged_ctx.retain``); the model
has no K/V layer (``attention_kinds`` is empty).  Without a paged context
the forward runs the first form over the whole sequence.

What the config does not give, each behind its own key of the
benchmark's configuration file (``assumed``): the degree p = 2 (phi's
tiles are written for it); one gate a KV head from a projection without
bias (``g_proj``; ``attention_bias`` is false); ``q_norm`` / ``k_norm`` a
head and rope at ``rope_theta`` kept from the Qwen3 lineage; the output
normalised by the sum of its weights, eps = ``power_retention.EPS`` = 1e-6
on every path; S and z in float32; the state form from the first token.
None of them is an option of ``BrumbyConfig``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from ..framework.dispatch import def_op
from ..framework.tensor import Tensor
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from ..ops import power_retention as pr
from .laguna import LagunaMLP as SwiGLU
from .llama import _rope_tables, apply_rope


@dataclass
class BrumbyConfig:
    """The published keys at their published values (Brumby-14B-Base)."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 32768
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.tie_word_embeddings or self.attention_bias:
            raise NotImplementedError(
                "the published model has an untied head and no bias")


@def_op("power_retention")
def _retention_full(q, k, v, log_g):
    """The first form over whole sequences: (b, s, heads, d) -> the same."""
    return jax.vmap(pr.retention_attention)(q, k, v, log_g).astype(q.dtype)


class BrumbyRetention(Layer):
    def __init__(self, config: BrumbyConfig, weight_attr):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = c.head_dim
        wide = self.num_heads * self.head_dim
        kv = self.num_kv_heads * self.head_dim
        self.q_proj = Linear(c.hidden_size, wide, weight_attr=weight_attr,
                             bias_attr=False)
        self.k_proj = Linear(c.hidden_size, kv, weight_attr=weight_attr,
                             bias_attr=False)
        self.v_proj = Linear(c.hidden_size, kv, weight_attr=weight_attr,
                             bias_attr=False)
        self.o_proj = Linear(wide, c.hidden_size, weight_attr=weight_attr,
                             bias_attr=False)
        self.g_proj = Linear(c.hidden_size, self.num_kv_heads,
                             weight_attr=weight_attr, bias_attr=False)
        self.q_norm = RMSNorm(c.head_dim, epsilon=c.rms_norm_eps)
        self.k_norm = RMSNorm(c.head_dim, epsilon=c.rms_norm_eps)

    def forward(self, x, cos, sin, position_offset=0, paged_ctx=None):
        b, s = x.shape[0], x.shape[1]
        with jax.named_scope("proj"):
            q = self.q_norm(self.q_proj(x).reshape(
                [b, s, self.num_heads, self.head_dim]))
            k = self.k_norm(self.k_proj(x).reshape(
                [b, s, self.num_kv_heads, self.head_dim]))
            v = self.v_proj(x).reshape(
                [b, s, self.num_kv_heads, self.head_dim])
            q, k = apply_rope(q, k, cos, sin, position_offset)
            log_g = jax.nn.log_sigmoid(
                self.g_proj(x)._data.astype(jnp.float32))       # (b, s, kvh)
        with jax.named_scope("state"):
            if paged_ctx is not None:
                y = paged_ctx.retain(q, k, v, log_g[:, 0])
            else:
                y = _retention_full(q, k, v, Tensor(log_g))
        with jax.named_scope("out"):
            return self.o_proj(y.astype(x.dtype).reshape(
                [b, s, self.num_heads * self.head_dim]))


class BrumbyDecoderLayer(Layer):
    def __init__(self, config: BrumbyConfig, weight_attr):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = BrumbyRetention(c, weight_attr)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                epsilon=c.rms_norm_eps)
        self.mlp = SwiGLU(c.hidden_size, c.intermediate_size, weight_attr)

    def forward(self, x, cos, sin, position_offset=0, paged_ctx=None):
        with jax.named_scope("retention"):
            x = x + self.self_attn(self.input_layernorm(x), cos, sin,
                                   position_offset, paged_ctx=paged_ctx)
        with jax.named_scope("dense_ffn"):
            return x + self.mlp(self.post_attention_layernorm(x))


class BrumbyModel(Layer):
    def __init__(self, config: BrumbyConfig, weight_attr):
        super().__init__()
        self.config = c = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=weight_attr)
        self.layers = LayerList([BrumbyDecoderLayer(c, weight_attr)
                                 for _ in range(c.num_hidden_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        cos, sin = _rope_tables(c.head_dim, c.max_position_embeddings,
                                c.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)
        #: the scope the last forward ran under (``_logits_of`` joins it)
        self.scope = "model"

    def forward(self, input_ids, position_offset=0, paged_ctx=None):
        self.scope = "serve/model" if paged_ctx is not None else "model"
        with jax.named_scope(self.scope):
            x = self.embed_tokens(input_ids)
            for i, layer in enumerate(self.layers):
                if paged_ctx is not None:
                    paged_ctx.layer_idx = i
                x = layer(x, self.rope_cos, self.rope_sin, position_offset,
                          paged_ctx=paged_ctx)
            return self.norm(x)


class BrumbyForCausalLM(Layer):
    """``weight_attr``: the initialiser of every matrix (embedding,
    projections, head), ``Normal(std=0.02)`` if None; whoever loads the
    values next passes one that draws nothing."""

    def __init__(self, config: BrumbyConfig, weight_attr=None):
        super().__init__()
        self.config = config
        if weight_attr is None:
            weight_attr = Normal(std=0.02)
        self.model = BrumbyModel(config, weight_attr)
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

    # ---- what the paged engine reads of the model
    def attention_kinds(self):
        """No layer holds K/V pages: no paged call, no pool (the extended
        description, (query heads, window, pool) a call, is
        ``models/laguna.py``'s to say)."""
        return []

    def recurrent_state(self) -> Optional[dict]:
        """The state a sequence every layer carries: how many layers, a
        slot's shape as ``ops/power_retention.py`` stores it, and the
        bytes of it the equations count (S in R^{D x d} and z in R^D,
        float32, D = d (d + 1) / 2) whatever is stored.  (``shape``: a
        slot of ONE array; a model whose slot is several says ``shapes``,
        a list, and the cache holds a pool of each a layer:
        ``models/phi4_flash.py``.)"""
        c = self.config
        return {"layers": c.num_hidden_layers,
                "shape": pr.state_shape(c.num_key_value_heads, c.head_dim,
                                        c.head_dim),
                "bytes": pr.state_bytes_symmetric(
                    c.num_key_value_heads, c.head_dim, c.head_dim)}
