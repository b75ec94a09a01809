"""Phi-4-mini-flash-reasoning: a "decoder-hybrid-decoder" (SambaY) whose
first half alternates Mamba-1 mixers with window-512 attention and ends in
ONE full-attention layer, and whose second half alternates Gated Memory
Units with CROSS attention: every later attention layer holds a query
projection only and reads that one full layer's keys and values.

Source of the architecture: the published ``config.json`` of
``microsoft/Phi-4-mini-flash-reasoning`` (``model_type`` ``phi4flash``) and
Ren et al., "Decoder-Hybrid-Decoder Architecture for Efficient Reasoning
with Long Generation", arXiv:2507.06607.  ``Phi4FlashConfig`` keeps the
config's key names.

Layer l of n, pre-norm residual with LayerNorm (gain and bias):
``x += Mixer_l(LN(x)); x += W_down(silu(g) * u), [g, u] = W_gate_up LN(x)``,
a final LayerNorm, the head tied to the embedding.  The mixer by index:

    l even, l <= n/2      Mamba-1; layer n/2 also hands its scan output m on
    l odd,  l <  n/2      differential attention over the last 512 keys
    l = n/2 + 1           differential attention, full; its K/V are the
                          second half's
    l even, l >  n/2      GMU: W_out(silu(W_in x_t) * m_t), m of layer n/2
    l odd,  l >  n/2 + 1  differential CROSS attention: W_q only, over layer
                          n/2 + 1's K/V

Mamba (``ops/selective_scan.py`` has the recurrence and its served forms):
``[u, z] = W_in x``; ``u <- silu(conv1d_causal(u))``; ``[dl, B, C] = W_x u``;
``delta = softplus(W_dt dl + b_dt)``; ``h_t = exp(delta_t A) h_{t-1} +
(delta_t u_t) B_t^T``, ``A = -exp(A_log)``; ``m_t = h_t C_t + D u_t``; the
output ``W_out(m_t * silu(z_t))``.  ``m`` is handed on BEFORE its gate.

Differential attention (no positions; scores scaled by head_dim^-1/2):
query heads pair up as (2i, 2i + 1), KV heads as (2j, 2j + 1), j = i // 2:

    A1 = softmax(q_2i k_2j^T)   A2 = softmax(q_2i+1 k_2j+1^T)   V = [v_2j | v_2j+1]
    o_i = (1 - l0) RMSNorm_2d(A1 V - lam A2 V),   lam = exp(lq1 . lk1) - exp(lq2 . lk2) + l0
    l0 = 0.8 - 0.6 exp(-0.3 l)

Served through the paged engine a K/V page holds a PAIR of KV heads as one
head of ``2 d`` lanes (``kv_page_shape``: [k_2j | k_2j+1] and [v_2j |
v_2j+1], each value once), and a query head rides as [q_2i | 0] or [0 |
q_2i+1]: the paged kernels' ordinary grouped walk then gives A1 V and A2 V
themselves, two softmaxes over one read of the pair's keys and values.  The
16 paged calls walk 9 pools (``attention_kinds``: the cross layers name the
full layer's and append nothing) and the 9 Mamba layers hold a slot of two
arrays (``recurrent_state``); ``m`` of the step's tokens lives inside the
step's program.  Without a paged context the forward runs the recurrence
and dense masked attention over whole sequences.

What the config does not give is listed, each with its reason, under
``assumed`` in the benchmark's configuration file
(``benchmark/configs/phi-4-mini-flash.serve-d32.json``); none of it is an
option of ``Phi4FlashConfig``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..framework.tensor import wrap_array
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import LayerNorm
from ..ops import selective_scan as ss

F32 = jnp.float32
SUBLN_EPS = 1e-5
#: Mamba-1's sizes by the family's convention (no config key gives them)
MAMBA_EXPAND, MAMBA_D_STATE, MAMBA_D_CONV = 2, 16, 4


@dataclass
class Phi4FlashConfig:
    """The published keys at their published values."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    mb_per_layer: int = 2
    sliding_window: int = 512
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    mlp_bias: bool = False
    lm_head_bias: bool = False
    tie_word_embeddings: bool = True

    def __post_init__(self):
        if (not self.tie_word_embeddings or self.mlp_bias
                or self.lm_head_bias or self.hidden_act != "silu"
                or self.mb_per_layer != 2):
            raise NotImplementedError(
                "the published model has a tied head without bias, a silu "
                "MLP without bias and a Mamba layer every second layer")
        if self.num_hidden_layers % 4 or self.num_attention_heads % 4 \
                or self.num_key_value_heads * 2 != self.num_attention_heads:
            raise NotImplementedError(
                "layers in fours (the split at n / 2 falls on a Mamba "
                "layer) and two query heads a KV head, as published")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return MAMBA_EXPAND * self.hidden_size

    @property
    def dt_rank(self):
        return math.ceil(self.hidden_size / 16)

    def mixer(self, i: int) -> str:
        half = self.num_hidden_layers // 2
        if i % 2 == 0:
            return "mamba" if i <= half else "gmu"
        return ("sliding" if i < half else
                "full" if i == half + 1 else "cross")

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)


class Phi4FlashMamba(Layer):
    def __init__(self, config: Phi4FlashConfig, weight_attr):
        super().__init__()
        c = config
        self.d_inner, self.d_state = c.d_inner, MAMBA_D_STATE
        self.dt_rank = c.dt_rank
        self.in_proj = Linear(c.hidden_size, 2 * c.d_inner,
                              weight_attr=weight_attr, bias_attr=False)
        self.conv_weight = self.create_parameter(
            (MAMBA_D_CONV, c.d_inner), attr=weight_attr)
        self.conv_bias = self.create_parameter((c.d_inner,), is_bias=True)
        self.x_proj = Linear(c.d_inner, c.dt_rank + 2 * MAMBA_D_STATE,
                             weight_attr=weight_attr, bias_attr=False)
        self.dt_proj = Linear(c.dt_rank, c.d_inner,
                              weight_attr=weight_attr)
        # A = 1 ... d_state a channel, D = 1: float32 whatever the rest is
        self.A_log = self.create_parameter(
            (c.d_inner, MAMBA_D_STATE), dtype="float32",
            default_initializer=lambda shape, dtype: jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=F32)), shape))
        self.D = self.create_parameter((c.d_inner,), dtype="float32",
                                       default_initializer=Constant(1.0))
        self.out_proj = Linear(c.d_inner, c.hidden_size,
                               weight_attr=weight_attr, bias_attr=False)

    def forward(self, x, paged_ctx=None):
        """x (b, s, hidden) -> (the mixer's output, m (b, s, d_inner)
        float32: the scan's output before its gate)."""
        b, s = x.shape[0], x.shape[1]
        n, r = self.d_state, self.dt_rank
        with jax.named_scope("proj"):
            uz = self.in_proj(x)._data
            u, z = uz[..., :self.d_inner], uz[..., self.d_inner:]
        w, cb = self.conv_weight._data, self.conv_bias._data
        with jax.named_scope("state"):
            if paged_ctx is not None:
                u = paged_ctx.conv_rows(u[:, 0], w, cb)[:, None]
            else:
                u = jax.vmap(lambda v: ss.conv_recurrence(v, w, cb)[0])(u)
        with jax.named_scope("proj"):
            u = jax.nn.silu(u).astype(x.dtype)
            dbc = self.x_proj(wrap_array(u))._data
            delta = jax.nn.softplus(
                self.dt_proj(wrap_array(dbc[..., :r]))._data.astype(F32))
            bb, cc = dbc[..., r:r + n], dbc[..., r + n:]
            a = -jnp.exp(self.A_log._data.astype(F32)).T        # (N, D)
        with jax.named_scope("state"):
            if paged_ctx is not None:
                m = paged_ctx.scan_rows(u[:, 0], delta[:, 0], a, bb[:, 0],
                                        cc[:, 0], self.D._data)[:, None]
            else:
                m = jax.vmap(lambda *xs: ss.scan_recurrence(
                    xs[0], xs[1], a, xs[2], xs[3], self.D._data)[0])(
                        u, delta, bb, cc)
        with jax.named_scope("out"):
            y = (m * jax.nn.silu(z.astype(F32))).astype(x.dtype)
            return self.out_proj(wrap_array(y)), m


class Phi4FlashGMU(Layer):
    def __init__(self, config: Phi4FlashConfig, weight_attr):
        super().__init__()
        self.in_proj = Linear(config.hidden_size, config.d_inner,
                              weight_attr=weight_attr, bias_attr=False)
        self.out_proj = Linear(config.d_inner, config.hidden_size,
                               weight_attr=weight_attr, bias_attr=False)

    def forward(self, x, m):
        g = jax.nn.silu(self.in_proj(x)._data.astype(F32))
        return self.out_proj(wrap_array((g * m).astype(x.dtype)))


def _dense_diff_attention(q, k, v, window, scale):
    """Without a cache: q (b, s, H, 2d) in the served layout ([q | 0] and
    [0 | q]), k / v (b, s, H / 4, 2d) -> (b, s, H, 2d) float32."""
    b, s, heads, d2 = q.shape
    qg = q.astype(F32).reshape(b, s, k.shape[2], -1, d2)
    dots = jnp.einsum("bthgd,bjhd->bhgtj", qg, k.astype(F32),
                      precision=jax.lax.Precision.HIGHEST) * scale
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    p = jax.nn.softmax(jnp.where(seen, dots, -jnp.inf), axis=-1)
    return jnp.einsum("bhgtj,bjhd->bthgd", p, v.astype(F32),
                      precision=jax.lax.Precision.HIGHEST
                      ).reshape(b, s, heads, d2)


class Phi4FlashAttention(Layer):
    """Differential attention; ``kind`` "sliding" / "full" (own K/V) or
    "cross" (a query projection only)."""

    def __init__(self, config: Phi4FlashConfig, layer_idx: int, kind: str,
                 weight_attr):
        super().__init__()
        c = config
        self.kind = kind
        self.num_heads = c.num_attention_heads
        self.num_kv_heads = c.num_key_value_heads
        self.head_dim = d = c.head_dim
        self.window = c.sliding_window if kind == "sliding" else None
        self.lambda_init = c.lambda_init(layer_idx)
        wide, kv = self.num_heads * d, self.num_kv_heads * d
        if kind == "cross":
            self.q_proj = Linear(c.hidden_size, wide, weight_attr=weight_attr)
        else:
            self.qkv_proj = Linear(c.hidden_size, wide + 2 * kv,
                                   weight_attr=weight_attr)
        self.o_proj = Linear(wide, c.hidden_size, weight_attr=weight_attr)
        small = Normal(std=0.1)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                (d,), default_initializer=small))
        self.subln_weight = self.create_parameter(
            (2 * d,), default_initializer=Constant(1.0))

    def forward(self, x, paged_ctx=None, shared_kv=None):
        """``shared_kv``: without a cache, the full layer's (k, v) for a
        cross layer.  Returns (out, this layer's (k, v) or None)."""
        b, s = x.shape[0], x.shape[1]
        h, d = self.num_heads, self.head_dim
        scale = 1.0 / math.sqrt(d)
        if self.kind == "cross":
            q, kv = self.q_proj(x)._data, None
        else:
            qkv = self.qkv_proj(x)._data
            q = qkv[..., :h * d]
            # a page's head is a PAIR of KV heads, 2 d lanes wide
            kv = tuple(t.reshape(b, s, self.num_kv_heads // 2, 2 * d)
                       for t in jnp.split(qkv[..., h * d:], 2, axis=-1))
        # head 2i rides as [q | 0] against the pair's first key, head
        # 2i + 1 as [0 | q] against its second
        q = q.reshape(b, s, h // 2, 2, 1, d)
        q = (q * jnp.eye(2, dtype=q.dtype)[:, :, None]).reshape(
            b, s, h, 2 * d)
        if paged_ctx is not None:
            k, v = (wrap_array(t) for t in kv) if kv else (None, None)
            o = paged_ctx.attend(wrap_array(q), k, v, window=self.window,
                                 scale=scale)._data
        else:
            k, v = kv if kv else shared_kv
            o = _dense_diff_attention(q, k, v, self.window, scale)
        lam = (jnp.exp(jnp.sum(self.lambda_q1._data.astype(F32)
                               * self.lambda_k1._data.astype(F32)))
               - jnp.exp(jnp.sum(self.lambda_q2._data.astype(F32)
                                 * self.lambda_k2._data.astype(F32)))
               + self.lambda_init)
        o = o.astype(F32).reshape(b, s, h // 2, 2, 2 * d)
        o = o[..., 0, :] - lam * o[..., 1, :]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + SUBLN_EPS)
        o = o * self.subln_weight._data.astype(F32) * (1.0 - self.lambda_init)
        return self.o_proj(wrap_array(
            o.astype(x.dtype).reshape(b, s, h * d))), kv


class Phi4FlashMLP(Layer):
    def __init__(self, config: Phi4FlashConfig, weight_attr):
        super().__init__()
        self.width = config.intermediate_size
        self.gate_up_proj = Linear(config.hidden_size, 2 * self.width,
                                   weight_attr=weight_attr, bias_attr=False)
        self.down_proj = Linear(self.width, config.hidden_size,
                                weight_attr=weight_attr, bias_attr=False)

    def forward(self, x):
        gu = self.gate_up_proj(x)._data
        return self.down_proj(wrap_array(
            jax.nn.silu(gu[..., :self.width]) * gu[..., self.width:]))


class Phi4FlashDecoderLayer(Layer):
    def __init__(self, config: Phi4FlashConfig, layer_idx: int, weight_attr):
        super().__init__()
        c = config
        self.kind = kind = c.mixer(layer_idx)
        self.input_layernorm = LayerNorm(c.hidden_size,
                                         epsilon=c.layer_norm_eps)
        if kind == "mamba":
            self.mixer = Phi4FlashMamba(c, weight_attr)
        elif kind == "gmu":
            self.mixer = Phi4FlashGMU(c, weight_attr)
        else:
            self.mixer = Phi4FlashAttention(c, layer_idx, kind, weight_attr)
        self.post_attention_layernorm = LayerNorm(c.hidden_size,
                                                  epsilon=c.layer_norm_eps)
        self.mlp = Phi4FlashMLP(c, weight_attr)

    def forward(self, x, carried, paged_ctx=None):
        """``carried``: what the layers hand on inside one forward: ``m``
        (layer n/2's scan output) and, without a cache, the full layer's
        ``kv``."""
        h = self.input_layernorm(x)
        if self.kind == "mamba":
            with jax.named_scope("mamba"):
                y, carried["m"] = self.mixer(h, paged_ctx=paged_ctx)
        elif self.kind == "gmu":
            with jax.named_scope("gmu"):
                y = self.mixer(h, carried["m"])
        else:
            with jax.named_scope(f"attn_{self.kind}"):
                y, kv = self.mixer(h, paged_ctx=paged_ctx,
                                   shared_kv=carried.get("kv"))
                if self.kind == "full":
                    carried["kv"] = kv
        x = x + y
        with jax.named_scope("dense_ffn"):
            return x + self.mlp(self.post_attention_layernorm(x))


class Phi4FlashModel(Layer):
    def __init__(self, config: Phi4FlashConfig, weight_attr):
        super().__init__()
        self.config = c = config
        self.embed_tokens = Embedding(c.vocab_size, c.hidden_size,
                                      weight_attr=weight_attr)
        self.layers = LayerList([Phi4FlashDecoderLayer(c, i, weight_attr)
                                 for i in range(c.num_hidden_layers)])
        self.final_layernorm = LayerNorm(c.hidden_size,
                                         epsilon=c.layer_norm_eps)
        # the pool each paged call walks, in the calls' order: a layer
        # with K/V of its own opens one, a cross layer names the full one's
        self.pool_of, pools = {}, 0
        for i in range(c.num_hidden_layers):
            kind = c.mixer(i)
            if kind in ("sliding", "full"):
                self.pool_of[i], pools = pools, pools + 1
            elif kind == "cross":
                self.pool_of[i] = pools - 1
        #: the scope the last forward ran under (``_logits_of`` joins it)
        self.scope = "model"

    def forward(self, input_ids, position_offset=0, paged_ctx=None):
        del position_offset                 # no positions: NoPE
        self.scope = "serve/model" if paged_ctx is not None else "model"
        with jax.named_scope(self.scope):
            x = self.embed_tokens(input_ids)
            carried = {}
            for i, layer in enumerate(self.layers):
                if paged_ctx is not None and i in self.pool_of:
                    paged_ctx.layer_idx = self.pool_of[i]
                x = layer(x, carried, paged_ctx=paged_ctx)
            return self.final_layernorm(x)


class Phi4FlashForCausalLM(Layer):
    """``weight_attr``: the initialiser of every matrix (embedding,
    projections, the convolution), ``Normal(std=0.02)`` if None; whoever
    loads the values next passes one that draws nothing."""

    def __init__(self, config: Phi4FlashConfig, weight_attr=None):
        super().__init__()
        self.config = config
        if weight_attr is None:
            weight_attr = Normal(std=0.02)
        self.model = Phi4FlashModel(config, weight_attr)

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
        """[(query heads, window or None, pool)] a paged call, in the
        calls' order: 16 calls on 9 pools.  ``pool`` is the index of the
        page pool the call walks; a call that names a pool an EARLIER call
        opened appends nothing to it (the cross layers, over the full
        layer's)."""
        c = self.config
        return [(c.num_attention_heads,
                 c.sliding_window if c.mixer(i) == "sliding" else None, pool)
                for i, pool in sorted(self.model.pool_of.items())]

    def kv_page_shape(self):
        """(KV heads, head width) of a page as ``attend`` is handed K and
        V: a pair of the config's KV heads a head, 2 x head_dim lanes."""
        c = self.config
        return c.num_key_value_heads // 2, 2 * c.head_dim

    def recurrent_state(self) -> dict:
        """The state a sequence the Mamba layers carry: how many layers, a
        slot's arrays as ``ops/selective_scan.py`` stores them (``h`` and
        the convolution's tail), and the bytes of both."""
        c = self.config
        sizes = (c.d_inner, MAMBA_D_STATE, MAMBA_D_CONV)
        return {"layers": sum(c.mixer(i) == "mamba"
                              for i in range(c.num_hidden_layers)),
                "shapes": ss.state_shapes(*sizes),
                "bytes": ss.state_bytes(*sizes)}
