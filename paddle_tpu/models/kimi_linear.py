"""Kimi-Linear: a hybrid decoder of Kimi Delta Attention (KDA) layers,
NoPE multi-head latent attention (MLA) layers and a 256-way sigmoid
mixture of experts with a shared expert.

Source of the architecture: the published ``config.json`` and modelling
code of ``moonshotai/Kimi-Linear-48B-A3B-Instruct``; ``KimiLinearConfig``
keeps its key names.  Pre-norm residual blocks, a block assembled from
(mixer kind) x (FFN kind) per layer index:

* KDA mixer (``linear_attn_config.kda_layers``, numbered from 1): q, k,
  v = SiLU(depthwise causal conv4(W x)); q, k L2-normalised per head (q
  scaled by dk^-1/2); a log-decay per CHANNEL a = -exp(A_log[h]) *
  softplus(W_f_b W_f_a x + dt_bias); beta = sigmoid(W_b x); the gated
  delta rule (``ops/kda.py``, chunkwise); y = W_o [RMSNorm_head(o) *
  sigmoid(W_g_b W_g_a x)].  No positional encoding.  Between the
  projections and W_o every stream is the [B, T, H * d] rows a
  projection writes, the layout the TPU kernels read: the per-head
  reductions are taken under ``_head_view``, never under a [B, T, H, d]
  view, which a TPU would have to copy the stream to make.
* MLA mixer (``full_attn_layers``; ``q_lora_rank`` null, ``mla_use_nope``
  true): q = W_q x (heads x (nope + rope)); c = W_kva x; c_kv =
  RMSNorm(c[:kv_lora_rank]); k_pe = c[kv_lora_rank:], shared by the
  heads and NOT rotated; [k_nope; v] = W_kvb c_kv; causal softmax
  attention with (nope + rope)-wide scores and v_head_dim-wide values.
* FFN: the first ``first_k_dense_replace`` layers a dense SwiGLU; the
  others ``MoELayer`` with ``SigmoidTopKGate`` over ``num_experts``, of
  which this program holds ``held_experts=(first, count)`` (one
  expert-parallel rank's share; None = all), plus the shared expert.

Each mixer states its state in its ``forward``: KDA ``(conv tails, S)``,
MLA ``(c_kv, k_pe)``; training passes none, a serving path can.

Trained by ``jit.TrainStep`` like ``LlamaForCausalLM`` (``forward(ids)
-> logits``); ``recompute_mixers`` wraps each mixer in
``fleet.recompute`` (the FFNs' activations are kept).
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..framework.dispatch import def_op
from ..framework.tensor import Tensor
from ..framework.tape import no_grad
from ..incubate.distributed.models.moe import (MoELayer, SigmoidTopKGate,
                                               SwiGLUExperts)
from ..nn import functional as F
from ..nn.initializer import Constant, Normal, Uniform
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.layers import Layer, LayerList
from ..nn.layer.norm import RMSNorm
from ..ops.kda import kda_chunk_rows
from ..ops.pallas.flash_attention import flash_attention_bshd

F32 = jnp.float32


def _published_linear_attn():
    full = [4, 8, 12, 16, 20, 24, 27]
    return {"full_attn_layers": full,
            "kda_layers": [i for i in range(1, 28) if i not in full],
            "head_dim": 128, "num_heads": 32, "short_conv_kernel_size": 4}


@dataclass
class KimiLinearConfig:
    """The published keys at their published values (Kimi-Linear-48B-A3B),
    then what this program adds."""
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 72                 # published; no mixer here reads it
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    linear_attn_config: dict = field(default_factory=_published_linear_attn)
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    rope_theta: float = 10000.0        # unused: NoPE
    rope_scaling: Optional[dict] = None
    model_max_length: int = 1048576    # nothing reads it: no positions
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    moe_intermediate_size: int = 1024
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_renormalize: bool = True
    moe_router_activation_func: str = "sigmoid"
    routed_scaling_factor: float = 2.446
    num_expert_group: int = 1
    topk_group: int = 1
    use_grouped_topk: bool = True
    num_nextn_predict_layers: int = 0
    model_type: str = "kimi_linear"
    # ---- not in the published file
    # (first id, count) of the routed experts this program holds; the
    # router keeps its num_experts outputs.  None: all of them.
    held_experts: Optional[Tuple[int, int]] = None
    # rank of the two low-rank maps W_f and W_g (None: the KDA head size,
    # the published code's choice)
    kda_gate_rank: Optional[int] = None
    recompute_mixers: bool = False

    def __post_init__(self):
        if self.held_experts is not None:
            self.held_experts = tuple(int(v) for v in self.held_experts)
        if self.q_lora_rank is not None or not self.mla_use_nope:
            raise NotImplementedError(
                "only the published form is written down: q_lora_rank null "
                "and mla_use_nope true (no rotary embedding)")
        if self.moe_router_activation_func != "sigmoid" \
                or self.num_expert_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                "the router is the published one: sigmoid scores, one "
                "expert group")
        if self.moe_layer_freq != 1 or self.num_nextn_predict_layers:
            raise NotImplementedError("moe_layer_freq 1, no MTP layers")

    @classmethod
    def from_dict(cls, d: dict) -> "KimiLinearConfig":
        """From a configuration file: the keys this class knows."""
        names = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})

    def mixer_kind(self, i: int) -> str:
        """'kda' or 'mla' for layer ``i`` (from 0; the file counts from 1)."""
        la = self.linear_attn_config
        if i + 1 in la["kda_layers"]:
            return "kda"
        if i + 1 in la["full_attn_layers"]:
            return "mla"
        raise ValueError(f"layer {i + 1} is in neither kda_layers nor "
                         "full_attn_layers")

    def ffn_kind(self, i: int) -> str:
        return "dense" if i < self.first_k_dense_replace else "moe"


# ------------------------------------------------------------------- ops
# A [B, T, H * d] stream is laid out on a TPU in tiles of 8 tokens x 128
# lanes: with d = 128 a tile is 8 tokens of ONE head.  A [B, T, H, d] view
# is tiled 8 heads x 128 lanes, so XLA re-lays the whole stream out to make
# it and again to leave it; a view that keeps 8 tokens together is the same
# bytes in the same order, and XLA reduces over a head's lanes inside the
# tile (PERF.md section 6, PR 46).
_TILE_TOKENS = 8


def _head_view(x, heads):
    """x [B, T, H * d] -> [B, T / 8, 8, H, d], the view of a row array
    under which a reduction over d costs no re-layout ([B, T, 1, H, d]
    where T is no multiple of 8)."""
    b, t, wide = x.shape
    g = _TILE_TOKENS if t % _TILE_TOKENS == 0 else 1
    return x.reshape(b, t // g, g, heads, wide // heads)


@def_op("short_conv_silu")
def _short_conv_silu(x, w, tail=None):
    """SiLU of a depthwise causal convolution over time.  x [B, T, C];
    w [C, K], w[:, K-1] multiplying the current token; ``tail`` the K-1
    inputs before x ([B, K-1, C], zeros if None).  Returns (y [B, T, C],
    the new tail)."""
    k = w.shape[-1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    t = x.shape[1]
    y = sum(xp[:, j:j + t].astype(F32) * w[:, j].astype(F32)
            for j in range(k))
    return jax.nn.silu(y).astype(x.dtype), xp[:, -(k - 1):]


@def_op("kda_gates")
def _kda_gates(q, k, f, a_log, dt_bias, b_logits, heads):
    """From the convolved streams to the delta rule's operands, every one
    in the rows it came in: q, k [B, T, H * dk] L2-normalised per head (q
    times dk^-1/2); the log-decay a = -exp(A_log[h]) softplus(f + dt_bias)
    [B, T, H * dk] float32; beta = sigmoid(b_logits) [B, T, H] float32."""
    dk = q.shape[-1] // heads

    def unit(x):
        v = _head_view(x.astype(F32), heads)
        v = v * jax.lax.rsqrt(jnp.sum(v * v, -1, keepdims=True) + 1e-6)
        return v.reshape(x.shape)

    # A_log [H] -> a value a channel [H * dk]: no head axis on the stream
    a = jnp.repeat(-jnp.exp(a_log.astype(F32)), dk) * jax.nn.softplus(
        f.astype(F32) + dt_bias.astype(F32))
    return ((unit(q) * dk ** -0.5).astype(q.dtype), unit(k).astype(k.dtype),
            a, jax.nn.sigmoid(b_logits.astype(F32)))


@def_op("gated_head_rms_norm")
def _gated_head_rms_norm(o, gate, weight, eps):
    """RMSNorm over each head's width, times sigmoid(gate).  o, gate
    [B, T, H * dv], weight [dv] -> [B, T, H * dv]."""
    v = _head_view(o.astype(F32), o.shape[-1] // weight.shape[-1])
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
    x = (v * weight.astype(F32)).reshape(o.shape) * jax.nn.sigmoid(
        gate.astype(F32))
    return x.astype(o.dtype)


@def_op("mla_attention")
def _mla_attention(q, k_nope, k_pe, v, scale):
    """Causal attention with (nope + rope)-wide q/k and narrower v on the
    flash path, whose kernels take a value width of their own.  q [B, Tq,
    H, dq], k_nope [B, Tk, H, dn], k_pe [B, Tk, dr] shared by the heads,
    v [B, Tk, H, dv]."""
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  k_nope.shape[:3] + k_pe.shape[-1:])], -1)
    return flash_attention_bshd(q, k, v, causal=True, scale=scale)


# ---------------------------------------------------------------- mixers
class ShortConv(Layer):
    """Depthwise causal convolution of width ``kernel`` with SiLU."""

    def __init__(self, channels, kernel):
        super().__init__()
        bound = kernel ** -0.5
        self.weight = self.create_parameter(
            [channels, kernel], attr=Uniform(-bound, bound))

    def forward(self, x, tail=None):
        return _short_conv_silu(x, self.weight, tail)


class KimiDeltaAttention(Layer):
    """The KDA mixer.  ``forward(x, state=None)``: ``state`` is
    ``((q_tail, k_tail, v_tail), S)`` — the three convolutions' last
    K-1 inputs and the recurrent state [B, H, dk, dv] float32 — and when
    given the new state is returned beside the output."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        c, la = config, config.linear_attn_config
        self.heads, self.dk = la["num_heads"], la["head_dim"]
        self.dv = la["head_dim"]
        self.eps = c.rms_norm_eps
        h, wide = c.hidden_size, self.heads * self.dk
        rank = c.kda_gate_rank or la["head_dim"]
        init = Normal(std=0.02)

        def lin(i, o):
            return Linear(i, o, weight_attr=init, bias_attr=False)

        self.q_proj, self.k_proj = lin(h, wide), lin(h, wide)
        self.v_proj = lin(h, self.heads * self.dv)
        k = la["short_conv_kernel_size"]
        self.q_conv1d, self.k_conv1d = ShortConv(wide, k), ShortConv(wide, k)
        self.v_conv1d = ShortConv(self.heads * self.dv, k)
        self.f_a_proj, self.f_b_proj = lin(h, rank), lin(rank, wide)
        self.b_proj = lin(h, self.heads)
        self.g_a_proj = lin(h, rank)
        self.g_b_proj = lin(rank, self.heads * self.dv)
        # the decay's two leaves stay float32 whatever the model's dtype
        self.A_log = self.create_parameter(
            [self.heads], dtype="float32",
            default_initializer=Uniform(0.0, 2.77))      # A in [1, 16]
        self.dt_bias = self.create_parameter(
            [wide], dtype="float32", default_initializer=Constant(-4.6))
        self.o_norm = RMSNorm(self.dv, epsilon=c.rms_norm_eps)
        self.o_proj = lin(self.heads * self.dv, h)

    def forward(self, x, state=None):
        # every stream stays in the rows its projection wrote,
        # [B, T, H * d], from here to o_proj (``ops/kda.py``); the scopes
        # name the mixer's parts in a device trace (train/model/kda/...)
        tails, s0 = state if state is not None else ((None,) * 3, None)
        with jax.named_scope("proj"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            f = self.f_b_proj(self.f_a_proj(x))
            gate = self.g_b_proj(self.g_a_proj(x))
            b_logits = self.b_proj(x)
        with jax.named_scope("conv"):
            q, q_tail = self.q_conv1d(q, tails[0])
            k, k_tail = self.k_conv1d(k, tails[1])
            v, v_tail = self.v_conv1d(v, tails[2])
        with jax.named_scope("gates"):
            q, k, a, beta = _kda_gates(q, k, f, self.A_log, self.dt_bias,
                                       b_logits, self.heads)
        with jax.named_scope("chunk"):
            o, s = kda_chunk_rows(q, k, v, a, beta, s0)
        with jax.named_scope("norm"):
            o = _gated_head_rms_norm(o, gate, self.o_norm.weight, self.eps)
        with jax.named_scope("out"):
            y = self.o_proj(o)
        if state is not None:
            return y, ((q_tail, k_tail, v_tail), s)
        return y


class KimiMLAttention(Layer):
    """The NoPE latent-attention mixer.  ``forward(x, state=None)``:
    ``state`` is ``(c_kv, k_pe)`` — the normed latents [B, S, kv_lora_rank]
    and the shared key part [B, S, qk_rope_head_dim] of the tokens so far
    — and when given the grown state is returned beside the output."""

    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        c = config
        self.heads = c.num_attention_heads
        self.dn, self.dr, self.dv = (c.qk_nope_head_dim, c.qk_rope_head_dim,
                                     c.v_head_dim)
        self.rank = c.kv_lora_rank
        init = Normal(std=0.02)

        def lin(i, o):
            return Linear(i, o, weight_attr=init, bias_attr=False)

        h = c.hidden_size
        self.q_proj = lin(h, self.heads * (self.dn + self.dr))
        self.kv_a_proj_with_mqa = lin(h, self.rank + self.dr)
        self.kv_a_layernorm = RMSNorm(self.rank, epsilon=c.rms_norm_eps)
        self.kv_b_proj = lin(self.rank, self.heads * (self.dn + self.dv))
        self.o_proj = lin(self.heads * self.dv, h)

    def forward(self, x, state=None):
        from .. import tensor as T
        b, t = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([b, t, self.heads, self.dn + self.dr])
        c = self.kv_a_proj_with_mqa(x)
        c_kv = self.kv_a_layernorm(c[:, :, :self.rank])
        k_pe = c[:, :, self.rank:]
        if state is not None:
            c_kv = T.concat([state[0], c_kv], axis=1)
            k_pe = T.concat([state[1], k_pe], axis=1)
        kv = self.kv_b_proj(c_kv).reshape(
            [b, c_kv.shape[1], self.heads, self.dn + self.dv])
        o = _mla_attention(q, kv[:, :, :, :self.dn], k_pe,
                           kv[:, :, :, self.dn:],
                           float(self.dn + self.dr) ** -0.5)
        y = self.o_proj(o.reshape([b, t, self.heads * self.dv]))
        if state is not None:
            return y, (c_kv, k_pe)
        return y


_MIXERS = {"kda": KimiDeltaAttention, "mla": KimiMLAttention}


# ------------------------------------------------------------------ FFNs
class KimiMLP(Layer):
    def __init__(self, hidden, width):
        super().__init__()
        init = Normal(std=0.02)
        self.gate_proj = Linear(hidden, width, weight_attr=init,
                                bias_attr=False)
        self.up_proj = Linear(hidden, width, weight_attr=init,
                              bias_attr=False)
        self.down_proj = Linear(width, hidden, weight_attr=init,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


def _moe_block(config: KimiLinearConfig) -> MoELayer:
    c = config
    first, count = c.held_experts or (0, c.num_experts)
    gate = SigmoidTopKGate(
        c.hidden_size, c.num_experts, 1, topk=c.num_experts_per_token,
        renormalize=c.moe_renormalize,
        routed_scaling_factor=c.routed_scaling_factor)
    shared = (KimiMLP(c.hidden_size,
                      c.moe_intermediate_size * c.num_shared_experts)
              if c.num_shared_experts else None)
    return MoELayer(
        c.hidden_size,
        SwiGLUExperts(count, c.hidden_size, c.moe_intermediate_size,
                      weight_attr=Normal(std=0.02)),
        gate=gate, held_experts=(first, count), shared_expert=shared)


# ----------------------------------------------------------------- model
class KimiDecoderLayer(Layer):
    """x += Mixer(RMSNorm(x)); x += FFN(RMSNorm(x)), the mixer and the
    FFN chosen by the layer's index."""

    def __init__(self, config: KimiLinearConfig, layer_idx: int):
        super().__init__()
        c = config
        self.mixer_kind = c.mixer_kind(layer_idx)
        self.ffn_kind = c.ffn_kind(layer_idx)
        self.recompute_mixer = c.recompute_mixers
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_norm_eps)
        self.self_attn = _MIXERS[self.mixer_kind](c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                epsilon=c.rms_norm_eps)
        self.mlp = (KimiMLP(c.hidden_size, c.intermediate_size)
                    if self.ffn_kind == "dense" else _moe_block(c))

    def forward(self, x, state=None):
        h = self.input_layernorm(x)
        with jax.named_scope(self.mixer_kind):
            if state is not None:
                mixed, state = self.self_attn(h, state)
            elif self.recompute_mixer and self.training:
                from ..distributed.fleet.recompute import recompute
                mixed = recompute(self.self_attn, h)
            else:
                mixed = self.self_attn(h)
        x = x + mixed
        h = self.post_attention_layernorm(x)
        if self.ffn_kind == "dense":
            with jax.named_scope("dense_ffn"):
                x = x + self.mlp(h)
        else:                    # MoELayer names moe/router, moe/experts
            x = x + self.mlp(h)
        return x if state is None else (x, state)


class KimiLinearModel(Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(config.vocab_size, config.hidden_size,
                                      weight_attr=Normal(std=0.02))
        self.layers = LayerList([KimiDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids, states=None):
        x = self.embed_tokens(input_ids)
        new_states = [] if states is not None else None
        for i, layer in enumerate(self.layers):
            if states is not None:
                x, s = layer(x, states[i])
                new_states.append(s)
            else:
                x = layer(x)
        x = self.norm(x)
        return x if states is None else (x, new_states)


ROUTING_COUNTS = ("moe_slots_total", "moe_held_slots_total",
                  "moe_rows_computed_total", "moe_held_slots_max_per_expert")


class KimiLinearForCausalLM(Layer):
    def __init__(self, config: KimiLinearConfig):
        super().__init__()
        if config.tie_word_embeddings:
            raise NotImplementedError("the published head is untied")
        self.config = config
        self.model = KimiLinearModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(std=0.02), bias_attr=False)

    def forward(self, input_ids, labels=None, states=None):
        """logits; (loss, logits) with ``labels``; (logits, states) with
        ``states`` (one entry a layer, see the mixers)."""
        if states is not None:
            hidden, states = self.model(input_ids, states)
            return self.lm_head(hidden), states
        logits = self.lm_head(self.model(input_ids))
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.config.vocab_size]),
                labels.reshape([-1]), ignore_index=-100)
            return loss, logits
        return logits

    def empty_states(self, batch: int):
        """What ``forward(..., states=)`` takes before the first token."""
        c, la = self.config, self.config.linear_attn_config
        dtype = self.model.embed_tokens.weight._data.dtype
        k = la["short_conv_kernel_size"] - 1
        wide = la["num_heads"] * la["head_dim"]

        def z(*shape, dt=dtype):
            return Tensor(jnp.zeros(shape, dt))

        out = []
        for i in range(c.num_hidden_layers):
            if c.mixer_kind(i) == "kda":
                out.append(((z(batch, k, wide),) * 3,
                            z(batch, la["num_heads"], la["head_dim"],
                              la["head_dim"], dt=F32)))
            else:
                out.append((z(batch, 0, c.kv_lora_rank),
                            z(batch, 0, c.qk_rope_head_dim)))
        return out

    def routing_counts(self, input_ids):
        """One forward pass without gradients; the expert layers' counts
        added up (the largest for ``..._max_per_expert``), as float32
        arrays under the ``ROUTING_COUNTS`` names.  Traceable."""
        with no_grad():
            self.model(input_ids)
        stats = [l.mlp.last_routing._data for l in self.model.layers
                 if l.ffn_kind == "moe"]
        if not stats:
            return {n: jnp.zeros((), F32) for n in ROUTING_COUNTS}
        st = jnp.stack(stats)
        total = jnp.sum(st, axis=0)
        return dict(zip(ROUTING_COUNTS, [*total[:3], jnp.max(st[:, 3])]))


def record_routing_counts(model: KimiLinearForCausalLM, batches) -> dict:
    """Run ``routing_counts`` (one jitted program) over ``batches`` of
    input ids and add the counts to the ``monitor`` counters of the same
    names (the per-expert maximum is kept as the largest seen, in a
    counter that only ever rises).  Returns the totals."""
    from .. import monitor
    params = [p for _, p in model.named_parameters()]
    fn = getattr(model, "_routing_counts_fn", None)
    if fn is None:           # traced once a model, not once a call

        def pure(arrays, ids):
            saved = [p._data for p in params]
            try:
                for p, a in zip(params, arrays):
                    p._data = a
                return model.routing_counts(Tensor(ids))
            finally:
                for p, a in zip(params, saved):
                    p._data = a

        fn = model._routing_counts_fn = jax.jit(pure)
    arrays = [p._data for p in params]
    got = [fn(arrays, ids._data if isinstance(ids, Tensor)
              else jnp.asarray(ids)) for ids in batches]
    # one fetch for all the batches, after the last is dispatched
    table = jax.device_get(jnp.stack(
        [jnp.stack([g[n] for n in ROUTING_COUNTS]) for g in got]))
    totals = dict(zip(ROUTING_COUNTS[:-1],
                      table[:, :-1].sum(axis=0).tolist()))
    totals[ROUTING_COUNTS[-1]] = float(table[:, -1].max())
    for name in ROUTING_COUNTS[:-1]:
        monitor.counter(name, "routed-expert slots of the counted "
                        "batches").inc(totals[name])
    peak = monitor.counter(ROUTING_COUNTS[-1],
                           "most slots one held expert got in a layer")
    peak.inc(max(0.0, totals[ROUTING_COUNTS[-1]] - peak.value()))
    return totals
