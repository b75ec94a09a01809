"""The plain reference of the Kimi-Linear cell: the decoder of
``moonshotai/Kimi-Linear-48B-A3B-Instruct`` as its published
``config.json`` and modelling code describe it, its token cross-entropy
and AdamW, in straightforward ``jax.numpy`` — float32, matmuls at
precision "highest", no kernels, no chunking of the delta rule.  It
imports nothing of the program; weights come from
``benchmark/weights.py`` by (seed, leaf name).

* KDA layers: the recurrence itself, ONE TOKEN A STEP,
  S_t = (I - b_t k_t k_t^T) Diag(exp(a_t)) S_{t-1} + b_t k_t v_t^T,
  o_t = S_t^T q_t (two nested scans, the inner one under
  ``jax.checkpoint``, so that 8,192 states are not kept for the
  backward; the arithmetic is token by token all the same).
* MLA layers: dense causal softmax over [k_nope ; k_pe], no rotation.
* Expert layers: ``top_k`` over all ``num_experts`` scores; of the
  chosen, those inside ``held_experts`` are computed (each held expert
  densely over all tokens, times the token's weight or zero) and the
  shared expert added.  What the experts this chip does not hold would
  add is left out, as in the program.

Departures from the published code, each also under ``assumed`` in the
configuration file: rank of W_f and W_g = the KDA head size; ``A_log``
a head, ``dt_bias`` a channel, both float32; L2 normalisation of q and
k with 1e-6 inside the root; the selection bias frozen.

``shape_leaf`` gives four kinds of leaf the ranges their published
initialisers give them (``benchmark/weights.py`` knows only matrices and
gains): ``A_log`` = log of a number in [1, 16], ``dt_bias`` the inverse
softplus of a step in [0.001, 0.1], the convolutions within +-0.55, the
selection bias within +-0.09 of zero.

``precision="int8"`` is the control, never the reference: every dense
matmul (projections, experts, router, head) rounds both operands to
int8 first (``llama_plain.int8_matmul``).
"""
from __future__ import annotations

import functools
import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import weights as W  # noqa: E402  (benchmark/weights.py)
from reference.llama_plain import (  # noqa: E402,F401
    _apply, _diff_norm, _moments, _norm, matmul, rms_norm, worst_gain_diff,
    worst_leaf_gap)

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FLOAT32_LEAVES = ("A_log", "dt_bias", "e_score_correction_bias")


# ------------------------------------------------------------- the shapes
def mixer_kind(cfg, i):
    return "kda" if i + 1 in cfg["linear_attn_config"]["kda_layers"] else "mla"


def held(cfg):
    return tuple(cfg.get("held_experts") or (0, cfg["num_experts"]))


def layer_specs(cfg, i):
    """Layer ``i``'s leaves in the program's order (a layer's own
    parameters before its sublayers')."""
    h, la = cfg["hidden_size"], cfg["linear_attn_config"]
    p = f"model.layers.{i}."
    out = [(p + "input_layernorm.weight", (h,))]
    a = p + "self_attn."
    if mixer_kind(cfg, i) == "kda":
        heads, d, k = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
        rank = cfg.get("kda_gate_rank") or d
        wide = heads * d
        out += [(a + "A_log", (heads,)), (a + "dt_bias", (wide,)),
                (a + "q_proj.weight", (h, wide)),
                (a + "k_proj.weight", (h, wide)),
                (a + "v_proj.weight", (h, wide)),
                (a + "q_conv1d.weight", (wide, k)),
                (a + "k_conv1d.weight", (wide, k)),
                (a + "v_conv1d.weight", (wide, k)),
                (a + "f_a_proj.weight", (h, rank)),
                (a + "f_b_proj.weight", (rank, wide)),
                (a + "b_proj.weight", (h, heads)),
                (a + "g_a_proj.weight", (h, rank)),
                (a + "g_b_proj.weight", (rank, wide)),
                (a + "o_norm.weight", (d,)),
                (a + "o_proj.weight", (wide, h))]
    else:
        heads = cfg["num_attention_heads"]
        dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        r = cfg["kv_lora_rank"]
        out += [(a + "q_proj.weight", (h, heads * (dn + dr))),
                (a + "kv_a_proj_with_mqa.weight", (h, r + dr)),
                (a + "kv_a_layernorm.weight", (r,)),
                (a + "kv_b_proj.weight", (r, heads * (dn + dv))),
                (a + "o_proj.weight", (heads * dv, h))]
    out.append((p + "post_attention_layernorm.weight", (h,)))
    m = p + "mlp."
    if i < cfg["first_k_dense_replace"]:
        w = cfg["intermediate_size"]
        out += [(m + "gate_proj.weight", (h, w)),
                (m + "up_proj.weight", (h, w)),
                (m + "down_proj.weight", (w, h))]
    else:
        w, n = cfg["moe_intermediate_size"], held(cfg)[1]
        ws = w * cfg["num_shared_experts"]
        out += [(m + "shared_expert.gate_proj.weight", (h, ws)),
                (m + "shared_expert.up_proj.weight", (h, ws)),
                (m + "shared_expert.down_proj.weight", (ws, h)),
                (m + "experts.gate_proj", (n, h, w)),
                (m + "experts.up_proj", (n, h, w)),
                (m + "experts.down_proj", (n, w, h)),
                (m + "gate.gate_weight", (h, cfg["num_experts"]))]
    return out


def buffer_specs(cfg):
    """The seeded leaves that are not trained: the routers' selection
    bias, under the program's names (``trainable=False`` parameters)."""
    return [(f"model.layers.{i}.mlp.gate.e_score_correction_bias",
             (cfg["num_experts"],))
            for i in range(cfg["first_k_dense_replace"],
                           cfg["num_hidden_layers"])]


def param_groups(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    groups = [[("model.embed_tokens.weight", (v, h))]]
    groups += [layer_specs(cfg, i) for i in range(cfg["num_hidden_layers"])]
    groups.append([("model.norm.weight", (h,)), ("lm_head.weight", (h, v))])
    return groups


def param_specs(cfg):
    """[(leaf name, shape)] of the program's parameters, in its order.
    Linear weights are (in, out), stacked experts (expert, in, out)."""
    return [s for g in param_groups(cfg) for s in g]


def leaf_dtype(name):
    """float32 for the decay's leaves and the selection bias, else the
    configuration's bfloat16."""
    return jnp.float32 if name.endswith(FLOAT32_LEAVES) else jnp.bfloat16


def shape_leaf(name, x):
    """The seed's value of a leaf (``weights.leaf_values``: a gain in 1
    +- 0.0866 for a 1-D leaf, a matrix entry in +- 0.0346) moved into
    the range its published initialiser gives it; every other leaf as
    it is.  Pure ``jax.numpy``: the driver applies the same function to
    the program's leaves."""
    unit = (x.astype(F32) - 1.0) / (0.05 * 3 ** 0.5) * 0.5 + 0.5   # [0, 1]
    if name.endswith(".A_log"):
        return jnp.log(1.0 + 15.0 * unit).astype(x.dtype)
    if name.endswith(".dt_bias"):
        dt = jnp.exp(unit * (math.log(0.1) - math.log(0.001))
                     + math.log(0.001))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(x.dtype)
    if name.endswith("e_score_correction_bias"):
        return (x.astype(F32) - 1.0).astype(x.dtype)
    if name.endswith("conv1d.weight"):
        return x * 16                      # exact in bfloat16
    return x


def bf16_exact(x):
    """Round float32 values to what bfloat16 holds, and stay float32.
    ``weights.leaf_values`` rounds by ``astype(bfloat16).astype(float32)``,
    which the TPU compiler is free to drop (a convert pair it may keep in
    "excess precision"): on the chip ``weights.make_leaf(..., float32)``
    hands back the UNROUNDED draw, up to half a bfloat16 step (0.0039 on
    a gain) off what the program's bfloat16 leaf holds.
    ``reduce_precision`` is not dropped, and changes nothing that is
    rounded already."""
    return jax.lax.reduce_precision(x.astype(F32), exponent_bits=8,
                                    mantissa_bits=7)


def make_leaf(seed, name, shape):
    """The float32 value the reference uses for a leaf: the benchmark's
    bfloat16 value for (seed, name), as float32, then ``shape_leaf``."""
    return shape_leaf(name, bf16_exact(W.make_leaf(seed, name, shape, F32)))


# --------------------------------------------------------------- the math
def short_conv_silu(x, w):
    """x (s, c), w (c, k): y_t = sum_j w[:, j] x_{t-k+1+j}, then SiLU."""
    k = w.shape[1]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    y = sum(xp[j:j + x.shape[0]] * w[:, j] for j in range(k))
    return jax.nn.silu(y)


def delta_rule(q, k, v, a, beta, block=64):
    """The recurrence, one token a step.  q, k, a (s, heads, dk), v
    (s, heads, dv), beta (s, heads) -> o (s, heads, dv).  ``block``
    only sets how many steps' states the backward keeps at a time."""
    s, heads, dk = q.shape
    dv = v.shape[-1]
    pad = -s % block
    xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
          for x in (q, k, v, a, beta)]
    xs = [x.reshape((-1, block) + x.shape[1:]) for x in xs]

    def token(state, x):
        qt, kt, vt, at, bt = x
        state = jnp.exp(at)[..., None] * state
        err = vt - jnp.einsum("hd,hde->he", kt, state, precision=HI)
        state = state + (bt[:, None] * kt)[..., None] * err[:, None, :]
        return state, jnp.einsum("hd,hde->he", qt, state, precision=HI)

    @jax.checkpoint
    def tokens(state, x):
        return jax.lax.scan(token, state, x)

    _, o = jax.lax.scan(tokens, jnp.zeros((heads, dk, dv), F32), tuple(xs))
    return o.reshape(-1, heads, dv)[:s]


def kda_mixer(x, w, cfg, precision):
    la = cfg["linear_attn_config"]
    heads, d = la["num_heads"], la["head_dim"]
    s = x.shape[0]
    mm = lambda t, n: matmul(t, w[n + ".weight"], precision)  # noqa: E731

    def unit(t):
        t = t.reshape(s, heads, d)
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = unit(short_conv_silu(mm(x, "q_proj"), w["q_conv1d.weight"])) * d ** -0.5
    k = unit(short_conv_silu(mm(x, "k_proj"), w["k_conv1d.weight"]))
    v = short_conv_silu(mm(x, "v_proj"), w["v_conv1d.weight"]).reshape(
        s, heads, d)
    f = mm(mm(x, "f_a_proj"), "f_b_proj")
    a = -jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(
        (f + w["dt_bias"]).reshape(s, heads, d))
    beta = jax.nn.sigmoid(mm(x, "b_proj"))
    o = delta_rule(q, k, v, a, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * w["o_norm.weight"]
    gate = jax.nn.sigmoid(mm(mm(x, "g_a_proj"), "g_b_proj"))
    return mm(o.reshape(s, heads * d) * gate, "o_proj")


def mla_mixer(x, w, cfg, precision):
    heads = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r, s = cfg["kv_lora_rank"], x.shape[0]
    q = matmul(x, w["q_proj.weight"], precision).reshape(s, heads, dn + dr)
    c = matmul(x, w["kv_a_proj_with_mqa.weight"], precision)
    c_kv = rms_norm(c[:, :r], w["kv_a_layernorm.weight"], cfg["rms_norm_eps"])
    k_pe = c[:, r:]                               # shared, not rotated
    kv = matmul(c_kv, w["kv_b_proj.weight"], precision).reshape(
        s, heads, dn + dv)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def one(args):                                 # one head at a time
        qh, knh, vh = args
        kh = jnp.concatenate([knh, k_pe], axis=-1)
        sc = jnp.einsum("qd,kd->qk", qh, kh, precision=HI) / np.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("qk,kd->qd", p, vh, precision=HI)

    o = jax.lax.map(jax.checkpoint(one),
                    (q.transpose(1, 0, 2), kv[..., :dn].transpose(1, 0, 2),
                     kv[..., dn:].transpose(1, 0, 2)))
    return matmul(o.transpose(1, 0, 2).reshape(s, heads * dv),
                  w["o_proj.weight"], precision)


def swiglu(x, gate, up, down, precision):
    return matmul(jax.nn.silu(matmul(x, gate, precision))
                  * matmul(x, up, precision), down, precision)


def route(x, w, cfg, precision):
    """(expert ids (s, k), weights (s, k)) over ALL ``num_experts``."""
    scores = jax.nn.sigmoid(matmul(x, w["gate.gate_weight"], precision))
    _, idx = jax.lax.top_k(scores + w["gate.e_score_correction_bias"],
                           cfg["num_experts_per_token"])
    wt = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["moe_renormalize"]:
        wt = wt / jnp.sum(wt, axis=-1, keepdims=True)
    return idx, wt * cfg["routed_scaling_factor"]


def moe_ffn(x, w, cfg, precision):
    """The held experts' part of the routed sum, plus the shared expert."""
    idx, wt = route(x, w, cfg, precision)
    first, count = held(cfg)

    def expert(e):
        share = jnp.sum(jnp.where(idx == first + e, wt, 0.0), axis=-1)
        return share[:, None] * swiglu(
            x, w["experts.gate_proj"][e], w["experts.up_proj"][e],
            w["experts.down_proj"][e], precision)

    y = functools.reduce(jnp.add, [jax.checkpoint(expert)(e)
                                   for e in range(count)])
    if cfg["num_shared_experts"]:
        y = y + swiglu(x, w["shared_expert.gate_proj.weight"],
                       w["shared_expert.up_proj.weight"],
                       w["shared_expert.down_proj.weight"], precision)
    return y


def layer_weights(params, i):
    p = f"model.layers.{i}."
    return {n[len(p):]: a for n, a in params.items() if n.startswith(p)}


def sub(w, prefix):
    return {n[len(prefix):]: a for n, a in w.items() if n.startswith(prefix)}


def layer_forward(x, w, i, cfg, precision):
    """x (s, hidden) -> (s, hidden) through layer ``i``."""
    eps = cfg["rms_norm_eps"]
    mixer = kda_mixer if mixer_kind(cfg, i) == "kda" else mla_mixer
    x = x + mixer(rms_norm(x, w["input_layernorm.weight"], eps),
                  sub(w, "self_attn."), cfg, precision)
    h = rms_norm(x, w["post_attention_layernorm.weight"], eps)
    m = sub(w, "mlp.")
    if i < cfg["first_k_dense_replace"]:
        return x + swiglu(h, m["gate_proj.weight"], m["up_proj.weight"],
                          m["down_proj.weight"], precision)
    return x + moe_ffn(h, m, cfg, precision)


def logits_fn(params, row, cfg, precision="f32"):
    """One sequence's logits (s, vocab)."""
    x = params["model.embed_tokens.weight"][row]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x_, w_, i_=i: layer_forward(x_, w_, i_, cfg, precision))(
            x, layer_weights(params, i))
    x = rms_norm(x, params["model.norm.weight"], cfg["rms_norm_eps"])
    return matmul(x, params["lm_head.weight"], precision)


def loss_fn(params, ids, labels, cfg, precision="f32"):
    """Mean token cross-entropy of a (batch, seq) batch."""
    logits = jax.lax.map(lambda r: logits_fn(params, r, cfg, precision), ids)
    logp = jax.nn.log_softmax(logits.reshape(-1, cfg["vocab_size"]))
    nll = -jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=1)[:, 0]
    return nll.mean()


# --------------------------------------------------------------- training
def all_leaves(cfg, seed):
    """{name: float32 array}: parameters and the seeded buffers."""
    return {n: make_leaf(seed, n, s)
            for n, s in param_specs(cfg) + buffer_specs(cfg)}


def train_reference(cfg, seed, batches, opt, precision="f32"):
    """Follow ``len(batches)`` AdamW steps from the seed's weights (decay
    on every trained leaf, before the Adam rule — the program's AdamW;
    the selection bias is not trained).  Returns the loss of each step,
    the norm of every leaf's FIRST gradient, the first gradient of the
    1-D leaves element by element, and the norm of every leaf's change
    over all the steps."""
    frozen_cfg = _freeze(cfg)
    specs = param_specs(cfg)
    buffers = {n: make_leaf(seed, n, s) for n, s in buffer_specs(cfg)}
    params = {n: make_leaf(seed, n, s) for n, s in specs}
    # both moments live on the HOST between updates: float32 parameters,
    # their gradients and a step's activations at 8,192 tokens leave no
    # room on a 16 GB chip for another 8 bytes a parameter
    m = {n: np.zeros(s, np.float32) for n, s in specs}
    v = {n: np.zeros(s, np.float32) for n, s in specs}
    f = lambda x: jnp.asarray(x, F32)  # noqa: E731
    losses, grad_norm, grad_gains = [], {}, {}
    for t, (ids, labels) in enumerate(batches, start=1):
        loss, grads = _loss_and_grads(
            params, buffers, jnp.asarray(ids, jnp.int32),
            jnp.asarray(labels, jnp.int32), frozen_cfg, precision)
        losses.append(float(loss))
        for n in list(grads):
            g = grads.pop(n)
            if t == 1:
                grad_norm[n] = float(_norm(g))
                if g.ndim == 1:
                    grad_gains[n] = np.asarray(g, np.float32)
            md, vd = _moments(jnp.asarray(m[n]), jnp.asarray(v[n]), g,
                              f(opt["beta1"]), f(opt["beta2"]))
            del g
            params[n] = _apply(params[n], md, vd, f(t), f(opt["lr"]),
                               f(opt["beta1"]), f(opt["beta2"]),
                               f(opt["eps"]), f(opt["weight_decay"]))
            m[n], v[n] = np.asarray(md), np.asarray(vd)
            del md, vd
    delta_norm = {n: float(_diff_norm(params[n], make_leaf(seed, n, s)))
                  for n, s in specs}
    return {"losses": losses, "grad_norm": grad_norm,
            "grad_gains": grad_gains, "delta_norm": delta_norm}


def _freeze(cfg: dict) -> str:
    """A configuration as something hashable (a static jit argument)."""
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _loss_and_grads(params, buffers, ids, labels, frozen_cfg, precision):
    cfg = json.loads(frozen_cfg)
    return jax.value_and_grad(
        lambda p: loss_fn({**p, **buffers}, ids, labels, cfg, precision))(
        params)


@functools.partial(jax.jit, static_argnums=(2, 3))
def logits_jit(leaves, ids, frozen_cfg, precision="f32"):
    cfg = json.loads(frozen_cfg)
    return jax.lax.map(lambda r: logits_fn(leaves, r, cfg, precision), ids)


def model_cfg(config: dict) -> dict:
    """The model's sizes out of a configuration file, under the
    program's names: the published keys as they are, but for the share —
    the file's ``num_experts`` counts the experts HELD here, from
    ``held_experts_first`` on, and ``routed_experts_published`` is the
    router's width."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps",
            "linear_attn_config", "kv_lora_rank", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "mla_use_nope", "first_k_dense_replace", "moe_layer_freq",
            "moe_intermediate_size", "num_experts_per_token",
            "num_shared_experts", "moe_renormalize",
            "moe_router_activation_func", "routed_scaling_factor",
            "num_expert_group", "topk_group", "tie_word_embeddings",
            "kda_gate_rank")
    cfg = {k: config[k] for k in keys if k in config}
    cfg["num_experts"] = config["routed_experts_published"]
    cfg["held_experts"] = (config["held_experts_first"],
                           config["num_experts"])
    return cfg
