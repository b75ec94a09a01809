"""The plain reference of Laguna-XS.2 (``models/laguna.py`` is the
program): the published forward pass in straightforward ``jax.numpy`` —
float32, every product at precision "highest", no kernels, no cache, no
batching; attention by an explicit mask, the experts by a loop over the
experts, each over the tokens that chose it.  It imports nothing of the
program and takes nothing the program made: weights come from
``benchmark/weights.py`` by (seed, leaf name), are KEPT in the bfloat16
they are served in and upcast where they are multiplied (a leaf stored
in bfloat16 holds the rounded value; an ``astype`` pair would be dropped
by the TPU compiler, PERF.md section 6, PR 29), one layer's leaves at a
time and one expert at a time (all of a layer's experts in float32 are
3.2 GB).

The layers, as published (``config.json`` of poolside/Laguna-XS.2):
pre-norm residual blocks, RMSNorm eps 1e-6, a final RMSNorm, an untied
head.  Attention of layer l: ``num_attention_heads_per_layer[l]`` query
heads over 8 KV heads of 128, no biases; rotary on the first
``partial_rotary_factor`` x 128 channels of every head, rotate-half over
those channels, by the layer type's ``rope_parameters`` (yarn in a full
layer: blended frequencies, cos and sin times ``attention_factor``);
softmax(q k^T / sqrt(128) + mask) v, causal, and in a sliding layer query
i sees key j only if 0 <= i - j < 512.  FFN: a dense SwiGLU in layer 0,
then scores over 256 experts, the 8 largest chosen, weights
s[idx] / sum(s[idx]) x 2.5 on each expert's OUTPUT, plus one shared
expert.

Departures from what the config states — none; what it names without
giving its form (the configuration file's ``assumed``):
1. ``gating: true``: one sigmoid gate a head on the attention output,
   g = sigmoid(W_g x'), o = W_o [g_h a_h]_h;
2. the router's scores: sigmoid; the selection bias is zero;
3. no normalisation of q and k.

``served_gaps`` takes two switches, for the planted faults of
``benchmark/tests/chip_limits_laguna.py`` only: ``window`` (None: the
sliding layers see everything) and ``top_k`` (7: one expert fewer).
"""
from __future__ import annotations

import functools
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import weights as W  # noqa: E402  (benchmark/weights.py)
from reference.llama_plain import _mm as matmul, rms_norm  # noqa: E402

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "attention_bias", "rms_norm_eps",
    "num_experts", "num_experts_per_tok", "moe_intermediate_size",
    "shared_expert_intermediate_size", "tie_word_embeddings", "gating",
    "sliding_window", "rope_parameters", "layer_types",
    "moe_apply_router_weight_on_input", "partial_rotary_factor",
    "mlp_layer_types", "moe_routed_scaling_factor",
    "num_attention_heads_per_layer")


def model_cfg(config: dict) -> dict:
    """The model's keys out of a configuration file, the per-layer lists
    cut to the depth it keeps."""
    cfg = {k: config[k] for k in MODEL_KEYS}
    n = cfg["num_hidden_layers"]
    for k in ("layer_types", "mlp_layer_types",
              "num_attention_heads_per_layer"):
        cfg[k] = list(cfg[k])[:n]
    return cfg


# ------------------------------------------------------------- the shapes
def layer_specs(cfg, i):
    """Layer ``i``'s leaves in the program's order (a layer's own
    parameters before its sublayers'); linear weights are (in, out)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads_per_layer"][i] * d
    kv = cfg["num_key_value_heads"] * d
    p = f"model.layers.{i}."
    out = [(p + "input_layernorm.weight", (h,)),
           (p + "self_attn.q_proj.weight", (h, q)),
           (p + "self_attn.k_proj.weight", (h, kv)),
           (p + "self_attn.v_proj.weight", (h, kv)),
           (p + "self_attn.o_proj.weight", (q, h))]
    if cfg["gating"]:
        out.append((p + "self_attn.g_proj.weight", (h, q // d)))
    out.append((p + "post_attention_layernorm.weight", (h,)))

    def swiglu(at, width):
        return [(at + "gate_proj.weight", (h, width)),
                (at + "up_proj.weight", (h, width)),
                (at + "down_proj.weight", (width, h))]

    if cfg["mlp_layer_types"][i] == "dense":
        return out + swiglu(p + "mlp.", cfg["intermediate_size"])
    e, w = cfg["num_experts"], cfg["moe_intermediate_size"]
    return out + swiglu(p + "mlp.shared_expert.",
                        cfg["shared_expert_intermediate_size"]) + [
        (p + "mlp.experts.gate_proj", (e, h, w)),
        (p + "mlp.experts.up_proj", (e, h, w)),
        (p + "mlp.experts.down_proj", (e, w, h)),
        (p + "mlp.gate.gate_weight", (h, e)),
        (p + "mlp.gate.e_score_correction_bias", (e,))]


def param_groups(cfg):
    """[(leaf name, shape), ...] per group: embedding, each layer, then
    final norm + head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    groups = [[("model.embed_tokens.weight", (v, h))]]
    groups += [layer_specs(cfg, i) for i in range(cfg["num_hidden_layers"])]
    groups.append([("model.norm.weight", (h,)), ("lm_head.weight", (h, v))])
    return groups


def param_specs(cfg):
    return [s for g in param_groups(cfg) for s in g]


def leaf_dtype(name):
    """bfloat16, as served; the selection bias float32 (and zero)."""
    return F32 if name.endswith("e_score_correction_bias") else jnp.bfloat16


def shape_leaf(name, x):
    """The seed's value of a leaf as the model holds it: the selection
    bias is a buffer the published weights keep outside training and is
    held at ZERO; every other leaf as drawn.  Pure ``jax.numpy``: the
    driver applies the same function to the program's leaves."""
    if name.endswith("e_score_correction_bias"):
        return jnp.zeros_like(x)
    return x


def make_leaf(seed, name, shape):
    return shape_leaf(name, W.make_leaf(seed, name, shape, leaf_dtype(name)))


def group_weights(seed, group):
    return {n: make_leaf(seed, n, s) for n, s in group}


# --------------------------------------------------------------- the math
def rope_tables(params, head_dim, n):
    """(cos, sin) [n, rot / 2] of one layer type's ``rope_parameters``
    entry; ``rot`` = partial_rotary_factor x head_dim channels rotate.
    yarn: the inverse frequencies are a blend of theta^(-2i/rot) / factor
    and theta^(-2i/rot) by a linear ramp over the channel index between
    the channels that turn beta_fast and beta_slow times within the
    original length (floor and ceiling, clamped to the channels there
    are), and cos and sin are multiplied by attention_factor."""
    rot = int(head_dim * params.get("partial_rotary_factor", 1))
    base = float(params["rope_theta"])
    inv = 1.0 / base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    scale = 1.0
    if params["rope_type"] == "yarn":
        orig = params["original_max_position_embeddings"]

        def channel_turning(times):
            return rot * math.log(orig / (times * 2 * math.pi)) \
                / (2 * math.log(base))

        low = max(math.floor(channel_turning(params["beta_fast"])), 0)
        high = min(math.ceil(channel_turning(params["beta_slow"])), rot - 1)
        if low == high:
            high += 0.001
        keep = 1.0 - np.clip(
            (np.arange(rot // 2, dtype=np.float64) - low) / (high - low),
            0.0, 1.0)
        inv = inv / float(params["factor"]) * (1.0 - keep) + inv * keep
        scale = float(params["attention_factor"])
    fr = np.outer(np.arange(n, dtype=np.float64), inv)
    return (jnp.asarray(np.cos(fr) * scale, F32),
            jnp.asarray(np.sin(fr) * scale, F32))


def rope(x, cos, sin):
    """x (s, heads, d): the first 2 x cos.shape[-1] channels rotated,
    rotate-half over those channels; the rest pass."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def attention(q, k, v, window):
    """Causal attention of ONE sequence by an explicit mask: q (s, heads,
    d), k/v (s, kv, d); query i sees key j if j <= i and, with a
    ``window``, i - j < window.  One KV head's query heads at a time."""
    s, heads, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, heads // kvh, d).transpose(1, 2, 0, 3)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = j <= i
    if window is not None:
        mask &= i - j < window

    def one(args):
        qh, kh, vh = args                      # (rep, s, d), (s, d), (s, d)
        sc = jnp.einsum("rqd,kd->rqk", qh, kh, precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->rqd", p, vh, precision=HI)

    out = jax.lax.map(one, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(s, heads, d)


def swiglu(x, w, at):
    g = jax.nn.silu(matmul(x, w[at + "gate_proj.weight"].astype(F32)))
    u = matmul(x, w[at + "up_proj.weight"].astype(F32))
    return matmul(g * u, w[at + "down_proj.weight"].astype(F32))


@functools.partial(jax.jit, static_argnums=(2, 3))
def route(x, w, k, scaling):
    """(expert ids (n, k), weights (n, k)): sigmoid scores in float32, the
    k largest of score + bias chosen, weights the chosen SCORES over their
    sum, times the routed scaling factor."""
    scores = jax.nn.sigmoid(
        jnp.matmul(x, w["mlp.gate.gate_weight"].astype(F32), precision=HI))
    _, idx = jax.lax.top_k(scores + w["mlp.gate.e_score_correction_bias"], k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, chosen / jnp.sum(chosen, axis=-1, keepdims=True) * scaling


@jax.jit
def _one_expert(x, rows, wg, wu, wd):
    """The expert's SwiGLU of the tokens ``rows`` names (a padded index
    list: a pad names a row past the end and reads zeros)."""
    xe = jnp.take(x, rows, axis=0, mode="fill", fill_value=0.0)
    g = jax.nn.silu(matmul(xe, wg.astype(F32)))
    return matmul(g * matmul(xe, wu.astype(F32)), wd.astype(F32))


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_rows(y, rows, ye, wts):
    return y.at[rows].add(ye * wts[:, None], mode="drop")


def experts(x, idx, wts, w):
    """sum_i w_i E_i(x) by a loop over the experts, each over the tokens
    that chose it (index lists padded to a power of two so few programs
    compile).  x (n, h) float32; idx, wts (n, k)."""
    n = x.shape[0]
    idx_np, wts_np = np.asarray(idx), np.asarray(wts, np.float32)
    y = jnp.zeros_like(x)
    for e in range(w["mlp.experts.gate_proj"].shape[0]):
        tok, slot = np.nonzero(idx_np == e)
        if tok.size == 0:
            continue
        pad = max(16, 1 << (tok.size - 1).bit_length()) - tok.size
        rows = jnp.asarray(np.pad(tok, (0, pad), constant_values=n),
                           jnp.int32)
        ye = _one_expert(x, rows, w["mlp.experts.gate_proj"][e],
                         w["mlp.experts.up_proj"][e],
                         w["mlp.experts.down_proj"][e])
        y = _add_rows(y, rows, ye, jnp.asarray(
            np.pad(wts_np[tok, slot], (0, pad))))
    return y


# ------------------------------------------------ a layer, over sequences
@functools.partial(jax.jit, static_argnums=(4, 5))
def _qkv(x, pos, w, tables, d, eps):
    n = x.shape[0]
    a = rms_norm(x, w["input_layernorm.weight"].astype(F32), eps)
    cos, sin = (t[pos] for t in tables)

    def proj(name):
        return matmul(a, w[f"self_attn.{name}_proj.weight"].astype(F32)
                      ).reshape(n, -1, d)

    gate = (jax.nn.sigmoid(matmul(
        a, w["self_attn.g_proj.weight"].astype(F32)))
        if "self_attn.g_proj.weight" in w else None)
    return rope(proj("q"), cos, sin), rope(proj("k"), cos, sin), \
        proj("v"), gate


_attention_jit = jax.jit(attention, static_argnums=(3,))


@functools.partial(jax.jit, static_argnums=(4,))
def _after_attention(x, o, gate, w, eps):
    """The gate, the output projection, the residual, the second norm,
    and the dense part of the FFN: the dense SwiGLU, or the shared
    expert.  Returns (x after attention, normed input of the FFN, the
    dense part's output)."""
    if gate is not None:
        o = o * gate[:, :, None]
    x = x + matmul(o.reshape(x.shape[0], -1),
                   w["self_attn.o_proj.weight"].astype(F32))
    m = rms_norm(x, w["post_attention_layernorm.weight"].astype(F32), eps)
    at = "mlp." if "mlp.gate_proj.weight" in w else "mlp.shared_expert."
    return x, m, swiglu(m, w, at)


def layer_forward(x, pos, bounds, w, i, cfg, tables, window="config",
                  top_k=None, pad_to=512):
    """Layer ``i`` over the tokens of several sequences laid end to end:
    x (n, hidden) float32, pos (n,) each token's position in its
    sequence, ``bounds`` [(start, end)] the sequences.  Attention runs a
    sequence at a time, everything else over all the tokens at once.
    Returns (x, the experts each token chose or None)."""
    kind, eps = cfg["layer_types"][i], cfg["rms_norm_eps"]
    q, k, v, gate = _qkv(x, pos, w, tables[kind], cfg["head_dim"], eps)
    if window == "config":
        window = cfg["sliding_window"]
    window = window if kind == SLIDING else None
    outs = []
    for a, b in bounds:
        n = -(-(b - a) // pad_to) * pad_to
        pad = ((0, n - (b - a)), (0, 0), (0, 0))
        outs.append(_attention_jit(jnp.pad(q[a:b], pad), jnp.pad(k[a:b], pad),
                                   jnp.pad(v[a:b], pad), window)[:b - a])
    o = jnp.concatenate(outs + [jnp.zeros_like(q[bounds[-1][1]:])])
    x, m, dense = _after_attention(x, o, gate, w, eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + dense, None
    idx, wts = route(m, w, top_k or cfg["num_experts_per_tok"],
                     cfg["moe_routed_scaling_factor"])
    return x + dense + experts(m, idx, wts, w), idx


def layer_weights(weights, i):
    p = f"model.layers.{i}."
    return {n[len(p):]: a for n, a in weights.items() if n.startswith(p)}


def all_tables(cfg, n):
    return {kind: rope_tables(cfg["rope_parameters"][kind], cfg["head_dim"],
                              n) for kind in (FULL, SLIDING)}


def hidden_states(cfg, seed, sequences, **switches):
    """The final hidden states (before the last norm) of every token of
    ``sequences`` (int arrays), laid end to end, with their bounds and
    the experts chosen in each sparse layer {layer: (n, k) ids}."""
    groups = param_groups(cfg)
    lens = [len(s) for s in sequences]
    ends = np.cumsum(lens)
    bounds = [(int(e - n), int(e)) for e, n in zip(ends, lens)]
    total = -(-int(ends[-1]) // 512) * 512           # few shapes compile
    ids = np.zeros(total, np.int32)
    pos = np.zeros(total, np.int32)
    for (a, b), s in zip(bounds, sequences):
        ids[a:b], pos[a:b] = s, np.arange(b - a)
    emb = group_weights(seed, groups[0])["model.embed_tokens.weight"]
    x = emb[jnp.asarray(ids)].astype(F32)
    del emb
    tables = all_tables(cfg, max(lens))
    chosen = {}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(group_weights(seed, groups[1 + i]), i)
        x, idx = layer_forward(x, jnp.asarray(pos), bounds, w, i, cfg,
                               tables, **switches)
        if idx is not None:
            chosen[i] = np.asarray(idx)
        del w
    return x, bounds, chosen


@functools.partial(jax.jit, static_argnums=(2,))
def _logits(x, w, eps):
    hid = rms_norm(x, w["model.norm.weight"].astype(F32), eps)
    return matmul(hid, w["lm_head.weight"].astype(F32))


def forward_logits(cfg, seed, ids, **switches):
    """Logits (len(ids), vocab) of one sequence: the full forward."""
    x, _, _ = hidden_states(cfg, seed, [np.asarray(ids, np.int32)],
                            **switches)
    w = group_weights(seed, param_groups(cfg)[-1])
    return _logits(x[:len(ids)], w, cfg["rms_norm_eps"])


def served_gaps(cfg, seed, sequences, **switches):
    """For each (prompt, served) pair of int arrays: the reference's
    logits at every position that chose a served token, reduced to
    ``best logit - served token's logit`` (>= 0; 0 where the served token
    is the reference's own first choice).  Returns (a list of float32
    arrays, one value a served token; {layer: (n, k)} the experts the
    reference chose for EVERY token fed, the sequences end to end; the
    sequences' bounds there)."""
    fed = [np.concatenate([p, s])[:-1].astype(np.int32)
           for p, s in sequences]
    x, bounds, chosen = hidden_states(cfg, seed, fed, **switches)
    w = group_weights(seed, param_groups(cfg)[-1])
    gaps = []
    for (prompt, served), (a, b) in zip(sequences, bounds):
        logits = _logits(x[a + len(prompt) - 1:b], w, cfg["rms_norm_eps"])
        got = jnp.take_along_axis(
            logits, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(jnp.max(logits, axis=-1) - got, np.float32))
    return gaps, chosen, bounds
