"""The plain reference of MiMo-V2-Flash (``models/mimo_v2_flash.py`` is the
program): the published forward pass in straightforward ``jax.numpy`` —
float32, every product at precision "highest", no kernels, no cache, no
batching; attention by an explicit mask in blocks of 512 queries, the sink
as one more column of the scores that is dropped after the softmax; the
experts by a loop over the HELD experts, each over the tokens that chose
it, the router 256 wide.  It imports nothing of the program and takes
nothing the program made: weights come from ``benchmark/weights.py`` by
(seed, leaf name), are KEPT in the bfloat16 they are served in and upcast
where they are multiplied, one layer's leaves at a time.

The layers, as published (``config.json`` of XiaomiMiMo/MiMo-V2-Flash):
pre-norm residual blocks, RMSNorm eps 1e-5, a final RMSNorm, an untied
head.  Attention of layer l (``hybrid_layer_pattern[l]``: 0 full, 1
sliding): 64 query heads; q and k heads 192 wide, v heads 128; 4 KV heads
in a full layer, 8 in a sliding one; no biases; rotary (rotate-half) on the
first 64 channels of every q and k head, theta 5e6 (full) or 1e4 (sliding);
softmax(q k^T / sqrt(192) + mask) v, causal, and in a sliding layer query i
sees key j only if 0 <= i - j < 128; v times ``attention_value_scale``.
Sliding layers: p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij')).  FFN: a
dense SwiGLU of 16,384 in layer 0, then sigmoid scores over 256 experts,
the 8 largest chosen, weights s[idx] / sum(s[idx]) on each expert's OUTPUT,
no shared expert.  Of the 256 experts the reference is given the SAME share
as the program (``held_experts = (first, count)``): a pair that falls on an
expert held elsewhere adds nothing, here as there.

What the config names without giving its form is the configuration file's
``assumed``: where the sink enters; that the value scale multiplies V; which
channels rotate; the score scale; no q/k norm; the selection bias zero;
``b_h`` from the seed (``shape_leaf``); no multi-token-prediction module.

``served_gaps`` takes switches, for the planted faults of
``benchmark/tests/chip_limits_mimo.py`` only (``faults``): ``sink`` (False:
no sink), ``window`` (127), ``value_scale`` (False: v as projected), ``rot``
(96 channels rotated), ``swa_kv_heads`` (4: a sliding layer's first 4 KV
heads serve all 64 query heads), ``top_k`` (7: one expert fewer); and for
the CPU tests ``k_as_wide_as_v`` (the scores over a head's first
``v_head_dim`` channels: what a cache with K as wide as V would hold).
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
from reference.laguna_plain import (  # noqa: E402
    _add_rows, _one_expert, rope, route, swiglu)
from reference.llama_plain import _mm as matmul, rms_norm  # noqa: E402

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
#: the model's keys of a configuration file, as published
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "v_head_dim",
    "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim",
    "swa_v_head_dim", "max_position_embeddings", "layernorm_epsilon",
    "rope_theta", "swa_rope_theta", "partial_rotary_factor",
    "sliding_window", "sliding_window_size", "attention_chunk_size",
    "attention_value_scale", "attention_bias",
    "add_swa_attention_sink_bias", "add_full_attention_sink_bias",
    "hybrid_layer_pattern", "moe_layer_freq", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "norm_topk_prob", "scoring_func", "n_group", "topk_group", "topk_method",
    "routed_scaling_factor", "hidden_act", "tie_word_embeddings")
#: the range the seeded sinks are spread over (``shape_leaf``): at these the
#: sinks of a sliding layer take about a quarter of a query's mass on average
#: at the published widths (0.23-0.28 over 512 positions, two seeds: the
#: configuration file's ``assumed.sink_values`` has the CPU reading)
SINK_RANGE = (3.0, 6.0)


def faults(cfg) -> dict:
    """The faults ``benchmark/tests/chip_limits_mimo.py`` plants in the
    reference, each as the switches of ``served_gaps`` that plant it; at the
    published sizes a window of 127, rotary on 96 channels, the first 4 KV
    heads of a sliding layer, top-7."""
    return {"sink_off": {"sink": False},
            "window_less_1": {"window": cfg["sliding_window"] - 1},
            "value_scale_off": {"value_scale": False},
            "rot_half_head": {"rot": cfg["head_dim"] // 2},
            "swa_kv_half": {"swa_kv_heads":
                            cfg["swa_num_key_value_heads"] // 2},
            "top_k_less_1": {"top_k": cfg["num_experts_per_tok"] - 1}}


def model_cfg(config: dict) -> dict:
    """The model's keys out of a configuration file: the per-layer lists
    cut to the depth it keeps; ``n_routed_experts`` in the file counts the
    experts HELD here (listed in ``reduced``) beside the published count
    and the first held id, so the model's own key goes back to the
    published width and the share becomes ``held_experts``."""
    cfg = {k: config[k] for k in MODEL_KEYS}
    n = cfg["num_hidden_layers"]
    for k in ("hybrid_layer_pattern", "moe_layer_freq"):
        cfg[k] = list(cfg[k])[:n]
    cfg["held_experts"] = (int(config.get("held_experts_first", 0)),
                           int(config["n_routed_experts"]))
    cfg["n_routed_experts"] = int(config.get(
        "routed_experts_published", config["n_routed_experts"]))
    return cfg


# ------------------------------------------------------------- the shapes
def kv_heads(cfg, i):
    return (cfg["swa_num_key_value_heads"] if cfg["hybrid_layer_pattern"][i]
            else cfg["num_key_value_heads"])


def has_sink(cfg, i):
    return bool(cfg["add_swa_attention_sink_bias"]
                if cfg["hybrid_layer_pattern"][i]
                else cfg["add_full_attention_sink_bias"])


def layer_specs(cfg, i):
    """Layer ``i``'s leaves in the program's order (a layer's own
    parameters before its sublayers'); linear weights are (in, out)."""
    h, d, dv = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"]
    heads, kv = cfg["num_attention_heads"], kv_heads(cfg, i)
    p = f"model.layers.{i}."
    out = [(p + "input_layernorm.weight", (h,))]
    if has_sink(cfg, i):
        out.append((p + "self_attn.sinks", (heads,)))
    out += [(p + "self_attn.q_proj.weight", (h, heads * d)),
            (p + "self_attn.k_proj.weight", (h, kv * d)),
            (p + "self_attn.v_proj.weight", (h, kv * dv)),
            (p + "self_attn.o_proj.weight", (heads * dv, h)),
            (p + "post_attention_layernorm.weight", (h,))]
    if not cfg["moe_layer_freq"][i]:
        w = cfg["intermediate_size"]
        return out + [(p + "mlp.gate_proj.weight", (h, w)),
                      (p + "mlp.up_proj.weight", (h, w)),
                      (p + "mlp.down_proj.weight", (w, h))]
    e, w = cfg["held_experts"][1], cfg["moe_intermediate_size"]
    return out + [
        (p + "mlp.experts.gate_proj", (e, h, w)),
        (p + "mlp.experts.up_proj", (e, h, w)),
        (p + "mlp.experts.down_proj", (e, w, h)),
        (p + "mlp.gate.gate_weight", (h, cfg["n_routed_experts"])),
        (p + "mlp.gate.e_score_correction_bias", (cfg["n_routed_experts"],))]


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
    """bfloat16, as served; the selection bias (zero) and the sinks
    float32."""
    return (F32 if name.endswith(("e_score_correction_bias", ".sinks"))
            else jnp.bfloat16)


def shape_leaf(name, x):
    """The seed's value of a leaf as the model holds it: the selection bias
    is held at ZERO; a sink is the benchmark's vector draw (1 + 0.05 z, z
    uniform with deviation 1: within 0.05 sqrt 3 of 1) spread linearly over
    ``SINK_RANGE``; every other leaf as drawn.  Pure ``jax.numpy``: the
    driver applies the same function to the program's leaves."""
    if name.endswith("e_score_correction_bias"):
        return jnp.zeros_like(x)
    if name.endswith(".sinks"):
        lo, hi = SINK_RANGE
        u = (x.astype(F32) - 1.0) / (0.05 * 3 ** 0.5)       # in [-1, 1]
        return ((lo + hi) / 2 + (hi - lo) / 2 * u).astype(x.dtype)
    return x


def make_leaf(seed, name, shape):
    return shape_leaf(name, W.make_leaf(seed, name, shape, leaf_dtype(name)))


def group_weights(seed, group):
    return {n: make_leaf(seed, n, s) for n, s in group}


# --------------------------------------------------------------- the math
def rope_tables(theta, rot, n):
    """(cos, sin) [n, rot / 2] float32: ``rot`` channels of a head rotate."""
    inv = 1.0 / float(theta) ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    fr = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(fr), F32), jnp.asarray(np.sin(fr), F32)


def attention(q, k, v, sinks, window, block=512):
    """Causal attention of ONE sequence by an explicit mask: q (s, heads,
    d), k (s, kv, d), v (s, kv, dv); query i sees key j if j <= i and, with
    a ``window``, i - j < window.  ``sinks`` (heads,) or None: one more
    column of the scores, the same for every query of a head, dropped after
    the softmax (it takes mass and gives no value).  One KV head's query
    heads at a time, ``block`` queries at a time (``s`` a multiple of it).
    With a ``window`` a block of queries is held against the keys it can
    reach and no others (the ``block`` keys beside it and the window's worth
    before them, in whole 128s: the mask does the rest), which is what keeps
    five sliding layers over 7,000 positions from costing what the two full
    ones do.  Returns (s, heads, dv) and the mass the sinks took, (s,
    heads)."""
    s, heads, d = q.shape
    kvh, rep = k.shape[1], heads // k.shape[1]
    qg = q.reshape(s // block, block, kvh, rep, d).transpose(2, 0, 3, 1, 4)
    sk = (None if sinks is None else sinks.astype(F32).reshape(kvh, rep))
    # keys before a block that its queries may reach, and all it is handed
    front = 0 if window is None else -(-window // 128) * 128
    reach = s if window is None else block + front
    k, v = (jnp.pad(x, ((front, 0), (0, 0), (0, 0))) for x in (k, v))

    def one_head(args):
        qh, kh, vh, sh = args       # (nb, rep, block, d), (s, d), (s, dv)

        def one_block(arg):
            qb, i0 = arg                                 # (rep, block, d)
            i = i0 + jnp.arange(block)[:, None]
            if window is None:
                kb, vb, j0 = kh, vh, 0
            else:                       # positions i0 - front .. i0 + block
                kb, vb = (jax.lax.dynamic_slice_in_dim(x, i0, reach)
                          for x in (kh, vh))
                j0 = i0 - front
            j = j0 + jnp.arange(reach)[None, :]
            mask = (j <= i) & (j >= 0)
            if window is not None:
                mask &= i - j < window
            sc = jnp.einsum("rqd,kd->rqk", qb, kb, precision=HI) \
                / math.sqrt(d)
            sc = jnp.where(mask, sc, -jnp.inf)
            if sh is not None:
                col = jnp.broadcast_to(sh[:, None, None], (rep, block, 1))
                p = jax.nn.softmax(jnp.concatenate([sc, col], axis=-1),
                                   axis=-1)
                p, took = p[..., :-1], p[..., -1]
            else:
                p, took = jax.nn.softmax(sc, axis=-1), jnp.zeros((rep, block))
            return jnp.einsum("rqk,kd->rqd", p, vb, precision=HI), took

        return jax.lax.map(one_block,
                           (qh, jnp.arange(s // block) * block))

    heads_in = (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2))
    if sk is None:
        out, took = jax.lax.map(lambda a: one_head(a + (None,)), heads_in)
    else:
        out, took = jax.lax.map(one_head, heads_in + (sk,))
    # (kvh, nb, rep, block, dv) -> (s, heads, dv)
    out = out.transpose(1, 3, 0, 2, 4).reshape(s, heads, v.shape[-1])
    return out, took.transpose(1, 3, 0, 2).reshape(s, heads)


def experts(x, idx, wts, w, first):
    """sum_i w_i E_i(x) over the pairs that fall on the HELD experts
    ``first ..``, by a loop over them, each over the tokens that chose it
    (index lists padded to a power of two so few programs compile).  x (n,
    h) float32; idx, wts (n, k) over the whole expert set."""
    n = x.shape[0]
    idx_np, wts_np = np.asarray(idx), np.asarray(wts, np.float32)
    y = jnp.zeros_like(x)
    for e in range(w["mlp.experts.gate_proj"].shape[0]):
        tok, slot = np.nonzero(idx_np == first + e)
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
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _qkv(x, pos, w, tables, d, dv, eps, value_scale, kv_used):
    n = x.shape[0]
    a = rms_norm(x, w["input_layernorm.weight"].astype(F32), eps)
    cos, sin = (t[pos] for t in tables)

    def proj(name, width):
        return matmul(a, w[f"self_attn.{name}_proj.weight"].astype(F32)
                      ).reshape(n, -1, width)

    k, v = proj("k", d), proj("v", dv) * value_scale
    if kv_used is not None:             # a planted fault: fewer KV heads
        k, v = k[:, :kv_used], v[:, :kv_used]
    return rope(proj("q", d), cos, sin), rope(k, cos, sin), v


_attention_jit = jax.jit(attention, static_argnums=(4, 5))


@functools.partial(jax.jit, static_argnums=(3,))
def _after_attention(x, o, w, eps):
    """The output projection, the residual, the second norm.  Returns (x
    after attention, the normed input of the FFN)."""
    x = x + matmul(o.reshape(x.shape[0], -1),
                   w["self_attn.o_proj.weight"].astype(F32))
    return x, rms_norm(x, w["post_attention_layernorm.weight"].astype(F32),
                       eps)


_dense = jax.jit(lambda m, w: swiglu(m, w, "mlp."))


def layer_forward(x, pos, bounds, w, i, cfg, tables, sink=True,
                  window="config", value_scale=True, swa_kv_heads=None,
                  top_k=None, k_as_wide_as_v=False, pad_to=512):
    """Layer ``i`` over the tokens of several sequences laid end to end:
    x (n, hidden) float32, pos (n,) each token's position in its sequence,
    ``bounds`` [(start, end)] the sequences.  Attention runs a sequence at
    a time, everything else over all the tokens at once.  Returns (x, the
    experts each token chose or None, the mean mass the layer's sinks took
    or None)."""
    sliding = bool(cfg["hybrid_layer_pattern"][i])
    eps = cfg["layernorm_epsilon"]
    q, k, v = _qkv(
        x, pos, w, tables[sliding], cfg["head_dim"], cfg["v_head_dim"], eps,
        float(cfg["attention_value_scale"]) if value_scale else 1.0,
        swa_kv_heads if sliding else None)
    if k_as_wide_as_v:      # the tests' plant: the scores over V's width
        q, k = q[..., :cfg["v_head_dim"]], k[..., :cfg["v_head_dim"]]
    if window == "config":
        window = cfg["sliding_window"]
    window = window if sliding else None
    sinks = w.get("self_attn.sinks") if sink else None
    outs, took = [], []
    for a, b in bounds:
        n = -(-(b - a) // pad_to) * pad_to
        pad = ((0, n - (b - a)), (0, 0), (0, 0))
        o, t = _attention_jit(jnp.pad(q[a:b], pad), jnp.pad(k[a:b], pad),
                              jnp.pad(v[a:b], pad), sinks, window, pad_to)
        outs.append(o[:b - a])
        took.append(t[:b - a])
    o = jnp.concatenate(outs + [jnp.zeros_like(outs[0], shape=(
        x.shape[0] - bounds[-1][1],) + outs[0].shape[1:])])
    mass = (float(jnp.concatenate(took).mean()) if sinks is not None
            else None)
    x, m = _after_attention(x, o, w, eps)
    if not cfg["moe_layer_freq"][i]:
        return x + _dense(m, w), None, mass
    scaling = cfg["routed_scaling_factor"]
    idx, wts = route(m, w, top_k or cfg["num_experts_per_tok"],
                     1.0 if scaling is None else float(scaling))
    return x + experts(m, idx, wts, w, cfg["held_experts"][0]), idx, mass


def layer_weights(weights, i):
    p = f"model.layers.{i}."
    return {n[len(p):]: a for n, a in weights.items() if n.startswith(p)}


def rotary_dim(cfg):
    return int(cfg["partial_rotary_factor"] * cfg["head_dim"]) // 2 * 2


def all_tables(cfg, n, rot=None):
    rot = rot or rotary_dim(cfg)
    return {False: rope_tables(cfg["rope_theta"], rot, n),
            True: rope_tables(cfg["swa_rope_theta"], rot, n)}


def hidden_states(cfg, seed, sequences, rot=None, weights=None, **switches):
    """The final hidden states (before the last norm) of every token of
    ``sequences`` (int arrays), laid end to end, with their bounds, the
    experts chosen in each sparse layer {layer: (n, k) ids} and the mean
    mass the sinks of each sliding layer took {layer: share}.  ``weights``:
    {leaf name: array} in place of the seed's (the tests' uncut layer)."""
    groups = param_groups(cfg)

    def group(g):
        if weights is not None:
            return {n: weights[n] for n, _ in groups[g]}
        return group_weights(seed, groups[g])

    lens = [len(s) for s in sequences]
    ends = np.cumsum(lens)
    bounds = [(int(e - n), int(e)) for e, n in zip(ends, lens)]
    total = -(-int(ends[-1]) // 512) * 512           # few shapes compile
    ids = np.zeros(total, np.int32)
    pos = np.zeros(total, np.int32)
    for (a, b), s in zip(bounds, sequences):
        ids[a:b], pos[a:b] = s, np.arange(b - a)
    emb = group(0)["model.embed_tokens.weight"]
    x = emb[jnp.asarray(ids)].astype(F32)
    del emb
    tables = all_tables(cfg, max(lens), rot)
    chosen, sunk = {}, {}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(group(1 + i), i)
        x, idx, mass = layer_forward(x, jnp.asarray(pos), bounds, w, i, cfg,
                                     tables, **switches)
        if idx is not None:
            chosen[i] = np.asarray(idx)
        if mass is not None:
            sunk[i] = mass
        del w
    return x, bounds, chosen, sunk


@functools.partial(jax.jit, static_argnums=(2,))
def _logits(x, w, eps):
    hid = rms_norm(x, w["model.norm.weight"].astype(F32), eps)
    return matmul(hid, w["lm_head.weight"].astype(F32))


def forward_logits(cfg, seed, ids, weights=None, **switches):
    """Logits (len(ids), vocab) of one sequence: the full forward."""
    x, _, _, _ = hidden_states(cfg, seed, [np.asarray(ids, np.int32)],
                               weights=weights, **switches)
    last = param_groups(cfg)[-1]
    w = ({n: weights[n] for n, _ in last} if weights is not None
         else group_weights(seed, last))
    return _logits(x[:len(ids)], w, cfg["layernorm_epsilon"])


def sink_mass(cfg, seed, sequences):
    """{layer: the mean share of a query's softmax mass its sink took} over
    ``sequences``: what the configuration file's ``assumed`` states of the
    seeded sinks' range."""
    return hidden_states(cfg, seed, sequences)[3]


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
    x, bounds, chosen, _ = hidden_states(cfg, seed, fed, **switches)
    w = group_weights(seed, param_groups(cfg)[-1])
    gaps = []
    for (prompt, served), (a, b) in zip(sequences, bounds):
        logits = _logits(x[a + len(prompt) - 1:b], w,
                         cfg["layernorm_epsilon"])
        got = jnp.take_along_axis(
            logits, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(jnp.max(logits, axis=-1) - got, np.float32))
    return gaps, chosen, bounds
