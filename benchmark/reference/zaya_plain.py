"""The plain reference of ZAYA1-8B (``models/zaya.py`` is the program): the
forward pass in straightforward ``jax.numpy`` — float32, every product at
precision "highest", the two convolutions and the value shift as padded
whole-sequence operations, dense causal attention in blocks of queries, the
experts by a loop over the experts, no cache, no slot, no kernel.  It
imports nothing of the program and is never handed the program's routing:
weights come from ``benchmark/weights.py`` by (seed, leaf name), are KEPT in
the bfloat16 they are served in and upcast where they are multiplied, one
layer's leaves at a time and one expert at a time (4.69 B in float32 would
be 18.8 GB).

The layer (``config.json`` of Zyphra/ZAYA1-8B gives the widths; what it
does not give is the configuration file's ``assumed``).  E hidden, d head
width, H_q query heads over H_k KV heads, G = H_q / H_k:

    x <- Merge_a(x, CCA(RMSNorm(x)));  (y, r_l) = MoE(RMSNorm(x), r_{l-1});
    x <- Merge_m(x, y);   Merge(x, y) = (a_r . x + b_r) + (a_y . y + b_y)

CCA, for token t of a sequence (everything before position 0 is zero):
    z_t = [W_Q x_t; W_K x_t]                         (H_q + H_k heads of d)
    a_t = w0[0] . z_{t-1} + w0[1] . z_t + b0          depthwise, 2 taps
    c_t = W1[0] a_{t-1} + W1[1] a_t + b1              a head a group, 2 taps;
          ONE front pad (of z): a_{-1} = b0
    mq_h = (z^q_h + z^k_g) / 2,  mk_g = mean_{h in g} mq_h
    q = c^q + mq,  k = c^k + mk
    v_t = [W_V1 x_t; W_V2 x_{t-1}]  cut into the H_k heads in that order
    q^ = q / rms_d(q),  k^ = tau_g k / rms_d(k);  rope on the first half of
         each head's lanes (theta 5e6, rotate-half)
    o = softmax(q^ k^T / sqrt(d), causal) v;  CCA = W_O o
The experts: r_l = W_d h + b_d (+ gamma_l . r_{l-1} for l > 0; handed on
as it stands here); p = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l) + b_1) +
b_2)); e* = argmax(p + beta); y = p_{e*} SwiGLU_{e*}(h).

``beta`` (the balancing bias) is no drawn leaf: ``balancing_biases`` sets it
once from the seed by the family's rule (the bias moved against the load)
over a seeded calibration batch, a layer at a time through THIS forward, and
both the program and ``served_gaps`` are handed the result.

``fault`` plants one fault, for ``benchmark/tests/chip_limits_zaya.py``
only (``FAULTS``).
"""
from __future__ import annotations

import functools
import math
import re
import sys
import zlib
from pathlib import Path
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import weights as W  # noqa: E402  (benchmark/weights.py)
from reference.llama_plain import _mm as matmul, rms_norm  # noqa: E402

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "cca_time0", "cca_time1",
    "partial_rotary_factor", "rope_parameters", "layer_types",
    "sliding_window", "num_experts", "num_experts_per_tok",
    "moe_intermediate_size", "router_hidden_size", "hidden_act",
    "rms_norm_eps", "attention_bias", "lm_head_bias", "tie_word_embeddings",
    "max_position_embeddings", "model_type")
#: the faults ``fault=`` plants: the three tails zeroed at every 128th
#: position (``reset<n>``: every n-th); head 1's value the token's own W_V2 x_t; no q-k mean; tau = 1;
#: the previous layer's router state not added; the front pad AFTER the
#: first convolution (a_{-1} = 0)
FAULTS = ("reset128", "no_shift", "no_qk_mean", "tau_one", "no_depth",
          "late_pad")
#: the draws' scales (the configuration file's ``assumed.scales``)
CONV0_SCALE, CONV1_SCALE, ROUTER_SCALE = 16.0, 5.0, 4.0
#: the merges' biases: a twenty-fifth of the vector draw's deviation, a
#: tenth of an embedding row's (at the draw's own 0.05 the 80 bias vectors
#: of 20 layers were most of the final stream: one constant direction)
MERGE_BIAS_SCALE = 0.04
#: the calibration of beta: sequences x tokens, the share of the even load
#: the fullest expert may keep, the step of the rule, its most rounds.
#: MANY short sequences: twenty layers deep 70 % of the stream's variance
#: lies between SEQUENCE means (random weights, attention averaging a
#: context), so a sequence's tokens route alike and a batch balances as
#: many clusters as it has sequences (settled on 8 x 256 the fullest expert
#: of a layer held up to 2.6 of its even share on fresh sequences, on
#: 64 x 64 up to 1.8: the configuration file's ``assumed.beta``)
CALIBRATION = {"sequences": 64, "tokens": 64, "target": 1.25,
               "step": 0.002, "rounds": 4000}


def model_cfg(config: dict) -> dict:
    """The model's keys out of a configuration file, ``layer_types`` cut
    to the depth it keeps."""
    cfg = {k: config[k] for k in MODEL_KEYS}
    cfg["layer_types"] = list(cfg["layer_types"])[:cfg["num_hidden_layers"]]
    return cfg


class Sizes(NamedTuple):
    hq: int
    hk: int
    d: int
    eps: float

    @property
    def lz(self):
        return (self.hq + self.hk) * self.d

    @property
    def vh(self):
        return self.hk * self.d // 2


def sizes(cfg) -> Sizes:
    return Sizes(cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"], float(cfg["rms_norm_eps"]))


# ------------------------------------------------------------- the shapes
def layer_specs(cfg, i):
    """Layer ``i``'s leaves in the program's order; linear weights are
    (in, out)."""
    e, sz = cfg["hidden_size"], sizes(cfg)
    r, n, w = (cfg["router_hidden_size"], cfg["num_experts"],
               cfg["moe_intermediate_size"])
    p = f"model.layers.{i}."

    def merge(at):
        return [(p + at + k, (e,)) for k in
                ("res_scale", "res_bias", "out_scale", "out_bias")]

    gate = [("down_weight", (e, r)), ("down_bias", (r,))] \
        + ([("gamma", (r,))] if i else []) \
        + [("norm_weight", (r,)), ("w1", (r, r)), ("b1", (r,)),
           ("w2", (r, r)), ("b2", (r,)), ("w3", (r, n)),
           ("balancing_bias", (n,))]
    return ([(p + "input_layernorm.weight", (e,)),
             (p + "self_attn.conv0_weight", (2, sz.lz)),
             (p + "self_attn.conv0_bias", (sz.lz,)),
             (p + "self_attn.conv1_weight", (2, sz.hq + sz.hk, sz.d, sz.d)),
             (p + "self_attn.conv1_bias", (sz.lz,)),
             (p + "self_attn.k_scale", (sz.hk,)),
             (p + "self_attn.qkv_proj.weight", (e, sz.lz + 2 * sz.vh)),
             (p + "self_attn.o_proj.weight", (sz.hq * sz.d, e))]
            + merge("attn_merge.")
            + [(p + "post_attention_layernorm.weight", (e,)),
               (p + "mlp.experts.gate_proj", (n, e, w)),
               (p + "mlp.experts.up_proj", (n, e, w)),
               (p + "mlp.experts.down_proj", (n, w, e))]
            + [(p + "mlp.gate." + k, s) for k, s in gate]
            + merge("mlp_merge."))


def param_groups(cfg):
    """[(leaf name, shape), ...] per group: the embedding (also the
    head), each layer, then the final norm."""
    groups = [[("model.embed_tokens.weight",
                (cfg["vocab_size"], cfg["hidden_size"]))]]
    groups += [layer_specs(cfg, i) for i in range(cfg["num_hidden_layers"])]
    groups.append([("model.norm.weight", (cfg["hidden_size"],))])
    return groups


def param_specs(cfg):
    return [s for g in param_groups(cfg) for s in g]


def leaf_dtype(name):
    """bfloat16, as served; float32 the router behind its first matrix
    (``zaya_high_prec``) and tau."""
    f32 = (".mlp.gate." in name and not name.endswith("down_weight")) \
        or name.endswith("k_scale")
    return F32 if f32 else jnp.bfloat16


def shape_leaf(name, x):
    """The seed's value of a leaf as the model holds it (the configuration
    file's ``assumed.scales`` has each with its reason).  The benchmark
    draws a matrix about zero (deviation 0.02) and a vector about one
    (0.05).  Pure ``jax.numpy``: the driver applies the same function to
    the program's leaves."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "balancing_bias":         # set by ``balancing_biases``
        return jnp.zeros_like(x)
    if leaf in ("res_bias", "out_bias"):  # about 0 beside an embedding row
        return (x - jnp.ones_like(x)) * jnp.asarray(MERGE_BIAS_SCALE, x.dtype)
    if leaf in ("conv0_bias", "conv1_bias", "down_bias", "b1", "b2"):
        return x - jnp.ones_like(x)      # a bias: the vector draw less one
    if leaf == "k_scale":                # tau over [0.5, 2], log-uniform
        return jnp.exp2((x - 1.0) / (0.05 * 3 ** 0.5)).astype(x.dtype)
    if leaf == "conv0_weight":
        return x * jnp.asarray(CONV0_SCALE, x.dtype)
    if leaf == "conv1_weight":
        return x * jnp.asarray(CONV1_SCALE, x.dtype)
    if ".mlp.gate." in name and leaf in ("w1", "w2", "w3"):
        return x * jnp.asarray(ROUTER_SCALE, x.dtype)
    return x


def make_leaf(seed, name, shape):
    return shape_leaf(name, W.make_leaf(seed, name, shape, leaf_dtype(name)))


@functools.partial(jax.jit, static_argnums=(2,))
def _make_group(key, folds, specs):
    """``weights.make_leaf`` of every leaf of a group in ONE program that 19 of
    the 20 layers share: ``weights.leaf_values`` with the fold of the
    leaf's name a traced operand (``weights.make_leaf`` compiles a
    program a NAME: 700 of them a pass over the model, which was two
    thirds of the check's time on the chip).  ``specs``: ((the name with
    its layer's number out, shape), ...)."""
    out = []
    for i, (name, shape) in enumerate(specs):
        z = jax.random.uniform(jax.random.fold_in(key, folds[i]), shape, F32,
                               -1.0, 1.0) * 3 ** 0.5
        v = 1.0 + 0.05 * z if len(shape) == 1 else 0.02 * z
        out.append(v.astype(jnp.bfloat16).astype(leaf_dtype(name)))
    return out


def group_weights(seed, group):
    """{leaf name: its value} of one group, bit for bit ``make_leaf``'s
    (``tests/test_zaya.py`` holds them equal)."""
    names = [n for n, _ in group]
    folds = jnp.asarray([zlib.crc32(n.encode()) & 0x7FFFFFFF for n in names],
                        jnp.uint32)
    specs = tuple((re.sub(r"^model\.layers\.\d+\.", "model.layers.*.", n),
                   tuple(s)) for n, s in group)
    made = _make_group(W.root_key(seed), folds, specs)
    # shaped eagerly, as the driver shapes the program's leaves: a fused
    # exp2 would round tau otherwise
    return {n: shape_leaf(n, a) for n, a in zip(names, made)}


# --------------------------------------------------------------- the math
def rope_tables(cfg, n):
    """(cos, sin) [n, rot / 2] of the ``hybrid`` rope entry."""
    params = cfg["rope_parameters"]["hybrid"]
    rot = int(cfg["head_dim"] * params["partial_rotary_factor"])
    inv = 1.0 / float(params["rope_theta"]) ** (
        np.arange(0, rot, 2, dtype=np.float64) / rot)
    fr = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(fr), F32), jnp.asarray(np.sin(fr), F32)


def rope(x, cos, sin):
    """x (n, heads, d): the first 2 x cos.shape[-1] lanes rotated,
    rotate-half over those; the rest pass."""
    half = cos.shape[-1]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def before(x, pos, every=None):
    """x (n, D) of sequences laid end to end -> each token's predecessor
    in ITS sequence: zeros at position 0 (and, for the planted reset, at
    every ``every``-th position)."""
    prev = jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]])
    first = pos == 0
    if every is not None:
        first |= pos % every == 0
    return jnp.where(first[:, None], 0.0, prev)


def attention(q, k, v, block=512):
    """Causal attention of ONE sequence, a block of queries at a time: q
    (s, heads, d), k / v (s, kv, d), s a multiple of ``block``."""
    s, heads, d = q.shape
    kvh = k.shape[1]
    qb = q.reshape(s // block, block, kvh, heads // kvh, d)
    j = jnp.arange(s)[None, :]

    def one(args):
        at, qh = args                               # (block, kv, rep, d)
        i = at * block + jnp.arange(block)[:, None]
        sc = jnp.einsum("qgrd,kgd->grqk", qh, k, precision=HI) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(j <= i, sc, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HI)

    out = jax.lax.map(one, (jnp.arange(s // block), qb))
    return out.reshape(s, heads, d)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _cca_qkv(x, pos, w, tables, sz, fault):
    """Steps 1-6: (q^, k^, v) of every token, (n, heads, d)."""
    n = x.shape[0]
    hq, hk, d, lz, vh = sz.hq, sz.hk, sz.d, sz.lz, sz.vh
    every = int(fault[5:]) if (fault or "").startswith("reset") else None
    h = rms_norm(x, w["input_layernorm.weight"].astype(F32), sz.eps)
    qkv = matmul(h, w["self_attn.qkv_proj.weight"].astype(F32))
    z, v1, v2 = qkv[:, :lz], qkv[:, lz:lz + vh], qkv[:, lz + vh:]
    w0 = w["self_attn.conv0_weight"].astype(F32)
    b0 = w["self_attn.conv0_bias"].astype(F32)
    a = w0[0] * before(z, pos, every) + w0[1] * z + b0
    # ONE front pad, of z: before a sequence's first token a reads b0
    a_prev = before(a - b0, pos, every) + b0
    if fault == "late_pad":
        a_prev = before(a, pos, every)
    w1 = w["self_attn.conv1_weight"].astype(F32)
    c = (jnp.einsum("nhi,hio->nho", a_prev.reshape(n, hq + hk, d), w1[0],
                    precision=HI)
         + jnp.einsum("nhi,hio->nho", a.reshape(n, hq + hk, d), w1[1],
                      precision=HI)
         + w["self_attn.conv1_bias"].astype(F32).reshape(hq + hk, d))
    zq = z[:, :hq * d].reshape(n, hk, hq // hk, d)
    mq = 0.5 * (zq + z[:, hq * d:].reshape(n, hk, 1, d))
    mk = jnp.mean(mq, axis=2)
    if fault == "no_qk_mean":
        mq, mk = jnp.zeros_like(mq), jnp.zeros_like(mk)
    q = c[:, :hq] + mq.reshape(n, hq, d)
    k = c[:, hq:] + mk
    shifted = v2 if fault == "no_shift" else before(v2, pos, every)
    v = jnp.concatenate([v1, shifted], axis=-1).reshape(n, hk, d)
    tau = w["self_attn.k_scale"].astype(F32)
    if fault == "tau_one":
        tau = jnp.ones_like(tau)
    q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + sz.eps)
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + sz.eps) \
        * tau[:, None]
    cos, sin = (t[pos] for t in tables)
    return rope(q, cos, sin), rope(k, cos, sin), v


_attention_jit = jax.jit(attention, static_argnums=(3,))


def merge(x, y, w, at):
    f = lambda k: w[at + k].astype(F32)                     # noqa: E731
    return (f("res_scale") * x + f("res_bias")
            + f("out_scale") * y + f("out_bias"))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _after_attention(x, o, r_prev, w, eps, fault):
    """W_O and the merge, the second norm, and the router up to its
    probabilities.  Returns (x after attention, the experts' normed input,
    p (n, experts), this layer's router state)."""
    x = merge(x, matmul(o.reshape(x.shape[0], -1),
                        w["self_attn.o_proj.weight"].astype(F32)),
              w, "attn_merge.")
    m = rms_norm(x, w["post_attention_layernorm.weight"].astype(F32), eps)
    g = lambda k: w["mlp.gate." + k].astype(F32)            # noqa: E731
    r = matmul(m, g("down_weight")) + g("down_bias")
    if "mlp.gate.gamma" in w and fault != "no_depth":
        r = r + g("gamma") * r_prev
    u = rms_norm(r, g("norm_weight"), eps)
    h = jax.nn.gelu(matmul(u, g("w1")) + g("b1"), approximate=False)
    h = jax.nn.gelu(matmul(h, g("w2")) + g("b2"), approximate=False)
    return x, m, jax.nn.softmax(matmul(h, g("w3")), axis=-1), r


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
    """p_e* E_e*(x) by a loop over the experts, each over the tokens that
    chose it (index lists padded to a power of two so few programs
    compile).  x (n, h) float32; idx, wts (n,)."""
    n = x.shape[0]
    idx_np, wts_np = np.asarray(idx), np.asarray(wts, np.float32)
    y = jnp.zeros_like(x)
    for e in range(w["mlp.experts.gate_proj"].shape[0]):
        tok = np.nonzero(idx_np == e)[0]
        if tok.size == 0:
            continue
        pad = max(16, 1 << (tok.size - 1).bit_length()) - tok.size
        rows = jnp.asarray(np.pad(tok, (0, pad), constant_values=n),
                           jnp.int32)
        ye = _one_expert(x, rows, w["mlp.experts.gate_proj"][e],
                         w["mlp.experts.up_proj"][e],
                         w["mlp.experts.down_proj"][e])
        y = _add_rows(y, rows, ye, jnp.asarray(np.pad(wts_np[tok], (0, pad))))
    return y


_merge_jit = jax.jit(merge, static_argnums=(3,))


def calibrate(p, real, target, step, rounds):
    """The balancing bias of ONE layer by the family's rule over the
    calibration batch's probabilities ``p`` (n, experts): each round the
    bias of an expert over its even share falls by ``step`` and that of
    one under it rises, until the fullest expert holds at most ``target``
    times the even share (of the ``real`` tokens).  Where 100 rounds bring
    the fullest expert no lower the step was too coarse for these
    probabilities: it is halved and the rule goes on from the best bias so
    far, which is what comes back if ``rounds`` run out."""
    p = np.asarray(p, np.float64)[np.asarray(real)]
    n, e = p.shape
    beta = best = np.zeros(e)
    fullest, since = n + 1, 0
    for _ in range(int(rounds)):
        load = np.bincount(np.argmax(p + beta, axis=1), minlength=e)
        if load.max() < fullest:
            fullest, best, since = load.max(), beta, 0
        if fullest <= target * n / e:
            break
        since += 1
        if since > 100:
            step, beta, since = step / 2, best, 0
        beta = beta - step * np.sign(load - n / e)
    return best.astype(np.float32)


def layer_forward(x, r_prev, pos, bounds, w, cfg, tables, beta=None,
                  fault=None, calibration=None, pad_to=512):
    """One layer over the tokens of several sequences laid end to end: x
    (n, hidden) float32, pos (n,) each token's position in its sequence,
    ``bounds`` [(start, end)] the sequences.  Attention runs a sequence at
    a time, everything else over all the tokens at once.  ``beta``: the
    layer's balancing bias (None: the leaf's, zero); ``calibration``: set
    it here from this batch (``calibrate``'s arguments).  Returns (x, this
    layer's router state, the expert each token chose (n, 1), beta)."""
    sz = sizes(cfg)
    q, k, v = _cca_qkv(x, pos, w, tables, sz, fault)
    outs = []
    for a, b in bounds:
        n = -(-(b - a) // pad_to) * pad_to
        pad = ((0, n - (b - a)), (0, 0), (0, 0))
        outs.append(_attention_jit(jnp.pad(q[a:b], pad), jnp.pad(k[a:b], pad),
                                   jnp.pad(v[a:b], pad), pad_to)[:b - a])
    o = jnp.concatenate(outs + [jnp.zeros_like(q[bounds[-1][1]:])])
    x, m, p, r = _after_attention(x, o, r_prev, w, sz.eps, fault)
    if calibration is not None:
        real = np.zeros(x.shape[0], bool)
        for a, b in bounds:
            real[a:b] = True
        beta = calibrate(p, real, **calibration)
    if beta is None:
        beta = w["mlp.gate.balancing_bias"]
    idx = jnp.argmax(p + jnp.asarray(beta, F32), axis=-1)
    wts = jnp.take_along_axis(p, idx[:, None], axis=-1)[:, 0]
    y = experts(m, idx, wts, w)
    return _merge_jit(x, y, w, "mlp_merge."), r, np.asarray(idx)[:, None], \
        beta


def layer_weights(weights, i):
    p = f"model.layers.{i}."
    return {n[len(p):]: a for n, a in weights.items() if n.startswith(p)}


def hidden_states(cfg, seed, sequences, beta=None, fault=None,
                  calibration=None):
    """The final hidden states (before the last norm) of every token of
    ``sequences`` (int arrays), laid end to end, with their bounds, the
    expert chosen in each layer {layer: (n, 1) ids} and the balancing
    biases used (layers, experts).  ``beta`` (layers, experts) or None."""
    assert fault is None or fault in FAULTS or fault[5:].isdigit(), fault
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
    tables = rope_tables(cfg, max(lens))
    pos = jnp.asarray(pos)
    r = jnp.zeros((total, cfg["router_hidden_size"]), F32)
    chosen, betas = {}, []
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(group_weights(seed, groups[1 + i]), i)
        x, r, chosen[i], b = layer_forward(
            x, r, pos, bounds, w, cfg, tables,
            beta=None if beta is None else beta[i], fault=fault,
            calibration=calibration)
        betas.append(np.asarray(b, np.float32))
        del w
    return x, bounds, chosen, np.stack(betas)


def balancing_biases(cfg, seed, **calibration):
    """beta (layers, experts) float32, from the seed alone: a calibration
    batch of seeded ids goes through this forward ONCE, and each layer's
    bias is settled on the batch's router probabilities there (the layers
    behind see the routing the settled bias gives).  Returns (beta, the
    fullest expert's share of the even load a layer)."""
    cal = dict(CALIBRATION, **calibration)
    rng = np.random.default_rng([int(seed), 0xBE7A])
    n = min(int(cal.pop("tokens")), cfg["max_position_embeddings"])
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for _ in range(int(cal.pop("sequences")))]
    _, bounds, chosen, beta = hidden_states(cfg, seed, seqs, calibration=cal)
    real = np.concatenate([np.arange(a, b) for a, b in bounds])
    e = cfg["num_experts"]
    fullest = [np.bincount(chosen[i][real, 0], minlength=e).max() * e
               / len(real) for i in sorted(chosen)]
    return beta, fullest


@functools.partial(jax.jit, static_argnums=(3,))
def _logits(x, norm_w, emb, eps):
    """The last norm and the tied head."""
    return jnp.matmul(rms_norm(x, norm_w.astype(F32), eps),
                      emb.astype(F32).T, precision=HI)


def _head(cfg, seed):
    groups = param_groups(cfg)
    return (group_weights(seed, groups[-1])["model.norm.weight"],
            group_weights(seed, groups[0])["model.embed_tokens.weight"])


def forward_logits(cfg, seed, ids, **switches):
    """Logits (len(ids), vocab) of one sequence: the full forward."""
    x, *_ = hidden_states(cfg, seed, [np.asarray(ids, np.int32)], **switches)
    return _logits(x[:len(ids)], *_head(cfg, seed), cfg["rms_norm_eps"])


def served_gaps(cfg, seed, sequences, block=512, **switches):
    """For each (prompt, served) pair of int arrays: the reference's
    logits at every position that chose a served token, reduced to
    ``best logit - served token's logit`` (>= 0; 0 where the served token
    is the reference's own first choice), ``block`` positions at a time
    (262,272 logits a position).  Returns (a list of float32 arrays, one
    value a served token; {layer: (n, 1)} the expert the reference chose
    for EVERY token fed, the sequences end to end; the sequences' bounds
    there)."""
    fed = [np.concatenate([p, s])[:-1].astype(np.int32)
           for p, s in sequences]
    x, bounds, chosen, _ = hidden_states(cfg, seed, fed, **switches)
    norm_w, emb = _head(cfg, seed)
    gaps = []
    for (prompt, served), (a, b) in zip(sequences, bounds):
        rows = x[a + len(prompt) - 1:b]
        n = -(-rows.shape[0] // block) * block
        rows = jnp.pad(rows, ((0, n - rows.shape[0]), (0, 0)))
        ids = np.pad(np.asarray(served, np.int32), (0, n - len(served)))
        out = []
        for at in range(0, n, block):
            logits = _logits(rows[at:at + block], norm_w, emb,
                             cfg["rms_norm_eps"])
            got = jnp.take_along_axis(
                logits, jnp.asarray(ids[at:at + block])[:, None], axis=-1)
            out.append(np.asarray(jnp.max(logits, axis=-1) - got[:, 0],
                                  np.float32))
        gaps.append(np.concatenate(out)[:len(served)])
    return gaps, chosen, bounds
