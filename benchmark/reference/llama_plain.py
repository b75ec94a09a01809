"""The plain reference: a Llama-family decoder (rmsnorm, rotary
embedding in split-half layout, grouped-query causal attention, SwiGLU,
untied head), its token cross-entropy and AdamW with decoupled decay,
in straightforward ``jax.numpy`` — float32, matmuls at precision
"highest", no kernels, no cache, no batching tricks.  It imports
nothing of the program and takes nothing the program made: weights come
from ``benchmark/weights.py`` by (seed, leaf name), a layer at a time.

Copied in spirit from ``tools/llama_oracle.py`` (the repo's external
oracle); what differs: weights are made here and not handed in, the
serving check walks the model layer by layer so one layer's float32
weights are resident at a time, attention runs one KV-head group at a
time so the score matrix of a 4k sequence fits, and the training
reference takes gradients one parameter group at a time (embedding, each
layer, head) so that float32 parameters, both moments and ONE group's
gradients are the most the device holds.

``precision="int8"`` is the control, never the reference: every matmul
rounds both operands to int8 with a scale per row (of the activations)
and per column (of the weights) first.  It is the nearest precision
below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
import weights as W  # noqa: E402  (benchmark/weights.py)

F32 = jnp.float32


# ------------------------------------------------------------- the shapes
def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_specs(cfg, i):
    h, inter, d = cfg["hidden_size"], cfg["intermediate_size"], head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    p = f"model.layers.{i}."
    return [(p + "input_layernorm.weight", (h,)),
            (p + "self_attn.q_proj.weight", (h, q)),
            (p + "self_attn.k_proj.weight", (h, kv)),
            (p + "self_attn.v_proj.weight", (h, kv)),
            (p + "self_attn.o_proj.weight", (q, h)),
            (p + "post_attention_layernorm.weight", (h,)),
            (p + "mlp.gate_proj.weight", (h, inter)),
            (p + "mlp.up_proj.weight", (h, inter)),
            (p + "mlp.down_proj.weight", (inter, h))]


def param_groups(cfg):
    """[(leaf name, shape), ...] per group: embedding, each layer, then
    final norm + head.  Linear weights are (in, out)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    groups = [[("model.embed_tokens.weight", (v, h))]]
    groups += [layer_specs(cfg, i) for i in range(cfg["num_hidden_layers"])]
    groups.append([("model.norm.weight", (h,)), ("lm_head.weight", (h, v))])
    return groups


def param_specs(cfg):
    return [s for g in param_groups(cfg) for s in g]


# --------------------------------------------------------------- the math
def rope_tables(cfg, n):
    d = head_dim(cfg)
    inv = 1.0 / (cfg["rope_theta"] ** (np.arange(0, d, 2, dtype=np.float64) / d))
    fr = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(fr), F32), jnp.asarray(np.sin(fr), F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, cos, sin):
    """x (s, heads, d), split-half rotation."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _int8(x, axis):
    """Round to int8 with one scale per slice along ``axis``, and back."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _mm(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


@jax.custom_vjp
def int8_matmul(a, w):
    """(rows, k) @ (k, n) with both operands of every product rounded to
    int8 first — forward and both backward products (w8a8, the
    gradients' operands too)."""
    return _mm(_int8(a, -1), _int8(w, 0))


def _int8_fwd(a, w):
    return int8_matmul(a, w), (a, w)


def _int8_bwd(res, dy):
    a, w = res
    return (_mm(_int8(dy, -1), _int8(w, 1).T),
            _mm(_int8(a, 0).T, _int8(dy, 0)))


int8_matmul.defvjp(_int8_fwd, _int8_bwd)


def matmul(a, w, precision):
    return int8_matmul(a, w) if precision == "int8" else _mm(a, w)


def attention(q, k, v):
    """Causal attention of ONE sequence: q (s, heads, d), k/v (s, kv, d).
    One KV head's group of query heads at a time."""
    s, heads, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, heads // kvh, d).transpose(1, 2, 0, 3)
    mask = jnp.tril(jnp.ones((s, s), bool))

    def one(args):
        qh, kh, vh = args                      # (rep, s, d), (s, d), (s, d)
        sc = jnp.einsum("rqd,kd->rqk", qh, kh,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        return jnp.einsum("rqk,kd->rqd", p, vh,
                          precision=jax.lax.Precision.HIGHEST)

    out = jax.lax.map(jax.checkpoint(one),
                      (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(2, 0, 1, 3).reshape(s, heads * d)


def layer_weights(params, i):
    """Layer ``i``'s leaves under their names inside the layer."""
    p = f"model.layers.{i}."
    return {n[len(p):]: a for n, a in params.items() if n.startswith(p)}


def layer_forward(x, w, cos, sin, cfg, precision):
    """x (s, hidden) -> (s, hidden) through one decoder layer whose
    leaves ``w`` holds under their names inside the layer."""
    p = ""
    s, d, eps = x.shape[0], head_dim(cfg), cfg["rms_norm_eps"]
    a = rms_norm(x, w[p + "input_layernorm.weight"], eps)
    q = matmul(a, w[p + "self_attn.q_proj.weight"], precision)
    k = matmul(a, w[p + "self_attn.k_proj.weight"], precision)
    v = matmul(a, w[p + "self_attn.v_proj.weight"], precision)
    q = rope(q.reshape(s, -1, d), cos, sin)
    k = rope(k.reshape(s, -1, d), cos, sin)
    o = attention(q, k, v.reshape(s, -1, d))
    x = x + matmul(o, w[p + "self_attn.o_proj.weight"], precision)
    m = rms_norm(x, w[p + "post_attention_layernorm.weight"], eps)
    g = jax.nn.silu(matmul(m, w[p + "mlp.gate_proj.weight"], precision))
    u = matmul(m, w[p + "mlp.up_proj.weight"], precision)
    return x + matmul(g * u, w[p + "mlp.down_proj.weight"], precision)


# ---------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnums=(4,))
def _layer_jit(x, w, cos, sin, cfg_items):
    return layer_forward(x, w, cos, sin, dict(cfg_items), "f32")


@functools.partial(jax.jit, static_argnums=(3,))
def _gaps_jit(x, w, tokens, cfg_items):
    cfg = dict(cfg_items)
    hid = rms_norm(x, w["model.norm.weight"], cfg["rms_norm_eps"])
    logits = matmul(hid, w["lm_head.weight"], "f32")
    got = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - got


def _cfg_items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float))))


def _group_weights(seed, group):
    return {n: W.make_leaf(seed, n, s, F32) for n, s in group}


def served_gaps(cfg, seed, sequences, pad_to=512):
    """For each (prompt, served) pair of int arrays: the float32 model's
    logits at every position that chose a served token, reduced to
    ``best logit - served token's logit`` (>= 0; 0 where the served
    token is the reference's own first choice).  Returns a list of
    float32 numpy arrays, one value per served token.  Sequences are
    padded to a multiple of ``pad_to`` so few layer programs compile."""
    items = _cfg_items(cfg)
    groups = param_groups(cfg)
    emb = _group_weights(seed, groups[0])["model.embed_tokens.weight"]
    xs, lens = [], []
    for prompt, served in sequences:
        toks = np.concatenate([prompt, served])[:-1].astype(np.int32)
        n = -(-len(toks) // pad_to) * pad_to
        lens.append(len(toks))
        xs.append(emb[jnp.asarray(np.pad(toks, (0, n - len(toks))))])
    del emb
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(_group_weights(seed, groups[1 + i]), i)
        for j, x in enumerate(xs):
            cos, sin = rope_tables(cfg, x.shape[0])
            xs[j] = _layer_jit(x, w, cos, sin, items)
        del w
    w = _group_weights(seed, groups[-1])
    gaps = []
    for (prompt, served), x, n in zip(sequences, xs, lens):
        rows = x[len(prompt) - 1:n]          # positions that chose a token
        g = _gaps_jit(rows, w, jnp.asarray(served, jnp.int32), items)
        gaps.append(np.asarray(g, np.float32))
    return gaps


# --------------------------------------------------------------- training
def loss_fn(params, ids, labels, cfg, precision):
    """Mean token cross-entropy of a (batch, seq) batch."""
    cos, sin = rope_tables(cfg, ids.shape[1])

    def one(row):
        x = params["model.embed_tokens.weight"][row]
        for i in range(cfg["num_hidden_layers"]):
            x = jax.checkpoint(
                lambda x_, w_: layer_forward(x_, w_, cos, sin, cfg,
                                             precision))(
                x, layer_weights(params, i))
        x = rms_norm(x, params["model.norm.weight"], cfg["rms_norm_eps"])
        return matmul(x, params["lm_head.weight"], precision)

    logits = jax.lax.map(one, ids)
    logp = jax.nn.log_softmax(logits.reshape(-1, cfg["vocab_size"]))
    nll = -jnp.take_along_axis(logp, labels.reshape(-1, 1), axis=1)[:, 0]
    return nll.mean()


@functools.partial(jax.jit, static_argnums=(4, 5))
def _group_grads(sub, rest, ids, labels, cfg_items, precision):
    cfg = dict(cfg_items)
    loss, g = jax.value_and_grad(
        lambda s: loss_fn({**rest, **s}, ids, labels, cfg, precision))(sub)
    return loss, g


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _moments(m, v, g, b1, b2):
    return b1 * m + (1 - b1) * g, b2 * v + (1 - b2) * g * g


@functools.partial(jax.jit, donate_argnums=(0,))
def _apply(p, m, v, step, lr, b1, b2, eps, wd):
    mhat = m / (1 - b1 ** step)
    vhat = v / (1 - b2 ** step)
    return p * (1 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)


_norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32)))))
_diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))


def train_reference(cfg, seed, batches, opt, precision="f32"):
    """Follow ``len(batches)`` AdamW steps from the seed's weights.
    ``batches`` is [(ids, labels)] of int32 (batch, seq) arrays; ``opt``
    has lr, beta1, beta2, eps, weight_decay (decay on every leaf, before
    the Adam rule — the program's AdamW).  Returns the loss of each
    step, the norm of every leaf's FIRST gradient and the norm of every
    leaf's change over all the steps."""
    items = _cfg_items(cfg)
    groups = param_groups(cfg)
    params = {n: W.make_leaf(seed, n, s, F32) for g in groups for n, s in g}
    m = {n: jnp.zeros(s, F32) for g in groups for n, s in g}
    v = {n: jnp.zeros(s, F32) for g in groups for n, s in g}
    f = lambda x: jnp.asarray(x, F32)  # noqa: E731
    losses, grad_norm, grad_gains = [], {}, {}
    for t, (ids, labels) in enumerate(batches, start=1):
        ids, labels = jnp.asarray(ids, jnp.int32), jnp.asarray(labels, jnp.int32)
        for g in groups:
            names = tuple(n for n, _ in g)
            sub = {n: params[n] for n in names}
            rest = {n: a for n, a in params.items() if n not in sub}
            loss, grads = _group_grads(sub, rest, ids, labels, items,
                                       precision)
            for n in names:
                if t == 1:
                    grad_norm[n] = float(_norm(grads[n]))
                    if grads[n].ndim == 1:
                        grad_gains[n] = np.asarray(grads[n], np.float32)
                m[n], v[n] = _moments(m[n], v[n], grads[n],
                                      f(opt["beta1"]), f(opt["beta2"]))
            del grads, sub, rest
        losses.append(float(loss))
        for n in params:
            params[n] = _apply(params[n], m[n], v[n], f(t), f(opt["lr"]),
                               f(opt["beta1"]), f(opt["beta2"]),
                               f(opt["eps"]), f(opt["weight_decay"]))
    delta_norm = {}
    for g in groups:
        for n, s in g:
            delta_norm[n] = float(_diff_norm(params[n],
                                             W.make_leaf(seed, n, s, F32)))
    return {"losses": losses, "grad_norm": grad_norm,
            "grad_gains": grad_gains, "delta_norm": delta_norm}


def worst_leaf_gap(got: dict, ref: dict):
    """The contract's comparison of two sets of per-leaf norms: the gap
    between the program's norm and the reference's, against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger; the worst leaf and its name."""
    med = float(np.median(list(ref.values())))
    worst, where = 0.0, None
    for n, r in ref.items():
        gap = abs(got[n] - r) / max(r, med)
        if gap >= worst:
            worst, where = gap, n
    return worst, where


def worst_gain_diff(got: dict, ref: dict):
    """||got - ref|| / ||ref|| of the first gradient, element by
    element, over the 1-D leaves: the worst leaf and its name.  Unlike
    a gap between two norms it is first-order in rounding noise."""
    worst, where = 0.0, None
    for n, r in ref.items():
        d = float(np.linalg.norm(np.asarray(got[n], np.float64) - r)
                  / np.linalg.norm(r))
        if d >= worst:
            worst, where = d, n
    return worst, where
