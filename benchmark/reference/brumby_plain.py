"""The plain reference of Brumby-14B-Base (``models/brumby.py`` is the
program): the forward pass in straightforward ``jax.numpy`` — float32,
every product at precision "highest", no kernels, no cache, no batching,
no phi and no state.  It imports nothing of the program and takes nothing
the program made: weights come from ``benchmark/weights.py`` by (seed,
leaf name), are KEPT in the bfloat16 they are served in and upcast where
they are multiplied (PERF.md section 6, PR 29), one layer's leaves at a
time.

The layer (``config.json`` of manifestai/Brumby-14B-Base; Buckman, Gelada,
Zhang, arXiv:2507.04239): pre-norm residual blocks, ``x += Mix(RMSNorm(x));
x += SwiGLU(RMSNorm(x))``, RMSNorm eps 1e-6, a final RMSNorm, an untied
head, no bias.  With x' = RMSNorm(x), KV head h of 8, query head i in h's
group of 5, d = 128:

    q_i = rope(RMSNorm_d(W_q x')_i)   k_h = rope(RMSNorm_d(W_k x')_h)   v_h = (W_v x')_h
    log g_h,t = logsigmoid((W_g x'_t)_h)
    a_i,t,j   = exp(sum_{m=j+1..t} log g_h,m) (s q_i,t . k_h,j)^p       j <= t,  p = 2,  s = d^-1/2
    y_i,t     = sum_j a_i,t,j v_h,j / (sum_j a_i,t,j + eps)             Mix = W_o [y_i]_i

computed as written, over the whole sequence in blocks of queries (so
that 6,000 tokens fit): this FIRST form only.  The program runs the same
numbers as a recurrence over a state S_h in R^{D x 128}, D = 8,256.

Departures from what the config states — none; what it does not give
(the configuration file's ``assumed``, each behind its own key): p = 2;
one gate a KV head from a projection without bias; ``q_norm`` /
``k_norm`` a head (a gain of 128, shared by the heads) and rotate-half
rope over the whole head at ``rope_theta``, from the Qwen3 lineage; the
output normalised by the sum of its weights with eps = 1e-6.

``served_gaps`` takes two switches, for the planted faults of
``benchmark/tests/chip_limits_brumby.py`` only: ``power`` (1: the plain
dot product) and ``reset_every`` (128: a query sees no key from before
its own chunk of that many positions, which is a state zeroed at every
chunk boundary).
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
POWER, RETENTION_EPS = 2, 1e-6
QUERY_BLOCK = 512
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "max_position_embeddings", "attention_bias", "rms_norm_eps",
    "rope_theta", "tie_word_embeddings")


def model_cfg(config: dict) -> dict:
    """The model's keys out of a configuration file."""
    return {k: config[k] for k in MODEL_KEYS}


# ------------------------------------------------------------- the shapes
def layer_specs(cfg, i):
    """Layer ``i``'s leaves in the program's order (a layer's own
    parameters before its sublayers'); linear weights are (in, out)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    w = cfg["intermediate_size"]
    p = f"model.layers.{i}."
    return [(p + "input_layernorm.weight", (h,)),
            (p + "self_attn.q_proj.weight", (h, q)),
            (p + "self_attn.k_proj.weight", (h, kv)),
            (p + "self_attn.v_proj.weight", (h, kv)),
            (p + "self_attn.o_proj.weight", (q, h)),
            (p + "self_attn.g_proj.weight", (h, kv // d)),
            (p + "self_attn.q_norm.weight", (d,)),
            (p + "self_attn.k_norm.weight", (d,)),
            (p + "post_attention_layernorm.weight", (h,)),
            (p + "mlp.gate_proj.weight", (h, w)),
            (p + "mlp.up_proj.weight", (h, w)),
            (p + "mlp.down_proj.weight", (w, h))]


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
    """bfloat16, as served."""
    return jnp.bfloat16


def shape_leaf(name, x):
    """The seed's value of a leaf as the model holds it: as drawn.  (The
    gate has no bias, so no function of a leaf alone holds the gates near
    one: with zero-mean weights W_g x' is zero-mean whatever W_g is.)
    Pure ``jax.numpy``: the driver applies the same function to the
    program's leaves."""
    return x


def make_leaf(seed, name, shape):
    return shape_leaf(name, W.make_leaf(seed, name, shape, leaf_dtype(name)))


def group_weights(seed, group):
    return {n: make_leaf(seed, n, s) for n, s in group}


# --------------------------------------------------------------- the math
def rope_tables(theta, head_dim, n):
    inv = 1.0 / float(theta) ** (
        np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    fr = np.outer(np.arange(n, dtype=np.float64), inv)
    return jnp.asarray(np.cos(fr), F32), jnp.asarray(np.sin(fr), F32)


def rope(x, cos, sin):
    """x (s, heads, d), rotate-half over the whole head."""
    half = cos.shape[-1]
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def retention(q, k, v, log_g, power=POWER, reset_every=None,
              eps=RETENTION_EPS, block=QUERY_BLOCK):
    """The layer's first form for ONE sequence: q (s, heads, d), k / v
    (s, kv, d), log_g (s, kv) -> (s, heads, d).  One KV head's group of
    query heads and one block of queries at a time, each against every
    key (the keys after a query masked)."""
    s, heads, d = q.shape
    kvh = k.shape[1]
    n_blocks = s // block
    qg = q.reshape(n_blocks, block, kvh, heads // kvh, d)
    cum = jnp.cumsum(log_g, axis=0)                            # (s, kv)
    j = jnp.arange(s)[None, :]

    def one_head(args):
        qh, kh, vh, ch = args      # (nb, block, g, d), (s, d), (s, d), (s,)

        def one_block(xs):
            qb, i = xs                                         # (block, g, d)
            dots = jnp.einsum("tgd,jd->gtj", qb, kh, precision=HI) \
                / math.sqrt(d)
            seen = j <= i[:, None]
            if reset_every is not None:
                seen &= j >= (i[:, None] // reset_every) * reset_every
            decay = jnp.exp(jnp.where(seen, ch[i][:, None] - ch[None, :],
                                      0.0))
            a = jnp.where(seen, decay * dots ** power, 0.0)    # (g, t, j)
            num = jnp.einsum("gtj,jd->tgd", a, vh, precision=HI)
            den = jnp.sum(a, axis=-1).T[..., None]             # (t, g, 1)
            return num / (den + eps)

        at = jnp.arange(s).reshape(n_blocks, block)
        return jax.lax.map(one_block, (qh, at))                # (nb, b, g, d)

    out = jax.lax.map(one_head, (qg.transpose(2, 0, 1, 3, 4),
                                 k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                                 cum.T))                       # (kv, nb, b, g, d)
    return out.transpose(1, 2, 0, 3, 4).reshape(s, heads, d)


def swiglu(x, w):
    g = jax.nn.silu(matmul(x, w["mlp.gate_proj.weight"].astype(F32)))
    u = matmul(x, w["mlp.up_proj.weight"].astype(F32))
    return matmul(g * u, w["mlp.down_proj.weight"].astype(F32))


# ------------------------------------------------ a layer, over sequences
@functools.partial(jax.jit, static_argnums=(4, 5))
def _qkvg(x, pos, w, tables, d, eps):
    n = x.shape[0]
    a = rms_norm(x, w["input_layernorm.weight"].astype(F32), eps)
    cos, sin = (t[pos] for t in tables)

    def proj(name):
        return matmul(a, w[f"self_attn.{name}_proj.weight"].astype(F32))

    q = rms_norm(proj("q").reshape(n, -1, d),
                 w["self_attn.q_norm.weight"].astype(F32), eps)
    k = rms_norm(proj("k").reshape(n, -1, d),
                 w["self_attn.k_norm.weight"].astype(F32), eps)
    return (rope(q, cos, sin), rope(k, cos, sin), proj("v").reshape(n, -1, d),
            jax.nn.log_sigmoid(proj("g")))


_retention_jit = jax.jit(retention, static_argnums=(4, 5, 6, 7))


@functools.partial(jax.jit, static_argnums=(3,))
def _after_mixer(x, y, w, eps):
    x = x + matmul(y.reshape(x.shape[0], -1),
                   w["self_attn.o_proj.weight"].astype(F32))
    m = rms_norm(x, w["post_attention_layernorm.weight"].astype(F32), eps)
    return x + swiglu(m, w)


def layer_forward(x, pos, bounds, w, cfg, tables, power=POWER,
                  reset_every=None):
    """One layer over the tokens of several sequences laid end to end: x
    (n, hidden) float32, pos (n,) each token's position in its sequence,
    ``bounds`` [(start, end)] the sequences.  The mixer runs a sequence
    at a time (padded to whole query blocks: a pad is after every real
    token and is seen by none), everything else over all the tokens."""
    eps = cfg["rms_norm_eps"]
    q, k, v, lg = _qkvg(x, pos, w, tables, cfg["head_dim"], eps)
    outs = []
    for a, b in bounds:
        n = -(-(b - a) // QUERY_BLOCK) * QUERY_BLOCK
        pad3, pad2 = ((0, n - (b - a)), (0, 0), (0, 0)), ((0, n - (b - a)),
                                                          (0, 0))
        outs.append(_retention_jit(
            jnp.pad(q[a:b], pad3), jnp.pad(k[a:b], pad3),
            jnp.pad(v[a:b], pad3), jnp.pad(lg[a:b], pad2), power,
            reset_every, RETENTION_EPS, QUERY_BLOCK)[:b - a])
    y = jnp.concatenate(outs + [jnp.zeros_like(q[bounds[-1][1]:])])
    return _after_mixer(x, y, w, eps)


def layer_weights(weights, i):
    p = f"model.layers.{i}."
    return {n[len(p):]: a for n, a in weights.items() if n.startswith(p)}


def hidden_states(cfg, seed, sequences, **switches):
    """The final hidden states (before the last norm) of every token of
    ``sequences`` (int arrays), laid end to end, with their bounds."""
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
    tables = rope_tables(cfg["rope_theta"], cfg["head_dim"], max(lens))
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(group_weights(seed, groups[1 + i]), i)
        x = layer_forward(x, jnp.asarray(pos), bounds, w, cfg, tables,
                          **switches)
        del w
    return x, bounds


@functools.partial(jax.jit, static_argnums=(2,))
def _logits(x, w, eps):
    hid = rms_norm(x, w["model.norm.weight"].astype(F32), eps)
    return matmul(hid, w["lm_head.weight"].astype(F32))


def forward_logits(cfg, seed, ids, **switches):
    """Logits (len(ids), vocab) of one sequence: the full forward."""
    x, _ = hidden_states(cfg, seed, [np.asarray(ids, np.int32)], **switches)
    w = group_weights(seed, param_groups(cfg)[-1])
    return _logits(x[:len(ids)], w, cfg["rms_norm_eps"])


def served_gaps(cfg, seed, sequences, **switches):
    """For each (prompt, served) pair of int arrays: the reference's
    logits at every position that chose a served token, reduced to
    ``best logit - served token's logit`` (>= 0; 0 where the served token
    is the reference's own first choice).  A list of float32 arrays, one
    value a served token."""
    fed = [np.concatenate([p, s])[:-1].astype(np.int32)
           for p, s in sequences]
    x, bounds = hidden_states(cfg, seed, fed, **switches)
    w = group_weights(seed, param_groups(cfg)[-1])
    gaps = []
    for (prompt, served), (a, b) in zip(sequences, bounds):
        logits = _logits(x[a + len(prompt) - 1:b], w, cfg["rms_norm_eps"])
        got = jnp.take_along_axis(
            logits, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(jnp.max(logits, axis=-1) - got, np.float32))
    return gaps
