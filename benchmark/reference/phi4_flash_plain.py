"""The plain reference of Phi-4-mini-flash-reasoning
(``models/phi4_flash.py`` is the program): the forward pass in
straightforward ``jax.numpy`` — float32, every product at precision
"highest", the selective scan token by token (``lax.scan``), dense masked
attention in blocks of queries, no kernels, no cache, no slot, no
batching.  It imports nothing of the program and takes nothing the program
made: weights come from ``benchmark/weights.py`` by (seed, leaf name), are
KEPT in the type they are served in and upcast where they are multiplied,
one layer's leaves at a time.

The model (``config.json`` of microsoft/Phi-4-mini-flash-reasoning; Ren et
al., arXiv:2507.06607): layer l of n = 32, pre-norm residual with
LayerNorm (gain and bias, eps 1e-5), ``x += Mixer_l(LN(x)); x +=
W_down(silu(g) * u), [g, u] = W_gate_up LN(x)``; a final LayerNorm; the
head is the embedding.  The mixer by index:

    l = 0, 2, ..., 14    Mamba
    l = 1, 3, ..., 15    differential attention over the last 512 keys
    l = 16               Mamba, whose scan output m is handed on
    l = 17               differential attention, full; its K/V are kept
    l = 18, 20, ..., 30  GMU: W_out(silu(W_in x_t) * m_t)
    l = 19, 21, ..., 31  differential CROSS attention: W_q only, over
                         layer 17's K/V

Mamba-1 (d_inner 5120, d_state 16, d_conv 4, dt_rank 160): ``[u, z] = W_in
x``; ``u <- silu(conv1d_causal(u))`` (depthwise, width 4, bias); ``[dl, B,
C] = W_x u``; ``delta = softplus(W_dt dl + b_dt)``; ``A = -exp(A_log)``;
``h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) B_t^T``; ``m_t = h_t C_t + D
u_t``; the output ``W_out(m_t * silu(z_t))``.

Differential attention (no positions; scores scaled by 64^-1/2; causal):
query heads pair up as (2i, 2i + 1), KV heads as (2j, 2j + 1), j = i // 2:
``A1 = softmax(q_2i k_2j^T)``, ``A2 = softmax(q_2i+1 k_2j+1^T)``, ``V =
[v_2j | v_2j+1]``; ``o_i = (1 - l0) RMSNorm_128(A1 V - lam A2 V)``, ``lam =
exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 l)``; the
20 o_i concatenated go through W_o.

What the config does not give is the configuration file's ``assumed``
(each behind its own key).  ``shape_leaf`` is where the drawn values of
the leaves that are no zero-mean matrix take their published ranges.

``served_gaps`` takes switches, for the planted faults of
``benchmark/tests/chip_limits_phi4_flash.py`` only: ``reset_h_every``
(128: the scan's state zeroed at every chunk boundary), ``drop_tail_every``
(128: the convolution sees zeros before every chunk boundary),
``lam_zero`` (plain attention), ``cross_from`` (15: the cross layers read
layer 15's keys and values) and ``window`` (511).
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
from reference.llama_plain import _mm as matmul  # noqa: E402

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
SUBLN_EPS = 1e-5
#: Mamba-1's sizes by the family's convention (``assumed.mamba``)
EXPAND, D_STATE, D_CONV = 2, 16, 4
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "mb_per_layer",
    "sliding_window", "max_position_embeddings", "layer_norm_eps",
    "hidden_act", "mlp_bias", "lm_head_bias", "tie_word_embeddings")
#: the leaves kept in float32 (``assumed.mamba_init``)
F32_LEAVES = ("mixer.A_log", "mixer.D", "mixer.dt_proj.bias")


def model_cfg(config: dict) -> dict:
    """The model's keys out of a configuration file."""
    return {k: config[k] for k in MODEL_KEYS}


def sizes(cfg):
    """(head width, d_inner, dt_rank)."""
    h = cfg["hidden_size"]
    return h // cfg["num_attention_heads"], EXPAND * h, math.ceil(h / 16)


def mixer(cfg, i):
    half = cfg["num_hidden_layers"] // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    return "sliding" if i < half else "full" if i == half + 1 else "cross"


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# ------------------------------------------------------------- the shapes
def layer_specs(cfg, i):
    """Layer ``i``'s leaves in the program's order (a layer's own
    parameters before its sublayers'); linear weights are (in, out)."""
    h, w = cfg["hidden_size"], cfg["intermediate_size"]
    d, inner, rank = sizes(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    p, kind = f"model.layers.{i}.", mixer(cfg, i)
    m = p + "mixer."
    if kind == "mamba":
        mix = [(m + "conv_weight", (D_CONV, inner)),
               (m + "conv_bias", (inner,)),
               (m + "A_log", (inner, D_STATE)), (m + "D", (inner,)),
               (m + "in_proj.weight", (h, 2 * inner)),
               (m + "x_proj.weight", (inner, rank + 2 * D_STATE)),
               (m + "dt_proj.weight", (rank, inner)),
               (m + "dt_proj.bias", (inner,)),
               (m + "out_proj.weight", (inner, h))]
    elif kind == "gmu":
        mix = [(m + "in_proj.weight", (h, inner)),
               (m + "out_proj.weight", (inner, h))]
    else:
        mix = [(m + f"lambda_{n}", (d,)) for n in ("q1", "k1", "q2", "k2")]
        mix.append((m + "subln_weight", (2 * d,)))
        mix += ([(m + "q_proj.weight", (h, q)), (m + "q_proj.bias", (q,))]
                if kind == "cross" else
                [(m + "qkv_proj.weight", (h, q + 2 * kv)),
                 (m + "qkv_proj.bias", (q + 2 * kv,))])
        mix += [(m + "o_proj.weight", (q, h)), (m + "o_proj.bias", (h,))]
    return ([(p + "input_layernorm.weight", (h,)),
             (p + "input_layernorm.bias", (h,))] + mix
            + [(p + "post_attention_layernorm.weight", (h,)),
               (p + "post_attention_layernorm.bias", (h,)),
               (p + "mlp.gate_up_proj.weight", (h, 2 * w)),
               (p + "mlp.down_proj.weight", (w, h))])


def param_groups(cfg):
    """[(leaf name, shape), ...] per group: embedding, each layer, then the
    final norm (the head is the embedding)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    groups = [[("model.embed_tokens.weight", (v, h))]]
    groups += [layer_specs(cfg, i) for i in range(cfg["num_hidden_layers"])]
    groups.append([("model.final_layernorm.weight", (h,)),
                   ("model.final_layernorm.bias", (h,))])
    return groups


def param_specs(cfg):
    return [s for g in param_groups(cfg) for s in g]


def leaf_dtype(name):
    """bfloat16 as served, but what the scan's decay is made of."""
    return F32 if name.endswith(F32_LEAVES) else jnp.bfloat16


def shape_leaf(name, x):
    """The seed's value of a leaf as the model holds it.  ``weights.py``
    draws a matrix uniform with deviation 0.02 and a vector as 1 + a
    uniform of deviation 0.05; what is neither takes its range here
    (``assumed`` in the configuration file says why each).  Pure
    ``jax.numpy``: the driver applies the same function to the program's
    leaves."""
    dt = x.dtype
    x = x.astype(F32)
    unit = (x - 1.0) / 0.05                 # a vector's draw: deviation 1
    if name.endswith("A_log"):              # A = 1 ... d_state a channel
        x = jnp.log(jnp.arange(1, x.shape[1] + 1, dtype=F32)) + x
    elif name.endswith("dt_proj.bias"):     # delta over [1e-3, 1e-1]
        share = jnp.clip(unit / (2 * 3 ** 0.5) + 0.5, 0.0, 1.0)
        dt0 = jnp.exp(math.log(1e-3) + share * math.log(1e2))
        x = dt0 + jnp.log(-jnp.expm1(-dt0))         # softplus^-1
    elif name.endswith("conv_weight"):      # deviation 0.32, not 0.02
        x = 16.0 * x
    elif name.endswith("x_proj.weight"):    # B and C of order one, as u is
        x = 4.0 * x
    elif ".lambda_" in name:                # deviation 0.1 about zero
        x = 0.1 * unit
    elif name.endswith(".bias") or name.endswith("conv_bias"):
        x = x - 1.0                         # deviation 0.05 about zero
    return x.astype(dt)


def make_leaf(seed, name, shape):
    return shape_leaf(name, W.make_leaf(seed, name, shape, leaf_dtype(name)))


@functools.lru_cache(maxsize=None)
def _group_maker(group):
    """ONE program a group (``weights.leaf_values`` of every leaf of it, as
    ``weights.make_all`` makes the program's): a program a LEAF, which is
    what ``weights.make_leaf`` is, makes 420 of them for this model, and
    loading them from the compile cache was half of the reference's four
    minutes."""
    return jax.jit(lambda key: [W.leaf_values(key, n, s, leaf_dtype(n))
                                for n, s in group])


def group_weights(seed, group):
    group = tuple((n, tuple(s)) for n, s in group)
    made = _group_maker(group)(W.root_key(seed))
    return {n: shape_leaf(n, a) for (n, _), a in zip(group, made)}


# --------------------------------------------------------------- the math
def layer_norm(x, w, name, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w[name + ".weight"]
            .astype(F32) + w[name + ".bias"].astype(F32))


def conv1d_causal(x, w, b, drop_tail_every=None):
    """Depthwise: x (s, c), w (K, c), b (c) -> (s, c);
    ``drop_tail_every``: a position sees nothing from before its own block
    of that many."""
    s, k = x.shape[0], w.shape[0]
    at = jnp.arange(s)
    y = b + w[k - 1] * x
    for back in range(1, k):
        seen = at >= back
        if drop_tail_every is not None:
            seen &= (at % drop_tail_every) >= back
        y = y + w[k - 1 - back] * jnp.where(
            seen[:, None], jnp.roll(x, back, axis=0), 0.0)
    return y


def selective_scan(u, delta, a_log, B, C, d, reset_h_every=None):
    """u / delta (s, c), a_log (c, n), B / C (s, n), d (c) -> m (s, c):
    the recurrence, a token at a time (h held as (n, c): the channels on
    the long axis)."""
    a = -jnp.exp(a_log).T

    def step(h, xs):
        t, ut, dt, bt, ct = xs
        if reset_h_every is not None:
            h = jnp.where(t % reset_h_every == 0, 0.0, h)
        h = jnp.exp(dt[None, :] * a) * h + (dt * ut)[None, :] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0) + d * ut

    _, m = jax.lax.scan(step, jnp.zeros(a.shape, F32),
                        (jnp.arange(u.shape[0]), u, delta, B, C), unroll=8)
    return m


def diff_attention(q, k, v, lam, gain, l0, window=None, block=QUERY_BLOCK):
    """ONE sequence: q (s, H, d), k / v (s, H / 2, d) -> (s, H / 2, 2 d),
    a block of queries at a time against every key."""
    s, heads, d = q.shape
    pairs = k.shape[1] // 2                        # KV pairs; 2 o_i a pair
    q1 = q[:, 0::2].reshape(s // block, block, pairs, 2, d)
    q2 = q[:, 1::2].reshape(s // block, block, pairs, 2, d)
    k1, k2 = k[:, 0::2], k[:, 1::2]                # (s, pairs, d)
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)
    j = jnp.arange(s)[None, :]

    def one_block(xs):
        a, b, i = xs                               # (block, pairs, 2, d)
        seen = j <= i[:, None]
        if window is not None:
            seen &= j > i[:, None] - window

        def side(qs, ks):
            dots = jnp.einsum("tpgd,jpd->pgtj", qs, ks, precision=HI) \
                / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen, dots, -jnp.inf), axis=-1)
            return jnp.einsum("pgtj,jpe->tpge", p, vv, precision=HI)

        o = side(a, k1) - lam * side(b, k2)        # (block, pairs, 2, 2d)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + SUBLN_EPS)
        return (1.0 - l0) * o * gain

    out = jax.lax.map(one_block, (q1, q2,
                                  jnp.arange(s).reshape(s // block, block)))
    return out.reshape(s, heads // 2, 2 * d)


# ------------------------------------------------ a layer, over sequences
def _mamba_seq(x, w, reset_h_every, drop_tail_every):
    """One sequence through a Mamba mixer: the normed input (s, hidden) ->
    (the mixer's output, m)."""
    inner, rank = w["mixer.D"].shape[0], w["mixer.dt_proj.weight"].shape[0]
    uz = matmul(x, w["mixer.in_proj.weight"].astype(F32))
    u, z = uz[:, :inner], uz[:, inner:]
    u = jax.nn.silu(conv1d_causal(
        u, w["mixer.conv_weight"].astype(F32),
        w["mixer.conv_bias"].astype(F32), drop_tail_every))
    dbc = matmul(u, w["mixer.x_proj.weight"].astype(F32))
    delta = jax.nn.softplus(
        matmul(dbc[:, :rank], w["mixer.dt_proj.weight"].astype(F32))
        + w["mixer.dt_proj.bias"].astype(F32))
    m = selective_scan(u, delta, w["mixer.A_log"].astype(F32),
                       dbc[:, rank:rank + D_STATE], dbc[:, rank + D_STATE:],
                       w["mixer.D"].astype(F32), reset_h_every)
    return matmul(m * jax.nn.silu(z),
                  w["mixer.out_proj.weight"].astype(F32)), m


@jax.jit
def _gmu(x, m, w):
    g = jax.nn.silu(matmul(x, w["mixer.in_proj.weight"].astype(F32)))
    return matmul(g * m, w["mixer.out_proj.weight"].astype(F32))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _qkv(x, w, d, cross):
    n = x.shape[0]
    name = "mixer.q_proj" if cross else "mixer.qkv_proj"
    y = matmul(x, w[name + ".weight"].astype(F32)) \
        + w[name + ".bias"].astype(F32)
    if cross:
        return y.reshape(n, -1, d), None, None
    wide = w["mixer.o_proj.weight"].shape[0]
    kv = (y.shape[1] - wide) // 2
    return (y[:, :wide].reshape(n, -1, d),
            y[:, wide:wide + kv].reshape(n, -1, d),
            y[:, wide + kv:].reshape(n, -1, d))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mamba_seqs(xs, w, reset_h_every, drop_tail_every):
    """``_mamba_seq`` over sequences stacked along the first axis, side by
    side: the scan is as many steps as ONE sequence is long (a step of
    the token-by-token recurrence costs the chip the same for ten
    sequences as for one, and ten in turn took four minutes)."""
    return jax.vmap(
        lambda x: _mamba_seq(x, w, reset_h_every, drop_tail_every))(xs)


@functools.partial(jax.jit, static_argnums=(6, 7))
def _attn_seqs(qs, ks, vs, lam, gain, l0, window, block):
    """``diff_attention`` over stacked sequences, one at a time; ``lam``
    and ``l0`` are traced, so the layers share one program a window."""
    return jax.lax.map(
        lambda x: diff_attention(*x, lam, gain, l0, window, block),
        (qs, ks, vs))


def _lam(w, l0, lam_zero):
    if lam_zero:
        return jnp.zeros((), F32)
    dot = lambda a, b: jnp.sum(w[f"mixer.lambda_{a}"].astype(F32)  # noqa: E731
                               * w[f"mixer.lambda_{b}"].astype(F32))
    return jnp.exp(dot("q1", "k1")) - jnp.exp(dot("q2", "k2")) + l0


@jax.jit
def _o_proj(y, w):
    return matmul(y.reshape(y.shape[0], -1),
                  w["mixer.o_proj.weight"].astype(F32)) \
        + w["mixer.o_proj.bias"].astype(F32)


@functools.partial(jax.jit, static_argnums=(3,))
def _after_mixer(x, y, w, eps):
    x = x + y
    a = layer_norm(x, w, "post_attention_layernorm", eps)
    gu = matmul(a, w["mlp.gate_up_proj.weight"].astype(F32))
    half = gu.shape[1] // 2
    return x + matmul(jax.nn.silu(gu[:, :half]) * gu[:, half:],
                      w["mlp.down_proj.weight"].astype(F32))


_norm_jit = jax.jit(layer_norm, static_argnums=(2, 3))


def _per_sequence(bounds, total, fn, *arrays):
    """``fn`` over the sequences' rows of ``arrays``, each padded to the
    longest's whole query blocks and stacked (a pad is after every real
    token and is seen by none: ONE shape a call, whatever the lengths);
    the results laid end to end again, zeros past the last."""
    n = -(-max(b - a for a, b in bounds) // QUERY_BLOCK) * QUERY_BLOCK
    got = fn(*[jnp.stack([jnp.pad(x[a:b], ((0, n - (b - a)),)
                                  + ((0, 0),) * (x.ndim - 1))
                          for a, b in bounds]) for x in arrays])
    end = bounds[-1][1]
    return [jnp.concatenate(
        [g[i, :b - a] for i, (a, b) in enumerate(bounds)]
        + [jnp.zeros((total - end,) + g.shape[2:], F32)])
        for g in (got if isinstance(got, tuple) else (got,))]


def layer_forward(x, bounds, w, cfg, i, carried, reset_h_every=None,
                  drop_tail_every=None, lam_zero=False, cross_from=None,
                  window=None):
    """Layer ``i`` over the tokens of several sequences laid end to end: x
    (n, hidden) float32, ``bounds`` [(start, end)] the sequences.  The
    mixers that look along a sequence run a sequence at a time.
    ``carried``: what earlier layers hand on (``m``; the K/V of the layer
    the cross layers read)."""
    eps, kind = cfg["layer_norm_eps"], mixer(cfg, i)
    d = sizes(cfg)[0]
    half = cfg["num_hidden_layers"] // 2
    kv_layer = half + 1 if cross_from is None else cross_from
    a = _norm_jit(x, w, "input_layernorm", eps)
    if kind == "mamba":
        y, m = _per_sequence(
            bounds, x.shape[0], lambda s: _mamba_seqs(
                s, w, reset_h_every, drop_tail_every), a)
        if i == half:
            carried["m"] = m
    elif kind == "gmu":
        y = _gmu(a, carried["m"], w)
    else:
        q, k, v = _qkv(a, w, d, kind == "cross")
        if kind == "cross":
            k, v = carried["kv"]
        elif i == kv_layer:
            carried["kv"] = (k, v)
        win = None if kind != "sliding" else (
            cfg["sliding_window"] if window is None else window)
        lam, l0 = _lam(w, lambda_init(i), lam_zero), lambda_init(i)
        gain = w["mixer.subln_weight"].astype(F32)
        (o,) = _per_sequence(
            bounds, x.shape[0], lambda *s: _attn_seqs(
                *s, lam, gain, l0, win, QUERY_BLOCK), q, k, v)
        y = _o_proj(o, w)
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
    for (a, b), s in zip(bounds, sequences):
        ids[a:b] = s
    emb = group_weights(seed, groups[0])["model.embed_tokens.weight"]
    x = emb[jnp.asarray(ids)].astype(F32)
    del emb
    carried = {}
    for i in range(cfg["num_hidden_layers"]):
        w = layer_weights(group_weights(seed, groups[1 + i]), i)
        x = layer_forward(x, bounds, w, cfg, i, carried, **switches)
        del w
    return x, bounds


@functools.partial(jax.jit, static_argnums=(3,))
def _logits(x, w, emb, eps):
    hid = layer_norm(x, w, "model.final_layernorm", eps)
    return jnp.einsum("th,vh->tv", hid, emb.astype(F32), precision=HI)


def _head(cfg, seed):
    groups = param_groups(cfg)
    return (group_weights(seed, groups[-1]),
            group_weights(seed, groups[0])["model.embed_tokens.weight"])


def forward_logits(cfg, seed, ids, **switches):
    """Logits (len(ids), vocab) of one sequence: the full forward."""
    x, _ = hidden_states(cfg, seed, [np.asarray(ids, np.int32)], **switches)
    w, emb = _head(cfg, seed)
    return _logits(x[:len(ids)], w, emb, cfg["layer_norm_eps"])


def served_gaps(cfg, seed, sequences, **switches):
    """For each (prompt, served) pair of int arrays: the reference's
    logits at every position that chose a served token, reduced to
    ``best logit - served token's logit`` (>= 0; 0 where the served token
    is the reference's own first choice).  A list of float32 arrays, one
    value a served token."""
    fed = [np.concatenate([p, s])[:-1].astype(np.int32)
           for p, s in sequences]
    x, bounds = hidden_states(cfg, seed, fed, **switches)
    w, emb = _head(cfg, seed)
    gaps = []
    # one shape for every request's logits: the longest's, in whole 256s
    n = -(-max(b - a - len(p) + 1
               for (p, _), (a, b) in zip(sequences, bounds)) // 256) * 256
    for (prompt, served), (a, b) in zip(sequences, bounds):
        rows = x[a + len(prompt) - 1:b]
        logits = _logits(jnp.pad(rows, ((0, n - rows.shape[0]), (0, 0))),
                         w, emb, cfg["layer_norm_eps"])[:rows.shape[0]]
        got = jnp.take_along_axis(
            logits, jnp.asarray(served, jnp.int32)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(jnp.max(logits, axis=-1) - got, np.float32))
    return gaps
