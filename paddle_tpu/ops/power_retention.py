"""Power retention, degree 2: a gated linear attention whose kernel is the
square of the dot product (Buckman, Gelada, Zhang, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239).

Per KV head, with q_i,t (the query heads of the head's group), k_t in
R^d, v_t in R^dv, a log-gate log g_t <= 0 and s = d^-1/2, the layer is

    a_i,t,j = exp(sum_{m=j+1..t} log g_m) (s q_i,t . k_j)^2        j <= t
    y_i,t   = sum_j a_i,t,j v_j / (sum_j a_i,t,j + EPS)

(``retention_attention``: the first form, quadratic in the length) and,
the same numbers as a recurrence over a state of fixed size, with phi an
embedding whose inner product is the squared dot product,
phi(x) . phi(y) = (x . y)^2:

    S_t = g_t S_{t-1} + phi(s^1/2 k_t) v_t^T       z_t = g_t z_{t-1} + phi(s^1/2 k_t)
    y_i,t = phi(s^1/2 q_i,t)^T S_t / (phi(s^1/2 q_i,t)^T z_t + EPS)

(``retention_recurrent``: token by token, the oracle of what a decode
row runs).  A chunk of c tokens (``chunk_update``): the products inside
the chunk in the first form, masked and decayed; the part from before
the chunk as phi(Q) S_prev decayed to each position; S and z carried to
the chunk's end.  Gates, phi, S and z are float32.

**phi and the state's layout.**  The symmetric power embedding of degree
2 has D = d (d + 1) / 2 entries: the d squares and sqrt(2) x_a x_b for
a < b (``phi_sym``; 8,256 at d = 128, which is 64.5 lane tiles).  What
is STORED is the same numbers as d / 2 + 1 whole lane tiles
(``phi``): tile o holds w_o x_a x_((a + o) mod d) for a = 0..d-1 — a
lane rotation and a product — with w_0 = 1, w_o = sqrt(2) for
0 < o < d / 2, and w_(d/2) = 1: the pairs half-way round occur twice in
their tile, each at weight 1, which is the one pair at sqrt(2).  So
phi(x) . phi(y) = (x . y)^2 exactly, no tile is padded, and 8,320
columns hold 8,256 distinct numbers (64 twice).

A sequence's state of one layer is ``[kv_heads, R, Dp]`` float32 with
Dp = (d / 2 + 1) d on the lane axis and R = dv + 1 rounded up to 8 on
the sublane axis: rows 0..dv-1 are S transposed (a row a value channel),
row dv is z, the rest zero.  z rides as a value channel that is always
one (``v' = [v, 1, 0..]``), so one update and one readout serve both.

**Against a pool of slots** (``retention_step``, what a retention layer
of the paged engine calls through ``_TracedPagedContext.retain``): the
pool ``[slots + 1, kv_heads, R, Dp]`` holds a slot a sequence, the last
one scratch for rows that are pad.  A ragged step's rows are of two
kinds.  A row of one token takes the one-token form: on a TPU the Pallas
kernel ``ops/pallas/retention_state.py::retention_decode`` aliased onto
the pool (a slot's blocks are read, updated on the vector unit and
written back, real rows only); elsewhere a gather, the update and a
scatter.  A row of several tokens (a prefill chunk; ``chunk_rows`` names
them, a static few) takes the chunk form: on a TPU ``retention_chunk``
does what touches the state (phi(Q) S_prev and the update, phi made in
VMEM, the slot read and written in place) and the chunk's own scores stay
here; elsewhere the chunk form in XLA against the slot read out of the
pool, its new state written by ``state_put``.  A row whose context is
empty starts from zero whatever its slot held: that is how a slot is
zeroed when taken.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from .pallas import retention_state as _pallas

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
EPS = 1e-6
CHUNK = 64


def sym_dim(d: int) -> int:
    """Entries of the symmetric power embedding of degree 2."""
    return d * (d + 1) // 2


def store_dim(d: int) -> int:
    """Columns phi is stored in: d / 2 + 1 lane tiles of d."""
    return (d // 2 + 1) * d


def state_rows(dv: int) -> int:
    """Rows of a head's state: dv value channels, z, rounded up to 8."""
    return -(-(dv + 1) // 8) * 8


def state_shape(kv_heads: int, d: int, dv: int):
    return (kv_heads, state_rows(dv), store_dim(d))


def state_bytes_symmetric(kv_heads: int, d: int, dv: int) -> int:
    """Bytes of one sequence's state of one layer as the equations have
    it: S in R^{D x dv} and z in R^D, float32, D the symmetric count —
    what is counted whatever is stored."""
    return kv_heads * sym_dim(d) * (dv + 1) * 4


def phi_sym(x):
    """The symmetric power embedding of degree 2, [..., d] ->
    [..., d (d + 1) / 2] float32: the squares, then sqrt(2) x_a x_b for
    a < b."""
    x = x.astype(F32)
    d = x.shape[-1]
    a, b = jnp.triu_indices(d, 1)
    return jnp.concatenate(
        [x * x, math.sqrt(2.0) * x[..., a] * x[..., b]], axis=-1)


def _tile_weights(d: int):
    w = [1.0] + [math.sqrt(2.0)] * (d // 2 - 1) + [1.0]
    return jnp.asarray(w, F32)


def phi(x):
    """phi as it is stored, [..., d] -> [..., (d / 2 + 1) d] float32:
    tile o is w_o x * roll(x, o) (module docstring)."""
    x = x.astype(F32)
    d = x.shape[-1]
    assert d % 2 == 0, "phi's tiles pair a with a + d / 2: d must be even"
    tiles = jnp.stack([x * jnp.roll(x, -o, axis=-1)
                       for o in range(d // 2 + 1)], axis=-2)
    tiles = tiles * _tile_weights(d)[:, None]
    return tiles.reshape(x.shape[:-1] + (store_dim(d),))


def augment(v):
    """v' = [v, 1, 0..]: [..., dv] -> [..., R] float32."""
    dv = v.shape[-1]
    r = state_rows(dv)
    one = jnp.ones(v.shape[:-1] + (1,), F32)
    pad = jnp.zeros(v.shape[:-1] + (r - dv - 1,), F32)
    return jnp.concatenate([v.astype(F32), one, pad], axis=-1)


def _scaled(x):
    """s^1/2 x in float32, s = d^-1/2."""
    return x.astype(F32) * x.shape[-1] ** -0.25


# ------------------------------------------------------ whole sequences
def retention_attention(q, k, v, log_g):
    """The first form over one whole sequence: q [T, Hq, d], k [T, Hk, d],
    v [T, Hk, dv], log_g [T, Hk] -> [T, Hq, dv] float32."""
    t, hq, d = q.shape
    hk = k.shape[1]
    qg = _scaled(q).reshape(t, hk, hq // hk, d)
    dots = jnp.einsum("thgd,jhd->hgtj", qg, _scaled(k), precision=_HI)
    cum = jnp.cumsum(log_g.astype(F32), axis=0)               # [T, Hk]
    decay = cum.T[:, :, None] - cum.T[:, None, :]             # [Hk, t, j]
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    a = jnp.where(mask, jnp.exp(jnp.where(mask, decay, 0.0))[:, None]
                  * dots * dots, 0.0)                         # [Hk, g, t, j]
    num = jnp.einsum("hgtj,jhv->thgv", a, v.astype(F32), precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]   # [t, Hk, g, 1]
    return (num / (den + EPS)).reshape(t, hq, v.shape[-1])


def retention_recurrent(q, k, v, log_g, state=None):
    """The recurrence token by token.  Returns (y [T, Hq, dv], the state
    after the last token [Hk, R, Dp])."""
    t, hq, d = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    if state is None:
        state = jnp.zeros(state_shape(hk, d, dv), F32)

    def step(s, x):
        qt, kt, vt, lg = x
        s = (jnp.exp(lg)[:, None, None] * s
             + augment(vt)[:, :, None] * phi(_scaled(kt))[:, None, :])
        fq = phi(_scaled(qt)).reshape(hk, hq // hk, -1)
        out = jnp.einsum("hgd,hrd->hgr", fq, s, precision=_HI)
        y = out[..., :dv] / (out[..., dv:dv + 1] + EPS)
        return s, y.reshape(hq, dv)

    state, y = lax.scan(step, state, (q, k, v, log_g.astype(F32)))
    return y, state


def chunk_update(q, k, v, log_g, state, n):
    """One chunk of one KV head against the state before it: q [c, G, d]
    (the head's group of query heads), k [c, d], v [c, dv], log_g [c],
    state [R, Dp], ``n`` (traced) the tokens that are real, from the
    front.  Returns (y [c, G, dv], the state after token n - 1); y past
    n is finite and meaningless."""
    c, grp, d = q.shape
    dv = v.shape[-1]
    real = jnp.arange(c) < n
    lg = jnp.where(real, log_g.astype(F32), 0.0)
    cum = jnp.cumsum(lg)                                       # [c]
    qs, ks = _scaled(q), jnp.where(real[:, None], _scaled(k), 0.0)
    va = jnp.where(real[:, None], augment(v), 0.0)             # [c, R]
    # inside the chunk: the first form, masked and decayed
    dots = jnp.einsum("tgd,jd->gtj", qs, ks, precision=_HI)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(causal, cum[:, None] - cum[None, :], 0.0))
    a = jnp.where(causal, decay * dots * dots, 0.0)            # [G, t, j]
    inside = jnp.einsum("gtj,jr->tgr", a, va, precision=_HI)
    # from before the chunk: phi(Q) S_prev decayed to each position
    fq = phi(qs.reshape(c * grp, d))                           # [c G, Dp]
    before = jnp.matmul(fq, state.T, precision=_HI).reshape(c, grp, -1)
    out = inside + jnp.exp(cum)[:, None, None] * before        # [c, G, R]
    y = out[..., :dv] / (out[..., dv:dv + 1] + EPS)
    # S and z carried to the chunk's end (a pad's key is zero, its
    # log-gate zero: the end is after token n - 1)
    fk = phi(ks) * jnp.exp(cum[-1] - cum)[:, None]             # [c, Dp]
    state = jnp.exp(cum[-1]) * state + jnp.matmul(va.T, fk, precision=_HI)
    return y, state


def retention_chunked(q, k, v, log_g, state=None, chunk=CHUNK):
    """The chunk form over one whole sequence in chunks of ``chunk``
    tokens, the last one ragged.  Shapes as ``retention_recurrent``."""
    t, hq, d = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    if state is None:
        state = jnp.zeros(state_shape(hk, d, dv), F32)
    pad = -t % chunk
    n_chunks = (t + pad) // chunk

    def cut(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((n_chunks, chunk) + x.shape[1:])

    qc = cut(q).reshape(n_chunks, chunk, hk, hq // hk, d)
    lens = jnp.minimum(chunk, t - jnp.arange(n_chunks) * chunk)
    heads = jax.vmap(chunk_update, in_axes=(1, 1, 1, 1, 0, None),
                     out_axes=(1, 0))

    def step(s, x):
        qx, kx, vx, lx, n = x
        y, s = heads(qx, kx, vx, lx, s, n)
        return s, y

    state, y = lax.scan(step, state, (qc, cut(k), cut(v), cut(log_g), lens))
    return y.reshape(n_chunks * chunk, hq, dv)[:t], state


# ------------------------------------------------ against a pool of slots
def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _decode_xla(pool, slots, active, fresh, q, k, v, log_g):
    """The one-token form by a gather, the update and a scatter: every
    row reads its slot, a row that is not ``active`` writes the scratch
    slot (the pool's last) and its own stays as it was."""
    b, hq, d = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    scratch = pool.shape[0] - 1
    s = jnp.where(fresh[:, None, None, None], 0.0, pool[slots])
    s = (jnp.exp(log_g.astype(F32))[:, :, None, None] * s
         + augment(v)[:, :, :, None] * phi(_scaled(k))[:, :, None, :])
    fq = phi(_scaled(q)).reshape(b, hk, hq // hk, -1)
    out = jnp.einsum("bhgd,bhrd->bhgr", fq, s, precision=_HI)
    y = out[..., :dv] / (out[..., dv:dv + 1] + EPS)
    pool = pool.at[jnp.where(active, slots, scratch)].set(s)
    return y.reshape(b, hq, dv), pool


def _decode_pallas(pool, slots, active, fresh, q, k, v, log_g,
                   interpret=False):
    """The one-token form through ``retention_decode``: the active rows
    are brought to the front (the kernel skips what follows them), phi of
    the rows' one query a head and one key is made here (25 MB at 16
    rows: the state is 580), and the kernel does what touches the
    state."""
    b, hq, d = q.shape
    hk, dv = k.shape[1], v.shape[-1]
    grp, r = hq // hk, state_rows(dv)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    fq = phi(_scaled(q)).reshape(b, hk, grp, -1)
    fk = phi(_scaled(k))[:, :, None, :]
    qk = jnp.concatenate(
        [fq, fk, jnp.zeros((b, hk, 8 - grp - 1, fq.shape[-1]), F32)], axis=2)
    lanes = jnp.ones((1, 1, 1, 128), F32)
    aux = jnp.concatenate(
        [augment(v)[..., None] * lanes,
         jnp.exp(log_g.astype(F32))[:, :, None, None] * lanes,
         jnp.zeros((b, hk, 7, 128), F32)], axis=2)      # [b, hk, R + 8, 128]
    out, pool = _pallas.retention_decode(
        pool, slots[order], fresh[order].astype(jnp.int32),
        jnp.sum(active).astype(jnp.int32).reshape(1), qk[order], aux[order],
        group=grp, interpret=interpret)
    out = out[jnp.argsort(order)][:, :, :grp, :]               # [b, hk, G, R]
    y = out[..., :dv] / (out[..., dv:dv + 1] + EPS)
    return y.reshape(b, hq, dv), pool


def decode_rows(pool, slots, active, fresh, q, k, v, log_g,
                interpret=False):
    """The one-token form for a step's rows against their slots: q [b,
    Hq, d], k [b, Hk, d], v [b, Hk, dv], log_g [b, Hk]; ``slots`` [b] the
    rows' slots, ``active`` [b] which rows run (the others' slots stay
    untouched and their y is meaningless), ``fresh`` [b] which start from
    zero.  Returns (y [b, Hq, dv] float32, the pool)."""
    grp = q.shape[1] // k.shape[1]
    lane_whole = pool.shape[-1] % 128 == 0 and grp < 8
    if lane_whole and (_use_pallas() or interpret):
        return _decode_pallas(pool, slots, active, fresh, q, k, v, log_g,
                              interpret=interpret)
    return _decode_xla(pool, slots, active, fresh, q, k, v, log_g)


def _chunk_rows(pool, slots, fresh, lens, q, k, v, log_g):
    """The chunk form for a few rows, each against its slot read out of
    the pool: q [C, c, Hq, d], k [C, c, Hk, d], v [C, c, Hk, dv], log_g
    [C, c, Hk], ``lens`` [C] the rows' real tokens (0: no such row, the
    work is skipped).  Returns (y [C, c, Hq, dv], states [C, Hk, R, Dp])."""
    n_rows, c, hq, d = q.shape
    hk, dv = k.shape[2], v.shape[-1]
    heads = jax.vmap(chunk_update, in_axes=(1, 1, 1, 1, 0, None),
                     out_axes=(1, 0))
    ys, states = [], []
    for i in range(n_rows):
        def run(i=i):
            s = lax.dynamic_index_in_dim(pool, slots[i], 0, keepdims=False)
            s = jnp.where(fresh[i], 0.0, s)
            y, s = heads(q[i].reshape(c, hk, hq // hk, d), k[i], v[i],
                         log_g[i], s, lens[i])
            return y.reshape(c, hq, dv), s

        def skip():
            return (jnp.zeros((c, hq, dv), F32),
                    jnp.zeros(pool.shape[1:], F32))

        y, s = lax.cond(lens[i] > 0, run, skip)
        ys.append(y)
        states.append(s)
    return jnp.stack(ys), jnp.stack(states)


def _chunk_rows_pallas(pool, slots, fresh, lens, q, k, v, log_g,
                       interpret=False):
    """The chunk form for a few rows with what touches the state in
    ``retention_chunk`` (phi made in VMEM, the slot read and written in
    place) and the chunk's own masked, decayed scores here.  Shapes as
    ``_chunk_rows``; returns (y [C, c, Hq, dv], the pool)."""
    n_rows, c, hq, d = q.shape
    hk, dv = k.shape[2], v.shape[-1]
    grp = hq // hk
    cp = -(-c // 128) * 128                   # whole MXU passes of tokens
    real = jnp.arange(c)[None, :] < lens[:, None]               # [C, c]
    lg = jnp.where(real[..., None], log_g.astype(F32), 0.0)
    cum = jnp.cumsum(lg, axis=1)                                # [C, c, Hk]
    qs = _scaled(q).reshape(n_rows, c, hk, grp, d)
    ks = jnp.where(real[..., None, None], _scaled(k), 0.0)
    va = jnp.where(real[..., None, None], augment(v), 0.0)      # [C, c, Hk, R]
    # inside the chunk: the first form, masked and decayed
    dots = jnp.einsum("nthgd,njhd->nhgtj", qs, ks, precision=_HI)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    ch = cum.transpose(0, 2, 1)                                 # [C, Hk, c]
    decay = jnp.exp(jnp.where(causal, ch[..., :, None] - ch[..., None, :],
                              0.0))
    a = jnp.where(causal, decay[:, :, None] * dots * dots, 0.0)
    inside = jnp.einsum("nhgtj,njhr->nthgr", a, va, precision=_HI)
    # what touches the state
    pad = ((0, 0), (0, cp - c)) + ((0, 0),) * 3
    q_k = jnp.pad(qs, pad).transpose(0, 2, 1, 3, 4).reshape(
        n_rows, hk, cp * grp, d)
    k_k = jnp.pad(ks, pad[:4]).transpose(0, 2, 1, 3)           # [C,Hk,cp,d]
    to_end = jnp.exp(cum[:, -1:, :] - cum)                      # [C, c, Hk]
    vt = jnp.pad(va * to_end[..., None], pad[:4]).transpose(0, 2, 3, 1)
    gamma = jnp.exp(cum[:, -1, :])[..., None, None] \
        * jnp.ones((1, 1, 8, 128), F32)
    there = lens > 0
    num, den, pool = _pallas.retention_chunk(
        pool, slots, fresh.astype(jnp.int32),
        jnp.sum(there).astype(jnp.int32).reshape(1), q_k, k_k, vt, gamma,
        dv=dv, interpret=interpret)
    num = num.reshape(n_rows, hk, cp, grp, dv)[:, :, :c].transpose(
        0, 2, 1, 3, 4)
    den = jnp.sum(den, axis=-1).reshape(n_rows, hk, cp, grp)[:, :, :c] \
        .transpose(0, 2, 1, 3)
    grow = jnp.exp(cum)[..., None]                              # [C, c, Hk, 1]
    top = inside[..., :dv] + grow[..., None] * num
    bottom = inside[..., dv] + grow * den
    y = top / (bottom[..., None] + EPS)
    return y.reshape(n_rows, c, hq, dv), pool


def state_put(pool, states, slots):
    """``pool[slots[i]] = states[i]`` for a few whole states (in place on
    a donated pool)."""
    for i in range(states.shape[0]):
        pool = lax.dynamic_update_index_in_dim(pool, states[i], slots[i], 0)
    return pool


@functools.partial(jax.jit, static_argnames=("span", "interpret"))
def retention_step(pool, slots, ctx_lens, q_lens, row_off, chunk_rows,
                   q, k, v, log_g, span, interpret=False):
    """One layer's retention for a ragged step against the pool.

    ``q`` [T, Hq, d], ``k`` [T, Hk, d], ``v`` [T, Hk, dv], ``log_g`` [T,
    Hk]: the step's tokens packed along one axis, row ``r``'s
    ``q_lens[r]`` tokens from ``row_off[r]`` on (None: the axis is the
    (rows, ``span``) rectangle, row-major).  ``slots`` [rows]: a row's
    slot, the pool's last for a pad row.  ``ctx_lens`` [rows]: tokens
    already in the row's state (0: it starts from zero).  ``chunk_rows``
    [C] (C static): the rows of several tokens, -1 where there are
    fewer; every other row holds one token.  Returns (y [T, Hq, dv]
    float32 — finite and meaningless at pad positions — and the pool).
    Jitted: a program's layers call it at the same shapes and share one
    traced and lowered body."""
    rows = slots.shape[0]
    t_all = q.shape[0]
    scratch = pool.shape[0] - 1
    first = (row_off if row_off is not None
             else jnp.arange(rows, dtype=jnp.int32) * span)
    fresh = ctx_lens == 0
    in_chunk = jnp.zeros(rows, bool)
    if chunk_rows.shape[0]:
        in_chunk = in_chunk.at[jnp.where(chunk_rows >= 0, chunk_rows, rows)
                               ].set(True, mode="drop")
    active = (slots < scratch) & ~in_chunk
    # ---- the rows of one token
    at = jnp.minimum(first, t_all - 1)
    y_one, pool_one = decode_rows(pool, slots, active, fresh, q[at], k[at],
                                  v[at], log_g[at], interpret=interpret)
    y = jnp.zeros((t_all,) + y_one.shape[1:], F32)
    y = y.at[jnp.where(active, at, t_all)].set(y_one, mode="drop")
    if not chunk_rows.shape[0]:
        return y, pool_one
    # ---- the rows of several, each against its slot read out of the pool
    # the one-token rows have been through (they do not touch these slots,
    # and the pool is consumed once by each call in turn: no copy of it)
    cr = jnp.maximum(chunk_rows, 0)
    there = chunk_rows >= 0
    pos = jnp.minimum(first[cr][:, None]
                      + jnp.arange(span, dtype=jnp.int32)[None, :], t_all - 1)
    lens = jnp.where(there, q_lens[cr], 0)
    c_slots = jnp.where(there, slots[cr], scratch)
    kernel = (pool.shape[-1] % 128 == 0 and q.shape[-1] == 128
              and (_use_pallas() or interpret))
    if kernel:
        y_c, pool = _chunk_rows_pallas(
            pool_one, c_slots, fresh[cr], lens, q[pos], k[pos], v[pos],
            log_g[pos], interpret=interpret)
    else:
        y_c, states = _chunk_rows(pool_one, c_slots, fresh[cr], lens,
                                  q[pos], k[pos], v[pos], log_g[pos])
        pool = state_put(pool_one, states, c_slots)
    real = jnp.arange(span)[None, :] < lens[:, None]
    y = y.at[jnp.where(real, pos, t_all).reshape(-1)].set(
        y_c.reshape((-1,) + y_c.shape[2:]), mode="drop")
    return y, pool
