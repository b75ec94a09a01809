"""Kimi Delta Attention: the gated delta rule with a decay per channel.

Per head, with q_t, k_t in R^dk, v_t in R^dv, a log-decay a_t <= 0 per
CHANNEL of the key (alpha_t = exp(a_t)) and a scalar beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                       S in R^{dk x dv}, S_0 given or 0

``kda_recurrent`` is that recurrence token by token (the oracle, and
what a decode step runs).  ``kda_chunk`` is the chunkwise form the
published kernels use (fla ``chunk_kda``), in plain XLA:

* within a chunk of C = 64 tokens, with g_r the cumulative log-decay,
  the pseudo-values U solve (I + A) U = beta (V - K+ S_0), where
  A[r, i] = beta_r sum_c k_rc k_ic exp(g_rc - g_ic) for i < r and
  K+ = k exp(g); so U = Wv - Wk S_0 with T = (I + A)^-1,
  Wk = T (beta K+), Wv = T (beta V);
* o = Q+ S_0 + P U with P[r, i] = sum_c q_rc k_ic exp(g_rc - g_ic),
  i <= r; and ONE state update a chunk,
  S_C = Diag(exp(g_C)) S_0 + (k exp(g_C - g))^T U.

Dividing by exp(g) overflows for strongly decaying channels (a chunk
can decay by e^-100 and more), so no exp(-g) is ever formed: as in the
published kernels a chunk is cut into sub-blocks of 16 rows; a row
block takes its decays relative to the cumulative decay at its own
start (rows: exp(g_r - g_ref) <= 1, earlier columns: exp(g_ref - g_i)
<= 1, one matmul a row block), and the 16 x 16 diagonal blocks are
summed pair by pair with exp(g_r - g_i), i <= r.  Underflow to zero is
the right limit.

Log-decays, the triangular inverse and S are float32; the matmul
operands are the inputs' dtype (bfloat16 in training).

Which path runs where is decided by what ``_kda_chunk_rows`` can see of
its input and nothing else.  On a TPU, with dk one 128-lane tile and dv
whole tiles, it is the two Pallas kernels of ``ops/pallas/kda_chunk.py``
(a chunk's working set stays in VMEM, the state is carried from chunk to
chunk in scratch); the backward there is no autodiff but the second
kernel behind a ``custom_vjp``, which keeps the state that enters each
chunk and recomputes a chunk's intermediates from it.  Everywhere else
(the CPU, another head width) it is ``_kda_chunk`` below, plain XLA and
the kernels' oracle: its backward is autodiff, bounded in memory by the
op itself: the sequence is walked in segments of ``segment_chunks``
chunks under ``jax.checkpoint``, so what is kept for the backward is the
inputs and one state a segment, and a segment's intermediates are
recomputed when its gradient is taken.

Which layout each path takes.  The kernels read and write a stream as
the ROWS a projection leaves it in, [B, T, H * d] (on a TPU a tile of
such an array is 8 tokens of one head; a [B, T, H, d] array is tiled 8
heads x 128 lanes, and going from one to the other copies the stream:
PERF.md section 6, PR 46).  ``kda_chunk_rows`` is the op in that layout:
``models/kimi_linear.py`` makes every stream in rows and takes rows back,
so on the kernel path no [B, T, H, d] view of a stream exists.
``_kda_chunk`` (and ``kda_recurrent``) work on [B, T, H, d]; where they
run, ``kda_chunk_rows`` hands them that view of its rows, which costs
nothing there.  ``kda_chunk`` keeps the [B, T, H, d] signature for every
other caller and is the same op behind the opposite view.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.dispatch import def_op
from .pallas import kda_chunk as _pallas

CHUNK = 64
SUB = 16
F32 = jnp.float32
_HI = lax.Precision.HIGHEST


@jax.custom_vjp
def _inv_unit_lower(n_mat):
    """T = (I + N)^-1 for strictly lower-triangular N [..., C, C], by
    forward substitution, which stays accurate when the keys of a chunk
    are nearly parallel (the power series sum (-N)^k does not: its terms
    reach 1e16 before they cancel).  The 16 x 16 diagonal blocks row by
    row, T[i] = e_i - sum_{j<i} N[i, j] T[j]; then block row by block
    row, T[I][J] = -T[I][I] sum_{J<=K<I} N[I][K] T[K][J].  Its gradient
    is taken from the result, dN = -T^T dT T^T."""
    lead, c = n_mat.shape[:-2], n_mat.shape[-1]
    nb = c // SUB
    blocks = n_mat.reshape(lead + (nb, SUB, nb, SUB))
    a = [[blocks[..., i, :, j, :] for j in range(nb)] for i in range(nb)]
    own = jnp.stack([a[i][i] for i in range(nb)], axis=-3)  # [..., nb, S, S]
    eye = jnp.eye(SUB, dtype=n_mat.dtype)
    rows = [jnp.broadcast_to(eye[0], own.shape[:-2] + (SUB,))]
    for i in range(1, SUB):
        done = jnp.stack(rows, axis=-2)                     # [..., i, SUB]
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", own[..., i, :i], done, precision=_HI))
    diag = jnp.stack(rows, axis=-2)                         # [..., nb, S, S]
    t = [[None] * nb for _ in range(nb)]
    for i in range(nb):
        t[i][i] = diag[..., i, :, :]
        for j in range(i):
            acc = sum(jnp.matmul(a[i][k], t[k][j], precision=_HI)
                      for k in range(j, i))
            t[i][j] = -jnp.matmul(t[i][i], acc, precision=_HI)
    zero = jnp.zeros_like(t[0][0])
    return jnp.concatenate(
        [jnp.concatenate([t[i][j] if j <= i else zero for j in range(nb)],
                         axis=-1) for i in range(nb)], axis=-2)


def _inv_fwd(n_mat):
    inv = _inv_unit_lower(n_mat)
    return inv, inv


def _inv_bwd(inv, d_inv):
    t = jnp.swapaxes(inv, -1, -2)
    return (-jnp.matmul(jnp.matmul(t, d_inv, precision=_HI), t,
                        precision=_HI),)


_inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


def _decay_products(q, k, g):
    """sum_c x_rc k_ic exp(g_rc - g_ic) for x = k and x = q, over the
    lower triangle (i <= r) of a chunk, without ever forming exp(-g).
    q, k [..., C, dk]; g [..., C, dk] float32 cumulative log-decay.
    Returns (KK, QK) [..., C, C] float32; entries with i > r are zero."""
    cd = q.dtype
    lead, (c, dk) = q.shape[:-2], q.shape[-2:]
    nb = c // SUB
    gb = g.reshape(lead + (nb, SUB, dk))
    kb = k.reshape(lead + (nb, SUB, dk)).astype(F32)
    qb = q.reshape(lead + (nb, SUB, dk)).astype(F32)
    # a row block's reference: the cumulative decay just before it
    ref = jnp.concatenate(
        [jnp.zeros_like(gb[..., :1, 0, :]), gb[..., :-1, SUB - 1, :]],
        axis=-2)                                          # [..., nb, dk]
    row = jnp.exp(gb - ref[..., None, :])                 # <= 1
    before = (jnp.arange(c)[None, :]
              < (jnp.arange(nb) * SUB)[:, None])          # [nb, C]
    col = jnp.exp(jnp.where(before[..., None],
                            ref[..., :, None, :] - g[..., None, :, :],
                            -jnp.inf))                    # [..., nb, C, dk]
    k_col = (k.astype(F32)[..., None, :, :] * col).astype(cd)

    def off(xb):
        return jnp.einsum("...nrc,...njc->...nrj", (xb * row).astype(cd),
                          k_col, preferred_element_type=F32)

    # the diagonal blocks, pair by pair
    tri = jnp.arange(SUB)[:, None] >= jnp.arange(SUB)[None, :]
    pair = jnp.exp(jnp.where(
        tri[..., None], gb[..., :, None, :] - gb[..., None, :, :],
        -jnp.inf))                                  # [..., nb, SUB, SUB, dk]
    kp = kb[..., None, :, :] * pair
    own = jnp.eye(nb, dtype=F32)[:, None, :, None]        # block (n, n)

    def full(xb):
        diag = jnp.sum(xb[..., :, None, :] * kp, axis=-1)  # [..., nb,SUB,SUB]
        blocks = off(xb).reshape(lead + (nb, SUB, nb, SUB))
        blocks = blocks + diag[..., :, :, None, :] * own
        return blocks.reshape(lead + (c, c))

    return full(kb), full(qb)


def _prepare(q, k, v, a, beta):
    """Everything of a chunk that does not need the incoming state.
    q, k [..., C, dk], v [..., C, dv], a [..., C, dk] float32 log-decay
    per token, beta [..., C].  Returns Wk, Wv, P, Q+, K~ and exp(g_C)."""
    cd = q.dtype
    c = q.shape[-2]
    g = jnp.cumsum(a, axis=-2)
    kk, qk = _decay_products(q, k, g)
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    bf = beta.astype(F32)[..., None]
    t_inv = _inv_unit_lower(jnp.where(strict, bf * kk, 0.0))
    decay = jnp.exp(g)
    g_end = g[..., -1:, :]
    k_plus = (k.astype(F32) * decay * bf).astype(cd)
    w_k = jnp.matmul(t_inv, k_plus, preferred_element_type=F32)
    w_v = jnp.matmul(t_inv, (v.astype(F32) * bf).astype(cd),
                     preferred_element_type=F32)
    q_plus = (q.astype(F32) * decay).astype(cd)
    k_tilde = (k.astype(F32) * jnp.exp(g_end - g)).astype(cd)
    return (w_k.astype(cd), w_v, qk.astype(cd), q_plus, k_tilde,
            jnp.exp(g_end[..., 0, :]))


def _segment(state, xs):
    """``seg`` chunks of every (batch, head): xs are [B, H, seg, C, D]
    slices, ``state`` [B, H, dk, dv] float32.  Returns the state after
    the segment and its outputs [B, H, seg, C, dv]."""
    q, k, v, a, beta = xs
    w_k, w_v, p, q_plus, k_tilde, gamma = _prepare(q, k, v, a, beta)

    def step(s, c):
        wk, wv, kt, gam = c
        u = wv - jnp.matmul(wk, s, preferred_element_type=F32)
        s_new = gam[..., None] * s + jnp.einsum(
            "...cd,...ce->...de", kt, u, preferred_element_type=F32)
        return s_new, (s, u)

    lead = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    state, (s_in, u) = lax.scan(
        step, state, (lead(w_k), lead(w_v), lead(k_tilde), lead(gamma)))
    s_in, u = jnp.moveaxis(s_in, 0, 2), jnp.moveaxis(u, 0, 2)
    o = jnp.matmul(q_plus, s_in, preferred_element_type=F32) \
        + jnp.matmul(p, u.astype(p.dtype), preferred_element_type=F32)
    return state, o.astype(v.dtype)


def _kda_chunk(q, k, v, a, beta, initial_state=None, segment_chunks=8):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n_chunks = -(-t // CHUNK)
    seg = min(int(segment_chunks), n_chunks)
    n_seg = -(-n_chunks // seg)
    pad = n_seg * seg * CHUNK - t
    a = a.astype(F32)

    def cut(x):
        # zero keys, values and betas and a zero log-decay leave the
        # state as it is, so the pad rows cost nothing but their time
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, n_seg, seg, CHUNK) + x.shape[2:])
        # -> [n_seg, B, H, seg, C, ...]
        return jnp.moveaxis(x, (1, 4), (0, 2))

    s0 = (jnp.zeros((b, h, dk, dv), F32) if initial_state is None
          else initial_state.astype(F32))
    state, o = lax.scan(jax.checkpoint(_segment), s0,
                        (cut(q), cut(k), cut(v), cut(a), cut(beta)))
    # [n_seg, B, H, seg, C, dv] -> [B, T, H, dv]
    o = jnp.moveaxis(o, (0, 2), (1, 4)).reshape(b, -1, h, dv)
    return o[:, :t], state


def _kda_chunk_rows(q, k, v, a, beta, initial_state=None, segment_chunks=8):
    """The one place the path is chosen (the module docstring).  Streams
    in rows, [B, T, H * d]; beta [B, T, H] says how many heads."""
    h = beta.shape[-1]
    if _pallas.supported(q.shape[-1] // h, v.shape[-1] // h):
        return _pallas.kda_chunk_pallas(q, k, v, a, beta, initial_state)

    def heads(x):
        return x.reshape(x.shape[:2] + (h, -1))

    o, state = _kda_chunk(heads(q), heads(k), heads(v), heads(a), beta,
                          initial_state, segment_chunks)
    return o.reshape(o.shape[:2] + (-1,)), state


@def_op("kda_chunk_rows")
def kda_chunk_rows(q, k, v, a, beta, initial_state=None, segment_chunks=8):
    """``kda_chunk`` over streams in the rows a projection writes: q, k, a
    [B, T, H * dk], v [B, T, H * dv], beta [B, T, H].  Returns (o
    [B, T, H * dv] in v's dtype, final state [B, H, dk, dv] float32).  The
    kernels take and return rows, so on their path no stream is re-laid
    out; the XLA path sees the [B, T, H, d] view."""
    return _kda_chunk_rows(q, k, v, a, beta, initial_state, segment_chunks)


@def_op("kda_chunk")
def kda_chunk(q, k, v, a, beta, initial_state=None, segment_chunks=8):
    """Chunkwise KDA.  q, k [B, T, H, dk] (q already scaled), v
    [B, T, H, dv], a [B, T, H, dk] log-decay per channel (<= 0), beta
    [B, T, H], initial_state [B, H, dk, dv] or None.  Returns
    (o [B, T, H, dv] in v's dtype, final state [B, H, dk, dv] float32).
    ``segment_chunks`` is the XLA path's; the kernels keep a state a
    chunk."""
    def rows(x):
        return x.reshape(x.shape[:2] + (-1,))

    o, state = _kda_chunk_rows(rows(q), rows(k), rows(v), rows(a), beta,
                               initial_state, segment_chunks)
    return o.reshape(v.shape), state


def _kda_recurrent(q, k, v, a, beta, initial_state=None):
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    s0 = (jnp.zeros((b, h, dk, dv), F32) if initial_state is None
          else initial_state.astype(F32))

    def step(s, x):
        qt, kt, vt, at, bt = x           # [B, H, dk] ... [B, H]
        s = jnp.exp(at)[..., None] * s
        err = vt - jnp.einsum("bhd,bhde->bhe", kt, s, precision=_HI)
        s = s + (bt[..., None] * kt)[..., None] * err[..., None, :]
        return s, jnp.einsum("bhd,bhde->bhe", qt, s, precision=_HI)

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, a, beta))
    state, o = lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), state


@def_op("kda_recurrent")
def kda_recurrent(q, k, v, a, beta, initial_state=None):
    """The recurrence itself, one token a step, all in float32: the
    oracle of ``kda_chunk`` and the form a decode step takes.  Same
    arguments and results."""
    return _kda_recurrent(q, k, v, a, beta, initial_state)
