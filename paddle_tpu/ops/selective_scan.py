"""The selective scan of a Mamba-1 mixer and its causal convolution, as the
three forms a served sequence meets them in.

With ``u`` the convolved inputs [T, D] (D = ``d_inner`` channels),
``delta`` [T, D] the step sizes (after softplus), ``a`` [N, D] the decay
rates (-exp(A_log), transposed: N = ``d_state`` on the sublane axis, the
channels on the lane axis), ``B`` / ``C`` [T, N] and ``d`` [D]:

    h_t = exp(delta_t * a) . h_{t-1} + (delta_t u_t) B_t^T        h in R^{N x D}
    m_t = sum_n h_t[n] C_t[n] + d . u_t

and, before it, the depthwise causal convolution of width K over the
layer's inputs x [T, D], ``w`` [K, D]:

    y_t = b + sum_{k < K} w[k] . x_{t - (K - 1) + k}              x_{< 0} = the tail, or 0

* ``scan_recurrence`` / ``conv_recurrence``: the recurrences token by
  token over one whole sequence (the oracle, and the model's forward
  without a cache);
* ``shift_step``: ``conv_step`` at width 2 with taps (1, 0): a token's
  predecessor, for a layer whose slot is nothing but such tails;
* ``scan_step`` / ``conv_step``: one layer of a RAGGED serving step
  against the pools of slots.  A sequence's state a layer is two arrays, a
  slot of each pool: ``h`` [N, D] and the convolution's tail, the last
  K - 1 inputs end to end [(K - 1) D], both float32.  A row of several
  tokens (a prefill chunk) runs from its slot's ``h`` and tail and writes
  both back;
  the rows of one token update theirs in place; a row that enters at
  context 0 starts from zeros whatever the slot held; a pad row works on
  the scratch slot (the pool's last).

On the TPU what touches ``h`` is one Pallas kernel (``_scan_kernel``)
aliased onto the pool, a grid step a row and a block of its tokens: the
slot's block comes in by scalar prefetch of the rows' slots, the row's
tokens are walked in VMEM (one for a decode row, up to ``span`` for a chunk
row, 32 a grid step: 80 vector registers of state a token, a few products
each), and the block goes back where it came from, so a step moves each real row's state once in and once out and
no other byte of the pool.  XLA's gather-update-scatter of the same
numbers is the path off the TPU (and the test's oracle of the kernel).
The convolution's tail is a thousandth of ``h`` and stays in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas.flash_attention import _use_pallas

F32 = jnp.float32
#: channels a pass of the kernel's token loop keeps in registers
LANE_BLOCK = 1280
#: tokens of a row a grid step holds in VMEM (u, delta and m of 32 tokens
#: are 2 MB, twice over for the pipeline: a chunk of any length fits)
TOKEN_BLOCK = 32


# ------------------------------------------------------- whole sequences
def conv_recurrence(x, w, b, tail=None):
    """x [T, D], w [K, D], b [D], ``tail`` [K - 1, D] the inputs before
    x[0] (zeros if None) -> (y [T, D], the new tail [K - 1, D])."""
    k = w.shape[0]
    x = x.astype(F32)
    if tail is None:
        tail = jnp.zeros((k - 1, x.shape[1]), F32)
    ext = jnp.concatenate([tail.astype(F32), x])
    t = x.shape[0]
    y = b.astype(F32) + sum(w[i].astype(F32) * ext[i:i + t]
                            for i in range(k))
    return y, ext[t:]


def scan_recurrence(u, delta, a, B, C, d, h0=None):
    """The recurrence token by token: u / delta [T, D], a [N, D], B / C
    [T, N], d [D], ``h0`` [N, D] (zeros if None) -> (m [T, D], h [N, D]),
    float32."""
    u, delta, a = u.astype(F32), delta.astype(F32), a.astype(F32)
    if h0 is None:
        h0 = jnp.zeros(a.shape, F32)

    def step(h, xs):
        ut, dt, bt, ct = xs
        h = jnp.exp(dt[None, :] * a) * h + (dt * ut)[None, :] * bt[:, None]
        return h, jnp.sum(h * ct[:, None], axis=0) + d.astype(F32) * ut

    h, m = lax.scan(step, h0.astype(F32),
                    (u, delta, B.astype(F32), C.astype(F32)))
    return m, h


# ------------------------------------------------- a ragged step's rows
def _token_rows(rows, t_all, row_off, span):
    """(first [rows], row [T], col [T]): where each row starts on the
    packed axis, and each position's row and place in it."""
    at = jnp.arange(t_all, dtype=jnp.int32)
    if row_off is None:
        return jnp.arange(rows, dtype=jnp.int32) * span, at // span, at % span
    row = jnp.sum(at[:, None] >= row_off[None, :], axis=1) - 1
    return row_off, row, at - row_off[row]


@functools.partial(jax.jit, static_argnames=("span",))
def conv_step(pool, slots, ctx_lens, q_lens, row_off, x, w, b, span):
    """One layer's convolution for a ragged step: ``x`` [T, D] the step's
    tokens packed along one axis (row ``r``'s ``q_lens[r]`` tokens from
    ``row_off[r]`` on; None: the (rows, ``span``) rectangle, row-major),
    ``pool`` [slots + 1, (K - 1) D] the tails (a slot's K - 1 inputs end
    to end: a row of whole lane tiles, which a gather and a scatter of rows
    take as they lie), ``slots`` / ``ctx_lens`` [rows].  Returns (y [T, D]
    float32, the pool with every row's tail moved on by its tokens)."""
    rows, t_all, k = slots.shape[0], x.shape[0], w.shape[0]
    x, w = x.astype(F32), w.astype(F32)
    first, row, col = _token_rows(rows, t_all, row_off, span)
    old = jnp.where((ctx_lens == 0)[:, None, None], 0.0,
                    pool[slots].reshape(rows, k - 1, -1))
    y = b.astype(F32) + w[k - 1] * x
    at = jnp.arange(t_all, dtype=jnp.int32)
    for back in range(1, k):
        before = jnp.where(
            (col >= back)[:, None], x[jnp.maximum(at - back, 0)],
            old[row, jnp.clip(k - 1 + col - back, 0, k - 2)])
        y = y + w[k - 1 - back] * before
    # the tail after the row's tokens: its last K - 1 inputs, some of them
    # the old tail's where the row holds fewer
    r = jnp.arange(rows)
    new = jnp.stack([
        jnp.where((q_lens - (k - 1) + i >= 0)[:, None],
                  x[jnp.clip(first + q_lens - (k - 1) + i, 0, t_all - 1)],
                  old[r, jnp.clip(q_lens + i, 0, k - 2)])
        for i in range(k - 1)], axis=1)
    return y, pool.at[slots].set(new.reshape(rows, -1))


def shift_step(pool, slots, ctx_lens, q_lens, row_off, x, span):
    """Every token's PREDECESSOR in its sequence: ``conv_step`` at K = 2
    with taps (1, 0) and no bias.  ``x`` [T, D] packed as there, ``pool``
    [slots + 1, D] each sequence's last token's ``x``: a row's first token
    reads its slot (zeros at context 0, whatever the slot held), and the
    slot moves on to the row's last token.  What a layer that mixes a
    token with the one before it keeps a sequence beside its pages
    (``models/zaya.py``: the convolutions' inputs and the shifted value).
    Returns (x_{t-1} [T, D] float32, the pool)."""
    width = x.shape[1]
    taps = jnp.stack([jnp.ones(width, F32), jnp.zeros(width, F32)])
    return conv_step(pool, slots, ctx_lens, q_lens, row_off, x, taps,
                     jnp.zeros(width, F32), span=span)


def _scan_kernel(slot_ref, fresh_ref, len_ref, u_ref, dt_ref, b_ref, c_ref,
                 a_ref, d_ref, h_in, m_ref, h_out, *, tokens, block):
    """A grid step a (row, block of ``tokens`` tokens): at the row's first
    block its slot comes in (zeros if the row is fresh), the block's
    tokens under ``len_ref[r]`` are walked, and ``h`` stays in the output
    block, which goes back to the pool when the row's last block is done.
    u / dt / m blocks [1, tokens, D], B / C [1, tokens, N, 1], a [N, D], d
    [1, D], the slot [1, N, D]."""
    del slot_ref                       # read by the index maps
    r, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        h_out[...] = jnp.where(fresh_ref[r] > 0, 0.0, h_in[...])

    m_ref[...] = jnp.zeros_like(m_ref)
    live = jnp.clip(len_ref[r] - j * tokens, 0, tokens)
    for k in range(h_in.shape[-1] // block):
        lanes = pl.ds(k * block, block)
        a, dvec = a_ref[:, lanes], d_ref[:, lanes]

        def token(t, h, lanes=lanes, a=a, dvec=dvec):
            at = pl.ds(t, 1)
            u, dt = u_ref[0, at, lanes], dt_ref[0, at, lanes]   # [1, block]
            h = jnp.exp(dt * a) * h + (dt * u) * b_ref[0, t]    # [N, block]
            m_ref[0, at, lanes] = (
                jnp.sum(h * c_ref[0, t], axis=0, keepdims=True) + dvec * u)
            return h

        if tokens == 1:
            h_out[0, :, lanes] = token(0, h_out[0, :, lanes])
        else:
            h_out[0, :, lanes] = lax.fori_loop(0, live, token,
                                               h_out[0, :, lanes])


def _scan_pallas(pool, slots, fresh, lens, u, delta, a, B, C, d,
                 interpret=False):
    """``_scan_kernel`` over R rows of ``span`` tokens each: u / delta [R,
    span, D], B / C [R, span, N]; ``lens`` [R] the rows' real tokens.
    Returns (m [R, span, D], the pool)."""
    rows, span, width = u.shape
    n = a.shape[0]
    block = LANE_BLOCK if width % LANE_BLOCK == 0 else width
    tokens = TOKEN_BLOCK if span % TOKEN_BLOCK == 0 else span
    tok = pl.BlockSpec((1, tokens, width), lambda r, j, *_: (r, j, 0))
    col = pl.BlockSpec((1, tokens, n, 1), lambda r, j, *_: (r, j, 0, 0))
    slot = pl.BlockSpec((1, n, width), lambda r, j, s, *_: (s[r], 0, 0))
    whole = lambda shape: pl.BlockSpec(shape, lambda r, j, *_: (0, 0))  # noqa: E731
    m, pool = pl.pallas_call(
        functools.partial(_scan_kernel, tokens=tokens, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows, span // tokens),
            in_specs=[tok, tok, col, col, whole((n, width)),
                      whole((1, width)), slot],
            out_specs=[tok, slot]),
        out_shape=[jax.ShapeDtypeStruct(u.shape, F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 9 (3 prefetched + u, delta, B, C, a, d) is the pool
        input_output_aliases={9: 1},
        interpret=interpret,
    )(slots.astype(jnp.int32), fresh.astype(jnp.int32),
      lens.astype(jnp.int32), u.astype(F32), delta.astype(F32),
      B.astype(F32)[..., None], C.astype(F32)[..., None], a.astype(F32),
      d.astype(F32)[None, :], pool)
    return m, pool


def _scan_rows_xla(pool, slots, fresh, lens, u, delta, a, B, C, d):
    """What ``_scan_pallas`` computes, in XLA: every row's slot gathered,
    its tokens walked (a token past ``lens`` leaves the state as it was)
    and the states scattered back."""
    span = u.shape[1]
    h = jnp.where(fresh[:, None, None], 0.0, pool[slots])
    a, d = a.astype(F32), d.astype(F32)

    def step(h, xs):
        t, ut, dt, bt, ct = xs                        # [R, D], [R, N]
        new = (jnp.exp(dt[:, None, :] * a) * h
               + (dt * ut)[:, None, :] * bt[:, :, None])
        h = jnp.where((t < lens)[:, None, None], new, h)
        return h, jnp.sum(h * ct[:, :, None], axis=1) + d * ut

    seq = lambda x: jnp.swapaxes(x.astype(F32), 0, 1)       # noqa: E731
    h, m = lax.scan(step, h, (jnp.arange(span), seq(u), seq(delta), seq(B),
                              seq(C)))
    return jnp.swapaxes(m, 0, 1), pool.at[slots].set(h)


def scan_rows(pool, slots, fresh, lens, u, delta, a, B, C, d,
              interpret=False):
    """R rows of up to ``span`` tokens against their slots: the kernel on
    the TPU (or interpreted) where the channels are whole lane tiles,
    else XLA's form."""
    if u.shape[-1] % 128 == 0 and (_use_pallas() or interpret):
        return _scan_pallas(pool, slots, fresh, lens, u, delta, a, B, C, d,
                            interpret=interpret)
    return _scan_rows_xla(pool, slots, fresh, lens, u, delta, a, B, C, d)


@functools.partial(jax.jit, static_argnames=("span", "interpret"))
def scan_step(pool, slots, ctx_lens, q_lens, row_off, chunk_rows, u, delta,
              a, B, C, d, span, interpret=False):
    """One layer's selective scan for a ragged step against the pool of
    ``h`` slots [slots + 1, N, D].

    ``u`` / ``delta`` [T, D], ``B`` / ``C`` [T, N]: the step's tokens
    packed as ``conv_step`` takes them; ``a`` [N, D], ``d`` [D].
    ``chunk_rows`` [C] (C static): the rows of several tokens, -1 where
    there are fewer; every other row holds one token.  Returns (m [T, D]
    float32, zeros at the positions that are no row's token, and the
    pool).  Jitted: a program's layers call it at the same shapes and
    share one traced and lowered body."""
    rows, t_all = slots.shape[0], u.shape[0]
    scratch = pool.shape[0] - 1
    first, _, _ = _token_rows(rows, t_all, row_off, span)
    fresh = ctx_lens == 0
    in_chunk = jnp.zeros(rows, bool)
    if chunk_rows.shape[0]:
        in_chunk = in_chunk.at[jnp.where(chunk_rows >= 0, chunk_rows, rows)
                               ].set(True, mode="drop")
    active = (slots < scratch) & ~in_chunk
    # ---- the rows of one token, each against its slot in place (the
    # others work on the scratch slot)
    at = jnp.minimum(first, t_all - 1)
    one = lambda x: x[at][:, None]                          # noqa: E731
    m_one, pool = scan_rows(
        pool, jnp.where(active, slots, scratch), fresh | ~active,
        jnp.ones(rows, jnp.int32), one(u), one(delta), a, one(B), one(C), d,
        interpret=interpret)
    m = jnp.zeros((t_all, u.shape[1]), F32)
    m = m.at[jnp.where(active, at, t_all)].set(m_one[:, 0], mode="drop")
    if not chunk_rows.shape[0]:
        return m, pool
    # ---- the rows of several, each from its slot and back into it
    cr = jnp.maximum(chunk_rows, 0)
    there = chunk_rows >= 0
    pos = jnp.minimum(first[cr][:, None]
                      + jnp.arange(span, dtype=jnp.int32)[None, :], t_all - 1)
    lens = jnp.where(there, q_lens[cr], 0)
    m_c, pool = scan_rows(
        pool, jnp.where(there, slots[cr], scratch), fresh[cr] | ~there, lens,
        u[pos], delta[pos], a, B[pos], C[pos], d, interpret=interpret)
    real = jnp.arange(span)[None, :] < lens[:, None]
    m = m.at[jnp.where(real, pos, t_all).reshape(-1)].set(
        m_c.reshape(-1, m_c.shape[-1]), mode="drop")
    return m, pool


# ------------------------------------------------- what a slot is, in bytes
def state_shapes(d_inner: int, d_state: int, d_conv: int):
    """A slot's two arrays as the pools store them: ``h`` [N, D] and the
    convolution's tail, its K - 1 inputs end to end [(K - 1) D], float32."""
    return [(d_state, d_inner), ((d_conv - 1) * d_inner,)]


def state_bytes(d_inner: int, d_state: int, d_conv: int) -> int:
    """Bytes of a layer's state a sequence, both arrays."""
    return 4 * d_inner * (d_state + d_conv - 1)
