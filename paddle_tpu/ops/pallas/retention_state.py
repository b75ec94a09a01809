"""The state traffic of power retention (``ops/power_retention.py``) as
Pallas calls aliased onto the pool of slots, so that a step updates the
slots of its rows in place and touches no other.

The pool is ``[slots + 1, kv_heads, R, Dp]`` float32: a slot a sequence
(the last is scratch), a head's state ``[R, Dp]`` with the value channels
(and z as one more) on the sublane axis and phi's ``Dp / 128`` lane tiles
on the lane axis.

``retention_decode``: the one-token form, S <- g S + v' phi(k)^T and
y = S phi(q_i) for the group's query heads, for the first ``n_active``
rows of a step.  Grid (rows, kv heads, blocks of lane tiles), the slot
by scalar prefetch in the block's index map, the pool both input and
output of the call (``input_output_aliases``): Pallas streams a block in
while the one before it is computed and the one before that goes back.
The update and the readout are elementwise products on the vector unit
(17 sublane groups x the block's lane tiles; the gate and phi(q), phi(k)
are rows broadcast over sublanes, v' arrives broadcast over lanes), the
readout summed over lane tiles into a ``[G, R, 128]`` scratch and over
lanes once a (row, head), by a product with ones.  The call is bound by
the 2 x 4.5 MB a (row, head) that cross HBM, not by its arithmetic.  The
rows past ``n_active`` (pad rows, rows of several tokens) map to the
block the last active row ended on (the scratch slot if there is none):
an index that does not change moves no data, and nothing is computed.

``retention_chunk``: what the chunk form does to a state, for the few rows
of several tokens: before[t] = phi(q_t)^T S_prev for every query of the
row (numerators, and the normaliser's partial sums a lane) and S <- gamma
S_prev + V'^T phi(K) with the decays to the chunk's end folded into V'.
Grid (rows, kv heads, blocks of lane tiles) over the same aliased pool;
phi is never held whole: lane tile o of it is x * roll(x, o), made in
VMEM from the chunk's 128-wide queries and keys as the block's tiles go
by.  The products run on the MXU as three bfloat16 passes each (both
operands split into a bfloat16 head and a bfloat16 remainder; the
remainders' product, 2^-16 of the result, is left out), summed in
float32.  The chunk's own masked and decayed scores stay in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANE = 128
#: lane tiles of phi a block holds: 13 of d = 128's 65 (five blocks a
#: head, 0.9 MB each: two in flight each way are 3.6 MB of VMEM)
BLOCK_TILES = 13


def block_tiles(n_tiles: int) -> int:
    """The largest divisor of ``n_tiles`` up to ``BLOCK_TILES``."""
    return max(t for t in range(1, min(BLOCK_TILES, n_tiles) + 1)
               if n_tiles % t == 0)


def _decode_kernel(slot_ref, fresh_ref, nact_ref, qk_ref, aux_ref, s_in,
                   s_out, y_ref, acc_ref, *, group, rows, tiles):
    r, t = pl.program_id(0), pl.program_id(2)

    @pl.when(r < nact_ref[0])
    def _():
        @pl.when(t == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        fresh = fresh_ref[r] > 0
        gate = aux_ref[rows:rows + 1, :]                      # [1, 128]

        def sublane_group(i, carry):
            at = pl.ds(pl.multiple_of(i * 8, 8), 8)
            acc = [acc_ref[g, at, :] for g in range(group)]
            vb = aux_ref[at, :]                               # [8, 128]
            for j in range(tiles):
                lanes = slice(j * LANE, (j + 1) * LANE)
                s = jnp.where(fresh, 0.0, s_in[at, lanes])
                s = s * gate + vb * qk_ref[group:group + 1, lanes]
                s_out[at, lanes] = s
                for g in range(group):
                    acc[g] = acc[g] + s * qk_ref[g:g + 1, lanes]
            for g in range(group):
                acc_ref[g, at, :] = acc[g]
            return carry

        lax.fori_loop(0, rows // 8, sublane_group, 0)

        @pl.when(t == pl.num_programs(2) - 1)
        def _():
            ones = jnp.ones((8, LANE), F32)
            for g in range(group):
                y = lax.dot_general(
                    ones, acc_ref[g], (((1,), (1,)), ((), ())),
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=F32)               # [8, R]
                y_ref[g:g + 1, :] = y[0:1, :]


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def retention_decode(pool, slots, fresh, n_active, qk, aux, group,
                     interpret=False):
    """``pool`` [slots + 1, Hk, R, Dp]; ``slots``, ``fresh`` [b] int32;
    ``n_active`` [1] int32: the rows that run, from the front; ``qk`` [b,
    Hk, 8, Dp]: phi of the group's queries in rows 0..group-1, phi of the
    key in row ``group``; ``aux`` [b, Hk, R + 8, 128]: v' broadcast over
    lanes in rows 0..R-1, the gate in row R.  Returns (y [b, Hk, 8, R]:
    row g is S phi(q_g) — the numerators and, in column dv, the
    denominator; meaningless past ``n_active`` — and the pool)."""
    b, hk, _, dp = qk.shape
    rows = pool.shape[2]
    n_tiles = dp // LANE
    tiles = block_tiles(n_tiles)
    n_blocks = n_tiles // tiles
    scratch = pool.shape[0] - 1

    def frozen(r, h, t, nact):
        """(row, head, block) a grid step works on: its own while the row
        is active, else where the last active row ended."""
        on = r < nact[0]
        last = jnp.maximum(nact[0] - 1, 0)
        return (jnp.where(on, r, last), jnp.where(on, h, hk - 1),
                jnp.where(on, t, n_blocks - 1))

    def small(r, h, t, slot, fresh, nact):
        rr, hh, _ = frozen(r, h, t, nact)
        return rr, hh, 0, 0

    def phis(r, h, t, slot, fresh, nact):
        rr, hh, tt = frozen(r, h, t, nact)
        return rr, hh, 0, tt

    def state(r, h, t, slot, fresh, nact):
        rr, hh, tt = frozen(r, h, t, nact)
        return jnp.where(nact[0] > 0, slot[rr], scratch), hh, 0, tt

    y, pool = pl.pallas_call(
        functools.partial(_decode_kernel, group=group, rows=rows,
                          tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hk, n_blocks),
            in_specs=[
                pl.BlockSpec((None, None, 8, tiles * LANE), phis),
                pl.BlockSpec((None, None, rows + 8, LANE), small),
                pl.BlockSpec((None, None, rows, tiles * LANE), state),
            ],
            out_specs=[
                pl.BlockSpec((None, None, rows, tiles * LANE), state),
                pl.BlockSpec((None, None, 8, rows), small),
            ],
            scratch_shapes=[pltpu.VMEM((group, rows, LANE), F32)]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, hk, 8, rows), F32)],
        # operands: slots, fresh, n_active, qk, aux, pool -> the pool is
        # the first output
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="retention_decode", interpret=interpret,
    )(slots, fresh, n_active, qk, aux, pool)[::-1]
    return y, pool


def _split(x):
    """x as a bfloat16 head and a bfloat16 remainder."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(F32)).astype(jnp.bfloat16)


def _dot3(a, b, dims):
    """a . b in three bfloat16 passes summed in float32."""
    (ah, al), (bh, bl) = _split(a), _split(b)

    def dot(x, y):
        return lax.dot_general(x, y, (dims, ((), ())),
                               preferred_element_type=F32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _chunk_kernel(slot_ref, fresh_ref, nreal_ref, q_ref, k_ref, vt_ref,
                  g_ref, s_in, s_out, num_ref, den_ref, *, dv, tiles, half):
    c, blk = pl.program_id(0), pl.program_id(2)

    @pl.when(c < nreal_ref[0])
    def _():
        @pl.when(blk == 0)
        def _():
            num_ref[...] = jnp.zeros_like(num_ref)
            den_ref[...] = jnp.zeros_like(den_ref)

        fresh = fresh_ref[c] > 0
        qs, ks, vt = q_ref[...], k_ref[...], vt_ref[...]
        gamma = g_ref[0:1, :]                                 # [1, 128]
        lane = qs.shape[-1]

        def tile(j, carry):
            num, den = carry
            o = blk * tiles + j
            at = pl.ds(pl.multiple_of(j * LANE, LANE), LANE)
            st = jnp.where(fresh, 0.0, s_in[:, at])           # [R, 128]
            w = jnp.where((o == 0) | (o == half), 1.0, 2.0 ** 0.5)
            shift = (lane - o) % lane
            fq = qs * pltpu.roll(qs, shift, 1)                # [M, 128]
            fk = ks * pltpu.roll(ks, shift, 1) * w            # [c, 128]
            num = num + _dot3(fq, st[:dv] * w, ((1,), (1,)))
            den = den + fq * (st[dv:dv + 1] * w)
            s_out[:, at] = gamma * st + _dot3(vt, fk, ((1,), (0,)))
            return num, den

        num, den = lax.fori_loop(0, tiles, tile,
                                 (num_ref[...], den_ref[...]))
        num_ref[...] = num
        den_ref[...] = den


@functools.partial(jax.jit, static_argnames=("dv", "interpret"))
def retention_chunk(pool, slots, fresh, n_real, qs, ks, vt, gamma, dv,
                    interpret=False):
    """``pool`` [slots + 1, Hk, R, Dp]; ``slots``, ``fresh`` [C] int32;
    ``n_real`` [1] int32: the rows that are there, from the front; ``qs``
    [C, Hk, M, d]: the scaled queries of a KV head's group, a row a
    (token, query head); ``ks`` [C, Hk, c, d]: the scaled keys, zero past
    the row's tokens; ``vt`` [C, Hk, R, c]: v' transposed, each token's
    column times its decay to the chunk's end (zero past the row's
    tokens); ``gamma`` [C, Hk, 8, 128]: the whole chunk's decay,
    broadcast.  d = 128, c and M multiples of 8.  Returns (num [C, Hk, M,
    dv]: phi(q)^T S_prev; den [C, Hk, M, 128]: phi(q)^T z_prev as partial
    sums a lane; the pool)."""
    n_rows, hk, m, d = qs.shape
    c = ks.shape[2]
    rows, dp = pool.shape[2], pool.shape[3]
    n_tiles = dp // LANE
    tiles = block_tiles(n_tiles)
    n_blocks = n_tiles // tiles
    scratch = pool.shape[0] - 1

    def frozen(i, h, t, nreal):
        on = i < nreal[0]
        last = jnp.maximum(nreal[0] - 1, 0)
        return (jnp.where(on, i, last), jnp.where(on, h, hk - 1),
                jnp.where(on, t, n_blocks - 1))

    def per_head(i, h, t, slot, fresh, nreal):
        ii, hh, _ = frozen(i, h, t, nreal)
        return ii, hh, 0, 0

    def state(i, h, t, slot, fresh, nreal):
        ii, hh, tt = frozen(i, h, t, nreal)
        return jnp.where(nreal[0] > 0, slot[ii], scratch), hh, 0, tt

    pool, num, den = pl.pallas_call(
        functools.partial(_chunk_kernel, dv=dv, tiles=tiles,
                          half=d // 2),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_rows, hk, n_blocks),
            in_specs=[
                pl.BlockSpec((None, None, m, d), per_head),
                pl.BlockSpec((None, None, c, d), per_head),
                pl.BlockSpec((None, None, rows, c), per_head),
                pl.BlockSpec((None, None, 8, LANE), per_head),
                pl.BlockSpec((None, None, rows, tiles * LANE), state),
            ],
            out_specs=[
                pl.BlockSpec((None, None, rows, tiles * LANE), state),
                pl.BlockSpec((None, None, m, dv), per_head),
                pl.BlockSpec((None, None, m, LANE), per_head),
            ]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((n_rows, hk, m, dv), F32),
                   jax.ShapeDtypeStruct((n_rows, hk, m, LANE), F32)],
        # operands: slots, fresh, n_real, qs, ks, vt, gamma, pool
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        name="retention_chunk", interpret=interpret,
    )(slots, fresh, n_real, qs, ks, vt, gamma, pool)
    return num, den, pool
