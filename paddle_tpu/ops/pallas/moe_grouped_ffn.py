"""A no-drop grouped SwiGLU product over (token, chosen expert) pairs.

The pairs a step's tokens chose are laid out GROUPED BY EXPERT in blocks
of ``BLOCK_ROWS`` rows: expert ``e``'s ``n_e`` pairs take
``ceil(n_e / BLOCK_ROWS)`` whole blocks, an expert nobody chose takes
none.  Every pair has a row (nothing is ever dropped, whatever the
routing), the rows computed are the pairs plus at most ``BLOCK_ROWS - 1``
a touched expert, and a block multiplies by ONE expert's weights, so the
weights read are those of the experts that were chosen.

``plan`` is the routing arithmetic (XLA, static shapes): each pair's row,
each block's expert, the blocks in use.  ``grouped_swiglu`` is the
product: on the TPU the Pallas kernel ``moe_grouped_ffn`` — a grid step a
block, the block's expert read from a scalar-prefetched table by the
weight BlockSpecs' index maps (a run of blocks of one expert fetches its
weights once; the blocks past the last in use point at the last expert
in use and fetch nothing), its compute skipped past the blocks in use,
an expert wider than ``TILE_WIDTH`` a tile of its width at a time
(``_ffn_pallas``);
elsewhere ``lax.ragged_dot`` over the same layout, the kernel's oracle.

Dispatch and combine are 0/1 matrices over (rows, tokens) multiplied on
the MXU, exact in any dtype (one nonzero a row; a token's ``k`` rows
summed in float32): sized for a serving step's tokens (hundreds), not
for a training batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _use_pallas

#: rows a block holds: the bf16 tile's sublanes, so a block is whole
#: tiles in the dtype the serving step computes in
BLOCK_ROWS = 16
#: columns of an expert's width a grid step holds in VMEM
TILE_WIDTH = 512
#: the most a several-tile call's float32 accumulator may take of VMEM
ACC_BYTES = 16 * 1024 * 1024


def plan_blocks(pairs: int, experts: int) -> int:
    """Blocks the layout needs for ``pairs`` pairs over ``experts``
    experts under ANY routing: sum_e ceil(n_e / B) <= (pairs + touched *
    (B - 1)) / B with at most min(experts, pairs) touched."""
    return (pairs + min(experts, pairs) * (BLOCK_ROWS - 1)) // BLOCK_ROWS


def plan(idx, held, experts: int):
    """Where every kept pair goes.  ``idx`` [T, k] int32 expert ids
    (local to the experts held: ``0 .. experts - 1`` wherever ``held``),
    ``held`` [T, k] bool (False: a pad token's pair, or one that fell on
    an expert held elsewhere).  Returns ``(dest [T, k] int32 row of each
    pair, -1 where not held; block_expert [NB] int32; n_blocks int32
    scalar; counts [experts] int32 pairs an expert got)``."""
    t, k = idx.shape
    nb = plan_blocks(t * k, experts)
    e = jnp.where(held, idx, experts).reshape(-1)
    chose = e[:, None] == jnp.arange(experts, dtype=jnp.int32)[None, :]
    seen = jnp.cumsum(chose.astype(jnp.int32), axis=0)     # [P, E]
    counts = seen[-1]
    blocks = (counts + BLOCK_ROWS - 1) // BLOCK_ROWS
    ends = jnp.cumsum(blocks)
    n_blocks = ends[-1]
    # a pair's row: its expert's first block, then its turn among the
    # pairs that chose the expert (token order)
    row0 = (ends - blocks) * BLOCK_ROWS
    dest = jnp.sum(jnp.where(chose, row0[None, :] + seen - 1, 0), axis=1)
    dest = jnp.where(held.reshape(-1), dest, -1).reshape(t, k)
    b = jnp.arange(nb, dtype=jnp.int32)
    of_block = jnp.sum(ends[None, :] <= b[:, None], axis=1)
    last = jnp.max(jnp.where(counts > 0,
                             jnp.arange(experts, dtype=jnp.int32), 0))
    block_expert = jnp.where(b < n_blocks, of_block, last).astype(jnp.int32)
    return dest.astype(jnp.int32), block_expert, n_blocks.astype(jnp.int32), \
        counts


def _ffn_kernel(be_ref, nb_ref, x_ref, wrow_ref, wg_ref, wu_ref, wd_ref,
                o_ref, *acc, tiles):
    """One block of ``BLOCK_ROWS`` rows through ONE TILE of its expert's
    SwiGLU: the tile's columns of the gate and up products, each row
    scaled by its routing weight, times the tile's rows of the down
    projection.  An expert of one tile is done there; one of several
    sums the tiles' products in ``acc`` (float32, every block's rows: the
    grid walks all blocks a tile, so a block comes back once a tile) and
    writes the sum so far each time, the last being the whole."""
    del be_ref                          # read by the weights' index maps
    b = pl.program_id(0 if tiles == 1 else 1)
    in_use = b < nb_ref[0]
    first_tile = pl.program_id(0) == 0      # (read outside the branches)

    @pl.when(in_use)
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * u * wrow_ref[:, :1]
        y = jnp.dot(h.astype(x.dtype), wd_ref[0],
                    preferred_element_type=jnp.float32)
        if tiles > 1:
            rows = pl.ds(pl.multiple_of(b * BLOCK_ROWS, BLOCK_ROWS),
                         BLOCK_ROWS)
            y = jnp.where(first_tile, y, acc[0][rows, :] + y)
            acc[0][rows, :] = y
        o_ref[...] = y.astype(o_ref.dtype)

    @pl.when(jnp.logical_not(in_use))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ffn_pallas(x_rows, w_rows, block_expert, n_blocks, w_gate, w_up,
                w_down, interpret=False):
    """``_ffn_kernel`` over the block layout.  Jitted like the paged
    kernels: a program's expert layers share one traced and lowered
    body.

    An expert's three matrices are held in VMEM a TILE of ``TILE_WIDTH``
    of its width at a time, two buffers each: at 2,048 x 512 (Laguna) the
    expert is one tile, 3 x 2.1 MB x 2 = 12.6 MB, and the grid is a step a
    block as it always was; at 2,048 x 2,048 (ZAYA) whole matrices would
    be 3 x 8.4 MB x 2 = 50.3 MB, over ``vmem_limit_bytes``, so the grid
    gains a LEADING axis over the four tiles (the same 12.6 MB of weight
    buffers) and a float32 accumulator of every block's rows (432 rows x
    2,048 x 4 B = 3.5 MB at 192 tokens).  Tiles outermost: within a tile
    a run of blocks of one expert still names one weight block, so each
    tile of each touched expert is fetched once a run, as each expert was.
    A width that is no multiple of the tile is one tile."""
    rows, m = x_rows.shape
    _, _, hidden = w_gate.shape
    nb = rows // BLOCK_ROWS
    tile = TILE_WIDTH if hidden % TILE_WIDTH == 0 else hidden
    tiles = hidden // tile
    if tiles > 1 and rows * m * 4 > ACC_BYTES:
        raise NotImplementedError(
            f"{rows} rows of {m} would hold {rows * m * 4} bytes of float32 "
            f"partial sums in VMEM (limit {ACC_BYTES}) beside the weight "
            f"tiles of {TILE_WIDTH}: an expert wider than a tile is served "
            "a step's tokens, not a training batch, and a share of few "
            "experts that wide (16 of 4,096 x 2,048 over 98 tokens at "
            "top-8) takes the dense product")
    wrow = jnp.broadcast_to(w_rows.astype(jnp.float32)[:, None], (rows, 128))

    # the grid's ids, then the two prefetched tables; one tile: (b,)
    def of_block(*a):
        return (a[-3], 0)

    def of_expert_cols(*a):
        return (a[-2][a[-3]], 0, a[0] if tiles > 1 else 0)

    def of_expert_rows(*a):
        return (a[-2][a[-3]], a[0] if tiles > 1 else 0, 0)

    return pl.pallas_call(
        functools.partial(_ffn_kernel, tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # block_expert, n_blocks
            grid=(nb,) if tiles == 1 else (tiles, nb),
            in_specs=[pl.BlockSpec((BLOCK_ROWS, m), of_block),
                      pl.BlockSpec((BLOCK_ROWS, 128), of_block),
                      pl.BlockSpec((1, m, tile), of_expert_cols),
                      pl.BlockSpec((1, m, tile), of_expert_cols),
                      pl.BlockSpec((1, tile, m), of_expert_rows)],
            out_specs=pl.BlockSpec((BLOCK_ROWS, m), of_block),
            scratch_shapes=([] if tiles == 1 else
                            [pltpu.VMEM((rows, m), jnp.float32)])),
        out_shape=jax.ShapeDtypeStruct((rows, m), x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(("arbitrary",) if tiles == 1
                                 else ("arbitrary", "arbitrary")),
            # three weight tiles, two buffers each, 12.6 MB at 2,048 x 512
            # whatever the expert's width; the accumulator beside them
            vmem_limit_bytes=48 * 1024 * 1024),
        name="moe_grouped_ffn", interpret=interpret,
    )(block_expert, n_blocks.reshape(1), x_rows, wrow, w_gate, w_up, w_down)


def _ffn_xla(x_rows, w_rows, block_expert, n_blocks, w_gate, w_up, w_down):
    """The same rows through ``lax.ragged_dot``, a group an expert: the
    sizes are the experts' whole blocks.  The rows past the last group
    are UNWRITTEN by XLA's grouped product on the TPU (PERF.md, PR 29):
    they are zeroed here, as the kernel zeroes its blocks not in use."""
    experts = w_gate.shape[0]
    in_use = jnp.arange(block_expert.shape[0]) < n_blocks
    sizes = jnp.sum((block_expert[:, None] == jnp.arange(experts)[None, :])
                    & in_use[:, None], axis=0).astype(jnp.int32) * BLOCK_ROWS
    g = lax.ragged_dot(x_rows, w_gate, sizes,
                       preferred_element_type=jnp.float32)
    u = lax.ragged_dot(x_rows, w_up, sizes,
                       preferred_element_type=jnp.float32)
    live = jnp.repeat(in_use, BLOCK_ROWS)[:, None]
    h = jnp.where(live, jax.nn.silu(g) * u * w_rows[:, None], 0.0)
    y = lax.ragged_dot(h.astype(x_rows.dtype), w_down, sizes,
                       preferred_element_type=jnp.float32)
    return jnp.where(live, y, 0.0).astype(x_rows.dtype)


def grouped_swiglu(x, idx, weight, held, w_gate, w_up, w_down,
                   interpret=False):
    """sum over a token's HELD pairs of weight x SwiGLU_expert(x).

    x [T, M]; idx [T, k] int32, in ``0 .. E - 1`` over the stacked weights
    [E, M, H] / [E, H, M] wherever ``held``; weight [T, k] float32; held
    [T, k] bool.
    Returns ``(y [T, M], pairs an expert got [E] int32, rows computed
    int32: the blocks in use)``."""
    t, m = x.shape
    experts = w_gate.shape[0]
    dest, block_expert, n_blocks, counts = plan(idx, held, experts)
    rows = block_expert.shape[0] * BLOCK_ROWS
    r = jnp.arange(rows, dtype=jnp.int32)
    at = dest[None, :, :] == r[:, None, None]                  # [R, T, k]
    place = jnp.any(at, axis=2)                                # [R, T]
    x_rows = jnp.dot(place.astype(x.dtype), x,
                     preferred_element_type=jnp.float32).astype(x.dtype)
    w_rows = jnp.sum(jnp.where(at, weight.astype(jnp.float32)[None], 0.0),
                     axis=(1, 2))
    if _use_pallas() or interpret:
        y_rows = _ffn_pallas(x_rows, w_rows, block_expert, n_blocks, w_gate,
                             w_up, w_down, interpret=interpret)
    else:
        y_rows = _ffn_xla(x_rows, w_rows, block_expert, n_blocks, w_gate,
                          w_up, w_down)
    y = jnp.dot(place.T.astype(x.dtype), y_rows,
                preferred_element_type=jnp.float32).astype(x.dtype)
    return y, counts, n_blocks * BLOCK_ROWS
