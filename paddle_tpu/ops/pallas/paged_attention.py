"""Paged attention + paged KV cache for serving (TPU decode path).

Capability parity: the reference's block attention serving stack —
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and
python/paddle/incubate/nn/functional/block_multihead_attention.py: KV lives
in fixed-size pages, a per-sequence block table maps logical positions to
pages, decode attends one query token against the paged cache.

TPU-native design (see /opt/skills/guides/pallas_guide.md):
  - the decode kernel is a Pallas grid (batch, kv_heads // hb): a grid
    step owns a row and a GROUP of ``hb`` of its kv heads
    (``walk_head_group``: from the shapes and a VMEM budget, all the heads
    where they fit) and WALKS THE ROW'S OWN CONTEXT in blocks of several
    pages (``walk_block_pages``: 128-512 tokens, from the rows of the
    SCORE TILE the kernel forms, the page's shape and a VMEM budget: 512
    for every tile of up to 512 rows, so for every ragged bucket, whose
    tile is at most 128; under a sliding window no more than the tile's
    reach, 256 at a window of 128) — ``ceil(length / block)`` blocks
    whatever the page table's width, so a table pinned wide for a
    compile-free window costs what a tight one costs;
  - the pools stay in HBM (``memory_space=pl.ANY``); the lengths and the
    page table ride in as SCALAR-PREFETCH arguments, and the kernel
    starts one asynchronous copy a page and a pool FOR ALL THE STEP'S
    HEADS (``pltpu.make_async_copy`` of ``pool.at[heads, page]``: the
    pool's head axis strided, as the append stages a page) into a VMEM
    buffer two blocks deep: the next block's pages are in flight while
    this block's scores and products are computed, a head at a time.
    Nothing is fetched past a row's length — the same discipline as jax's
    production paged_attention kernel, with per-row query spans and the
    int8 scale pools besides;
  - online softmax in VMEM scratch across blocks; the tail block is
    column-masked (scores) and its unfetched slots zeroed (values);
  - the ragged kernel computes A ROW'S OWN QUERIES: the (span x group)
    query block is cut into tiles of whole query positions
    (``query_tile_rows``: 128 rows, 96 for a group of 6) and scores,
    softmax and products run for the ``ceil(q_len * group / tile)`` live
    tiles of each context block, so a one-token row of a 128-wide bucket
    costs one tile a block and not the bucket; a (tile x block) score
    tile is the largest array of scores there ever is, so the walk's
    block is cut by the TILE's rows and not by the bucket's; dead
    queries are zeros;
  - and it TAKES THEM FROM THE STEP'S PACKED TOKENS: its query operand is
    the packed stream, kv-head-major with a token on an untiled major
    axis (``[kv_heads, tokens, group, d]``, a re-layout of the step's
    tokens), in HBM like the pools; each row's offset rides with the
    lengths, a grid step copies its row's live tiles from there and
    writes the row's own positions of the output back there.  No array
    has the (rows x span) rectangle's size; the rectangle of the public
    signature is the packed stream whose rows start a span apart;
  - GQA: the q-head group of each kv head computes together (group x
    head_dim MXU tiles);
  - the append (``append_rows``) keeps a pool in the one layout the
    kernels read: a Pallas call aliased onto the pool stages the page a
    new row belongs to, selects the row into its slot and writes the
    page back, real positions only — no pool-sized copy in a step;
  - off-TPU the same math runs as gather + dense masked attention (the
    correctness reference).

The page allocator (PagedKVCache) is host-side bookkeeping like the
reference's BlockTable scheduler; page data lives on device.
"""
from __future__ import annotations

import functools
import hashlib
import math
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DEFAULT_MASK_VALUE, _use_pallas
from ...testing import faults as _faults


# -------------------------------------------------------- int8 KV quant
def quantize_kv(x):
    """Symmetric int8 quantization for KV appends (ISSUE 9): per-token,
    per-head absmax over the head_dim axis.  x (..., d) float ->
    (q int8 (..., d), scale f32 (..., 1)).  Scales are per-SLOT because
    pages are append-only: a per-page scale would have to grow when a
    later token's absmax exceeds the page's, silently corrupting the
    already-stored int8 values of earlier tokens.

    ONE symmetric-int8 rule for the whole tree: this delegates to
    ``quant_matmul.dynamic_act_quant`` so the engine's round-trip
    exactness contracts can never drift between the KV and activation
    quantizers."""
    from .quant_matmul import dynamic_act_quant
    return dynamic_act_quant(x)


def dequantize_kv(q, scale, dtype):
    """Invert :func:`quantize_kv`: int8 values x broadcast f32 scales,
    cast back to the cache's compute ``dtype``.  The ONE dequant rule
    every consumer shares — the paged-attention gathers, the traced
    scatter's returned values, and prefill's round-trip fake-quant —
    so 'attention sees exactly what the pages hold' can never drift
    between sites."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


# ------------------------------------------------------------------ kernel
#: what one block of the walk may take of VMEM: the float32 score tile
#: (the rows the kernel computes at once x block tokens), and ONE kv head's
#: double K and V buffers with their scale buffers.  Both sized for the
#: v5e's 16 MB of scoped VMEM with room for the score tile's temporaries
#: (mask, exponentials, their bf16 copy).
_SCORE_BLOCK_BYTES = 1 << 20
_KV_BUFFER_BYTES = 2 << 20
_MAX_BLOCK_TOKENS = 512
#: what the kv heads of one grid step may hold of VMEM together
#: (``walk_head_group``), and what a call asks the compiler for: that and
#: the room one head's block always had (the v5e has 128 MiB of VMEM)
_HEAD_GROUP_BYTES = 32 << 20
_VMEM_LIMIT_BYTES = _HEAD_GROUP_BYTES + (16 << 20)


def _round_up(x, m):
    return -(-x // m) * m


def k_pack(head_dim):
    """KV heads whose K rows lie SIDE BY SIDE in one row of a K pool: 1,
    except for a head wider than a lane tile that is not whole tiles (192:
    the TPU lays an array's last axis out in tiles of 128 lanes, so a pool
    ``[..., 192]`` takes 256 in HBM and Mosaic cannot slice a page out of
    it).  Such heads are stored in the fewest that fill whole tiles (two
    of 192 = 384 = three tiles), the pool ``(kv_heads / n, pages,
    page_size, n * head_dim)``: no byte of padding in HBM, and a page of
    it is one aligned copy.  The kernels read the packing off the pools'
    shapes (V's head axis over K's)."""
    if head_dim <= 128 or head_dim % 128 == 0:
        return 1
    return 128 // math.gcd(128, head_dim)


def _own_lane(q_heads, kv_heads, pack):
    """(q_heads,) which of a packed K row's ``pack`` heads a query head's
    own kv head is."""
    return (jnp.arange(q_heads) // (q_heads // kv_heads)) % pack


def packed_queries(q, k_pages, v_pages):
    """Queries (..., q_heads, d) as the kernels multiply them by a K pool
    whose rows hold several heads side by side (``k_pack``): (..., q_heads,
    n * d), a head's values in the lanes of its own kv head and zeros under
    the row's other heads (exact: their products are zeros).  As they are
    for a pool of one head a row, or when already so packed.  The ragged
    step packs its tokens' queries before its call; a (rows, span)
    rectangle handed to ``paged_attention_ragged`` is packed by the call."""
    kv_heads, wide = v_pages.shape[0], k_pages.shape[-1]
    pack = kv_heads // k_pages.shape[0]
    if pack == 1 or q.shape[-1] == wide:
        return q
    mine = (_own_lane(q.shape[-2], kv_heads, pack)[:, None]
            == jnp.arange(pack)[None, :])                   # (q_heads, pack)
    return jnp.where(mine[:, :, None], q[..., None, :], 0) \
        .reshape(q.shape[:-1] + (wide,))


def _unpacked_queries(q, k_pages, v_pages):
    """The oracles' inverse of :func:`packed_queries`: a head's own lanes."""
    kv_heads, wide = v_pages.shape[0], k_pages.shape[-1]
    pack = kv_heads // k_pages.shape[0]
    if pack == 1 or q.shape[-1] != wide:
        return q
    q_heads = q.shape[-2]
    split = q.reshape(q.shape[:-1] + (pack, wide // pack))
    lane = _own_lane(q_heads, kv_heads, pack).reshape(
        (1,) * (q.ndim - 2) + (q_heads, 1, 1))
    return jnp.take_along_axis(split, lane, axis=-2)[..., 0, :]


def _k_head_dim(k_pages, v_pages):
    """A K head's width, whatever the rows of its pool hold."""
    return k_pages.shape[-1] * k_pages.shape[0] // v_pages.shape[0]


def _page_vmem_bytes(page_size, head_dim, kv_dtype):
    """VMEM one page of one kv head takes in a walk buffer: whole tiles of
    its dtype (8 sublanes of 32 bits, so 8 / 16 / 32 rows by itemsize), and
    in the int8 mode its (page_size, 1) float32 scales over a lane tile."""
    item = jnp.dtype(kv_dtype).itemsize
    lanes = _round_up(head_dim, 128)
    page_bytes = _round_up(page_size, 32 // item) * lanes * item
    if item == 1:
        page_bytes += _round_up(page_size, 8) * 128 * 4
    return page_bytes


def _kv_page_vmem_bytes(page_size, head_dim, v_dim, kv_dtype):
    """VMEM a page of one kv head takes in the K and the V walk buffers
    together: a K row holds ``k_pack`` heads, so a head's part of it is
    unpadded; V by its own width."""
    pack = k_pack(head_dim)
    return (_page_vmem_bytes(page_size, pack * head_dim, kv_dtype) // pack
            + _page_vmem_bytes(page_size, v_dim, kv_dtype))


def walk_block_pages(page_size, head_dim, tile_rows, kv_dtype, v_dim=None,
                     reach=None):
    """Pages one block of the kernel's walk holds, from shapes alone: as
    many as keep the float32 SCORE TILE (``tile_rows`` x tokens) and ONE
    kv head's double-buffered K and V pages (with the int8 mode's scale
    pages) inside their VMEM budgets, at most 512 tokens, at least one
    page.  ``tile_rows`` is the rows of the one score array the kernel
    forms at a time (``walk_cut``): the ragged kernel's query tile
    (``query_tile_rows``, at most 128 rows, whatever the bucket: its block
    is 512 tokens at every bucket a cell runs), the whole ``n_query *
    group`` block of the one-query and the uniform verify kernels.
    ``head_dim`` is K's width and ``v_dim`` V's where it is another (each
    buffer is budgeted by its own width).  Whole multiples of 128 tokens
    where that many fit, so the score tile's lane axis is unpadded.  The
    table's width is NOT an input: a row's blocks are cut the same
    whatever table carries them, which is what makes a pinned table free
    and its results bit-identical to a tight one's.  Nor are the heads a
    grid step owns (``walk_head_group``): the buffers grow with them, the
    block does not.  ``reach`` (a windowed call: ``walk_cut``) is the most
    columns any score tile of the call can see, and a block holds no more
    than that in whole 128s: the rest would be columns no query sees
    (a window of 128 walks in 256, one of 512 in 512)."""
    page_bytes = _kv_page_vmem_bytes(page_size, head_dim, v_dim or head_dim,
                                     kv_dtype)
    by_kv = _KV_BUFFER_BYTES // (2 * page_bytes)     # K, V x two slots
    by_score = _SCORE_BLOCK_BYTES // (4 * _round_up(tile_rows, 8)
                                      * page_size)
    pages = min(_MAX_BLOCK_TOKENS // page_size, by_kv, by_score)
    if reach is not None:
        pages = min(pages, -(-_round_up(reach, 128) // page_size))
    pages = max(1, pages)
    per_128 = 128 // math.gcd(128, page_size)        # pages to 128 tokens
    if pages >= per_128:
        pages -= pages % per_128
    return pages


@functools.lru_cache(maxsize=None)      # the host asks at every step
def walk_head_group(kv_heads, page_size, head_dim, rows, kv_dtype, q_dtype,
                    v_dim=None, sinks=False, tile_rows=None, reach=None):
    """KV heads one grid step of the kernel owns, from shapes alone: the
    largest divisor of the call's ``kv_heads`` whose heads together keep
    their q and out blocks (two buffers each — the pipeline's, or in the
    ragged kernel the queries as copied and as rows and the outputs'
    stage, which take less; q as wide as K, out as wide as V: ``v_dim``
    where that is not ``head_dim``), their
    softmax scratch (m, l, acc in float32), with ``sinks`` the rows' sink
    block (two buffers), and their double K and V buffers of
    ``walk_block_pages`` pages inside ``_HEAD_GROUP_BYTES``.  ``rows`` is
    the bucket's ``n_query * group`` (what the blocks and the scratch
    hold); ``tile_rows`` the score tile's where the kernel cuts the bucket
    into tiles (the ragged kernel: ``walk_cut``), which with a windowed
    call's ``reach`` is what the buffers' block is cut by.  A page copy
    then serves the whole group: ONE descriptor a (row, group, page, pool)
    where a grid step of one head issued one a head.  All the heads for
    the one-query and verify kernels (a few KB a head beside the buffers)
    and for the ragged kernel's 512 / 768 / 1,024-row buckets at 8 heads
    (1.8 / 2.5 / 3.1 MB a head) and MiMo's 2,048 rows at 4 (8.0 MB a
    head, queries of 384 lanes), 4 of its 8 sliding heads (1,024 rows and
    their sinks: 5.4 MB); 1 is the grid of one head a step.  The walk of
    a (row, head) does not depend on the group it rides in."""
    v_dim = v_dim or head_dim
    # packed K rows (``k_pack``): a head's queries ride in a row as wide
    # as the pack's, zeros beside them, and a step owns whole packs
    pack = k_pack(head_dim)
    lanes, v_lanes = _round_up(pack * head_dim, 128), _round_up(v_dim, 128)
    q_item = jnp.dtype(q_dtype).itemsize
    block_pages = walk_block_pages(page_size, head_dim, tile_rows or rows,
                                   kv_dtype, v_dim, reach)
    per_head = (
        2 * block_pages * _kv_page_vmem_bytes(page_size, head_dim, v_dim,
                                              kv_dtype)
        + 2 * _round_up(rows, 32 // q_item) * (lanes + v_lanes) * q_item
        + _round_up(rows, 8) * (128 + 128 + v_lanes) * 4       # m, l, acc
        + (2 * _round_up(rows, 8) * 128 * 4 if sinks else 0))
    return max(g for g in range(pack, kv_heads + 1, pack)
               if kv_heads % g == 0
               and (g == pack or g * per_head <= _HEAD_GROUP_BYTES))


def walk_cut(kv_heads, page_size, head_dim, n_query, group, kv_dtype,
             q_dtype, v_dim=None, sinks=False, ragged=True, window=None):
    """``(tile_rows, block_pages, head_group)`` of one paged call, from
    shapes alone: THE rule the call builds its program by
    (``_decode_call``) and the host counts by (``kv_tokens_walked``,
    ``page_copies``), so the three cannot disagree.  ``tile_rows`` is the
    rows of the score tile the kernel forms: the ``ragged`` kernel's query
    tile (``query_tile_rows``), the whole ``n_query * group`` block of the
    one-query and the uniform verify kernels (one static tile; a bucket
    of one query is one tile either way).  The walk's block
    (``walk_block_pages``) and the heads of a grid step
    (``walk_head_group``, whose buffers hold that block) follow from
    it, and under a sliding ``window`` from the tile's REACH: its
    ``tile / group`` query positions see their own ``window`` keys each,
    from the page of the first of them (``window_first_token``) — at most
    ``window + positions - 1 + page_size`` columns."""
    rows = n_query * group
    tile = query_tile_rows(rows, group, q_dtype) if ragged else rows
    reach = None if window is None \
        else window + tile // group - 1 + page_size
    return (tile,
            walk_block_pages(page_size, head_dim, tile, kv_dtype, v_dim,
                             reach),
            walk_head_group(kv_heads, page_size, head_dim, rows, kv_dtype,
                            q_dtype, v_dim, sinks, tile, reach))


def window_first_token(lengths, q_lens, window, page_size):
    """First position of the page that holds a row's first VISIBLE key
    under a sliding ``window``: the row's first query stands at position
    ``length - q_len`` and sees the ``window`` keys that end with itself,
    so nothing before ``length - q_len + 1 - window`` is visible to any
    query of the row.  The one rule of the kernels' walk, of their XLA
    oracles' mask and of the host's count (numpy or traced integers)."""
    start = lengths - q_lens + 1 - window
    xp = jnp if isinstance(start, jax.Array) else np
    return xp.maximum(start, 0) // page_size * page_size


def kv_tokens_walked(lengths, block_tokens, window=None, q_lens=1,
                     page_size=1):
    """KV positions the kernel walks for rows of these ``lengths``: every
    row costs its context rounded up to whole blocks,
    ``ceil(length / block) * block`` — the rule ``_decode_kernel``'s loop
    bound applies per (row, kv head), here on the host for the dispatch
    record (``kernel.paged_attn.walk_useful``).  Under a ``window`` the
    walk starts at the page of the row's first visible key
    (``window_first_token``) and costs the rest in whole blocks."""
    lengths = np.asarray(lengths, np.int64)
    if window is not None:
        lengths = lengths - window_first_token(
            lengths, np.asarray(q_lens, np.int64), int(window), page_size)
    return int((-(-lengths // block_tokens) * block_tokens).sum())


def kv_pages_copied(lengths, page_size, table_pages, window=None, q_lens=1):
    """Pages the kernel copies for rows of these ``lengths``, a (kv head,
    pool): the pages that hold a row's context, never past the table and
    under a ``window`` from the page of the row's first visible key —
    ``_decode_kernel``'s ``n_pages``, here on the host for the dispatch
    record.  Nothing is copied past a row's length, so this is NOT the
    walk in whole blocks (``kv_tokens_walked``).  A call issues one
    descriptor a page for each GROUP of ``walk_head_group`` heads and each
    pool (K, V and in the int8 mode their scales), and serves one
    (page, head, pool) read a head (``kernel.paged_attn.copy_share``)."""
    lengths = np.asarray(lengths, np.int64)
    pages = np.minimum(-(-lengths // page_size), table_pages)
    if window is not None:
        pages = pages - window_first_token(
            lengths, np.asarray(q_lens, np.int64), int(window),
            page_size) // page_size
    return int(pages.sum())


def kv_tokens_visible(lengths, q_lens, window=None):
    """KV positions some query of each row attends: the row's context, or
    under a ``window`` its last ``window + q_len - 1`` positions."""
    lengths = np.asarray(lengths, np.int64)
    if window is not None:
        lengths = np.minimum(
            lengths, int(window) + np.asarray(q_lens, np.int64) - 1)
    return int(lengths.sum())


#: rows of one query tile of the ragged kernel: the height at which a
#: chunk row's products still fill the 128 x 128 MXU
_QUERY_TILE_ROWS = 128


@functools.lru_cache(maxsize=None)      # the host asks at every step
def query_tile_rows(rows, group, q_dtype):
    """Rows of one query tile of the ragged kernel, from shapes alone.
    The kernel's ``rows = n_query * group`` query rows (row =
    ``s * group + g``) are cut into tiles of whole query positions
    (multiples of ``group``) and whole sublane tiles of the q dtype (8
    rows of 32 bits: 16 of bfloat16), the tallest such tile of at most
    128 rows that divides the bucket: 128 rows for groups 4 and 8 (32 and
    16 positions), 96 for a group of 6 (16 positions).  A bucket that no
    such tile divides (a verify bucket of a few tokens) is one tile."""
    unit = math.lcm(group, 32 // jnp.dtype(q_dtype).itemsize)
    fits = [t for t in range(unit, min(rows, _QUERY_TILE_ROWS) + 1, unit)
            if rows % t == 0]
    return fits[-1] if fits else rows


def live_query_tiles(q_lens, group, tile):
    """Tiles of ``tile`` rows that hold the live queries of rows of
    these ``q_lens``: a row's queries are its first ``q_len * group``
    rows.  The one rule of the kernel's trip count and of the host's
    count (numpy or traced integers)."""
    return (q_lens * group + tile - 1) // tile


def q_positions_computed(q_lens, n_query, group, q_dtype):
    """Query positions the ragged kernel computes for rows of these
    ``q_lens`` in an ``n_query`` bucket: every row costs its own queries
    rounded up to whole tiles, ``live_query_tiles`` tiles of
    ``tile / group`` positions — ``_decode_kernel``'s trip count a (row,
    kv head), here on the host for the dispatch record
    (``kernel.paged_attn.query_useful``).  A bucket of one position is
    the one-query kernel's: a position a row."""
    tile = query_tile_rows(n_query * group, group, q_dtype)
    tiles = live_query_tiles(np.asarray(q_lens, np.int64), group, tile)
    return int(tiles.sum()) * (tile // group)


def q_positions_moved(q_lens, n_query, group, q_dtype):
    """Query positions a paged call copies from HBM into VMEM for rows of
    these ``q_lens`` in an ``n_query`` bucket: the ragged kernel copies
    what it computes, a row's live tiles from the row's offset on the
    packed stream (``_decode_kernel``'s ``each_query_copy`` rides the
    loop of its products, so this IS ``q_positions_computed``), where a
    block a row moved ``n_query`` positions whatever the row held — the
    (rows x span) rectangle ``kernel.paged_attn.query_moved_share`` holds
    this against.  The outputs go back a row's own ``q_len`` positions,
    never more.  The one-query kernel's block is a position a row."""
    return q_positions_computed(q_lens, n_query, group, q_dtype)


def _decode_kernel(lens_ref, tabs_ref, q_ref, k_hbm, v_hbm, *rest,
                   scale, page_size, block_pages, n_query=1, group=1,
                   quantized=False, ragged=False, window=None, tile=None,
                   sinks=False):
    """Online-softmax paged attention for ``n_query`` query tokens per
    sequence, one grid step per (row, group of ``hb`` kv heads: the head
    axis of the blocks and buffers it is handed).  The step WALKS THE
    ROW'S OWN CONTEXT: ``ceil(length / block)`` blocks of ``block_pages``
    pages, whatever the table's width.  The pools stay in HBM; the kernel
    reads the page indices from the scalar-prefetched table and starts ONE
    asynchronous copy a page and a pool for all its heads into a VMEM
    buffer, two buffers deep, so the next block's pages are in flight
    while this block's scores and products are computed — a head at a
    time over the same block: a (row, head)'s blocks, their order and
    every operation on them are those of a step that owns the one head.
    Only pages that hold context are fetched: nothing is issued past the
    row's length, the tail block's unfetched slots are masked by column
    (scores) and zeroed (values).

    ``n_query == 1`` is the classic decode step; n_query > 1 is the
    RAGGED MULTI-QUERY verify path (speculative decoding): the
    block's tokens are already scattered into the pages, ``lens`` counts
    them, and query ``s`` of the block attends causally to
    ``cols < length - (n_query - 1 - s)`` — per-row, per-query limits,
    so variable accept lengths cost masking, not padding.

    ``ragged`` (ISSUE 17): ``lens_ref`` is (3, batch) — kv lengths in
    row 0, PER-ROW query-span lengths in row 1, where each row's queries
    start on the step's PACKED token axis in row 2 — and ``q_ref`` and
    ``o_ref`` are that axis whole, in HBM: ``[kv_heads, tokens, group,
    d]`` (the group padded to whole sublane tiles, ``_staged_group``).
    The step copies its row's live tiles of positions into a stage (one
    descriptor a tile for all its heads, started before the first
    block's pages and waited for behind them), makes rows of them (row =
    ``s * group + g``: what a block a row held before), and after the
    walk copies the row's OWN ``qlen`` positions of the output back, by
    the bits of ``qlen``: the next row's tokens stand right behind this
    row's, so nothing may land past them, and the positions no row owns
    keep the zeros the output is aliased onto.  Query ``j``
    of row ``b`` attends ``cols < kv - qlen + j + 1``.  One grid shape
    then serves a batch mixing decode rows (qlen 1), prefill/chunk
    spans, and verify blocks — and THE QUERY WORK OF A GRID STEP FOLLOWS
    THE ROW'S OWN ``qlen``: the (n_query * group, d) query block is cut
    into tiles of ``tile`` rows (whole query positions, whole sublane
    tiles: ``query_tile_rows``), row = ``s * group + g`` puts the row's
    live queries in its first ``qlen * group`` rows, and the scratch
    reset, each context block's scores, mask, online softmax and P.V,
    and the final division run for the ``ceil(qlen * group / tile)``
    live tiles only; a block's K and V are loaded once and shared by its
    tiles.  A one-token row is one tile, a full chunk row all of them
    (what the whole block computed before the cut).  A (tile, block)
    score tile is the largest array of scores the kernel forms, so the
    walk's block is cut by the TILE's rows (``walk_cut``: 512 tokens at
    every tile of up to 512 rows, whatever the bucket; no more than a
    window's reach); every tile of a
    row walks the same blocks in the same order, so a live query's output
    does not depend on the tile it falls in.  Dead queries (j >= qlen)
    COME BACK AS ZEROS: whole dead tiles are never computed, and those of
    the last live tile (which clamp at the full kv length and compute
    finite values from whatever the stream holds behind the row) are
    never copied out.  The two uniform modes take the whole block as one
    static tile, and their walk's block is cut by that: the program they
    always were.

    ``quantized`` (ISSUE 9): the K/V pages arrive as INT8 with their
    per-slot f32 scale pages copied alongside — dequantization happens
    here in VMEM right before the MXU dots, so full-precision KV never
    round-trips HBM (the whole point of the int8 storage mode).

    ``window`` (a sliding-attention layer): a query at position ``p``
    sees keys ``p - window + 1 .. p``.  The walk STARTS at the page that
    holds the row's first visible key (``window_first_token``) — no copy
    is issued for a page before it — and the scores are masked on both
    sides; ``None`` is the causal walk from page 0, the same program as
    before the window existed.

    K and V need not be as wide as each other: the queries and the K
    buffer are ``k_buf``'s width, the V buffer, the accumulator and the
    output ``v_buf``'s (MiMo-V2-Flash: 192 and 128).

    ``sinks`` (a learned sink a query head): one more operand, the sink
    of each of the step's query rows over a lane tile (``sink_ref``
    (hb, rows, 128) float32, row ``s * group + g`` holds query head
    ``g``'s).  The softmax's denominator holds ``exp(sink)`` beside the
    visible keys' terms, and the sink gives no value: a row's running max,
    sum and accumulator START from it, ``(sink, 1, 0)``, where without it
    they start from ``(-inf, 0, 0)``; nothing else differs."""
    # operands: [K and V scales] [sinks] [the zeros the packed output is
    # aliased onto] out; scratch: K, V buffers [scale buffers] semaphores
    # m l acc [the ragged kernel's query and output stages]
    ins = 2 * quantized + sinks
    ks_hbm, vs_hbm = rest[:2] if quantized else (None, None)
    sink_ref = rest[ins - 1] if sinks else None
    ins += ragged
    o_ref, k_buf, v_buf = rest[ins:ins + 3]
    ks_buf, vs_buf = rest[ins + 3:ins + 5] if quantized else (None, None)
    if ragged:
        q_stage, q_rows, o_stage = rest[-3:]
        rest = rest[:-3]
    sems, m_scr, l_scr, acc_scr = rest[-4:]
    # what a page is copied from and to; its scale block travels with it
    pools = [(k_hbm, k_buf), (v_hbm, v_buf)]
    if quantized:
        pools += [(ks_hbm, ks_buf), (vs_hbm, vs_buf)]
    b = pl.program_id(0)
    hb = v_buf.shape[2]                 # the step's kv heads
    # a K row may hold several heads side by side (``k_pack``): the K
    # pool's head axis is then that many times shorter than V's
    pack = hb // k_buf.shape[2]
    heads = {n: pl.ds(pl.program_id(1) * n, n)
             for n in dict.fromkeys(buf.shape[2] for _, buf in pools)}
    block = block_pages * page_size

    length = lens_ref[0, b] if ragged else lens_ref[b]
    # the pages that hold the row's context (never past the table)
    n_pages = jnp.minimum(pl.cdiv(length, page_size), tabs_ref.shape[1])
    if window is None:
        page0 = tok0 = 0
    else:
        # the walk's pages and columns count from the first visible page
        qn = lens_ref[1, b] if ragged else n_query
        tok0 = window_first_token(length, qn, window, page_size)
        page0 = tok0 // page_size
        n_pages = n_pages - page0
    n_blocks = pl.cdiv(n_pages, block_pages)

    def each_page_copy(blk, slot, act):
        """``act`` on every copy of block ``blk``'s pages into buffer
        ``slot`` — the same descriptors to start and to wait on.  A
        descriptor carries a page of ALL the step's heads (the pool's
        head axis is strided, as in the append's staged page)."""
        first = blk * block_pages

        def body(i, carry):
            page = tabs_ref[b, page0 + first + i]
            for hbm, buf in pools:
                act(pltpu.make_async_copy(hbm.at[heads[buf.shape[2]], page],
                                          buf.at[slot, i], sems.at[slot]))
            return carry

        lax.fori_loop(0, jnp.minimum(block_pages, n_pages - first), body, 0)

    def load(buf, s_buf, slot, j, dtype):
        """Head ``j``'s part of buffer ``slot`` as (block tokens, d) in
        the compute dtype."""
        x = buf[slot, :, j]                     # (block_pages, page, d)
        if quantized:
            # per-slot dequant in VMEM: int8 page * (page_size, 1)
            # scale, ROUNDED through the compute dtype — the same
            # dequantize_kv rule every other consumer applies, so a
            # bf16 model's decode sees bit-identical K/V to what
            # prefill's fake-quant round-trip and the XLA gathers
            # produced (the exactness invariant)
            x = x.astype(jnp.float32) * s_buf[slot, :, j][:, :, :1]
        elif page_size % (32 // x.dtype.itemsize):
            # a page that is not whole tiles of its dtype folds into
            # the token axis as float32, whose 8-row tile it does fill
            x = x.astype(jnp.float32)
        return x.reshape(block, buf.shape[-1]).astype(dtype)

    def each_head(act):
        """``act(j)`` on every kv head of the step, one after another."""
        def body(j, carry):
            act(j)
            return carry

        lax.fori_loop(0, hb, body, 0)

    if ragged:
        # the row's own queries: ``qlen`` positions of the step's packed
        # stream from ``off`` on, which make the first ``qlen * group``
        # rows of its query block (row = s * group + g), in whole tiles
        qlen, off = lens_ref[1, b], lens_ref[2, b]
        n_tiles = live_query_tiles(qlen, group, tile)
        per = tile // group             # query positions a tile
        q_hbm, o_hbm = q_ref, o_ref     # the packed stream and its twin
        mine = heads[hb]                # the kv-head axis of both

        def each_tile(act):
            """``act(rows, row0)`` on every live tile of the query block:
            ``rows`` indexes the tile in a (rows, ...) ref."""
            if tile == m_scr.shape[1]:  # a bucket of one tile, of any height
                pl.when(n_tiles > 0)(lambda: act(slice(None), 0))
                return

            def body(t, carry):
                row0 = pl.multiple_of(t * tile, tile)
                act(pl.ds(row0, tile), row0)
                return carry

            lax.fori_loop(0, n_tiles, body, 0)

        def positions(row0, first=0):
            """The query positions of the tile whose rows start at
            ``row0``, counted from ``first``."""
            at = row0 // group if isinstance(row0, int) \
                else lax.div(row0, group)
            return pl.ds(first + at, per)

        def each_query_copy(act):
            """``act`` on the copy of every live tile's positions, all the
            step's heads a descriptor, from the packed stream into the
            stage.  A tile's tail past the row's own tokens is its
            successors' (or the stream's pad): dead queries."""
            def copy(rows, row0):
                act(pltpu.make_async_copy(
                    q_hbm.at[mine, positions(row0, off)],
                    q_stage.at[:, positions(row0)], sems.at[2]))

            each_tile(copy)

        def each_output_copy(act):
            """``act`` on the copies of the row's OWN ``qlen`` positions
            from the stage to the packed output: one a set bit of
            ``qlen``, so nothing lands on the next row's tokens, which
            stand right behind this row's.  (A row is no longer than its
            bucket, nor than the stream.)"""
            for bit in reversed(range(
                    min(n_query, o_hbm.shape[1]).bit_length())):
                at = (qlen >> (bit + 1)) << (bit + 1)   # the higher bits
                copy = pltpu.make_async_copy(
                    o_stage.at[:, pl.ds(at, 1 << bit)],
                    o_hbm.at[mine, pl.ds(off + at, 1 << bit)], sems.at[3])
                pl.when((qlen >> bit) & 1 == 1)(functools.partial(act, copy))

        each_query_copy(lambda c: c.start())
    else:
        def each_tile(act):             # the whole block, as one
            act(slice(None), None)

    # a tile's slice of a head's three (rows, lanes) float32 scratch arrays
    height = tile if ragged else m_scr.shape[1]
    narrow, wide = (height, m_scr.shape[2]), (height, acc_scr.shape[2])

    def reset(j):
        def tile_reset(rows, row0):
            if sinks:       # the sink's own term: exp(sink - m) = 1
                m_scr[j, rows] = sink_ref[j, rows]
                l_scr[j, rows] = jnp.ones(narrow, l_scr.dtype)
            else:
                m_scr[j, rows] = jnp.full(narrow, -jnp.inf, m_scr.dtype)
                l_scr[j, rows] = jnp.zeros(narrow, l_scr.dtype)
            acc_scr[j, rows] = jnp.zeros(wide, acc_scr.dtype)

        each_tile(tile_reset)

    each_head(reset)

    @pl.when(n_blocks > 0)
    def _first():
        each_page_copy(0, 0, lambda c: c.start())

    if ragged:
        # the staged positions as the rows the products take, while the
        # first block's pages are in flight: (positions, group, d) ->
        # (rows, d), a re-layout in VMEM where the group is not whole
        # sublane tiles (``_staged_group``: its padding is dropped here)
        each_query_copy(lambda c: c.wait())

        def stage_in(j):
            def tile_in(rows, row0):
                x = q_stage[j, positions(row0), :group]
                q_rows[j, rows] = x.reshape(tile, x.shape[-1])

            each_tile(tile_in)

        each_head(stage_in)

    def seen_by(shape, row0, blk):
        """Which of block ``blk``'s columns the queries of a ``shape``
        (rows, block) score tile that starts at row ``row0`` see: the
        same for every head."""
        cols = tok0 + blk * block + lax.broadcasted_iota(jnp.int32, shape, 1)
        # row r serves query position r // group of the block; its
        # causal window ends (n_query - 1 - qpos) tokens short of the
        # full length (the later block tokens it must not see)
        qrow = lax.broadcasted_iota(jnp.int32, shape, 0)
        if row0 is not None:
            qrow = qrow + row0
        qpos = qrow // group
        if ragged:
            # per-row span: query j's context is kv - qlen + j + 1
            # tokens; a full row (qlen == n_query) reduces this to
            # the verify limit below BIT-EXACTLY, so the unified step
            # can never drift from the legacy modes it replaces.  The
            # dead queries of a live tile (j >= qlen) clamp at the
            # full length: finite, and zeroed at the end
            limit = jnp.minimum(length, length - qlen + 1 + qpos)
        else:
            limit = length - (n_query - 1 - qpos)
        seen = cols < limit
        if window is not None:
            seen &= cols >= limit - window
        return seen

    def walk(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _next():
            each_page_copy(blk + 1, 1 - slot, lambda c: c.start())

        each_page_copy(blk, slot, lambda c: c.wait())

        # slots past the length were never fetched and hold whatever the
        # buffer held — their weights are exact zeros, and so must the
        # values be (0 * NaN)
        toks = tok0 + blk * block \
            + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        fetched = toks < length
        # one tile: the heads share its mask
        whole = None if ragged else seen_by((m_scr.shape[1], block), None,
                                            blk)

        def head(j):
            def keys():
                # a packed row: the head's queries are zeros beside the
                # lanes of the row's other heads
                return load(k_buf, ks_buf, slot,
                            j if pack == 1 else j // pack, q_ref.dtype)

            def values():
                # same rounding rule as the keys, then the SAME dot the
                # full-precision path runs on its pages
                v = load(v_buf, vs_buf, slot, j, q_ref.dtype)
                return jnp.where(fetched, v, jnp.zeros_like(v))

            if ragged:
                # a head's K and V of the block are loaded once and
                # shared by its tiles
                k_blk, v_blk = keys(), values()
                keys, values = (lambda: k_blk), (lambda: v_blk)

            def update(rows, row0):
                # (tile or rows, d)
                q = q_rows[j, rows] if ragged else q_ref[0, j, rows]
                s = lax.dot_general(q, keys(), (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                    * scale
                seen = seen_by(s.shape, row0, blk) if ragged else whole
                s = jnp.where(seen, s, DEFAULT_MASK_VALUE)

                m_prev = m_scr[j, rows, :1]
                m_next = jnp.maximum(m_prev,
                                     jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(m_prev - m_next)
                pexp = jnp.exp(s - m_next)
                l_scr[j, rows] = jnp.broadcast_to(
                    alpha * l_scr[j, rows, :1]
                    + jnp.sum(pexp, axis=1, keepdims=True), narrow)
                v = values()
                acc_scr[j, rows] = acc_scr[j, rows] * alpha \
                    + lax.dot_general(
                        pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_scr[j, rows] = jnp.broadcast_to(m_next, narrow)

            each_tile(update)

        each_head(head)
        return carry

    lax.fori_loop(0, n_blocks, walk, 0)

    def finish(j):
        def tile_finish(rows, row0):
            l = l_scr[j, rows, :1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            out = (acc_scr[j, rows] / l_safe).astype(o_ref.dtype)
            if ragged:
                # back to (positions, group, d) for the copies out
                o_stage[j, positions(row0), :group] = \
                    out.reshape(per, group, out.shape[-1])
            else:
                o_ref[0, j, rows] = out

        each_tile(tile_finish)

    each_head(finish)

    if ragged:
        # the row's own positions and no other: the dead queries of its
        # last live tile stay in the stage, and what no row owns keeps
        # the zeros the output was handed as
        each_output_copy(lambda c: c.start())
        each_output_copy(lambda c: c.wait())


def _staged_group(group, q_dtype):
    """The group axis of the ragged kernel's staged queries and outputs,
    ``[kv_heads, tokens, group, d]``: a token's (group, d) block is what
    one copy moves, so it has to be whole tiles of the array as Mosaic
    lays it out — sublane tiles of the next power of two over ``group``,
    from one 32-bit row (2 of bfloat16) to the dtype's full tile (8 rows
    of 32 bits).  2, 4, 8 and 16 of bfloat16 stand as they are; 6 is
    padded to 8 (Laguna), 3 to 4, 1 to 2; the kernel drops the padding
    where it makes rows of the stage."""
    packing = 4 // jnp.dtype(q_dtype).itemsize
    unit = min(8 * packing, max(packing, 1 << (group - 1).bit_length()))
    return _round_up(group, unit)


def _decode_call(q, k_pages, v_pages, lengths, page_tables, scale,
                 interpret=False, n_query=1, k_scales=None, v_scales=None,
                 q_lens=None, window=None, head_group=None, sinks=None,
                 row_off=None):
    """The ``pallas_call`` behind :func:`_decode_pallas` (its arguments).
    ``head_group``: the kv heads a grid step owns where a TEST wants
    another count than the shapes give (``walk_head_group``); no caller of
    the program passes it."""
    # K rows that hold several heads side by side (``k_pack``): the
    # queries as wide, zeros under a row's other heads, if the caller has
    # not packed them yet (the ragged step has, on its packed tokens)
    q = packed_queries(q, k_pages, v_pages)
    ragged = q_lens is not None
    batch, handed = lengths.shape[0], q.shape
    if ragged and q.ndim == 4:
        # the (rows, span) rectangle IS a packed stream: its rows start
        # ``span`` apart (a reshape)
        q = q.reshape((batch * n_query,) + q.shape[2:])
    q_heads, d = q.shape[-2:]
    kv_heads, _tot, page_size, dv = v_pages.shape   # the output: V's width
    pack = kv_heads // k_pages.shape[0]
    assert k_pages.shape[-1] == d, (k_pages.shape, v_pages.shape, q.shape)
    group = q_heads // kv_heads
    rows = n_query * group
    # the score tile the kernel forms, the walk's block and the heads of a
    # grid step: one rule, the host's too
    tile, block_pages, hb = walk_cut(
        kv_heads, page_size, d // pack, n_query, group, k_pages.dtype,
        q.dtype, dv, sinks is not None, ragged, window)
    hb = head_group or hb
    lanes, v_lanes = _round_up(d, 128), _round_up(dv, 128)

    if ragged:
        # the step's packed tokens, kv-head-major, a token on an UNTILED
        # major axis: (tokens, q_heads, d) -> (kv_heads, tokens, group,
        # d), so that a grid step copies its row's positions from
        # ``row_off`` on by themselves.  A re-layout of the step's tokens,
        # not of the (rows, span) rectangle; past them stand the ``per -
        # 1`` positions a whole-tile read at the last row's offset may
        # take (no rectangle's row needs them: a tile divides the span)
        tokens, staged = q.shape[0], _staged_group(group, q.dtype)
        slack = 0 if row_off is None else tile // group - 1
        q4 = q.reshape(tokens, kv_heads, group, d).transpose(1, 0, 2, 3)
        if slack or staged != group or lanes != d:
            q4 = jnp.pad(q4, [(0, 0), (0, slack), (0, staged - group),
                              (0, lanes - d)])
        q_lens = jnp.asarray(q_lens, jnp.int32)
        if row_off is None:
            row_off = jnp.arange(batch, dtype=jnp.int32) * n_query
        # the lengths, the spans and where each starts ride in ONE (3,
        # batch) scalar-prefetch argument — the index maps never read it.
        # No copy leaves the stream, whatever the caller's offsets
        lengths = jnp.stack([
            jnp.asarray(lengths, jnp.int32), q_lens,
            jnp.clip(row_off, 0, jnp.maximum(tokens - q_lens, 0))])
    elif n_query == 1:
        # (batch, q_heads, d) -> (batch, kv_heads, group, d): the kv-head
        # group rides as its own FULL axis so the q block's trailing dims
        # (group, d) match the array dims exactly — Mosaic requires
        # trailing block dims divisible by (8, 128) or spanning the whole
        # axis, and group (e.g. 3) satisfies neither as a partial slice of
        # q_heads.  Multi-query folds the query axis in as well (row =
        # s*group + g)
        q4 = q.reshape(batch, kv_heads, group, d)
    else:
        q4 = q.reshape(batch, n_query, kv_heads, group, d) \
             .transpose(0, 2, 1, 3, 4).reshape(batch, kv_heads, rows, d)

    quantized = k_scales is not None
    # Mosaic slices a page out of a pool only if the pool's lane axis is
    # whole 128-lane tiles.  A head_dim that is not (64) is zero-padded
    # to one — exact: the extra q.k terms are zeros, the extra output
    # columns are dropped — and the (page_size, 1) scale blocks are
    # broadcast over a tile.  Both are XLA copies of a pool per call
    # (the scale pools' was already there: the one-lane layout was
    # re-tiled for the kernel on every call).
    # (A K pool whose rows hold several heads is whole tiles as it is
    # stored, and no pool is copied for it.)
    if lanes != d:
        pad = [(0, 0)] * 3 + [(0, lanes - d)]
        k_pages = jnp.pad(k_pages, pad)
        if not ragged:
            q4 = jnp.pad(q4, pad)
    if v_lanes != dv:
        v_pages = jnp.pad(v_pages, [(0, 0)] * 3 + [(0, v_lanes - dv)])
    if quantized:
        k_scales, v_scales = (
            jnp.broadcast_to(x, x.shape[:-1] + (128,))
            for x in (k_scales, v_scales))
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=page_size,
                               block_pages=block_pages, n_query=n_query,
                               group=group, quantized=quantized,
                               ragged=ragged, window=window, tile=tile,
                               sinks=sinks is not None)

    def of_row(width):
        return pl.BlockSpec((1, hb, rows, width),
                            lambda b, g, lens, tabs: (b, g, 0, 0))

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [hbm if ragged else of_row(lanes), hbm, hbm]
    inputs = [lengths, page_tables, q4, k_pages, v_pages]

    # two buffers a pool: one block computing, the next in flight; a
    # page of the step's ``hb`` heads lands side by side
    def page_buf(heads, width):
        return pltpu.VMEM((2, block_pages, heads, page_size, width),
                          k_pages.dtype)

    scratch = [page_buf(hb // pack, lanes), page_buf(hb, v_lanes)]
    if quantized:
        in_specs += [hbm, hbm]
        inputs += [k_scales, v_scales]
        scale_buf = pltpu.VMEM((2, block_pages, hb, page_size, 128),
                               jnp.float32)
        scratch += [scale_buf, scale_buf]
    if sinks is not None:
        # query row ``s * group + g`` of kv head ``h`` is query head
        # ``h * group + g``: its sink over a lane tile, the same for
        # every row of the batch
        of_head = jnp.tile(sinks.astype(jnp.float32)
                           .reshape(kv_heads, 1, group), (1, n_query, 1))
        in_specs.append(pl.BlockSpec((hb, rows, 128),
                                     lambda b, g, lens, tabs: (g, 0, 0)))
        inputs.append(jnp.broadcast_to(
            of_head.reshape(kv_heads, rows, 1), (kv_heads, rows, 128)))
    scratch += [
        # one a buffer slot; the ragged kernel's queries in, outputs out
        pltpu.SemaphoreType.DMA((4 if ragged else 2,)),
        pltpu.VMEM((hb, rows, 128), jnp.float32),
        pltpu.VMEM((hb, rows, 128), jnp.float32),
        pltpu.VMEM((hb, rows, v_lanes), jnp.float32),
    ]
    out_shape = (batch, kv_heads, rows, v_lanes)
    aliases = {}
    if ragged:
        # the output is the packed stream's twin and stays in HBM too: a
        # grid step writes its row's own positions, and the rest (the
        # pack's pad, a rectangle's dead queries) are the zeros it is
        # aliased onto
        out_shape = (kv_heads, tokens, staged, v_lanes)
        in_specs.append(hbm)
        inputs.append(jnp.zeros(out_shape, q.dtype))
        aliases = {len(inputs) - 1: 0}
        scratch += [pltpu.VMEM((hb, n_query, staged, lanes), q.dtype),
                    pltpu.VMEM((hb, rows, lanes), q.dtype),
                    pltpu.VMEM((hb, n_query, staged, v_lanes), q.dtype)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # lengths, page_tables
        grid=(batch, kv_heads // hb),
        in_specs=in_specs,
        out_specs=hbm if ragged else of_row(v_lanes),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        name="paged_attention_ragged" if ragged else "paged_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*inputs)
    if ragged:
        return out[:, :, :group, :dv].transpose(1, 0, 2, 3) \
            .reshape(handed[:-1] + (dv,))
    out = out[..., :dv]
    if n_query == 1:
        return out.reshape(batch, q_heads, dv)
    return out.reshape(batch, kv_heads, n_query, group, dv) \
        .transpose(0, 2, 1, 3, 4).reshape(batch, n_query, q_heads, dv)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "n_query", "window"))
def _decode_pallas(q, k_pages, v_pages, lengths, page_tables, scale,
                   interpret=False, n_query=1, k_scales=None,
                   v_scales=None, q_lens=None, window=None, sinks=None,
                   row_off=None):
    """``q`` is (batch, q_heads, d) for n_query == 1, else
    (batch, n_query, q_heads, d).  ``k_scales``/``v_scales``
    (kv_heads, total_pages, page_size, 1) f32 mark the int8 KV mode.
    ``q_lens`` (batch,) int32 selects the RAGGED kernel: per-row query
    spans left-aligned in the n_query bucket (ISSUE 17), computed in
    tiles of :func:`query_tile_rows` rows, a row's live tiles only; its
    ``q`` may also be the step's packed tokens (tokens, q_heads, d) with
    ``row_off`` (batch,) saying where each row's queries start, and the
    output is then packed the same.

    The grid is (batch, kv_heads // hb): a grid step owns a row and
    ``hb`` of its kv heads (:func:`walk_head_group`: all of them where
    they fit).  The pools are handed over whole and stay in HBM, and each
    grid step walks its row's context in blocks of
    :func:`walk_block_pages` pages (see ``_decode_kernel``).

    Jitted, so that a program's layers, which all call it at the same
    shapes, share ONE traced and lowered kernel: a serving engine builds
    a program a (rows, span) bucket, each of them every layer deep."""
    return _decode_call(q, k_pages, v_pages, lengths, page_tables, scale,
                        interpret=interpret, n_query=n_query,
                        k_scales=k_scales, v_scales=v_scales,
                        q_lens=q_lens, window=window, sinks=sinks,
                        row_off=row_off)


def _gather_dequant(pages, scales, page_tables, batch, kv_heads,
                    max_tokens, last, out_dtype):
    """Gather table-indexed pages to (batch, kv_heads, T, last); with
    ``scales`` (the int8 KV mode) dequantize per slot right after the
    gather — the XLA-fallback twin of the kernel's in-VMEM dequant."""
    def g(pool, width):
        got = jnp.take(pool, page_tables, axis=1)
        return got.transpose(1, 0, 2, 3, 4).reshape(
            batch, kv_heads, max_tokens, width)

    out = g(pages, last)
    if scales is not None:
        return dequantize_kv(out, g(scales, 1), out_dtype)
    return out.astype(out_dtype)


def _unpacked(k, d):
    """Gathered K (batch, rows, T, n * d) whose rows hold ``n`` heads
    side by side (``k_pack``) as (batch, n * rows, T, d): a head a row."""
    b, rows, t, wide = k.shape
    if wide == d:
        return k
    return k.reshape(b, rows, t, wide // d, d).transpose(0, 1, 3, 2, 4) \
        .reshape(b, rows * (wide // d), t, d)


def _seen(cols, limit, window):
    """The mask every oracle applies: column ``cols`` is seen by a query
    whose causal limit is ``limit`` (its own position + 1), and under a
    ``window`` only the ``window`` columns that end there."""
    seen = cols < limit
    if window is not None:
        seen &= cols >= limit - window
    return seen


def _softmax(s, sinks):
    """The oracles' softmax over the last axis of ``s`` (batch, q_heads,
    ..., keys).  ``sinks`` (q_heads,) float32 or None: a learned sink a
    query head stands in the denominator as one more column that no key
    carries, dropped after the softmax (it takes mass and gives no
    value)."""
    if sinks is None:
        return jax.nn.softmax(s, axis=-1)
    col = sinks.astype(s.dtype).reshape((1, -1) + (1,) * (s.ndim - 2))
    col = jnp.broadcast_to(col, s.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([s, col], axis=-1),
                          axis=-1)[..., :-1]


def _decode_xla(q, k_pages, v_pages, lengths, page_tables, scale,
                k_scales=None, v_scales=None, window=None, sinks=None):
    """Gather + dense masked attention (CPU fallback / correctness ref)."""
    q = _unpacked_queries(q, k_pages, v_pages)
    batch, q_heads, d = q.shape
    kv_heads, _tot, page_size, _d = v_pages.shape
    group = q_heads // kv_heads
    max_tokens = page_tables.shape[1] * page_size

    def gather(pages, scales):
        return _gather_dequant(pages, scales, page_tables, batch,
                               pages.shape[0], max_tokens, pages.shape[-1],
                               q.dtype)

    k = _unpacked(gather(k_pages, k_scales), d)
    v = gather(v_pages, v_scales)
    if group != 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhd,bhkd->bhk", q, k,
                   preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(max_tokens)[None, None, :]
    s = jnp.where(_seen(cols, lengths[:, None, None], window), s,
                  DEFAULT_MASK_VALUE)
    p = _softmax(s, sinks)
    return jnp.einsum("bhk,bhkd->bhd", p.astype(v.dtype), v).astype(q.dtype)


def _multi_xla(q, k_pages, v_pages, lengths, page_tables, scale,
               k_scales=None, v_scales=None, window=None, sinks=None):
    """Gather + dense masked multi-query attention (CPU fallback /
    correctness reference for the ragged verify path)."""
    q = _unpacked_queries(q, k_pages, v_pages)
    batch, n_query, q_heads, d = q.shape
    kv_heads, _tot, page_size, _d = v_pages.shape
    group = q_heads // kv_heads
    max_tokens = page_tables.shape[1] * page_size

    def gather(pages, scales):
        return _gather_dequant(pages, scales, page_tables, batch,
                               pages.shape[0], max_tokens, pages.shape[-1],
                               q.dtype)

    k = _unpacked(gather(k_pages, k_scales), d)
    v = gather(v_pages, v_scales)
    if group != 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    qt = q.transpose(0, 2, 1, 3)                  # (b, qh, nq, d)
    s = jnp.einsum("bhsd,bhtd->bhst", qt, k,
                   preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(max_tokens, dtype=jnp.int32)[None, None, None, :]
    # query s of the block sees cols < length - (n_query - 1 - s): the
    # per-row, per-query ragged causal limit
    qpos = jnp.arange(n_query, dtype=jnp.int32)[None, None, :, None]
    limit = (lengths[:, None, None, None]
             - (n_query - 1 - qpos)).astype(jnp.int32)
    s = jnp.where(_seen(cols, limit, window), s, DEFAULT_MASK_VALUE)
    p = _softmax(s, sinks)
    out = jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), v)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


# ------------------------------------------------ the ragged step's pack
# The ragged program runs over the step's tokens PACKED along one axis —
# row 0's span, then row 1's, ... then pad.  ``off[r]`` is where row ``r``
# starts on the packed axis; ``off=None`` says the packed axis is as long
# as the (rows, span) rectangle and every row keeps its place in it (a
# reshape).  The ragged KERNEL reads a row's queries from the packed axis
# itself; these two take the axis to the rectangle and back for its XLA
# oracle and for the program's tail.  Both are jitted: a program's layers
# all call them at the same shapes and share one traced and lowered body.

@functools.partial(jax.jit, static_argnames=("span",))
def _rows_of_packed(x, off, span):
    """``x[T, ...]`` packed -> ``[rows, span, ...]``: row ``r`` is the
    ``span`` entries from ``off[r]`` on.  Past the row's own tokens that
    is its successors' (or pad): whoever is handed the rows' own lengths
    never computes with them."""
    if off is None:
        return x.reshape((-1, span) + x.shape[1:])
    at = off[:, None] + jnp.arange(span, dtype=jnp.int32)[None, :]
    return x[jnp.minimum(at, x.shape[0] - 1)]


@functools.partial(jax.jit, static_argnames=("tokens",))
def _packed_of_rows(x, off, tokens):
    """``x[rows, span, ...]`` -> ``[tokens, ...]`` packed: position ``t``
    takes row ``r``'s column ``t - off[r]``, ``r`` the last row that
    starts at or before ``t``.  Past the step's tokens that is the last
    row's tail: of the ragged oracle's output, its dead queries, which
    are zeros — the dense layers, the router and the head run over the
    pad positions too, and the caller discards what they make of them."""
    rows, span = x.shape[:2]
    flat = x.reshape((rows * span,) + x.shape[2:])
    if off is None:
        return flat
    at = jnp.arange(tokens, dtype=jnp.int32)
    row = jnp.sum(at[:, None] >= off[None, :], axis=1) - 1
    col = jnp.minimum(at - off[row], span - 1)
    return flat[row * span + col]


def _ragged_xla(q, k_pages, v_pages, lengths, q_lens, page_tables, scale,
                k_scales=None, v_scales=None, window=None, sinks=None):
    """Gather + dense masked attention with PER-ROW query spans (CPU
    fallback / correctness oracle for the ragged unified step).  Same
    einsum structure as ``_multi_xla`` — only the causal limit differs
    — so a row whose span fills the bucket reproduces the verify mask
    bit-exactly, and masked columns contribute EXACT zeros (exp of the
    mask value underflows), keeping results identical across bucket
    widths."""
    q = _unpacked_queries(q, k_pages, v_pages)
    batch, n_query, q_heads, d = q.shape
    kv_heads, _tot, page_size, _d = v_pages.shape
    group = q_heads // kv_heads
    max_tokens = page_tables.shape[1] * page_size

    def gather(pages, scales):
        return _gather_dequant(pages, scales, page_tables, batch,
                               pages.shape[0], max_tokens, pages.shape[-1],
                               q.dtype)

    k = _unpacked(gather(k_pages, k_scales), d)
    v = gather(v_pages, v_scales)
    if group != 1:
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    qt = q.transpose(0, 2, 1, 3)                  # (b, qh, nq, d)
    s = jnp.einsum("bhsd,bhtd->bhst", qt, k,
                   preferred_element_type=jnp.float32) * scale
    cols = jnp.arange(max_tokens, dtype=jnp.int32)[None, None, None, :]
    # row b's real queries sit LEFT-aligned in the bucket: query j sees
    # cols < kv - qlen + j + 1; bucket pads (j >= qlen) clamp at kv and
    # are zeroed, as the kernel writes them
    qpos = jnp.arange(n_query, dtype=jnp.int32)[None, None, :, None]
    kv = lengths[:, None, None, None].astype(jnp.int32)
    ql = q_lens[:, None, None, None].astype(jnp.int32)
    limit = jnp.minimum(kv, kv - ql + 1 + qpos)
    s = jnp.where(_seen(cols, limit, window), s, DEFAULT_MASK_VALUE)
    p = _softmax(s, sinks)
    out = jnp.einsum("bhst,bhtd->bhsd", p.astype(v.dtype), v)
    out = jnp.where(qpos < ql, out, 0.0)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def paged_attention(q, k_pages, v_pages, lengths, page_tables, scale=None,
                    interpret=False, k_scales=None, v_scales=None,
                    window=None, sinks=None):
    """Decode-step attention over a paged KV cache.

    q:           (batch, q_heads, head_dim) — ONE new token per sequence
    k/v_pages:   (kv_heads, total_pages, page_size, head_dim); V's pages
                 may be of another width than K's (and q's): the output
                 is as wide as V, the scores as wide as K
    lengths:     (batch,) int32 — valid cached tokens per sequence
                 (including the current token, already written to pages)
    page_tables: (batch, max_pages_per_seq) int32
    k/v_scales:  (kv_heads, total_pages, page_size, 1) f32 — present
                 when the pages store INT8 KV (ISSUE 9): dequant is
                 fused into the kernel (or the gather on the XLA path),
                 so full-precision KV never round-trips HBM.
    window:      None, or the width of a sliding-attention layer: the
                 query (at position ``length - 1``) sees the ``window``
                 keys that end with itself, and the kernel walks from
                 the page of the first of them.  The same in
                 ``paged_attention_multi`` and ``paged_attention_ragged``
                 for every query of a row.
    sinks:       None, or (q_heads,) float32: a learned sink a query
                 head, ``p_j = exp(s_j) / (exp(sink) + sum_j' exp(s_j'))``
                 over the visible keys; the sink takes mass and gives no
                 value.  The same in the other two entry points.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(_k_head_dim(k_pages, v_pages))
    if _use_pallas() or interpret:
        return _decode_pallas(q, k_pages, v_pages, lengths, page_tables,
                              scale, interpret=interpret,
                              k_scales=k_scales, v_scales=v_scales,
                              window=window, sinks=sinks)
    return _decode_xla(q, k_pages, v_pages, lengths, page_tables, scale,
                       k_scales=k_scales, v_scales=v_scales, window=window,
                       sinks=sinks)


def paged_attention_multi(q, k_pages, v_pages, lengths, page_tables,
                          scale=None, interpret=False, k_scales=None,
                          v_scales=None, window=None, sinks=None):
    """Ragged MULTI-QUERY decode attention: ``n_query`` new tokens per
    sequence in one pass — the speculative-decoding verify step's
    attention ("Ragged Paged Attention" shape: [B, k] queries against
    paged KV + the in-flight block suffix).

    q:           (batch, n_query, q_heads, head_dim) — the verify block,
                 whose K/V are ALREADY scattered into the pages
    lengths:     (batch,) int32 — valid cached tokens per sequence
                 INCLUDING the whole block; query ``s`` attends
                 causally to ``cols < length - (n_query - 1 - s)``
    page_tables: (batch, max_pages_per_seq) int32

    Returns (batch, n_query, q_heads, head_dim).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(_k_head_dim(k_pages, v_pages))
    if q.shape[1] == 1:
        out = paged_attention(q[:, 0], k_pages, v_pages, lengths,
                              page_tables, scale=scale,
                              interpret=interpret, k_scales=k_scales,
                              v_scales=v_scales, window=window, sinks=sinks)
        return out[:, None]
    if _use_pallas() or interpret:
        return _decode_pallas(q, k_pages, v_pages, lengths, page_tables,
                              scale, interpret=interpret,
                              n_query=q.shape[1], k_scales=k_scales,
                              v_scales=v_scales, window=window, sinks=sinks)
    return _multi_xla(q, k_pages, v_pages, lengths, page_tables, scale,
                      k_scales=k_scales, v_scales=v_scales, window=window,
                      sinks=sinks)


def paged_attention_ragged(q, k_pages, v_pages, lengths, q_lens,
                           page_tables, scale=None, interpret=False,
                           k_scales=None, v_scales=None, window=None,
                           sinks=None, row_off=None, span=None):
    """RAGGED paged attention (ISSUE 17): ONE kernel over a batch whose
    rows carry DIFFERENT query-span lengths — decode rows (q_len 1),
    prefill/chunk spans, and speculative verify blocks mix in a single
    grid, so the serving engine's whole step is one dispatch instead of
    an alternation of per-mode programs ("Ragged Paged Attention"
    shape).

    q:           (batch, max_q, q_heads, head_dim) — row ``b``'s
                 ``q_lens[b]`` real query tokens sit LEFT-aligned in
                 the ``max_q`` bucket; pad positions come back as ZEROS.
                 Or, with ``span`` (the bucket ``max_q``) given, the
                 step's PACKED tokens (tokens, q_heads, head_dim): row
                 ``b``'s queries stand together from ``row_off[b]`` on
                 (``None``: ``b * span``, the rectangle row-major), and
                 the output comes back packed, zeros where no row has a
                 query.  The kernel is the same: it takes each row's
                 queries from the packed axis, whole tiles of them
                 (:func:`query_tile_rows`), and the rectangle is the
                 packed axis whose rows start ``max_q`` apart; the XLA
                 path gathers the rectangle and zeroes after the fact
    lengths:     (batch,) int32 — valid cached tokens per sequence
                 INCLUDING the row's whole span (already scattered
                 into the pages)
    q_lens:      (batch,) int32 — real query tokens per row; query
                 ``j`` attends causally to
                 ``cols < lengths[b] - q_lens[b] + j + 1``
    page_tables: (batch, max_pages_per_seq) int32
    k/v_scales:  int8 KV mode scale pools — dequant fuses into the
                 kernel / gather exactly as in the uniform paths

    A row whose span fills the bucket (``q_lens[b] == max_q``)
    reproduces :func:`paged_attention_multi`'s verify mask bit-exactly;
    a ``max_q == 1`` call routes through :func:`paged_attention`
    itself, so the unified step can never drift from the legacy modes.
    Returns ``q``'s form, as wide as V.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(_k_head_dim(k_pages, v_pages))
    packed = span is not None
    kw = dict(k_scales=k_scales, v_scales=v_scales, window=window,
              sinks=sinks)
    if (span if packed else q.shape[1]) == 1:
        # every span is one token: literally the decode step
        out = paged_attention(q if packed else q[:, 0], k_pages, v_pages,
                              lengths, page_tables, scale=scale,
                              interpret=interpret, **kw)
        return out if packed else out[:, None]
    if _use_pallas() or interpret:
        return _decode_pallas(q, k_pages, v_pages, lengths, page_tables,
                              scale, interpret=interpret,
                              n_query=span if packed else q.shape[1],
                              q_lens=q_lens, row_off=row_off, **kw)
    rect = _rows_of_packed(q, row_off, span) if packed else q
    out = _ragged_xla(rect, k_pages, v_pages, lengths, q_lens, page_tables,
                      scale, **kw)
    return _packed_of_rows(out, row_off, q.shape[0]) if packed else out


# ------------------------------------------------------------- page cache
#: positions a grid step of ``_append_kernel`` takes (its new rows ride
#: in as one pipelined block: APPEND_BLOCK x kv_heads x last float32)
APPEND_BLOCK = 128


def _append_kernel(pg_ref, sl_ref, vals_ref, pool_in, pool_out, page_buf,
                   cur_ref, sem, *, n_pages):
    """Write the REAL positions of one block of ``APPEND_BLOCK`` positions
    into the pool, which stays in HBM and is this call's own output
    (``input_output_aliases``).  A slot is narrower than the pool's tile
    (two bf16 slots share a sublane), so a slot is never copied alone:
    the page that holds it is STAGED in VMEM — all kv heads' tiles of it
    in one copy — the position's row is selected into its slot, and the
    page goes back when the walk moves on to another page (or ends).
    Positions of one row are consecutive slots, so a 128-token chunk
    stages 8 or 9 pages and a decode row one.  Out-of-range positions
    (pads) cost a scalar compare and nothing else."""
    del pool_in                     # the same buffer as ``pool_out``
    step, last_step = pl.program_id(0), pl.num_programs(0) - 1
    kv_heads, page_size, last = page_buf.shape

    @pl.when(step == 0)
    def _():
        cur_ref[0] = -1

    def page_copy(page, back):
        hbm = pool_out.at[:, page]
        src, dst = (page_buf, hbm) if back else (hbm, page_buf)
        return pltpu.make_async_copy(src, dst, sem)

    def put_back():
        @pl.when(cur_ref[0] >= 0)
        def _():
            copy = page_copy(cur_ref[0], back=True)
            copy.start()
            copy.wait()

    slot_of = lax.broadcasted_iota(jnp.int32, (page_size, last), 0)

    def body(i, carry):
        t = step * APPEND_BLOCK + i
        page, slot = pg_ref[t], sl_ref[t]
        real = ((page >= 0) & (page < n_pages)
                & (slot >= 0) & (slot < page_size))

        @pl.when(real & (page != cur_ref[0]))
        def _():
            put_back()
            copy = page_copy(page, back=False)
            copy.start()
            copy.wait()
            cur_ref[0] = page

        @pl.when(real)
        def _():
            new = vals_ref[i]                       # (kv_heads, last) f32
            for h in range(kv_heads):
                row = jnp.broadcast_to(new[h:h + 1], (page_size, last))
                held = page_buf[h].astype(jnp.float32)
                page_buf[h] = jnp.where(slot_of == slot, row,
                                        held).astype(page_buf.dtype)
        return carry

    lax.fori_loop(0, APPEND_BLOCK, body, 0)

    @pl.when(step == last_step)
    def _():
        put_back()


@functools.partial(jax.jit, static_argnames=("interpret",))
def _append_pallas(pool, pages, slots, vals, interpret=False):
    """``_append_kernel`` over blocks of ``APPEND_BLOCK`` positions.
    Jitted like ``_decode_pallas``: a serving program appends twice a
    layer, and traced and lowered afresh each time the 32 programs of an
    engine cost 100 s of set-up more (the kernel body is lowered to
    Mosaic at every call site)."""
    kv_heads, n_pages, page_size, last = pool.shape
    tokens = vals.shape[1]
    pad = -tokens % APPEND_BLOCK
    # pad positions carry an out-of-range page: the kernel skips them
    pages = jnp.pad(pages, (0, pad), constant_values=n_pages)
    slots = jnp.pad(slots, (0, pad))
    # (tokens, kv_heads, last) float32: a position is one leading index
    # and one whole float32 tile per 8 heads (exact for bf16 and int8)
    rows = jnp.pad(jnp.swapaxes(vals.astype(pool.dtype), 0, 1)
                   .astype(jnp.float32), ((0, pad), (0, 0), (0, 0)))
    return pl.pallas_call(
        functools.partial(_append_kernel, n_pages=n_pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=((tokens + pad) // APPEND_BLOCK,),
            in_specs=[
                pl.BlockSpec((APPEND_BLOCK, kv_heads, last),
                             lambda i, pg, sl: (i, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((kv_heads, page_size, last), pool.dtype),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands: pages, slots, rows, pool -> the pool is the output
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="kv_append_rows", interpret=interpret,
    )(pages, slots, rows, pool)


def _append_xla(pool, pages, slots, vals):
    """The append as ONE row scatter over the pool viewed as
    ``[kv_heads * pages * page_size, last]``: the scattered axis is
    outermost and the window is the lane axis alone, so the (donated)
    pool is updated in the layout it arrives in.  A position that is out
    of range would be page 0 of the NEXT head in this view: it is sent
    past the end of the flat pool, which ``mode="drop"`` discards."""
    kv_heads, n_pages, page_size, last = pool.shape
    rows = n_pages * page_size
    assert kv_heads * rows < 2 ** 31, "flat row index overflows int32"
    ok = ((pages >= 0) & (pages < n_pages)
          & (slots >= 0) & (slots < page_size))
    row = jnp.arange(kv_heads, dtype=jnp.int32)[:, None] * rows \
        + (pages * page_size + slots)[None, :]
    row = jnp.where(ok[None, :], row, kv_heads * rows)
    flat = pool.reshape(kv_heads * rows, last).at[row.reshape(-1)].set(
        vals.astype(pool.dtype).reshape(-1, last), mode="drop")
    return flat.reshape(pool.shape)


def append_rows(pool, pages, slots, vals, interpret=False):
    """THE append: write ``vals[:, t]`` to ``pool[:, pages[t], slots[t]]``
    for every position ``t``.  ``pool`` (kv_heads, pages, page_size,
    last) — a bf16/f32/int8 K or V pool, or a ``[..., 1]`` scale pool;
    ``vals`` (kv_heads, tokens, last); ``pages``/``slots`` (tokens,).

    The pool keeps ONE layout from the jit boundary through this call to
    the paged kernels and back out, so a donated pool is written in
    place.  (Indexing ``pool[:, pg, sl]`` put the kv-head axis, which is
    major to both scattered axes, inside the scatter's window: XLA's TPU
    scatter then re-laid the whole pool out before the scatter and back
    after it, two pool-sized copies a pool a step.)

    A position whose page or slot is out of range — pad positions carry
    page ``total_pages`` — is DROPPED for every head and never reaches a
    real slot.

    On the TPU, a pool whose pages are whole tiles of its dtype goes
    through ``_append_kernel`` (one staged page a run of positions, so
    the cost follows the REAL positions of a step); any other pool — a
    ``[..., 1]`` scale pool, int8 pages of 16 slots — and every other
    backend takes the row scatter ``_append_xla``, whose cost follows
    the positions a step is padded to."""
    kv_heads, n_pages, page_size, last = pool.shape
    pages = pages.astype(jnp.int32)
    slots = slots.astype(jnp.int32)
    whole_tiles = (last % 128 == 0
                   and page_size % (32 // pool.dtype.itemsize) == 0)
    if whole_tiles and (_use_pallas() or interpret):
        return _append_pallas(pool, pages, slots, vals, interpret=interpret)
    return _append_xla(pool, pages, slots, vals)


def packed_k_rows(vals, pool):
    """K rows ``vals`` (kv_heads, tokens, d) as ``pool``'s rows take them:
    where a row of the pool holds several heads side by side (``k_pack``:
    its head axis is that many times shorter), (kv_heads / n, tokens,
    n * d); else as they are."""
    heads, tokens, d = vals.shape
    n = heads // pool.shape[0]
    if n == 1:
        return vals
    return vals.reshape(heads // n, n, tokens, d).transpose(0, 2, 1, 3) \
        .reshape(heads // n, tokens, n * d)


# the eager cache's append: the pool buffer is DONATED, so the step's
# rows are written in place instead of into a copy of the pool
_append_rows_donated = jax.jit(append_rows, donate_argnums=(0,),
                               static_argnames=("interpret",))


def paged_layout(model) -> dict:
    """What a model's paged calls look like, read from the model (the one
    place ``PagedKVCache.from_model`` and ``JittedPagedDecoder`` both ask):

    * ``calls``: [(query heads, window or None, pool)] a paged call, in the
      calls' order, from ``model.attention_kinds()`` where the model has it
      (an entry of two names the pool of its own index; an entry of three
      names its pool, and a call on a pool an earlier call opened walks it
      without appending: ``shared``; a fourth member says the call hands
      ``attend`` a learned sink a query head: ``sinks``, a flag a call),
      else one full-attention call a layer;
    * ``pools``: how many page pools that makes;
    * ``pool_shapes``: [(kv_heads, k_dim, v_dim)] a pool: a page's heads
      as ``attend`` is handed K and V for that pool, K's width and V's.
      From ``model.kv_page_shape()`` where the model lays them out
      otherwise than its config says: ``(kv_heads, head_dim)`` for pools
      that are all alike, or one ``(kv_heads, k_dim, v_dim)`` a pool for
      a model whose pools differ (MiMo-V2-Flash: 8 heads in its sliding
      pools and 4 in its full ones, K 192 wide beside V of 128);
    * ``kv_heads`` / ``head_dim``: the one shape of pools that are all
      alike with K as wide as V, None where they are not;
    * ``state``: ``model.recurrent_state()`` or None, its slot's arrays
      under ``shapes`` (a model that says ``shape`` has one).  A slot is
      whatever arrays the model lists, ``layers`` times over, in the order
      its layers name them to the paged context: a retention layer's one
      state, a Mamba layer's ``h`` and convolution tail, a convolutional-
      attention layer's one-token tails (``models/zaya.py``).  A layer
      with a slot may ALSO own a page pool: ``calls`` and ``state`` are
      counted apart, and a sequence takes and returns a slot of every
      state layer with its pages."""
    c = model.config
    if hasattr(model, "attention_kinds"):
        kinds = list(model.attention_kinds())
    else:
        kinds = [(c.num_attention_heads, None)] * c.num_hidden_layers
    calls, sinks, opened = [], [], set()
    for i, kind in enumerate(kinds):
        heads, window = kind[0], kind[1]
        pool = kind[2] if len(kind) > 2 and kind[2] is not None else i
        calls.append((heads, window, pool, pool in opened))
        sinks.append(bool(kind[3]) if len(kind) > 3 else False)
        opened.add(pool)
    if hasattr(model, "kv_page_shape"):
        shape = model.kv_page_shape()
    else:
        # a config that states its head_dim means it (2,048 / 48 query
        # heads is not 128)
        shape = (c.num_key_value_heads,
                 getattr(c, "head_dim", None)
                 or c.hidden_size // c.num_attention_heads)
    if isinstance(shape[0], (tuple, list)):       # a shape a pool
        shapes = [tuple(int(x) for x in p) for p in shape]
        if len(shapes) != len(opened):
            raise ValueError(
                f"kv_page_shape() gives {len(shapes)} pool shapes for the "
                f"{len(opened)} pools the model's calls open")
        alike = len(set(shapes)) == 1 and shapes[0][1] == shapes[0][2]
        kv_heads, head_dim = shapes[0][:2] if alike else (None, None)
    else:
        kv_heads, head_dim = (int(x) for x in shape)
        shapes = [(kv_heads, head_dim, head_dim)] * len(opened)
    state = (model.recurrent_state()
             if hasattr(model, "recurrent_state") else None)
    if state is not None and "shapes" not in state:
        state = dict(state, shapes=[tuple(state["shape"])])
    return {"calls": calls, "sinks": sinks, "pools": len(opened),
            "pool_shapes": shapes, "kv_heads": kv_heads,
            "head_dim": head_dim, "state": state}


class _PrefixEntry:
    """One cached page-aligned prompt prefix: the pages holding its KV
    plus the token count they cover.  The entry itself holds one index
    ref on every page so the KV survives the registering sequence's
    retirement (evictable under pool pressure, LRU order)."""

    __slots__ = ("pages", "n_tokens")

    def __init__(self, pages: List[int], n_tokens: int):
        self.pages = pages
        self.n_tokens = n_tokens


class PagedKVCache:
    """Paged KV cache: device page pools per layer + host-side page-table
    bookkeeping (reference: the BlockTable management around
    block_multihead_attention), with REFCOUNTED pages and a prefix index.

    Layout per pool: (kv_heads, total_pages, page_size, head_dim); a pool
    a K/V layer, which later layers may walk without one of their own
    (:func:`paged_layout`).  A pool's shape is ITS OWN (``pool_shapes``:
    (kv_heads, k_dim, v_dim) a pool): the KV heads may differ from pool to
    pool and K's pages need not be as wide as V's — ``k_pages[p]``
    ``(kv_heads_p, total_pages, page_size, k_dim_p)`` beside ``v_pages[p]``
    ``(..., v_dim_p)``; a K head of 192 lies two to a row of 384
    (:func:`k_pack`), so no pool holds a padded lane.  One page table a
    sequence all the same: a page index is valid in every pool, the pools
    differ in the bytes a page holds (``page_bytes``).  Beside the pages, for a model whose layers
    carry a recurrent state: slot pools, ``state_slots`` + 1 slots of each
    array of a layer's state (``state_pools``, a layer's arrays side by
    side), a slot a sequence taken and returned with its pages; a layer
    may hold both (a slot beside its own pages: ``models/zaya.py``).

    Pages carry two kinds of references: sequence refs (a live sequence
    maps the page in its table) and index refs (a cached prompt prefix
    retains the page for reuse).  A page returns to the free list only
    when both drop to zero.  Pages are append-only, so a FULL page whose
    tokens are a page-aligned prompt prefix can be shared read-only by
    any request with the same prefix — the sharer maps the pages,
    prefills only its suffix, and copy-on-writes nothing (the first
    partially-filled page is never shared).  Index-retained pages with
    no sequence ref are *evictable*: ``allocate`` reclaims them in LRU
    order under pool pressure, so they count as available capacity
    (``free_pages``).
    """

    @classmethod
    def from_model(cls, model, total_pages: int = 256,
                   page_size: int = 16,
                   kv_dtype: Optional[str] = None,
                   mesh=None, state_slots: int = 0) -> "PagedKVCache":
        """Cache sized for a causal-LM model (single wiring point shared
        by PagedGenerator and ContinuousBatchingEngine), from what the
        model says of itself (:func:`paged_layout`): ONE page pool for
        each pool its paged calls name (a layer that reads another
        layer's pages opens none), pages of the heads ``attend`` is handed,
        and for a model with a recurrent state ``state_slots`` + 1 slots
        of every array of its slot, a layer.
        ``kv_dtype="int8"`` selects the quantized storage mode;
        ``mesh`` shards the pools on the KV-head axis (ISSUE 20)."""
        layout = paged_layout(model)
        state = layout["state"]
        if state is not None and mesh is not None:
            raise ValueError(
                "a recurrent state has no placement over a tensor mesh: "
                "the slot pools are not sharded")
        return cls(
            num_layers=layout["pools"], kv_heads=layout["kv_heads"],
            head_dim=layout["head_dim"],
            pool_shapes=(None if layout["kv_heads"] is not None
                         else layout["pool_shapes"]),
            total_pages=total_pages, page_size=page_size,
            dtype=model.model.embed_tokens.weight._data.dtype,
            kv_dtype=kv_dtype, mesh=mesh,
            state_layers=state["layers"] if state else 0,
            state_shape=[tuple(x) for x in state["shapes"]] if state else (),
            state_slots=state_slots if state else 0)

    def __init__(self, num_layers: int, kv_heads: int, head_dim: int,
                 total_pages: int = 256, page_size: int = 16,
                 dtype=jnp.float32, kv_dtype: Optional[str] = None,
                 mesh=None, state_layers: int = 0, state_shape=(),
                 state_slots: int = 0, pool_shapes=None):
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {kv_dtype!r}")
        self.num_layers = num_layers
        # ``kv_heads`` / ``head_dim``: the one shape of pools that are all
        # alike; None for a cache built from ``pool_shapes``, [(kv_heads,
        # k_dim, v_dim)] a pool, which then speak for every pool
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        if pool_shapes is None:
            pool_shapes = [(kv_heads, head_dim, head_dim)] * num_layers
        self.pool_shapes = [tuple(int(x) for x in p) for p in pool_shapes]
        if len(self.pool_shapes) != num_layers:
            raise ValueError(f"{len(self.pool_shapes)} pool shapes for "
                             f"{num_layers} pools")
        for heads, k_dim, _ in self.pool_shapes:
            if heads % k_pack(k_dim):
                raise ValueError(
                    f"{heads} KV heads of {k_dim} do not fill whole "
                    f"128-lane rows {k_pack(k_dim)} at a time")
        unlike = (len(set(self.pool_shapes)) > 1
                  or any(k != v for _, k, v in self.pool_shapes))
        if unlike and (kv_dtype is not None or mesh is not None):
            raise ValueError(
                ("kv_dtype='int8'" if kv_dtype is not None else "a tensor "
                 "mesh") + f": the pools differ in shape "
                f"({sorted(set(self.pool_shapes))} as (kv heads, K width, V "
                "width)), and neither the int8 scale pools nor the head-"
                "axis sharding has been held to a reference for pools of "
                "unequal heads or a K wider than its V")
        self.page_size = page_size
        self.total_pages = total_pages
        # tensor-parallel serving (ISSUE 20): under a ('tensor',) mesh
        # every pool (data AND scale — both lead with the kv-head axis)
        # lands as PartitionSpec('tensor'), so each chip holds
        # kv_heads/tp heads' pages and per-chip pool HBM drops by the
        # TP degree.  The sharding is re-applied by reset_pools so a
        # donated-buffer recovery rebuilds the pools on the same mesh.
        self.mesh = mesh
        self.tp = 1
        self._pool_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self.tp = int(mesh.size)
            if self.tp > 1 and kv_heads % self.tp != 0:
                raise ValueError(
                    f"kv_heads ({kv_heads}) must divide evenly over the "
                    f"tensor mesh ({self.tp} devices) to shard the page "
                    f"pools on the head axis")
            self._pool_sharding = NamedSharding(mesh,
                                                PartitionSpec("tensor"))
        # int8 KV mode (ISSUE 9): pages store int8 values with a
        # parallel per-slot scale pool; ``compute_dtype`` is what the
        # attention kernels dequantize toward (the model's dtype)
        self.kv_quant = kv_dtype == "int8"
        self.compute_dtype = dtype
        self._page_zeros()
        # recurrent slots: float32 whatever the pages' type (a state is
        # summed over the whole sequence)
        self.state_layers = int(state_layers)
        self.state_slots = int(state_slots) if state_layers else 0
        # a slot's arrays: one shape, or a list of them (a layer then
        # holds a pool of each, side by side in ``state_pools``)
        shapes = list(state_shape) if state_layers else []
        if shapes and not isinstance(shapes[0], (tuple, list)):
            shapes = [shapes]
        self.state_shapes = [tuple(x) for x in shapes]
        self.state_pools = self._state_zeros()
        self._free_slots: List[int] = list(range(self.state_slots))[::-1]
        self._seq_slot: Dict[int, int] = {}
        self._free: List[int] = list(range(total_pages))
        self._seq_pages: Dict[int, List[int]] = {}
        self._seq_len: Dict[int, int] = {}
        # page -> refcount, split by holder kind: a page is PINNED while
        # any sequence maps it, EVICTABLE while only the prefix index
        # retains it, and free when neither does
        self._seq_refs: Dict[int, int] = {}
        self._idx_refs: Dict[int, int] = {}
        # page-aligned prompt-prefix hash-chain key -> _PrefixEntry, in
        # LRU order (oldest first; touched entries move to the end)
        self._prefix_index: "OrderedDict[bytes, _PrefixEntry]" = \
            OrderedDict()
        self.prefix_evictions = 0           # entries dropped under pressure
        # crash consistency (ISSUE 8): bumped every time reset_pools
        # rebuilds the device pools zeroed — the engine compares it
        # across a failed step to tell a host-side fault (KV intact)
        # from a REAL donated-buffer loss (survivors need replay)
        self.generation = 0

    def _page_zeros(self):
        """Zeroed page pools (and in the int8 mode their scale pools), a
        pool by its own shape: K rows as :func:`k_pack` lays them."""
        store = jnp.int8 if self.kv_quant else self.compute_dtype
        pages, ps = self.total_pages, self.page_size
        self.k_pages, self.v_pages = [], []
        for heads, k_dim, v_dim in self.pool_shapes:
            n = k_pack(k_dim)
            self.k_pages.append(self._zeros(
                (heads // n, pages, ps, n * k_dim), store))
            self.v_pages.append(self._zeros((heads, pages, ps, v_dim), store))
        self.k_scales, self.v_scales = [], []
        if self.kv_quant:
            for heads, _, _ in self.pool_shapes:
                sshape = (heads, pages, ps, 1)
                self.k_scales.append(self._zeros(sshape, jnp.float32))
                self.v_scales.append(self._zeros(sshape, jnp.float32))

    def page_bytes(self, pool: int) -> int:
        """Bytes one page index holds in pool ``pool``: K and V of its
        ``page_size`` positions (the int8 mode's scales apart)."""
        heads, k_dim, v_dim = self.pool_shapes[pool]
        return (heads * self.page_size * (k_dim + v_dim)
                * self.k_pages[pool].dtype.itemsize)

    def _state_zeros(self):
        return [jnp.zeros((self.state_slots + 1,) + shape, jnp.float32)
                for _ in range(self.state_layers)
                for shape in self.state_shapes]

    # --------------------------------------------------- recurrent slots
    @property
    def scratch_slot(self) -> int:
        """The slot of a row that is pad: written, never read."""
        return self.state_slots

    @property
    def free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def slots_in_use(self) -> int:
        return len(self._seq_slot)

    def take_slot(self, seq_id) -> int:
        """The sequence's slot, taken from the free ones if it has none.
        Nothing is cleared here: the row that enters a slot with an
        empty context starts from zero."""
        slot = self._seq_slot.get(seq_id)
        if slot is None:
            if not self._free_slots:
                raise RuntimeError(
                    f"PagedKVCache out of recurrent slots "
                    f"({self.state_slots}); free() finished sequences")
            slot = self._seq_slot[seq_id] = self._free_slots.pop()
        return slot

    def release_slot(self, seq_id) -> None:
        """Return the sequence's slot (its pages and length stay)."""
        slot = self._seq_slot.pop(seq_id, None)
        if slot is not None:
            self._free_slots.append(slot)

    def slot_of(self, seq_id) -> int:
        """The sequence's slot; the scratch slot for one that has none
        (a pad row)."""
        return self._seq_slot.get(seq_id, self.scratch_slot)

    def _zeros(self, shape, dtype):
        """A zeroed pool buffer created IN its placement: sharded over
        the cache's mesh directly (never built whole on the default
        device first — at real pool sizes that transit alone can
        exhaust one chip), on the default device for the 1-chip cache."""
        return jnp.zeros(shape, dtype, device=self._pool_sharding)

    # ------------------------------------------------------- bookkeeping
    def _decref_seq(self, page: int) -> bool:
        """Drop one sequence ref; True if the page became unpinned."""
        n = self._seq_refs[page] - 1
        if n:
            self._seq_refs[page] = n
            return False
        del self._seq_refs[page]
        if page not in self._idx_refs:
            self._free.append(page)
        return True

    def _decref_idx(self, page: int) -> None:
        n = self._idx_refs[page] - 1
        if n:
            self._idx_refs[page] = n
            return
        del self._idx_refs[page]
        if page not in self._seq_refs:
            self._free.append(page)

    def _evict_prefixes(self, n_pages: int) -> None:
        """Drop prefix entries in LRU order until ``n_pages`` pages are
        free (or nothing more is reclaimable).  Entries whose pages are
        ALL pinned by live sequences are skipped — dropping them would
        free nothing while losing a prefix an active sharer still
        maps."""
        for key in list(self._prefix_index):
            if len(self._free) >= n_pages:
                break
            entry = self._prefix_index[key]
            if all(p in self._seq_refs for p in entry.pages):
                continue
            del self._prefix_index[key]
            self.prefix_evictions += 1
            for p in entry.pages:
                self._decref_idx(p)

    def _pop_free_page(self) -> int:
        _faults.maybe_fire("page_alloc")
        if not self._free:
            self._evict_prefixes(1)
        if not self._free:
            raise RuntimeError(
                f"PagedKVCache out of pages "
                f"({self.total_pages} x {self.page_size} tokens); "
                "free() finished sequences or grow total_pages")
        p = self._free.pop()
        self._seq_refs[p] = 1
        return p
    def allocate_batch_atomic(self, seq_ids, n_tokens) -> None:
        """Reserve pages for MORE tokens on EVERY sequence, or none at
        all: a mid-batch exhaustion rolls back this call's reservations
        before re-raising, so a caller can fall back to finer-grained
        allocation against an undrained pool.  ``n_tokens`` is one
        count for the whole batch, or a per-sequence sequence of counts
        — the ragged unified step's rows grow by different spans
        (ISSUE 17)."""
        seq_ids = list(seq_ids)
        if isinstance(n_tokens, (int, np.integer)):
            counts = [int(n_tokens)] * len(seq_ids)
        else:
            counts = [int(n) for n in n_tokens]
        before = {sid: len(self._seq_pages.get(sid, ()))
                  for sid in seq_ids}
        try:
            for sid, n in zip(seq_ids, counts):
                self.allocate(sid, n)
        except RuntimeError:
            for sid in seq_ids:
                pages = self._seq_pages.get(sid, [])
                while len(pages) > before[sid]:
                    self._decref_seq(pages.pop())
            raise

    def allocate(self, seq_id: int, n_tokens: int) -> None:
        """Reserve pages so the sequence can hold n_tokens MORE tokens.
        Under pool pressure, evictable prefix-cache pages are reclaimed
        LRU-first before this raises."""
        pages = self._seq_pages.setdefault(seq_id, [])
        need_total = -(-(self._seq_len.get(seq_id, 0) + n_tokens)
                       // self.page_size)
        while len(pages) < need_total:
            pages.append(self._pop_free_page())

    def free(self, seq_id: int) -> int:
        """Release the sequence's refs on its pages.  Pages still held
        by another sharer or by the prefix index stay resident; returns
        the number of pages that stopped being PINNED (newly free or
        newly evictable) — the engine's reservation arithmetic uses it
        to release exactly the capacity this retirement uncovers."""
        released = 0
        for p in self._seq_pages.pop(seq_id, []):
            released += self._decref_seq(p)
        self._seq_len.pop(seq_id, None)
        self.release_slot(seq_id)
        return released

    def reset_pools(self) -> None:
        """Reallocate zeroed page pools (same shapes/dtype).  For
        recovery after a failed donated-buffer step invalidated the old
        pools: bookkeeping survives, cached K/V content does not — so
        the prefix index (whose hits would replay that lost content)
        is dropped wholesale.  ``generation`` is bumped so the engine
        can see the loss and replay every survivor's KV (ISSUE 8)."""
        self.generation += 1
        # _zeros: a TP cache's rebuilt pools must come back SHARDED on
        # the same mesh, or the next compiled call would silently
        # re-replicate them (and the decoder's pinned input shardings
        # would force a transfer per dispatch).  The scale pools are part
        # of the KV state: a rebuild zeroes them too, and the survivor
        # replay re-registers each page's scales alongside its int8 values
        self._page_zeros()
        # the slots' content is gone with the pages': who holds one
        # replays into it from an empty context
        self.state_pools = self._state_zeros()
        while self._prefix_index:
            _, entry = self._prefix_index.popitem(last=False)
            for p in entry.pages:
                self._decref_idx(p)

    # ---------------------------------------------------- prefix caching
    def _usable_prefix_tokens(self, tokens: np.ndarray) -> int:
        """Longest page-aligned prefix a request with this prompt may
        share: full pages only, and at least one prompt token must stay
        un-shared so prefill still produces next-token logits."""
        return (len(tokens) - 1) // self.page_size * self.page_size

    def _prefix_keys(self, tokens: np.ndarray, n_pages: int) -> List[bytes]:
        """Index key per page-aligned prefix, as an INCREMENTAL hash
        chain (key_i = blake2b(key_{i-1} || page_i tokens)): hashing
        every candidate prefix of a prompt is O(prompt), not
        O(prompt^2/page_size) as rehashing each prefix from scratch
        would be — probe_prefix runs under the engine's scheduler lock
        on every admission attempt."""
        keys, h = [], b""
        ps = self.page_size
        for i in range(n_pages):
            h = hashlib.blake2b(h + tokens[i * ps:(i + 1) * ps].tobytes(),
                                digest_size=16).digest()
            keys.append(h)
        return keys

    def _lookup_prefix(self, tokens):
        """(key, entry) for the LONGEST cached page-aligned prefix of
        ``tokens``, or None — the single search both probe_prefix and
        acquire_prefix use, so the engine's probe-then-acquire pair is
        structurally guaranteed to find the same entry."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = self._usable_prefix_tokens(tokens)
        keys = self._prefix_keys(tokens, n // self.page_size)
        for key in reversed(keys):
            entry = self._prefix_index.get(key)
            if entry is not None:
                return key, entry
        return None

    def probe_prefix(self, tokens) -> Tuple[int, int]:
        """(shared_tokens, newly_pinned_pages) for the longest cached
        prefix of ``tokens`` — WITHOUT acquiring it.  newly_pinned is
        how many of the hit's pages have no sequence ref yet, i.e. how
        much currently-reclaimable capacity an acquire would pin."""
        hit = self._lookup_prefix(tokens)
        if hit is None:
            return 0, 0
        _, entry = hit
        newly = sum(1 for p in entry.pages if p not in self._seq_refs)
        return entry.n_tokens, newly

    def acquire_prefix(self, seq_id, tokens) -> int:
        """Map the longest cached prefix of ``tokens`` into ``seq_id``
        read-only: the sequence starts at the shared length with the
        shared pages at the front of its table, each pinned by one
        sequence ref.  Returns the shared token count (0 = miss).  The
        sequence must be fresh (no pages yet)."""
        assert seq_id not in self._seq_pages, "sequence already has pages"
        hit = self._lookup_prefix(tokens)
        if hit is None:
            return 0
        key, entry = hit
        self._prefix_index.move_to_end(key)              # LRU touch
        for p in entry.pages:
            self._seq_refs[p] = self._seq_refs.get(p, 0) + 1
        self._seq_pages[seq_id] = list(entry.pages)
        self._seq_len[seq_id] = entry.n_tokens
        return entry.n_tokens

    def register_prefix(self, seq_id, tokens) -> int:
        """After ``seq_id``'s prompt KV is written, retain every
        page-aligned prefix of ``tokens`` in the index (one index ref
        per page per entry) so later requests sharing the prefix can
        skip its prefill.  Idempotent for already-cached prefixes.
        Returns the number of NEW entries."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        pages = self._seq_pages.get(seq_id, [])
        added = 0
        n_pages = len(tokens) // self.page_size
        for i, key in enumerate(self._prefix_keys(tokens, n_pages), 1):
            if key in self._prefix_index:
                self._prefix_index.move_to_end(key)
                continue
            held = pages[:i]
            for p in held:
                self._idx_refs[p] = self._idx_refs.get(p, 0) + 1
            self._prefix_index[key] = _PrefixEntry(held,
                                                   i * self.page_size)
            added += 1
        return added

    def prefix_key_hex(self, tokens, n_tokens: int) -> Optional[str]:
        """Stable CONTENT hash (hex) of the page-aligned prefix
        covering ``n_tokens`` of ``tokens``, or None below one page —
        the journal's page-provenance records carry it (ISSUE 14): page
        indices are replica-local, but this key names the same prefix
        on every replica, so failover can group sharers and a
        disaggregated tier can re-attach transported pages."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n_pages = int(n_tokens) // self.page_size
        if n_pages <= 0:
            return None
        return self._prefix_keys(tokens, n_pages)[-1].hex()

    def _device_pools(self):
        """Every device buffer backing the cache — data pages plus (in
        the int8 mode) the parallel scale pools.  The buffer-loss fault
        site deletes these; ``_recover_pools`` probes them for
        deadness."""
        return (list(self.k_pages) + list(self.v_pages)
                + list(self.k_scales) + list(self.v_scales)
                + list(self.state_pools))

    @property
    def kv_pool_bytes(self) -> int:
        """Resident bytes of what the cache holds a sequence: the KV data
        pages, a pool once however many layers walk it (a layer that
        reads another layer's pages has none of its own), and the
        recurrent slot pools."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in list(self.k_pages) + list(self.v_pages)
                   + list(self.state_pools))

    @property
    def state_pool_bytes(self) -> int:
        """Resident bytes of the recurrent slot pools alone."""
        return sum(int(a.size) * a.dtype.itemsize for a in self.state_pools)

    @property
    def kv_scale_bytes(self) -> int:
        """Resident bytes of the int8 mode's scale pools (0 when the
        cache stores full-precision KV)."""
        return sum(int(a.size) * a.dtype.itemsize
                   for a in list(self.k_scales) + list(self.v_scales))

    @property
    def kv_pool_bytes_per_chip(self) -> int:
        """Per-chip resident bytes of the KV data pages: the global
        pool (a shared pool counted once, as ``kv_pool_bytes`` does)
        divided by the TP degree (the head-axis sharding's HBM win;
        equals ``kv_pool_bytes`` for a 1-chip cache)."""
        return self.kv_pool_bytes // max(1, self.tp)

    @property
    def pinned_pages(self) -> int:
        """Pages currently mapped by at least one live sequence."""
        return len(self._seq_refs)

    @property
    def cached_prefix_pages(self) -> int:
        """Index-retained pages with no sequence ref (reclaimable).
        Iterates a key SNAPSHOT: the /health handler thread reads this
        while the engine thread mutates the refcount dicts."""
        return sum(1 for p in list(self._idx_refs)
                   if p not in self._seq_refs)

    def truncate(self, seq_id, length: int) -> None:
        """Roll a sequence's logical length back (pages stay allocated,
        their tail slots are simply rewritten by later writes) — used by
        the continuous-batching scheduler's scratch padding sequence."""
        if self._seq_len.get(seq_id, 0) > length:
            self._seq_len[seq_id] = length

    @property
    def free_pages(self) -> int:
        """Pool capacity available to new allocations: truly-free pages
        plus evictable prefix-cache pages (reclaimed on demand) — so an
        idle engine reports a fully reclaimed pool even while warm
        prefixes stay cached."""
        return len(self._free) + self.cached_prefix_pages

    def length(self, seq_id: int) -> int:
        return self._seq_len.get(seq_id, 0)

    def page_table(self, seq_ids, max_pages: Optional[int] = None):
        """(batch, max_pages) int32 table + (batch,) lengths for a batch."""
        tables = [self._seq_pages.get(s, []) for s in seq_ids]
        if max_pages is None:
            max_pages = max(1, max(len(t) for t in tables))
        tab = np.zeros((len(seq_ids), max_pages), np.int32)
        for i, t in enumerate(tables):
            tab[i, :len(t)] = t
        lens = np.asarray([self._seq_len.get(s, 0) for s in seq_ids],
                          np.int32)
        return jnp.asarray(tab), jnp.asarray(lens)

    # ------------------------------------------------------- data writes
    def write(self, layer: int, seq_id: int, k_new, v_new) -> None:
        """Append (tokens, kv_heads, head_dim) k/v for one sequence into
        its pages (call allocate() first; the last layer's write advances
        the length)."""
        self.write_batch(layer, [seq_id], k_new[None], v_new[None])

    def plan_write(self, seq_ids, n: int):
        """Host-side half of a step's write: (page, slot) targets for
        ``n`` new tokens per sequence, as flat (batch*n,) int32 arrays,
        WITHOUT touching the device — the jitted decode path scatters
        inside its compiled program using these.  Does NOT advance
        lengths (call advance() once the write is in flight)."""
        b = len(seq_ids)
        pages_flat = np.empty(b * n, np.int32)
        slots_flat = np.empty(b * n, np.int32)
        for i, sid in enumerate(seq_ids):
            start = self._seq_len.get(sid, 0)
            pages = self._seq_pages[sid]
            pos = start + np.arange(n)
            pages_flat[i * n:(i + 1) * n] = [
                pages[p] for p in pos // self.page_size]
            slots_flat[i * n:(i + 1) * n] = pos % self.page_size
        return pages_flat, slots_flat

    def advance(self, seq_ids, n: int) -> None:
        """Advance logical lengths by ``n`` tokens per sequence."""
        for sid in seq_ids:
            self._seq_len[sid] = self._seq_len.get(sid, 0) + n

    def write_batch(self, layer: int, seq_ids, k_new, v_new) -> None:
        """Append one step's k/v for MANY sequences in a single scatter
        per pool: k_new/v_new (batch, tokens, kv_heads, head_dim).  All
        (page, slot) targets for the step are computed host-side from the
        allocator tables, then written with one donated-buffer .set per
        layer — O(step tokens) device work instead of O(pool) per
        sequence (the write-amplification the per-sequence path had).
        The last layer's write advances the lengths."""
        b, n = k_new.shape[0], k_new.shape[1]
        pages_flat, slots_flat = self.plan_write(seq_ids, n)
        pg = jnp.asarray(pages_flat)
        sl = jnp.asarray(slots_flat)
        # (b, n, kvh, d) -> (kvh, b*n, d) to line up with pool[:, pg, sl]
        ks = jnp.swapaxes(
            jnp.reshape(k_new, (b * n,) + k_new.shape[2:]), 0, 1)
        vs = jnp.swapaxes(
            jnp.reshape(v_new, (b * n,) + v_new.shape[2:]), 0, 1)
        if self.kv_quant:
            # quantize fused into the append (eager twin of the traced
            # context's in-program scatter)
            ks, ksc = quantize_kv(ks)
            vs, vsc = quantize_kv(vs)
            self.k_scales[layer] = _append_rows_donated(
                self.k_scales[layer], pg, sl, ksc)
            self.v_scales[layer] = _append_rows_donated(
                self.v_scales[layer], pg, sl, vsc)
        self.k_pages[layer] = _append_rows_donated(
            self.k_pages[layer], pg, sl,
            packed_k_rows(ks, self.k_pages[layer]))
        self.v_pages[layer] = _append_rows_donated(
            self.v_pages[layer], pg, sl, vs)
        if layer == self.num_layers - 1:
            self.advance(seq_ids, n)
