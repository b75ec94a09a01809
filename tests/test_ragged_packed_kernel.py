"""The ragged paged kernel takes a row's queries from the step's PACKED
tokens (ISSUE 50): a grid step copies its row's live tiles from the
row's offset on the packed axis and writes the row's own positions back
there.  The (rows, span) rectangle is the same kernel at offsets a span
apart, so every live query of a packed call must equal the rectangle
call's BIT FOR BIT, and every position no row owns must be zero —
whatever the group, the lane width, the mask, the storage and the way
the rows lie on the axis.  The kernels run interpreted here; the chip's
own arithmetic is ``tools/paged_ragged_micro.py``'s."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.paged import _TracedPagedContext
from paddle_tpu.framework.tensor import wrap_array
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import (
    _ragged_xla, _rows_of_packed, _staged_group, k_pack, live_query_tiles,
    paged_attention_ragged, q_positions_moved,
    query_tile_rows, quantize_kv)

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32
PAGE = 16


def pools(rng, kvh, pages, dk, dv, kv):
    """(k_pages, v_pages, scales): K heads of 192 lie two to a pool row
    (``k_pack``); ``kv`` 'int8' stores both pools quantized."""
    k = jnp.asarray(rng.standard_normal((kvh, pages, PAGE, dk)), BF16)
    v = jnp.asarray(rng.standard_normal((kvh, pages, PAGE, dv)), BF16)
    kw = {}
    if kv == "int8":
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        kw = dict(k_scales=ks, v_scales=vs)
    n = k_pack(dk)
    k = k.reshape(kvh // n, n, pages, PAGE, dk).transpose(0, 2, 3, 1, 4) \
        .reshape(kvh // n, pages, PAGE, n * dk)
    return k, v, kw


def case(seed, group, dk, dv, span, q_lens, *, kvh=2, kv="bf16",
         order="forward", tokens=None, gap=0):
    """A packed stream and what the kernel is told of it: rows of
    ``q_lens`` queries whose contexts end with them, lying on the axis
    one behind the other (``forward``), the LAST row first (``backward``:
    a grid step then runs after the one whose tokens stand right behind
    its own, so a tail written past a row's own positions would stay), or
    a span apart (``rectangle``: ``row_off`` None)."""
    rng = np.random.default_rng(seed)
    n = len(q_lens)
    ctx = rng.integers(0, 700, n)
    lens = np.asarray(q_lens) + ctx
    lens[np.asarray(q_lens) == 0] = 5
    need = -(-lens // PAGE)
    pages = int(need.sum()) + 2
    kp, vp, kw = pools(rng, kvh, pages, dk, dv, kv)
    table = int(need.max()) + 1
    tabs = np.zeros((n, table), np.int32)
    perm, at = rng.permutation(pages), 0
    for i, k in enumerate(need):
        tabs[i, :k] = perm[at:at + k]
        at += k
    if order == "rectangle":
        off, total = None, n * span
        starts = np.arange(n) * span
    else:
        sizes = np.asarray(q_lens) + gap
        starts = np.cumsum(sizes) - sizes
        if order == "backward":
            starts = sizes.sum() - np.cumsum(sizes)
        total = tokens or int(sizes.sum())
        off = jnp.asarray(starts, I32)
    q = jnp.asarray(rng.standard_normal((total, kvh * group, dk)), BF16)
    return dict(q=q, kp=kp, vp=vp, lens=jnp.asarray(lens, I32),
                q_lens=jnp.asarray(q_lens, I32), tabs=jnp.asarray(tabs),
                off=off, starts=starts, span=span, kw=kw,
                scale=1 / math.sqrt(dk))


def both(c, **kw):
    """(packed output, rectangle output, the calls' keywords) of one
    case, both through the interpreted kernel."""
    kw = dict(c["kw"], interpret=True, scale=c["scale"], **kw)
    call = (c["kp"], c["vp"], c["lens"], c["q_lens"], c["tabs"])
    got = paged_attention_ragged(c["q"], *call, row_off=c["off"],
                                 span=c["span"], **kw)
    rect = paged_attention_ragged(
        _rows_of_packed(c["q"], c["off"], c["span"]), *call, **kw)
    assert got.shape == c["q"].shape[:2] + (c["vp"].shape[-1],)
    return (np.asarray(got.astype(F32)), np.asarray(rect.astype(F32)),
            kw)


def held(c, got, rect):
    """Every live query of the packed call is the rectangle call's, bit
    for bit; every other position of both is zero."""
    own = np.zeros(got.shape[0], bool)
    for r, (at, n) in enumerate(zip(c["starts"], np.asarray(c["q_lens"]))):
        np.testing.assert_array_equal(got[at:at + n], rect[r, :n])
        assert not rect[r, n:].any()
        own[at:at + n] = True
        assert n == 0 or got[at:at + n].any(axis=(1, 2)).all()
    assert not got[~own].any()
    assert np.isfinite(got).all()


#: name -> q_lens as a function of (span, positions a tile)
MIXES = {
    "all_one": lambda s, per: [1] * 6,
    "two_chunks_and_ones": lambda s, per: [s, 1, s, 1, 1, 1],
    "a_row_of_none": lambda s, per: [3, 0, s // 2 + 1, 1],
    # a tail tile of one, of all but one, and of one over a tile's edge
    "tails_that_abut": lambda s, per: [per + 1, 2 * per - 1, 1, per, 2],
}

#: group, K width, V width, span: the serving cells' groups (Phi-4-flash
#: 2 and 4, Mistral and ZAYA 4, Laguna 6 and 8, MiMo 8 and 16) at 128
#: lanes and at MiMo's packed rows of two 192-wide heads
SHAPES = [(g, dk, dv, 128 if g == 2 else 64 if g < 16 else 32)
          for g in (2, 4, 6, 8, 16) for dk, dv in ((128, 128), (192, 128))]


class TestPackedIsTheRectangleBitForBit:
    @pytest.mark.parametrize("order", ["forward", "backward"])
    @pytest.mark.parametrize("group,dk,dv,span", SHAPES, ids=[
        f"g{g}x{dk}" for g, dk, _dv, _s in SHAPES])
    def test_every_group_and_lane_width(self, group, dk, dv, span, order):
        """Each shape with the mix that follows it round the list, the
        rows standing forward and backward on the axis."""
        names = sorted(MIXES)
        at = SHAPES.index((group, dk, dv, span))
        tile = query_tile_rows(span * group, group, BF16)
        q_lens = MIXES[names[at % len(names)]](span, tile // group)
        c = case(at, group, dk, dv, span, q_lens, order=order)
        got, rect, kw = both(c)
        held(c, got, rect)
        ref = np.asarray(_ragged_xla(
            _rows_of_packed(c["q"], c["off"], span), c["kp"], c["vp"],
            c["lens"], c["q_lens"], c["tabs"], c["scale"]).astype(F32))
        np.testing.assert_allclose(rect, ref, rtol=3e-2, atol=3e-2)

    @pytest.mark.parametrize("mix", sorted(MIXES))
    @pytest.mark.parametrize("how", ["window", "sinks", "int8",
                                     "window_sinks_192"])
    def test_every_mask_and_storage(self, mix, how):
        group, dk, dv, span = (8, 192, 128, 32) if "192" in how \
            else (4, 128, 128, 64)
        tile = query_tile_rows(span * group, group, BF16)
        c = case(len(mix), group, dk, dv, span,
                 MIXES[mix](span, tile // group), order="backward",
                 kv="int8" if how == "int8" else "bf16")
        rng = np.random.default_rng(7)
        kw = {}
        if "window" in how:
            kw["window"] = 48
        if "sinks" in how:
            kw["sinks"] = jnp.asarray(rng.standard_normal(2 * group), F32)
        got, rect, _ = both(c, **kw)
        held(c, got, rect)

    @pytest.mark.parametrize("group", [4, 6, 16])
    def test_the_rectangle_is_offsets_a_span_apart(self, group):
        """``row_off`` None and the offsets written out give one output."""
        span = 32
        c = case(3, group, 128, 128, span, [span, 1, 0, 7],
                 order="rectangle")
        got, rect, kw = both(c)
        held(c, got, rect)
        spelled = paged_attention_ragged(
            c["q"], c["kp"], c["vp"], c["lens"], c["q_lens"], c["tabs"],
            row_off=jnp.arange(4, dtype=I32) * span, span=span, **kw)
        np.testing.assert_array_equal(got, np.asarray(spelled.astype(F32)))

    @pytest.mark.parametrize("group", [4, 8])
    def test_the_last_row_at_the_pack_s_end(self, group):
        """The last row's one token is the stream's last position: its
        tile's tail is read from the slack past the stream."""
        span = 64
        c = case(5, group, 128, 128, span, [span, 1, 1])
        assert int(c["starts"][-1]) == c["q"].shape[0] - 1
        got, rect, _ = both(c)
        held(c, got, rect)

    def test_pad_between_and_behind_the_rows_is_zeros(self):
        """Rows that leave positions between them, and a stream longer
        than its rows: what no row owns reads zeros."""
        c = case(6, 4, 128, 128, 64, [5, 1, 40, 1], gap=3, tokens=96)
        got, rect, _ = both(c)
        held(c, got, rect)

    def test_a_head_dim_under_a_lane_tile_and_a_group_of_three(self):
        c = case(8, 3, 64, 64, 16, [16, 1, 5, 0, 2], order="backward")
        got, rect, _ = both(c)
        held(c, got, rect)

    def test_offsets_past_the_stream_copy_nothing_outside_it(self):
        """An offset the caller got wrong is held inside the stream: the
        copies of a chip fault on an address outside their array."""
        c = case(9, 4, 128, 128, 64, [1, 1])
        c["off"] = jnp.asarray([0, 10 ** 6], I32)
        got, _, _ = both(c)
        assert np.isfinite(got).all() and got[-1].any()


class TestTheStepCallsThePackedKernel:
    """``_TracedPagedContext.attend`` on the packed tokens, the kernel
    interpreted: a layer that appends and a layer that reads the pool
    another wrote (``attend(q, None, None)``)."""

    def test_an_own_and_a_shared_pool(self, monkeypatch):
        from paddle_tpu.inference import paged
        rng = np.random.default_rng(11)
        kvh, group, d, span = 2, 4, 128, 16
        q_lens = np.asarray([16, 1, 3, 1])
        ctx_lens = np.asarray([20, 7, 0, 33])
        off = np.cumsum(q_lens) - q_lens
        t = 32                                   # 21 tokens and pad
        need = -(-(q_lens + ctx_lens) // PAGE)
        pages = int(need.sum()) + 1
        kp, vp, _ = pools(rng, kvh, pages, d, d, "bf16")
        tabs = np.zeros((4, 4), np.int32)
        at = 0
        for i, k in enumerate(need):
            tabs[i, :k] = np.arange(at, at + k)
            at += k
        pg = np.full(t, pages, np.int32)         # pad: the dropped page
        sl = np.zeros(t, np.int32)
        for r in range(4):
            for j in range(q_lens[r]):
                p = ctx_lens[r] + j
                pg[off[r] + j] = tabs[r, p // PAGE]
                sl[off[r] + j] = p % PAGE

        def x(heads):
            return wrap_array(jnp.asarray(
                rng.standard_normal((t, 1, heads, d)), BF16))

        q, k, v, q2 = x(kvh * group), x(kvh), x(kvh), x(kvh * 2)

        def outs(row_off, tokens):
            ctx = _TracedPagedContext(
                [kp], [vp], jnp.asarray(pg[:tokens]), jnp.asarray(sl[:tokens]),
                jnp.asarray(q_lens + ctx_lens, I32), jnp.asarray(tabs),
                q_lens=jnp.asarray(q_lens, I32), row_off=row_off, span=span)
            take = (lambda a: wrap_array(a._data[:tokens]))
            own = ctx.attend(take(q), take(k), take(v))
            shared = ctx.attend(take(q2), None, None, window=24)
            return (np.asarray(own._data.astype(F32)),
                    np.asarray(shared._data.astype(F32)))

        # the oracle on the stream gathered to the rectangle, then the
        # kernel on the stream itself
        oracle = outs(jnp.asarray(off, I32), t)
        monkeypatch.setattr(
            paged, "paged_attention_ragged",
            functools.partial(paged_attention_ragged, interpret=True))
        packed = outs(jnp.asarray(off, I32), t)
        for got, ref in zip(packed, oracle):
            assert got.shape == ref.shape
            np.testing.assert_allclose(got[:21], ref[:21], rtol=3e-2,
                                       atol=3e-2)
            assert got[:21].any(axis=(1, 2, 3)).all()
            assert not got[21:].any() and not ref[21:].any()


class TestWhatTheCopiesMove:
    @pytest.mark.parametrize("group,want", [(1, 2), (2, 2), (3, 4), (4, 4),
                                            (6, 8), (8, 8), (12, 16),
                                            (16, 16), (24, 32)])
    def test_staged_group_of_bfloat16(self, group, want):
        assert _staged_group(group, BF16) == want

    def test_staged_group_of_float32(self):
        assert [_staged_group(g, F32) for g in (1, 3, 4, 6, 8, 12)] == \
            [1, 4, 4, 8, 8, 16]

    def test_positions_moved_by_bucket(self):
        """The MiMo cell's chunk step: 2 rows of 128 tokens and 30 of one
        in a (32, 128) bucket, groups 16 and 8 (8 and 16 positions a
        tile): an eighth of the rectangle's 4,096 positions, where a
        block a row moved them all; a decode step moves a position a
        row."""
        ql = [128, 128] + [1] * 30
        assert q_positions_moved(ql, 128, 16, BF16) == 256 + 30 * 8
        assert q_positions_moved(ql, 128, 8, BF16) == 256 + 30 * 16
        assert q_positions_moved(ql, 128, 4, BF16) == 256 + 30 * 32
        assert q_positions_moved([1] * 32, 1, 16, BF16) == 32
        assert q_positions_moved([5, 0, 1], 64, 6, BF16) == 2 * 16

    @pytest.mark.parametrize("hb", [2, 1])
    @pytest.mark.parametrize("group,span", [(4, 64), (16, 32), (6, 16)])
    def test_host_count_is_what_the_copy_loops_issue(self, monkeypatch,
                                                     group, span, hb):
        """``q_positions_moved`` against the query descriptors an
        interpreted kernel STARTS (a tile of positions each, all the
        step's heads), and the output descriptors against the rows' own
        tokens: one a set bit of a row's ``q_len``."""
        tile = query_tile_rows(span * group, group, BF16)
        per = tile // group
        q_lens = [span, 1, 0, min(per + 1, span), 3]
        # V twice as wide as K: a copy of four axes is a query tile where
        # it is 128 lanes wide and a piece of the output where it is 256
        c = case(12, group, 128, 256, span, q_lens)
        seen = {128: [], 256: [], "page": []}
        make = pa.pltpu.make_async_copy

        class Counted:
            def __init__(self, src, dst, sem):
                self.copy = make(src, dst, sem)
                assert src.shape == dst.shape
                self.kind = src.shape[-1] if len(src.shape) == 4 else "page"
                self.positions = src.shape[1]

            def start(self):
                jax.debug.callback(
                    lambda: seen[self.kind].append(self.positions))
                self.copy.start()

            def wait(self):
                self.copy.wait()

        monkeypatch.setattr(pa.pltpu, "make_async_copy", Counted)
        out = pa._decode_call(
            c["q"], c["kp"], c["vp"], c["lens"], c["tabs"], c["scale"],
            interpret=True, n_query=span, q_lens=c["q_lens"],
            row_off=c["off"], head_group=hb)
        jax.block_until_ready(out)
        jax.effects_barrier()
        steps = 2 // hb
        tiles = live_query_tiles(np.asarray(q_lens), group, tile)
        assert seen[128] == [per] * (steps * int(tiles.sum()))
        assert sum(seen[128]) == steps * q_positions_moved(
            q_lens, span, group, BF16)
        assert sum(seen[256]) == steps * sum(q_lens)
        assert len(seen[256]) == steps * sum(
            bin(n).count("1") for n in q_lens)
        assert seen["page"]
