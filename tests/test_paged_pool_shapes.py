"""A page's shape as a property of the POOL (ISSUE 49): K pages wider than V
pages (192 beside 128: two K heads a row of 384, ``k_pack``), a learned sink
a query head in the softmax's denominator, pools of unequal KV heads in one
cache.  The Pallas kernels (interpreted here) against their XLA oracles for
the one-query and the ragged calls; the oracles against a plain numpy
attention with the sink written out as one more column; what the host counts
of a grid step's heads; and that a model whose pools are alike builds the
pools it built and traces the program it traced."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas.paged_attention import (
    PagedKVCache, _decode_call, _decode_xla, _ragged_xla, k_pack,
    packed_k_rows, paged_attention, paged_attention_ragged, walk_block_pages,
    walk_cut, walk_head_group)

F32, I32 = jnp.float32, jnp.int32
PAGE, PAGES, TABLE = 4, 48, 10


def packed(k):
    """A K pool (kv_heads, pages, page, d) as the cache lays it out."""
    kvh, pages, page, d = k.shape
    n = k_pack(d)
    return k.reshape(kvh // n, n, pages, page, d).transpose(0, 2, 3, 1, 4) \
        .reshape(kvh // n, pages, page, n * d)


def make(kvh, heads, dk, dv, nq, sinks, seed=0):
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.normal(size=(kvh, PAGES, PAGE, dk)), F32)
    v = jnp.asarray(rng.normal(size=(kvh, PAGES, PAGE, dv)), F32)
    lens = jnp.asarray([nq + 2, 17, 37], I32)
    tabs = jnp.asarray(rng.permutation(PAGES)[:3 * TABLE].reshape(3, TABLE),
                       I32)
    q = jnp.asarray(rng.normal(size=(3, nq, heads, dk)), F32)
    q_lens = jnp.asarray([nq, 1, max(1, nq // 2)], I32)
    b = jnp.asarray(rng.normal(size=(heads,)) * 2, F32) if sinks else None
    return q, k, v, lens, q_lens, tabs, b


def numpy_attention(q, k, v, lens, q_lens, tabs, scale, window, sinks):
    """Every real query against its row's keys, the mask and the sink
    written out: one more column in the softmax, dropped afterwards."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    n, nq, qh, _ = q.shape
    kvh = k.shape[0]
    out = np.zeros(q.shape[:3] + (v.shape[-1],))
    for r in range(n):
        L, ql = int(lens[r]), int(q_lens[r])
        need = -(-L // PAGE)
        ks = k[:, np.asarray(tabs[r, :need])].reshape(kvh, -1, k.shape[-1])
        vs = v[:, np.asarray(tabs[r, :need])].reshape(kvh, -1, v.shape[-1])
        for j in range(ql):
            p = L - ql + j
            lo = 0 if window is None else max(0, p - window + 1)
            for h in range(qh):
                g = h // (qh // kvh)
                s = ks[g, lo:p + 1] @ q[r, j, h] * scale
                if sinks is not None:
                    s = np.append(s, float(sinks[h]))
                w = np.exp(s - s.max())
                w = (w / w.sum())[:p + 1 - lo]
                out[r, j, h] = w @ vs[g, lo:p + 1]
    return out


SHAPES = [(2, 8, 24, 16), (4, 8, 192, 128), (2, 4, 192, 128), (2, 6, 16, 16)]


class TestWidthsAndSinks:
    @pytest.mark.parametrize("window", [None, 6], ids=["full", "window6"])
    @pytest.mark.parametrize("sinks", [False, True], ids=["plain", "sinks"])
    @pytest.mark.parametrize("kvh,heads,dk,dv", SHAPES,
                             ids=["24x16", "192x128g2", "192x128g2kv2",
                                  "16x16g3"])
    def test_ragged_kernel_and_oracle(self, kvh, heads, dk, dv, sinks,
                                      window):
        q, k, v, lens, q_lens, tabs, b = make(kvh, heads, dk, dv, 8, sinks)
        scale = 1 / math.sqrt(dk)
        want = numpy_attention(q, k, v, lens, q_lens, tabs, scale, window, b)
        oracle = _ragged_xla(q, packed(k), v, lens, q_lens, tabs, scale,
                             window=window, sinks=b)
        got = paged_attention_ragged(q, packed(k), v, lens, q_lens, tabs,
                                     scale, interpret=True, window=window,
                                     sinks=b)
        assert got.shape == oracle.shape == q.shape[:3] + (dv,)
        assert np.abs(np.asarray(oracle) - want).max() < 1e-4
        assert np.abs(np.asarray(got) - want).max() < 1e-4
        # the dead queries of a row are zeros in both
        assert not np.asarray(got[1, 1:]).any()
        assert not np.asarray(oracle[1, 1:]).any()

    @pytest.mark.parametrize("window", [None, 6], ids=["full", "window6"])
    @pytest.mark.parametrize("sinks", [False, True], ids=["plain", "sinks"])
    @pytest.mark.parametrize("kvh,heads,dk,dv", SHAPES[:3],
                             ids=["24x16", "192x128g2", "192x128g2kv2"])
    def test_one_query_kernel_and_oracle(self, kvh, heads, dk, dv, sinks,
                                         window):
        q, k, v, lens, _, tabs, b = make(kvh, heads, dk, dv, 1, sinks, seed=1)
        scale = 1 / math.sqrt(dk)
        want = numpy_attention(q, k, v, lens, np.ones(3, int), tabs, scale,
                               window, b)[:, 0]
        oracle = _decode_xla(q[:, 0], packed(k), v, lens, tabs, scale,
                             window=window, sinks=b)
        got = paged_attention(q[:, 0], packed(k), v, lens, tabs, scale,
                              interpret=True, window=window, sinks=b)
        assert np.abs(np.asarray(oracle) - want).max() < 1e-4
        assert np.abs(np.asarray(got) - want).max() < 1e-4

    @pytest.mark.parametrize("group,span,window,sinks", [
        (16, 64, None, False), (8, 128, 128, True), (8, 128, 700, True)],
        ids=["full-g16", "sliding-g8-w128", "sliding-g8-w700"])
    def test_rows_over_several_blocks_of_512(self, group, span, window,
                                             sinks):
        """MiMo's full and sliding calls in little (two KV heads of 192
        beside 128, a pool row of 384) at buckets whose 1,024 rows cut
        the walk at 256 tokens before ISSUE 51: tiles of 128 rows walk
        blocks of 512.  Rows whose context ends inside the third block,
        on the second's last token, a token into the second, inside the
        first; a chunk row, a few queries, one; the engine's pad row.  A
        window of 128 reaches 159 columns of a tile and keeps blocks of
        256 (a chunk row's 255 visible keys cross two of them), one of
        700 walks two blocks of 512 from the page of the first visible
        key."""
        page, kvh, dk, dv = 16, 2, 192, 128
        tile, pages, _hb = walk_cut(kvh, page, dk, span, group, F32, F32,
                                    dv, sinks, window=window)
        assert (tile, page * pages) == (128, 256 if window == 128 else 512)
        assert page * walk_block_pages(page, dk, span * group, F32, dv) == 256
        rng = np.random.default_rng(51)
        lens = np.asarray([1100, 1300, 1024, 530, 513, 40, 1])
        q_lens = np.asarray([span, 1, 5, span // 2, 1, 3, 1])
        need = -(-lens // page)
        total = int(need.sum()) + 3
        k = jnp.asarray(rng.normal(size=(kvh, total, page, dk)), F32)
        v = jnp.asarray(rng.normal(size=(kvh, total, page, dv)), F32)
        tabs, perm, at = np.zeros((len(lens), 96), np.int32), \
            rng.permutation(total), 0
        for r, n in enumerate(need):
            tabs[r, :n] = perm[at:at + n]
            at += n
        q = jnp.asarray(rng.normal(size=(len(lens), span, kvh * group, dk)),
                        F32)
        b = jnp.asarray(rng.normal(size=(kvh * group,)) * 2, F32) \
            if sinks else None
        args = (q, packed(k), v, jnp.asarray(lens, I32),
                jnp.asarray(q_lens, I32), jnp.asarray(tabs))
        scale = 1 / math.sqrt(dk)
        got = np.asarray(paged_attention_ragged(
            *args, scale, interpret=True, window=window, sinks=b))
        want = np.asarray(_ragged_xla(*args, scale, window=window, sinks=b))
        live = np.arange(span)[None, :] < q_lens[:, None]
        assert np.abs(got[live] - want[live]).max() < 1e-4
        assert np.abs(want[live]).max() > 0.1
        assert not got[~live].any() and not want[~live].any()

    def test_a_sink_takes_mass_and_gives_no_value(self):
        """A sink far above the scores leaves nearly nothing of the
        values; one far below changes nothing."""
        q, k, v, lens, q_lens, tabs, _ = make(2, 8, 24, 16, 8, False)
        call = functools.partial(paged_attention_ragged, q, packed(k), v,
                                 lens, q_lens, tabs, 0.2, interpret=True)
        plain = np.asarray(call())
        high = np.asarray(call(sinks=jnp.full((8,), 40.0)))
        low = np.asarray(call(sinks=jnp.full((8,), -40.0)))
        assert np.abs(high).max() < 1e-6 < np.abs(plain).max()
        assert np.abs(low - plain).max() < 1e-6

    def test_no_sinks_is_bit_equal_to_no_argument(self):
        q, k, v, lens, q_lens, tabs, _ = make(2, 8, 16, 16, 8, False)
        a = paged_attention_ragged(q, k, v, lens, q_lens, tabs, 0.25,
                                   interpret=True)
        b = paged_attention_ragged(q, k, v, lens, q_lens, tabs, 0.25,
                                   interpret=True, sinks=None)
        assert np.array_equal(np.asarray(a), np.asarray(b))


class TestPoolsOfUnequalShape:
    def test_one_cache_two_kinds_of_pool(self):
        """A pool of 2 KV heads beside one of 4, K of 192 beside V of 128,
        written through the eager cache and attended by the one-query
        kernel: one page table serves both, and a page's bytes are the
        pool's own."""
        shapes = [(2, 192, 128), (4, 192, 128)]
        cache = PagedKVCache(2, None, None, total_pages=8, page_size=PAGE,
                             pool_shapes=shapes)
        assert cache.kv_heads is None and cache.head_dim is None
        assert [a.shape for a in cache.k_pages] == [(1, 8, 4, 384),
                                                    (2, 8, 4, 384)]
        assert [a.shape for a in cache.v_pages] == [(2, 8, 4, 128),
                                                    (4, 8, 4, 128)]
        assert [cache.page_bytes(p) for p in (0, 1)] \
            == [2 * 4 * 320 * 4, 4 * 4 * 320 * 4]
        assert cache.kv_pool_bytes == 8 * (cache.page_bytes(0)
                                           + cache.page_bytes(1))
        rng = np.random.default_rng(5)
        n = 11
        cache.allocate(0, n)
        new = {}
        for layer, (kvh, dk, dv) in enumerate(shapes):
            new[layer] = (jnp.asarray(rng.normal(size=(n, kvh, dk)), F32),
                          jnp.asarray(rng.normal(size=(n, kvh, dv)), F32))
            cache.write(layer, 0, *new[layer])
        assert cache.length(0) == n
        tab, lens = cache.page_table([0])
        for layer, (kvh, dk, dv) in enumerate(shapes):
            q = jnp.asarray(rng.normal(size=(1, 8, dk)), F32)
            got = paged_attention(q, cache.k_pages[layer],
                                  cache.v_pages[layer], lens, tab,
                                  interpret=True)
            k, v = (np.asarray(a, np.float64) for a in new[layer])
            for h in range(8):
                g = h // (8 // kvh)
                s = k[:, g] @ np.asarray(q[0, h], np.float64) / math.sqrt(dk)
                w = np.exp(s - s.max())
                assert np.abs(np.asarray(got[0, h])
                              - (w / w.sum()) @ v[:, g]).max() < 1e-4
        cache.reset_pools()
        assert [a.shape for a in cache.k_pages] == [(1, 8, 4, 384),
                                                    (2, 8, 4, 384)]

    def test_packed_rows_are_two_heads_side_by_side(self):
        vals = jnp.arange(4 * 3 * 192, dtype=F32).reshape(4, 3, 192)
        pool = jnp.zeros((2, 8, 4, 384))
        rows = packed_k_rows(vals, pool)
        assert rows.shape == (2, 3, 384)
        assert np.array_equal(np.asarray(rows[1, 2, :192]),
                              np.asarray(vals[2, 2]))
        assert np.array_equal(np.asarray(rows[1, 2, 192:]),
                              np.asarray(vals[3, 2]))
        assert packed_k_rows(vals[:, :, :128], jnp.zeros((4, 8, 4, 128))) \
            .shape == (4, 3, 128)
        with pytest.raises(ValueError, match="fill whole"):
            PagedKVCache(1, None, None, total_pages=2,
                         pool_shapes=[(3, 192, 128)])

    @pytest.mark.parametrize("kvh,span,window,hb,pages,before", [
        (8, 1, 128, 8, 16, 32), (8, 128, 128, 4, 16, 16),
        (4, 1, None, 4, 32, 32), (4, 128, None, 4, 32, 8)])
    def test_the_cell_s_head_groups_and_blocks(self, kvh, span, window, hb,
                                               pages, before):
        """MiMo-V2-Flash's calls at a decode step (8 or 16 query rows a KV
        head, every head a grid step) and at a 128-token span (tiles of
        128 rows; all 4 full heads a grid step, and 4 of the 8 sliding
        ones: the sink block and the 384-wide queries count).  The full
        layers walk in blocks of 512 tokens at both, where the span's
        2,048 bucket rows cut them at 128; the sliding ones, whose window
        of 128 reaches 144 or 159 columns of a tile, in 256 at both, where
        the decode step walked 512 (ISSUE 51)."""
        bf16, group, sinks = jnp.bfloat16, 64 // kvh, window is not None
        tile, got, heads = walk_cut(kvh, 16, 192, span, group, bf16, bf16,
                                    128, sinks, span > 1, window)
        assert (tile, got, heads) == (min(span * group, 128), pages, hb)
        reach = window and window + tile // group - 1 + 16
        assert walk_block_pages(16, 192, tile, bf16, 128, reach) == pages
        assert walk_block_pages(16, 192, span * group, bf16, 128) == before
        assert walk_head_group(kvh, 16, 192, span * group, bf16, bf16, 128,
                               sinks, tile, reach) == hb


class TestAlikePoolsAreWhatTheyWere:
    def test_the_pools_are_byte_for_byte_the_old_constructor_s(self):
        old = PagedKVCache(3, 2, 16, total_pages=8, page_size=PAGE)
        new = PagedKVCache(3, None, None, total_pages=8, page_size=PAGE,
                           pool_shapes=[(2, 16, 16)] * 3)
        for a, b in zip(old._device_pools(), new._device_pools()):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)
        assert old.kv_pool_bytes == new.kv_pool_bytes
        assert old.pool_shapes == new.pool_shapes
        assert old.page_bytes(0) == 2 * PAGE * 32 * 4

    @pytest.mark.parametrize("nq", [1, 8])
    def test_the_program_is_the_one_without_the_new_operands(self, nq):
        """K as wide as V and no sink: the call takes the operands it took
        (two prefetched tables, q, K, V), pads and selects nothing around
        the kernel, and its text does not depend on how the absent sink is
        spelled."""
        q, k, v, lens, q_lens, tabs, _ = make(2, 8, 128, 128, nq, False)
        q = q if nq > 1 else q[:, 0]

        def text(**kw):
            return str(jax.make_jaxpr(functools.partial(
                _decode_call, scale=0.1, interpret=True, n_query=nq,
                q_lens=q_lens if nq > 1 else None, **kw))(
                    q, k, v, lens, tabs))

        plain = text()
        assert plain == text(sinks=None)
        outer = plain.split("pallas_call")[0]
        assert "select_n" not in outer and " pad[" not in outer
        assert plain.count("pallas_call[") == 1
        with_sink = text(sinks=jnp.zeros((8,), F32))
        assert with_sink != plain


class TestQueriesPackedBeforeTheRectangle:
    def test_packed_queries_are_what_the_call_would_pack(self):
        """The ragged step packs a head's queries into its K row's lanes on
        the step's TOKENS and hands the kernel the rectangle already as
        wide as a K row: the same output bit for bit, and nothing selected
        around the kernel at the rectangle's size."""
        q, k, v, lens, q_lens, tabs, b = make(4, 8, 192, 128, 8, True)
        kp = packed(k)
        wide = pa.packed_queries(q, kp, v)
        assert wide.shape == q.shape[:3] + (384,)
        assert pa.packed_queries(wide, kp, v) is wide
        # head 2 (kv head 1 of a group of two: the row's second half)
        assert not np.asarray(wide[..., 2, :192]).any()
        assert np.array_equal(np.asarray(wide[..., 2, 192:]),
                              np.asarray(q[..., 2, :]))
        assert np.array_equal(
            np.asarray(pa._unpacked_queries(wide, kp, v)), np.asarray(q))
        kw = dict(interpret=True, window=6, sinks=b)
        narrow = paged_attention_ragged(q, kp, v, lens, q_lens, tabs, **kw)
        packed_first = paged_attention_ragged(wide, kp, v, lens, q_lens,
                                              tabs, **kw)
        assert np.array_equal(np.asarray(narrow), np.asarray(packed_first))
        oracle = _ragged_xla(wide, kp, v, lens, q_lens, tabs,
                             1 / math.sqrt(192), window=6, sinks=b)
        assert np.abs(np.asarray(oracle) - np.asarray(narrow)).max() < 1e-4
        text = str(jax.make_jaxpr(functools.partial(
            _decode_call, scale=0.1, interpret=True, n_query=8,
            q_lens=q_lens))(wide, kp, v, lens, tabs))
        assert "select_n" not in text.split("pallas_call")[0]
