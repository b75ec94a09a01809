"""Paged attention + KV cache + fused norm/rope kernels (VERDICT r3 item
4b/4c; reference: block_multi_head_attention_kernel.cu, fused_rope_*.cu).
Pallas kernels run in interpret mode on CPU; on TPU the same code
compiles via Mosaic."""
import os
import sys
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.paged_attention import (
    PagedKVCache, append_rows, paged_attention, paged_attention_multi,
    paged_attention_ragged, _decode_xla, _multi_xla, _ragged_xla,
    kv_pages_copied, kv_tokens_walked, live_query_tiles,
    q_positions_computed, query_tile_rows, quantize_kv, walk_block_pages,
    walk_cut, walk_head_group)
from paddle_tpu.ops.pallas import paged_attention as paged_attention_mod
from paddle_tpu.ops.pallas.flash_attention import mha_reference
from paddle_tpu.ops.pallas.fused_norm_rope import (
    rms_norm_pallas, rms_norm_xla, fused_rope_pallas, fused_rope_xla)


def _fill_cache(rng, cache, lens):
    per_seq = {}
    for i, L in enumerate(lens):
        cache.allocate(i, L)
        k = jnp.asarray(rng.standard_normal(
            (L, cache.kv_heads, cache.head_dim)), jnp.float32)
        v = jnp.asarray(rng.standard_normal(
            (L, cache.kv_heads, cache.head_dim)), jnp.float32)
        for layer in range(cache.num_layers):
            cache.write(layer, i, k, v)
        per_seq[i] = (k, v)
    return per_seq


class TestPagedAttention:
    def test_kernel_matches_dense_reference(self):
        rng = np.random.default_rng(0)
        q_heads, kv_heads, d, page = 8, 2, 128, 16
        cache = PagedKVCache(1, kv_heads, d, total_pages=64, page_size=page)
        lens = [37, 5, 64]          # ragged; 5 < one page, 64 = exact pages
        kv = _fill_cache(rng, cache, lens)
        q = jnp.asarray(rng.standard_normal((3, q_heads, d)), jnp.float32)
        tab, lengths = cache.page_table(range(3))

        out = paged_attention(q, cache.k_pages[0], cache.v_pages[0],
                              lengths, tab, interpret=True)
        out_xla = _decode_xla(q, cache.k_pages[0], cache.v_pages[0],
                              lengths, tab, 1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_xla),
                                   rtol=2e-4, atol=2e-4)
        for i, L in enumerate(lens):
            K, V = kv[i]
            ref = mha_reference(q[i][None, :, None, :],
                                jnp.swapaxes(K, 0, 1)[None],
                                jnp.swapaxes(V, 0, 1)[None],
                                causal=False)[0, :, 0]
            np.testing.assert_allclose(np.asarray(out[i]),
                                       np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)

    def test_multi_query_kernel_matches_per_token_decode(self):
        """The ragged multi-query verify path (ISSUE 6): S query tokens
        per row in one pass must equal S single-token decode calls at
        the interleaved lengths — per row, per query position — on both
        the Pallas kernel (interpret) and the XLA fallback."""
        rng = np.random.default_rng(1)
        q_heads, kv_heads, d, page, S = 8, 2, 128, 16, 4
        cache = PagedKVCache(1, kv_heads, d, total_pages=64,
                             page_size=page)
        lens = [37, 6, 64]          # POST-block totals, ragged
        _fill_cache(rng, cache, lens)
        q = jnp.asarray(rng.standard_normal((3, S, q_heads, d)),
                        jnp.float32)
        tab, lengths = cache.page_table(range(3))

        out_k = paged_attention_multi(q, cache.k_pages[0],
                                      cache.v_pages[0], lengths, tab,
                                      interpret=True)
        out_x = _multi_xla(q, cache.k_pages[0], cache.v_pages[0],
                           lengths, tab, 1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                                   rtol=2e-4, atol=2e-4)
        # reference: query s attends to cols < length - (S - 1 - s),
        # exactly what a single-token decode at that length computes
        for s in range(S):
            ref = _decode_xla(q[:, s], cache.k_pages[0],
                              cache.v_pages[0],
                              lengths - (S - 1 - s), tab,
                              1.0 / np.sqrt(d))
            np.testing.assert_allclose(np.asarray(out_x[:, s]),
                                       np.asarray(ref),
                                       rtol=2e-4, atol=2e-4)

    def test_multi_query_s1_equals_decode(self):
        """n_query == 1 must route through (and match) the classic
        decode path bit-for-bit."""
        rng = np.random.default_rng(2)
        cache = PagedKVCache(1, 2, 64, total_pages=16, page_size=8)
        _fill_cache(rng, cache, [11, 3])
        q = jnp.asarray(rng.standard_normal((2, 1, 4, 64)), jnp.float32)
        tab, lengths = cache.page_table(range(2))
        multi = paged_attention_multi(q, cache.k_pages[0],
                                      cache.v_pages[0], lengths, tab)
        single = paged_attention(q[:, 0], cache.k_pages[0],
                                 cache.v_pages[0], lengths, tab)
        np.testing.assert_array_equal(np.asarray(multi[:, 0]),
                                      np.asarray(single))

    def test_page_pool_exhaustion_raises(self):
        cache = PagedKVCache(1, 2, 64, total_pages=2, page_size=4)
        cache.allocate(0, 8)        # both pages
        with pytest.raises(RuntimeError, match="out of pages"):
            cache.allocate(1, 1)
        cache.free(0)
        cache.allocate(1, 8)        # reuses the freed pages

    def test_paged_generation_matches_dense(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference.paged import PagedGenerator

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 128, (3, 9)).astype("int32")

        dense = model.generate(paddle.to_tensor(ids), max_new_tokens=8)
        dense = np.asarray(dense.numpy() if hasattr(dense, "numpy")
                           else dense)
        gen = PagedGenerator(model, total_pages=64, page_size=8)
        paged = gen.generate(ids, max_new_tokens=8)
        np.testing.assert_array_equal(dense, paged)
        # pages are reclaimed when the batch finishes
        assert len(gen.cache._free) == gen.cache.total_pages


class TestRaggedPagedAttention:
    """Ragged unified-step kernel (ISSUE 17): per-row query spans —
    decode rows (q_len 1), prefill/chunk spans and verify blocks in
    ONE grid — against the XLA oracle and the per-query decode
    definition."""

    def test_ragged_kernel_matches_oracle_and_per_query_decode(self):
        rng = np.random.default_rng(10)
        q_heads, kv_heads, d, page, S = 8, 2, 128, 16, 4
        cache = PagedKVCache(1, kv_heads, d, total_pages=64,
                             page_size=page)
        lens = [37, 6, 64]          # POST-span totals, ragged
        _fill_cache(rng, cache, lens)
        q = jnp.asarray(rng.standard_normal((3, S, q_heads, d)),
                        jnp.float32)
        tab, lengths = cache.page_table(range(3))
        # a decode row, a mid-prompt chunk span, a full verify block
        q_lens = jnp.asarray([1, 3, 4], jnp.int32)

        out_k = paged_attention_ragged(q, cache.k_pages[0],
                                       cache.v_pages[0], lengths,
                                       q_lens, tab, interpret=True)
        out_x = _ragged_xla(q, cache.k_pages[0], cache.v_pages[0],
                            lengths, q_lens, tab, 1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                                   rtol=2e-4, atol=2e-4)
        # definition: row b's query j is a single-token decode at the
        # interleaved length lengths[b] - q_lens[b] + j + 1
        for b, qlen in enumerate(int(x) for x in q_lens):
            for j in range(qlen):
                ref = _decode_xla(q[b:b + 1, j], cache.k_pages[0],
                                  cache.v_pages[0],
                                  lengths[b:b + 1] - qlen + j + 1,
                                  tab[b:b + 1], 1.0 / np.sqrt(d))
                np.testing.assert_allclose(np.asarray(out_x[b, j]),
                                           np.asarray(ref[0]),
                                           rtol=2e-4, atol=2e-4)

    def test_full_span_rows_reproduce_verify_mask_bitexact(self):
        """q_lens[b] == max_q on every row is exactly the verify mask:
        the ragged oracle and kernel must match the multi-query path
        bit-for-bit — the unified step cannot drift from the legacy
        verify program."""
        rng = np.random.default_rng(11)
        S = 3
        cache = PagedKVCache(1, 2, 64, total_pages=32, page_size=8)
        _fill_cache(rng, cache, [17, 9])
        q = jnp.asarray(rng.standard_normal((2, S, 4, 64)), jnp.float32)
        tab, lengths = cache.page_table(range(2))
        q_lens = jnp.full((2,), S, jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(_ragged_xla(q, cache.k_pages[0], cache.v_pages[0],
                                   lengths, q_lens, tab, 0.125)),
            np.asarray(_multi_xla(q, cache.k_pages[0], cache.v_pages[0],
                                  lengths, tab, 0.125)))
        np.testing.assert_array_equal(
            np.asarray(paged_attention_ragged(
                q, cache.k_pages[0], cache.v_pages[0], lengths, q_lens,
                tab, interpret=True)),
            np.asarray(paged_attention_multi(
                q, cache.k_pages[0], cache.v_pages[0], lengths, tab,
                interpret=True)))

    def test_max_q_1_routes_to_decode_bitexact(self):
        rng = np.random.default_rng(12)
        cache = PagedKVCache(1, 2, 64, total_pages=16, page_size=8)
        _fill_cache(rng, cache, [11, 3])
        q = jnp.asarray(rng.standard_normal((2, 1, 4, 64)), jnp.float32)
        tab, lengths = cache.page_table(range(2))
        ragged = paged_attention_ragged(q, cache.k_pages[0],
                                        cache.v_pages[0], lengths,
                                        jnp.ones((2,), jnp.int32), tab)
        single = paged_attention(q[:, 0], cache.k_pages[0],
                                 cache.v_pages[0], lengths, tab)
        np.testing.assert_array_equal(np.asarray(ragged[:, 0]),
                                      np.asarray(single))

    def test_ragged_int8_kv_interpret_matches_oracle(self):
        """int8 KV dequant fuses into the ragged kernel exactly as in
        the uniform paths."""
        rng = np.random.default_rng(13)
        kvh, total, page, d, S = 2, 8, 8, 16, 3
        kp = jnp.asarray(rng.integers(-127, 128, (kvh, total, page, d)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (kvh, total, page, d)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (kvh, total, page, 1)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (kvh, total, page, 1)),
                         jnp.float32)
        q = jnp.asarray(rng.normal(size=(3, S, 4, d)), jnp.float32)
        tabs = jnp.asarray(rng.permutation(8)[:6].reshape(3, 2),
                           jnp.int32)
        lens = jnp.asarray([5, 11, 16], jnp.int32)
        q_lens = jnp.asarray([1, 2, 3], jnp.int32)
        ref = _ragged_xla(q, kp, vp, lens, q_lens, tabs, d ** -0.5,
                          k_scales=ks, v_scales=vs)
        out = paged_attention_ragged(q, kp, vp, lens, q_lens, tabs,
                                     k_scales=ks, v_scales=vs,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_allocate_batch_atomic_per_row_counts(self):
        """Per-row growth (the ragged step's mixed spans) reserves the
        right page count per sequence, and a mid-batch exhaustion rolls
        the WHOLE call back."""
        cache = PagedKVCache(1, 2, 64, total_pages=6, page_size=4)
        cache.allocate(0, 2)                          # 1 page
        cache.allocate(1, 4)                          # 1 page
        cache.allocate_batch_atomic([0, 1], [6, 5])   # +1 page each
        assert len(cache._seq_pages[0]) == 2
        assert len(cache._seq_pages[1]) == 2
        free_before = len(cache._free)
        with pytest.raises(RuntimeError, match="out of pages"):
            # seq 0's extra page fits; seq 1 then exhausts the pool —
            # BOTH reservations must unwind
            cache.allocate_batch_atomic([0, 1], [12, 20])
        assert len(cache._free) == free_before
        assert len(cache._seq_pages[0]) == 2
        assert len(cache._seq_pages[1]) == 2


def _walk_pools(rng, kvh, pages, page, d, kv):
    """Random pools in one storage mode: ``(k, v, scale kwargs)``."""
    k = rng.standard_normal((kvh, pages, page, d)).astype("float32")
    v = rng.standard_normal((kvh, pages, page, d)).astype("float32")
    if kv == "int8":
        (k8, ks), (v8, vs) = quantize_kv(jnp.asarray(k)), \
            quantize_kv(jnp.asarray(v))
        return k8, v8, {"k_scales": ks, "v_scales": vs}
    dt = jnp.bfloat16 if kv == "bf16" else jnp.float32
    return jnp.asarray(k, dt), jnp.asarray(v, dt), {}


def _walk_call(mode, q, kp, vp, lens, q_lens, tabs, scale, kw,
               oracle=False):
    """One of the three entries, kernel (interpreted) or XLA oracle."""
    if mode == "decode":
        if oracle:
            return _decode_xla(q[:, 0], kp, vp, lens, tabs, scale, **kw)
        return paged_attention(q[:, 0], kp, vp, lens, tabs,
                               interpret=True, **kw)
    if mode == "multi":
        if oracle:
            return _multi_xla(q, kp, vp, lens, tabs, scale, **kw)
        return paged_attention_multi(q, kp, vp, lens, tabs,
                                     interpret=True, **kw)
    if oracle:
        return _ragged_xla(q, kp, vp, lens, q_lens, tabs, scale, **kw)
    return paged_attention_ragged(q, kp, vp, lens, q_lens, tabs,
                                  interpret=True, **kw)


def _real_queries(mode, out, q_lens):
    """The queries a caller keeps: every one but a ragged row's pads."""
    out = np.asarray(out.astype(jnp.float32))
    if mode != "ragged":
        return out
    keep = np.arange(out.shape[1])[None, :] < np.asarray(q_lens)[:, None]
    return out[keep]


class TestAppendRows:
    """``append_rows`` (ISSUE 30) against the indexing it replaced,
    ``pool.at[:, pg, sl].set(vals)``, bit for bit, in both of its forms
    (the row scatter, and the kernel that stages a page, interpreted) —
    and the hazard of the scatter's flat row view: a pad position's
    out-of-range page must not land in page 0 of the next kv head."""

    KVH, PAGES, PAGE, D = 4, 12, 16, 128

    @pytest.fixture(scope="class")
    def append(self):
        return jax.jit(append_rows, static_argnames=("interpret",))

    def _targets(self, rng, shape):
        """(pages, slots, real) of one step: ``decode`` is 8 rows of one
        token, ``chunk`` 2 rows of 24 positions of which row 0 holds 24
        real tokens and row 1 one, ``weave`` 150 positions that
        alternate between two rows' pages (every position leaves the
        page the one before it wrote).  Every other position is a pad at
        page ``total_pages`` with a NON-zero slot.  One real position is
        the pool's very last slot; page 0 takes none."""
        rows, span, real_per_row = {"decode": (8, 1, [1] * 6 + [0, 0]),
                                    "chunk": (2, 24, [24, 1]),
                                    "weave": (2, 75, [40, 40])}[shape]
        pg = np.full((rows, span), self.PAGES, np.int32)
        sl = np.asarray(rng.integers(1, self.PAGE, (rows, span)), np.int32)
        # page 0 takes no real position: a pad that leaks lands there
        free = list(1 + rng.permutation(self.PAGES - 2))
        for r, n in enumerate(real_per_row):
            start = int(rng.integers(0, self.PAGE))
            pos = start + np.arange(n)
            own = [free.pop() for _ in range(int(pos[-1]) // self.PAGE + 1)
                   ] if n else []
            pg[r, :n] = [own[p] for p in pos // self.PAGE]
            sl[r, :n] = pos % self.PAGE
        real = pg < self.PAGES
        # the last page's last slot takes one real position
        r = int(np.argmax(real.sum(1) >= 1))
        pg[r, 0], sl[r, 0] = self.PAGES - 1, self.PAGE - 1
        if shape == "weave":        # row 0's and row 1's positions in turn
            pg, sl, real = (a.T for a in (pg, sl, real))
        return pg.reshape(-1), sl.reshape(-1), real.reshape(-1)

    @pytest.mark.parametrize("shape", ["decode", "chunk", "weave"])
    @pytest.mark.parametrize("kv", ["bf16", "bf16-kernel", "f32-kernel",
                                    "int8"])
    def test_matches_indexed_set_and_drops_pads(self, append, kv, shape):
        rng = np.random.default_rng(30)
        pg, sl, real = self._targets(rng, shape)
        assert real.any() and not real.all() and (sl[~real] > 0).all()
        full = (self.KVH, self.PAGES, self.PAGE, self.D)
        vals = jnp.asarray(rng.standard_normal(
            (self.KVH, pg.size, self.D)), jnp.float32)
        if kv == "int8":
            v8, vsc = quantize_kv(vals)
            cases = [(jnp.asarray(rng.integers(-127, 128, full), jnp.int8),
                      v8),
                     (jnp.asarray(rng.random(full[:3] + (1,)), jnp.float32),
                      vsc)]
        else:
            dtype = jnp.float32 if kv.startswith("f32") else jnp.bfloat16
            cases = [(jnp.asarray(rng.standard_normal(full), dtype), vals)]
        for pool, new in cases:
            want = pool.at[:, pg, sl].set(new.astype(pool.dtype))
            got = append(pool, jnp.asarray(pg), jnp.asarray(sl), new,
                         interpret=kv.endswith("kernel"))
            assert got.dtype == pool.dtype and got.shape == pool.shape
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(want, np.float32))
            got, pool = np.asarray(got, np.float32), np.asarray(
                pool, np.float32)
            # exactly the real positions moved (for every head) ...
            touched = np.zeros(full[:3], bool)
            touched[:, pg[real], sl[real]] = True
            np.testing.assert_array_equal(got[~touched], pool[~touched])
            np.testing.assert_array_equal(
                got[:, pg[real], sl[real]],
                np.asarray(new.astype(want.dtype), np.float32)[:, real])
            # ... the last page's last slot among them, and no pad row
            # in page 0 of the next head (where its flat index points)
            assert touched[:, -1, -1].all() and not touched[:, 0].any()
            np.testing.assert_array_equal(got[:, 0], pool[:, 0])


class TestContextWalk:
    """The kernel walks a row's own context in blocks of several pages
    fetched by its own copies (ISSUE 28): the block edges, rows of
    different depths in one call, every entry and storage mode, and the
    table's width costing nothing and changing nothing."""

    MODES = ["decode", "multi", "ragged"]
    KVS = ["bf16", "int8"]

    @staticmethod
    def _case(rng, mode, kv, lens, *, q_heads=8, kvh=2, d=128, page=16,
              table=None, span=4, q_lens=None):
        n = len(lens)
        need = [-(-int(L) // page) for L in lens]
        table = table or max(need)
        pages = sum(need) + 3
        kp, vp, kw = _walk_pools(rng, kvh, pages, page, d, kv)
        # every row's pages scattered over the pool; slots past a
        # row's pages point at page 0, as the engine's tables do
        perm = rng.permutation(pages)
        tabs = np.zeros((n, table), np.int32)
        at = 0
        for i, k in enumerate(need):
            tabs[i, :k] = perm[at:at + k]
            at += k
        nq = 1 if mode == "decode" else span
        qdt = jnp.float32 if kv == "int8" else kp.dtype
        q = jnp.asarray(rng.standard_normal((n, nq, q_heads, d)), qdt)
        if q_lens is None:
            q_lens = [min(nq, int(L)) for L in lens]
        return (q, kp, vp, jnp.asarray(lens, jnp.int32),
                jnp.asarray(q_lens, jnp.int32), jnp.asarray(tabs),
                1.0 / np.sqrt(d), kw)

    @pytest.mark.parametrize("kv", KVS)
    @pytest.mark.parametrize("mode", MODES)
    def test_lengths_around_a_block_edge(self, mode, kv):
        """1, block - 1, block, block + 1 and the full table, one batch."""
        rng = np.random.default_rng(28)
        span = 1 if mode == "decode" else 4
        block = 16 * walk_block_pages(
            16, 128, span * 4, jnp.int8 if kv == "int8" else jnp.bfloat16)
        assert block == 512
        lens = [1, block - 1, block, block + 1, 40 * 16]
        # a multi-query row needs its whole block in the cache
        if mode == "multi":
            lens[0] = span
        args = self._case(rng, mode, kv, lens, table=40, span=span)
        out = _walk_call(mode, *args)
        ref = _walk_call(mode, *args, oracle=True)
        tol = 2e-2 if kv == "bf16" else 2e-4
        np.testing.assert_allclose(_real_queries(mode, out, args[4]),
                                   _real_queries(mode, ref, args[4]),
                                   rtol=tol, atol=tol)

    @pytest.mark.parametrize("kv", KVS)
    def test_mixed_depths_and_spans_with_pad_rows(self, kv):
        """A chunk span, a verify block, decode rows two blocks deep and
        the engine's length-1 pad rows in ONE ragged call."""
        rng = np.random.default_rng(29)
        lens = [700, 130, 1030, 1, 1, 37, 1, 512]
        q_lens = [16, 5, 1, 1, 1, 1, 1, 16]
        args = self._case(rng, "ragged", kv, lens, span=16,
                          q_lens=q_lens, table=128)
        out = _walk_call("ragged", *args)
        ref = _walk_call("ragged", *args, oracle=True)
        tol = 2e-2 if kv == "bf16" else 2e-4
        np.testing.assert_allclose(_real_queries("ragged", out, args[4]),
                                   _real_queries("ragged", ref, args[4]),
                                   rtol=tol, atol=tol)
        assert not np.isnan(np.asarray(out.astype(jnp.float32))).any()

    @pytest.mark.parametrize("mode", MODES)
    def test_group_of_three_and_head_dim_64(self, mode):
        """Shapes off the tiles: 6 query heads over 2 KV heads of 64
        (the pool's lane axis is padded to a tile for the copies),
        float32 pages of 8 tokens."""
        rng = np.random.default_rng(30)
        lens = [3, 64, 65, 200]
        args = self._case(rng, mode, "f32", lens, q_heads=6, kvh=2, d=64,
                          page=8, span=3)
        out = _walk_call(mode, *args)
        ref = _walk_call(mode, *args, oracle=True)
        np.testing.assert_allclose(_real_queries(mode, out, args[4]),
                                   _real_queries(mode, ref, args[4]),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("kv", KVS)
    @pytest.mark.parametrize("mode", MODES)
    def test_pinned_table_is_bit_identical_to_a_tight_one(self, mode, kv):
        """THE PINNING PROPERTY: the same rows through a table of exactly
        the pages they need and through one padded to 256 pages give the
        same bits — a row's blocks are cut from its length, never from
        the table."""
        rng = np.random.default_rng(31)
        span = 1 if mode == "decode" else 4
        lens = [4, 300, 513, 900]
        q, kp, vp, lengths, q_lens, tabs, scale, kw = self._case(
            rng, mode, kv, lens, span=span)
        assert tabs.shape[1] == -(-900 // 16)
        wide = jnp.zeros((len(lens), 256), jnp.int32) \
            .at[:, :tabs.shape[1]].set(tabs)
        tight = _walk_call(mode, q, kp, vp, lengths, q_lens, tabs, scale,
                           kw)
        pinned = _walk_call(mode, q, kp, vp, lengths, q_lens, wide, scale,
                            kw)
        np.testing.assert_array_equal(
            np.asarray(tight.astype(jnp.float32)),
            np.asarray(pinned.astype(jnp.float32)))

    def test_table_one_page_wide(self):
        rng = np.random.default_rng(32)
        args = self._case(rng, "ragged", "bf16", [5, 16], span=2)
        assert args[5].shape[1] == 1
        out = _walk_call("ragged", *args)
        ref = _walk_call("ragged", *args, oracle=True)
        np.testing.assert_allclose(_real_queries("ragged", out, args[4]),
                                   _real_queries("ragged", ref, args[4]),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("page,d,rows,dtype,pages", [
        (16, 128, 4, jnp.bfloat16, 32),      # the cell's decode step
        (16, 128, 128, jnp.bfloat16, 32),    # a ragged kernel's tile
        (16, 128, 96, jnp.bfloat16, 32),     # and that of a group of 6
        (16, 128, 512, jnp.bfloat16, 32),    # the tallest tile at 512
        (16, 128, 512, jnp.int8, 32),
        (16, 128, 1024, jnp.bfloat16, 16),   # the score tile binds: a
        (16, 128, 8192, jnp.bfloat16, 2),    # verify block of that many
        (16, 128, 1 << 16, jnp.bfloat16, 1),  # down to one page a block
        (128, 128, 4, jnp.bfloat16, 4),
        (8, 64, 3, jnp.float32, 64),
        (16, 512, 4, jnp.float32, 16),       # the page buffers bind
        (48, 128, 4, jnp.bfloat16, 8),       # 384 tokens: whole 128s
    ])
    def test_block_rule_reads_shapes_only(self, page, d, rows, dtype,
                                          pages):
        """``rows``: those of the score TILE the kernel forms — a ragged
        bucket's tile, a one-query or verify kernel's whole block."""
        assert walk_block_pages(page, d, rows, dtype) == pages

    def test_tokens_walked_is_the_context_in_whole_blocks(self):
        assert kv_tokens_walked([1, 511, 512, 513, 0], 512) == \
            512 + 512 + 512 + 1024
        assert kv_tokens_walked(np.asarray([4096] * 8), 512) == 8 * 4096


class TestRaggedQueryTiles:
    """The ragged kernel computes a row's own queries (ISSUE 40): the
    (span x group) query block in tiles of whole query positions, a
    row's live tiles only; dead queries are zeros; every live query
    equals the whole block computed as ONE tile, which is what the
    kernel computed before the cut."""

    SPAN = 64
    # a decode row, a verify row of a few, a ragged tail over a tile's
    # edge, the full bucket, a row of NO query, the engine's pad row
    Q_LENS = [1, 3, 37, 64, 0, 1]
    LENS = [700, 40, 1100, 64, 5, 1]

    @staticmethod
    def _case(rng, group, kv, d, q_lens, lens, span, kvh=2):
        """``TestContextWalk``'s ragged case with the model's own dtype
        for the queries over int8 pages too, as the engine's."""
        q, *rest = TestContextWalk._case(
            rng, "ragged", kv, lens, q_heads=group * kvh, kvh=kvh, d=d,
            span=span, q_lens=q_lens)
        return (q.astype(jnp.bfloat16), *rest)

    @staticmethod
    def _one_tile(monkeypatch, q, kp, vp, lens, q_lens, tabs, scale, kw,
                  window):
        """The ragged kernel with the whole bucket as ONE tile: every
        query position of every row against every block, the rule
        before the cut (a jitted call would keep the tiled program)."""
        monkeypatch.setattr(paged_attention_mod, "query_tile_rows",
                            lambda rows, group, dtype: rows)
        out = paged_attention_mod._decode_pallas.__wrapped__(
            q, kp, vp, lens, tabs, scale, interpret=True,
            n_query=q.shape[1], q_lens=q_lens, window=window, **kw)
        monkeypatch.undo()
        return out

    @pytest.mark.parametrize("d", [128, 64])
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("window", [None, 512])
    @pytest.mark.parametrize("group", [4, 6, 8])
    def test_live_queries_are_the_whole_block_s_and_dead_ones_zero(
            self, monkeypatch, group, window, kv, d):
        rng = np.random.default_rng(40)
        args = self._case(rng, group, kv, d, self.Q_LENS, self.LENS,
                          self.SPAN)
        q, q_lens = args[0], np.asarray(self.Q_LENS)
        rows = self.SPAN * group
        tile = query_tile_rows(rows, group, q.dtype)
        assert tile == (96 if group == 6 else 128) and rows // tile >= 2
        out = paged_attention_ragged(*args[:6], interpret=True,
                                     window=window, **args[7])
        whole = self._one_tile(monkeypatch, *args, window)
        ref = _ragged_xla(*args[:7], window=window, **args[7])
        live = np.arange(self.SPAN)[None, :] < q_lens[:, None]
        out, whole, ref = (np.asarray(x.astype(jnp.float32))
                           for x in (out, whole, ref))
        # bit for bit: a row's scores, softmax and products do not
        # depend on the other rows of its tile
        np.testing.assert_array_equal(out[live], whole[live])
        np.testing.assert_allclose(out[live], ref[live], rtol=2e-2,
                                   atol=2e-2)
        # dead queries: exact zeros from the kernel and from the oracle
        assert not out[~live].any() and not ref[~live].any()
        assert np.isfinite(out).all()
        # the host's count is the kernel's rule: tiles by hand
        per = tile // group
        tiles = [-(-n * group // tile) for n in self.Q_LENS]
        assert list(live_query_tiles(q_lens, group, tile)) == tiles
        assert q_positions_computed(q_lens, self.SPAN, group,
                                    q.dtype) == sum(tiles) * per
        assert tiles[0] == tiles[1] == tiles[5] == 1 and tiles[4] == 0
        assert tiles[3] == rows // tile

    @pytest.mark.parametrize("group", [4, 6])
    def test_full_rows_of_several_tiles_are_the_verify_kernel_s(
            self, group):
        """Every row full: the tiled walk is ``paged_attention_multi``'s
        one static block bit for bit — the uniform kernels are
        untouched and the ragged one cannot drift from them."""
        rng = np.random.default_rng(41)
        lens = [700, 64, 300]
        args = self._case(rng, group, "bf16", 128, [self.SPAN] * 3, lens,
                          self.SPAN)
        out = paged_attention_ragged(*args[:6], interpret=True)
        multi = paged_attention_multi(*args[:4], args[5], interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(multi.astype(jnp.float32)))

    @pytest.mark.parametrize("rows,group,dtype,tile", [
        (512, 4, jnp.bfloat16, 128),    # the Mistral cell: 32 positions
        (768, 6, jnp.bfloat16, 96),     # Laguna's full layers: 16
        (1024, 8, jnp.bfloat16, 128),   # Laguna's sliding layers: 16
        (192, 6, jnp.bfloat16, 96),     # its span-32 bucket
        (96, 6, jnp.bfloat16, 96),      # span 16: one tile
        (128, 4, jnp.float32, 128),
        (384, 3, jnp.float32, 96),      # float32: sublane tiles of 8
        (24, 6, jnp.bfloat16, 24),      # no whole tile divides: one tile
        (8, 4, jnp.bfloat16, 8),        # a verify bucket of two tokens
        (4, 4, jnp.bfloat16, 4),        # the one-query kernel's block
    ])
    def test_tile_rule_reads_shapes_only(self, rows, group, dtype, tile):
        assert query_tile_rows(rows, group, dtype) == tile
        assert tile % group == 0 and rows % tile == 0

    def test_positions_computed_by_bucket(self):
        """A chunk step of the two cells: one 128-token row, seven of one
        token (a pad row is one token long); a decode-only step is the
        one-query kernel's, a position a row."""
        ql = [128, 1, 1, 1, 1, 1, 1, 1]
        assert q_positions_computed(ql, 128, 4, jnp.bfloat16) == 128 + 7 * 32
        assert q_positions_computed(ql, 128, 6, jnp.bfloat16) == 128 + 7 * 16
        assert q_positions_computed(ql, 128, 8, jnp.bfloat16) == 128 + 7 * 16
        assert q_positions_computed([37, 1], 128, 4, jnp.bfloat16) == 64 + 32
        assert q_positions_computed([1] * 8, 1, 4, jnp.bfloat16) == 8

    def test_one_loop_more_in_each_of_three_places(self):
        """ONE body: the ragged kernel is the uniform kernel with a loop
        over the live tiles around the scratch reset, the block update
        and the final division — no second walk, no second program — and
        four that bring the row's queries (ISSUE 50): the tiles' copies
        started, waited for, and made rows of (heads x tiles)."""
        def loops(**kw):
            q = jnp.zeros((2, 64, 8, 128), jnp.bfloat16)
            pool = jnp.zeros((2, 8, 16, 128), jnp.bfloat16)
            text = str(jax.make_jaxpr(
                lambda *a: paged_attention_mod._decode_pallas.__wrapped__(
                    *a, 0.1, n_query=64, **kw))(
                q, pool, pool, jnp.ones((2,), jnp.int32),
                jnp.zeros((2, 4), jnp.int32)))
            return text.count("while[") + text.count("scan[")

        assert loops(q_lens=jnp.ones((2,), jnp.int32)) == loops() + 3 + 4

    def test_pad_positions_of_the_packed_stream_are_zeros(self):
        """``_packed_of_rows`` hands the last row's dead columns to the
        packed stream's pad positions: zeros now, whatever the kernel's
        scratch held."""
        from paddle_tpu.inference.paged import _packed_of_rows
        rng = np.random.default_rng(42)
        q_lens, lens = [16, 1, 1, 1], [300, 70, 9, 1]
        args = self._case(rng, 4, "bf16", 128, q_lens, lens, 16, kvh=1)
        out = paged_attention_ragged(*args[:6], interpret=True)
        off = jnp.asarray(np.cumsum([0] + q_lens[:-1]), jnp.int32)
        packed = np.asarray(_packed_of_rows(out, off, 24)
                            .astype(jnp.float32))
        assert packed[:sum(q_lens)].any(axis=(1, 2)).all()
        assert not packed[sum(q_lens):].any()


def head_group_call(mode, q, kp, vp, lens, q_lens, tabs, scale, kw,
                    window=None, head_group=None):
    """The kernel behind the three entries with the kv heads a grid step
    owns FORCED (``None``: what the shapes give): ``_decode_call``, which
    the jitted ``_decode_pallas`` wraps and no program calls itself."""
    ragged = {"q_lens": q_lens} if mode == "ragged" else {}
    if mode == "decode":
        q = q[:, 0]
    return paged_attention_mod._decode_call(
        q, kp, vp, lens, tabs, scale, interpret=True,
        n_query=1 if mode == "decode" else q.shape[1], window=window,
        head_group=head_group, **ragged, **kw)


def head_group_check(mode, args, window, hb):
    """A grouped call against its XLA oracle, and BIT FOR BIT against the
    same call a head a grid step (the program before the groups): a
    (row, head)'s blocks, their order and every operation on them do not
    depend on the group it rides in."""
    q, kp = args[0], args[1]
    rows = (1 if mode == "decode" else q.shape[1]) * q.shape[2] // kp.shape[0]
    assert walk_head_group(kp.shape[0], kp.shape[2], q.shape[3], rows,
                           kp.dtype, q.dtype) == hb
    kw = dict(args[7], window=window)
    out = head_group_call(mode, *args, window=window)
    one = head_group_call(mode, *args, window=window, head_group=1)
    ref = _walk_call(mode, *args[:7], kw, oracle=True)
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(one.astype(jnp.float32)))
    tol = 2e-4 if q.dtype == jnp.float32 else 2e-2
    held = np.asarray(args[3]) > 0      # an empty row attends nothing
    np.testing.assert_allclose(
        _real_queries(mode, out[held], args[4][held]),
        _real_queries(mode, ref[held], args[4][held]), rtol=tol, atol=tol)
    assert np.isfinite(np.asarray(out.astype(jnp.float32))).all()
    return out


# lengths of 0, one page, a whole block and not a whole block (blocks
# of 512 and 256 tokens), the engine's pad row among them
HEAD_GROUP_LENS = [0, 16, 512, 700, 1, 300]


class TestHeadGroups:
    """A grid step owns a row and a GROUP of its kv heads (ISSUE 42): a
    page is copied once for the group, scores, softmax and products run
    a head at a time over that block.  ``walk_head_group`` reads the
    call's shapes alone; every output is what one head a step gave."""

    # name: mode, kv heads, group, head_dim, storage, span, hb
    CASES = {
        "phi4_flash_one_query": ("decode", 10, 4, 128, "bf16", 1, 10),
        "mistral_ragged": ("ragged", 8, 4, 128, "bf16", 128, 8),
        "laguna_full_ragged": ("ragged", 8, 6, 128, "bf16", 128, 8),
        "verify_bucket": ("multi", 8, 4, 128, "bf16", 4, 8),
        "int8_pages_and_scale_pools": ("ragged", 4, 4, 128, "int8", 32, 4),
        "int8_one_query": ("decode", 4, 4, 128, "int8", 1, 4),
        "head_dim_64": ("ragged", 4, 3, 64, "bf16", 16, 4),
        "a_tp_shard_s_two_heads": ("ragged", 2, 4, 128, "bf16", 128, 2),
        "float32_one_query": ("decode", 6, 2, 128, "f32", 1, 6),
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_grouped_is_one_head_a_step_bit_for_bit(self, name):
        mode, kvh, group, d, kv, span, hb = self.CASES[name]
        rng = np.random.default_rng(42)
        lens = HEAD_GROUP_LENS
        if name == "phi4_flash_one_query":      # the cell's 32 rows
            lens = lens * 5 + [1300, 47]
        q_lens = None
        if mode == "ragged":                    # a chunk row, decode rows
            q_lens = [min(n, L, span) for n, L in zip(
                [0, 1, span, 37, 1, 1], lens)]
        if mode == "multi":
            lens = [max(L, span) for L in lens]
        args = TestContextWalk._case(
            rng, mode, kv, lens, q_heads=kvh * group, kvh=kvh, d=d,
            span=span, q_lens=q_lens, table=96)
        if kv == "int8":            # the model's dtype over int8 pages
            args = (args[0].astype(jnp.bfloat16), *args[1:])
        out = head_group_check(mode, args, None, hb)
        if mode == "ragged":        # dead queries: zeros
            dead = np.arange(span)[None, :] >= np.asarray(q_lens)[:, None]
            assert not np.asarray(out.astype(jnp.float32))[dead].any()

    @pytest.mark.parametrize("kvh", [3, 7])
    def test_heads_no_divisor_of_which_fits_but_one(self, monkeypatch, kvh):
        """A prime count of heads that do not fit together: one head a
        grid step, the program before the groups, and the same bits as
        the heads together."""
        rng = np.random.default_rng(43)
        args = TestContextWalk._case(
            rng, "ragged", "bf16", HEAD_GROUP_LENS, q_heads=kvh * 4,
            kvh=kvh, span=32, q_lens=[0, 1, 32, 5, 1, 1])
        rule = walk_head_group.__wrapped__
        shapes = (kvh, 16, 128, 128, jnp.bfloat16, jnp.bfloat16)
        assert rule(*shapes) == kvh
        together = head_group_call("ragged", *args)
        # room for two heads' buffers and blocks, not for three
        monkeypatch.setattr(paged_attention_mod, "_HEAD_GROUP_BYTES",
                            1 << 21)
        assert rule(*shapes) == 1
        monkeypatch.setattr(paged_attention_mod, "walk_head_group", rule)
        alone = head_group_call("ragged", *args)
        np.testing.assert_array_equal(
            np.asarray(together.astype(jnp.float32)),
            np.asarray(alone.astype(jnp.float32)))

    @pytest.mark.parametrize("kvh,rows,tile,kv,hb,pages", [
        (10, 4, 4, jnp.bfloat16, 10, 32),   # Phi-4-flash, a decode step
        (10, 512, 128, jnp.bfloat16, 10, 32),   # its chunk step: 18.4 MB
        (8, 4, 4, jnp.bfloat16, 8, 32),     # Mistral and Laguna, decode
        (8, 512, 128, jnp.bfloat16, 8, 32),     # Mistral's chunk step
        (8, 768, 96, jnp.bfloat16, 8, 32),      # Laguna's full layers
        (8, 1024, 128, jnp.bfloat16, 8, 32),    # Laguna's sliding layers
        (8, 512, 128, jnp.int8, 8, 32),
        (2, 512, 128, jnp.bfloat16, 2, 32),     # a shard of tp = 4
        (32, 512, 128, jnp.bfloat16, 16, 32),   # 1.8 MB a head: 16 of 32
        (8, 2048, 128, jnp.bfloat16, 4, 32),    # 5.8 MB a head: half
        (7, 2048, 128, jnp.bfloat16, 1, 32),    # and no half of seven
        (8, 2048, 2048, jnp.bfloat16, 4, 8),    # a verify block so tall
        (8, 1 << 16, 128, jnp.bfloat16, 1, 32),  # nothing fits: a head a
        (8, 1 << 16, 1 << 16, jnp.bfloat16, 1, 1),  # step, whatever block
    ])
    def test_group_rule_reads_shapes_only(self, kvh, rows, tile, kv, hb,
                                          pages):
        """``rows`` the bucket's, ``tile`` the score tile's: the ragged
        kernel's 128 (96), the whole block of the other two kernels."""
        got = walk_head_group(kvh, 16, 128, rows, kv, jnp.bfloat16,
                              tile_rows=tile)
        assert got == hb and kvh % got == 0
        # the block of a (row, head) is the tile's: the buffers grow
        # with the group, the block does not
        assert walk_block_pages(16, 128, tile, kv) == pages
        per_head = (4 * pages * paged_attention_mod._page_vmem_bytes(
            16, 128, kv) + 4 * rows * 128 * 2 + rows * 384 * 4)
        budget = paged_attention_mod._HEAD_GROUP_BYTES
        assert got == 1 or got * per_head <= budget
        bigger = [g for g in range(got + 1, kvh + 1) if kvh % g == 0]
        assert all(g * per_head > budget for g in bigger)

    @pytest.mark.parametrize("window", [None, 64])
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    def test_host_count_is_the_kernel_s_own(self, monkeypatch, kv, window):
        """``kv_pages_copied`` x pools x grid steps a row against the
        descriptors an interpreted kernel STARTS, counted where it starts
        them; one a (page, head, pool) a head a step."""
        started = []
        make = paged_attention_mod.pltpu.make_async_copy

        class Counted:
            """A page's copy: (heads, slots, lanes); the copies of the
            row's queries and outputs have an axis more (``tests/
            test_ragged_packed_kernel.py`` counts those)."""
            def __init__(self, src, dst, sem):
                self.copy, self.page = make(src, dst, sem), len(src.shape) == 3

            def start(self):
                if self.page:
                    jax.debug.callback(lambda: started.append(1))
                self.copy.start()

            def wait(self):
                self.copy.wait()

        monkeypatch.setattr(paged_attention_mod.pltpu, "make_async_copy",
                            Counted)
        rng = np.random.default_rng(44)
        lens, q_lens = [0, 16, 100, 700, 1], [0, 1, 8, 3, 1]
        args = TestContextWalk._case(
            rng, "ragged", kv, lens, q_heads=16, kvh=4, span=8,
            q_lens=q_lens)
        args = (*args[:5], args[5][:, :40], *args[6:])  # cuts the 700 short
        pools = 4 if kv == "int8" else 2
        pages = kv_pages_copied(lens, 16, 40, window, q_lens)
        by_hand = [min(-(-L // 16), 40) - (
            max(L - n + 1 - window, 0) // 16 if window else 0)
            for L, n in zip(lens, q_lens)]
        assert pages == sum(by_hand)
        for hb in (4, 2, 1):
            started.clear()
            jax.block_until_ready(head_group_call(
                "ragged", *args, window=window, head_group=hb))
            jax.effects_barrier()
            assert len(started) == pools * pages * (4 // hb)


def _micro_shapes():
    """``tools/paged_ragged_micro.py::SHAPES``: what the serving cells hand
    the paged kernels."""
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import paged_ragged_micro
    finally:
        sys.path.remove(tools)
    return paged_ragged_micro.SHAPES


class TestWalkCut:
    """ONE rule cuts a paged call's walk (ISSUE 51): the rows of the score
    tile the kernel forms (the ragged kernel's query tile, never its
    bucket), the block that tile, the page's shape and a window's reach
    allow, the heads whose buffers of that block fit a grid step.
    ``_decode_call`` builds its program by it, ``walk_head_group`` budgets
    the same block, and the host's ``kv_tokens_walked`` and ``page_copies``
    count by it."""

    SHAPES = _micro_shapes()
    #: the heads of a grid step at spans 1 / 64 / 128: all of them but at
    #: MiMo's sliding span-128 bucket (1,024 rows of 384 lanes and sinks)
    HEAD_GROUPS = {"mimo-sliding": (8, 8, 4)}
    #: a window of 128 reaches 128 + 16 - 1 + 16 columns of a tile: blocks
    #: of 256; one of 512 reaches past 512
    BLOCKS = {"mimo-sliding": 256}
    #: the blocks of the rule before, where the BUCKET's rows cut them
    BLOCKS_BEFORE = {("laguna-full", 128): 256, ("laguna-sliding", 128): 256,
                     ("mimo-full", 64): 256, ("mimo-full", 128): 128,
                     ("mimo-sliding", 128): 256}

    @pytest.mark.parametrize("span", [1, 64, 128])
    @pytest.mark.parametrize("shape", sorted(SHAPES))
    def test_the_call_the_group_and_the_host_answer_alike(
            self, monkeypatch, shape, span):
        heads, kvh, window, _p, _t, dk, dv, sinks = self.SHAPES[shape]
        bf16, group, ragged = jnp.bfloat16, heads // kvh, span > 1
        rows = span * group
        tile, pages, hb = walk_cut(kvh, 16, dk, span, group, bf16, bf16, dv,
                                   sinks, ragged, window)
        assert tile == (query_tile_rows(rows, group, bf16) if ragged
                        else rows) <= 128
        # every bucket of a cell walks in the same blocks: 512 tokens
        assert 16 * pages == self.BLOCKS.get(shape, 512)
        reach = window and window + tile // group - 1 + 16
        assert 16 * walk_block_pages(16, dk, rows, bf16, dv) \
            == self.BLOCKS_BEFORE.get((shape, span), 512)
        assert hb == self.HEAD_GROUPS.get(shape, (kvh,) * 3)[
            (1, 64, 128).index(span)]
        assert pages == walk_block_pages(16, dk, tile, bf16, dv, reach)
        assert hb == walk_head_group(kvh, 16, dk, rows, bf16, bf16, dv,
                                     sinks, tile, reach)

        # the call: what its kernel is built with, and its page buffers
        built, kernel = {}, paged_attention_mod._decode_kernel

        def spy(*refs, **kw):
            bufs = [r.shape for r in refs if len(r.shape) == 5]
            built.update(kw, k_buf=bufs[0], v_buf=bufs[1])
            return kernel(*refs, **kw)

        monkeypatch.setattr(paged_attention_mod, "_decode_kernel", spy)
        pack = paged_attention_mod.k_pack(dk)

        def call(q, kp, vp, lens, tabs, q_lens, b):
            return paged_attention_mod._decode_call(
                q, kp, vp, lens, tabs, 0.1, n_query=span,
                q_lens=q_lens if ragged else None, window=window,
                sinks=b if sinks else None)

        i32 = jnp.int32
        out = jax.eval_shape(
            call, jax.ShapeDtypeStruct(
                (3, span, heads, dk) if ragged else (3, heads, dk), bf16),
            jax.ShapeDtypeStruct((kvh // pack, 8, 16, pack * dk), bf16),
            jax.ShapeDtypeStruct((kvh, 8, 16, dv), bf16),
            jax.ShapeDtypeStruct((3,), i32),
            jax.ShapeDtypeStruct((3, 4), i32),
            jax.ShapeDtypeStruct((3,), i32),
            jax.ShapeDtypeStruct((heads,), jnp.float32))
        assert out.shape[-2:] == (heads, dv)
        assert built["block_pages"] == pages
        assert built["tile"] == tile
        assert built["k_buf"] == (2, pages, hb // pack, 16,
                                  -(-pack * dk // 128) * 128)
        assert built["v_buf"] == (2, pages, hb, 16, dv)

        # the host: a row of one token walks one block, and its one page
        # costs a descriptor a pool and a group of heads
        from paddle_tpu.inference.paged import JittedPagedDecoder
        decoder = types.SimpleNamespace(_attn_kinds={
            (group, window, False, kvh, dk, dv, sinks): 1})
        cache = types.SimpleNamespace(
            page_size=16, kv_quant=None, tp=1, compute_dtype=bf16,
            k_pages=[types.SimpleNamespace(dtype=jnp.dtype(bf16))])
        one = np.ones(1, np.int64)
        counts = JittedPagedDecoder._walk_counts(decoder, cache, one, one,
                                                 span, 1, 4)
        assert counts["kv_tokens_walked"] == 16 * pages
        assert counts["page_copies"] == 2 * (kvh // hb)
        assert counts["head_page_reads"] == 2 * kvh


class TestFusedNormRope:
    @pytest.mark.parametrize("shape,dt", [((5, 7, 768), jnp.float32),
                                          ((3, 129, 512), jnp.bfloat16)])
    def test_rms_norm_kernel(self, shape, dt):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(shape), dt)
        w = jnp.asarray(rng.standard_normal(shape[-1]), dt)
        a = rms_norm_pallas(x, w, 1e-6, interpret=True)
        b = rms_norm_xla(x, w, 1e-6)
        tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)

    def test_fused_custom_vjp_grads(self, monkeypatch):
        # the autotune winner may be the fused (Pallas) path under
        # training: grads must flow via the custom_vjp and match the XLA
        # form (review r4: pallas_call has no transpose rule)
        import paddle_tpu.ops.pallas.fused_norm_rope as FNR
        monkeypatch.setattr(FNR, "_INTERPRET", True)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 33, 256)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(256), jnp.float32)
        g = jnp.asarray(rng.standard_normal((4, 33, 256)), jnp.float32)
        dx_f, dw_f = jax.grad(
            lambda a, b: (FNR.rms_norm_fused(a, b, 1e-6) * g).sum(),
            argnums=(0, 1))(x, w)
        dx_r, dw_r = jax.grad(
            lambda a, b: (FNR.rms_norm_xla(a, b, 1e-6) * g).sum(),
            argnums=(0, 1))(x, w)
        np.testing.assert_allclose(np.asarray(dx_f), np.asarray(dx_r),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_r),
                                   rtol=1e-4, atol=1e-4)

        b, s, h, kvh, d = 2, 33, 4, 2, 64
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
        inv = 1.0 / (10000.0 ** (np.arange(0, d, 2) / d))
        fr = np.outer(np.arange(s), inv)
        cos = jnp.asarray(np.cos(fr), jnp.float32)
        sin = jnp.asarray(np.sin(fr), jnp.float32)
        gq = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        gk = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)

        def lf(q_, k_):
            oq, ok = FNR.fused_rope_fused(q_, k_, cos, sin)
            return (oq * gq).sum() + (ok * gk).sum()

        def lr(q_, k_):
            oq, ok = FNR.fused_rope_xla(q_, k_, cos, sin)
            return (oq * gq).sum() + (ok * gk).sum()

        for a, b_ in zip(jax.grad(lf, argnums=(0, 1))(q, k),
                         jax.grad(lr, argnums=(0, 1))(q, k)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-5, atol=1e-5)

    def test_rope_position_bounds_raise(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=32, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=2, max_position_embeddings=8)
        model = LlamaForCausalLM(cfg)
        ids = paddle.to_tensor(np.zeros((1, 9), np.int32))
        with pytest.raises(ValueError, match="rope position"):
            model(ids)

    def test_fused_rope_kernel_gqa(self):
        rng = np.random.default_rng(0)
        b, s, h, kvh, d = 2, 77, 8, 2, 64
        q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
        inv = 1.0 / (10000.0 ** (np.arange(0, d, 2) / d))
        fr = np.outer(np.arange(s), inv)
        cos = jnp.asarray(np.cos(fr), jnp.float32)
        sin = jnp.asarray(np.sin(fr), jnp.float32)
        oq_p, ok_p = fused_rope_pallas(q, k, cos, sin, interpret=True)
        oq_x, ok_x = fused_rope_xla(q, k, cos, sin)
        np.testing.assert_allclose(np.asarray(oq_p), np.asarray(oq_x),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ok_p), np.asarray(ok_x),
                                   rtol=1e-5, atol=1e-5)


class TestJittedDecoderOracle:
    """The compiled decode step (JittedPagedDecoder) vs the eager
    _PagedContext decode branch — the branch stays as the numerics
    oracle for the write/lens protocol."""

    def test_jitted_step_matches_eager_context(self):
        import jax.numpy as jnp
        import paddle_tpu as paddle
        from paddle_tpu.framework.tape import no_grad
        from paddle_tpu.framework.tensor import wrap_array
        from paddle_tpu.inference.paged import (
            JittedPagedDecoder, PagedGenerator, _PagedContext)
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=64)
        model = LlamaForCausalLM(cfg)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 64, (2, 7)).astype("int32")

        def prefill(gen, seq_ids):
            for sid in seq_ids:
                gen.cache.allocate(sid, ids.shape[1])
            ctx = _PagedContext(gen.cache, seq_ids, prefill=True)
            with no_grad():
                hidden = model.model(wrap_array(jnp.asarray(ids)), 0,
                                     paged_ctx=ctx)
                return np.asarray(
                    model._logits_of(hidden[:, -1:])._data[:, -1],
                    np.float32)

        # eager decode: one token through the _PagedContext branch
        gen_e = PagedGenerator(model, total_pages=32, page_size=8)
        logits0 = prefill(gen_e, [0, 1])
        nxt = logits0.argmax(-1).astype("int32")[:, None]
        for sid in (0, 1):
            gen_e.cache.allocate(sid, 1)
        ctx = _PagedContext(gen_e.cache, [0, 1], prefill=False)
        with no_grad():
            hidden = model.model(wrap_array(jnp.asarray(nxt)),
                                 ids.shape[1], paged_ctx=ctx)
            eager_logits = np.asarray(
                model._logits_of(hidden)._data[:, -1], np.float32)

        # jitted decode: same token through the compiled step
        gen_j = PagedGenerator(model, total_pages=32, page_size=8)
        prefill(gen_j, [0, 1])
        dec = JittedPagedDecoder(model)
        jit_logits = dec.step(gen_j.cache, [0, 1], nxt,
                              np.full(2, ids.shape[1], np.int32))
        np.testing.assert_allclose(jit_logits, eager_logits, atol=2e-5)
        # both protocols agree on the cache state too
        for l in range(cfg.num_hidden_layers):
            np.testing.assert_allclose(
                np.asarray(gen_j.cache.k_pages[l]),
                np.asarray(gen_e.cache.k_pages[l]), atol=2e-5)


class TestMultiStepFusedDecode:
    """The greedy fast path: N decode steps in ONE lax.scan program
    (one host dispatch per generation) must be token-identical to the
    stepwise path, including eos masking and the pool-pressure
    fallback."""

    def _model(self, seed=0):
        import paddle_tpu as paddle
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(seed)
        return LlamaForCausalLM(LlamaConfig(
            vocab_size=128, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=2, max_position_embeddings=128))

    def _gen_pair(self, model, **kw):
        from paddle_tpu.inference.paged import PagedGenerator
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 128, (2, 7)).astype("int32")
        fused = PagedGenerator(model, total_pages=64, page_size=8)
        out_fused = fused.generate(ids, **kw)

        stepwise = PagedGenerator(model, total_pages=64, page_size=8)

        def no_multi(*a, **k):
            raise RuntimeError("out of pages (forced: exercise fallback)")

        stepwise._decoder.multi_step = no_multi
        out_step = stepwise.generate(ids, **kw)
        return out_fused, out_step

    def test_greedy_parity_with_stepwise(self):
        model = self._model()
        a, b = self._gen_pair(model, max_new_tokens=12)
        n = min(a.shape[1], b.shape[1])
        np.testing.assert_array_equal(a[:, :n], b[:, :n])
        assert a.shape[1] == 7 + 12          # fused always decodes fully

    def test_eos_masking_matches(self):
        model = self._model(seed=1)
        # find an eos id that actually occurs early in greedy output
        probe, _ = self._gen_pair(model, max_new_tokens=8)
        eos = int(probe[0, 9])               # 3rd generated token, row 0
        a, b = self._gen_pair(model, max_new_tokens=8, eos_token_id=eos)
        n = min(a.shape[1], b.shape[1])
        np.testing.assert_array_equal(a[:, :n], b[:, :n])
        # everything after the first eos is eos in the fused output
        row = a[0, 7:]
        hits = np.nonzero(row == eos)[0]
        if hits.size:
            assert (row[hits[0]:] == eos).all()

    def test_sampling_still_uses_stepwise(self):
        # the fused path is greedy-only; sampling goes through the loop
        model = self._model(seed=2)
        from paddle_tpu.inference.paged import PagedGenerator
        gen = PagedGenerator(model, total_pages=64, page_size=8)

        def boom(*a, **k):
            raise AssertionError("multi_step must not run for sampling")

        gen._decoder.multi_step = boom
        ids = np.random.default_rng(1).integers(0, 128, (1, 5)).astype("int32")
        out = gen.generate(ids, max_new_tokens=4, do_sample=True, seed=7)
        assert out.shape == (1, 9)

    def test_pool_pressure_falls_back_to_per_token_continuation(self):
        # chunk reservations are atomic (rolled back on exhaustion) and
        # a mid-generation pool squeeze continues per-token from the
        # exact (cur, pos) the chunks reached — early eos still finishes
        # a generation the upfront reservation could never fit
        from paddle_tpu.inference.paged import PagedGenerator
        model = self._model(seed=3)
        ids = np.random.default_rng(3).integers(0, 128, (1, 6)).astype(
            "int32")
        probe = PagedGenerator(model, total_pages=128,
                               page_size=4).generate(ids,
                                                     max_new_tokens=90)
        eos = int(probe[0, 6 + 20])          # reachable within the pool
        # 12 pages x 4 = 48 tokens: the 64-token upfront chunk can never
        # reserve, but per-token decoding reaches the eos at +20 easily
        tight = PagedGenerator(model, total_pages=12, page_size=4)
        out = tight.generate(ids, max_new_tokens=90, eos_token_id=eos)
        ref = probe.copy()
        hit = ref[:, 6:] == eos
        after = (np.cumsum(hit, axis=1) - hit.astype(int)) > 0
        ref[:, 6:][after] = eos
        n = min(out.shape[1], ref.shape[1])
        np.testing.assert_array_equal(out[:, :n], ref[:, :n])
        # every page returned to the pool (atomic rollback + final free)
        assert len(tight.cache._free) == tight.cache.total_pages
