"""Refcounted prefix caching in the paged-KV serving path (ISSUE 2):
page-aligned prompt prefixes stay resident after retirement (LRU,
evicted under pool pressure) and later requests sharing them map the
pages read-only and prefill only their suffix."""
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache


def tiny_model(vocab=64, layers=2, seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=layers,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def model():
    return tiny_model()


class TestCacheBookkeeping:
    """Host-side refcount/index logic, no device work."""

    def _cache(self, total_pages=8, page_size=4):
        return PagedKVCache(1, 2, 8, total_pages=total_pages,
                            page_size=page_size)

    def test_hit_only_on_page_aligned_full_pages(self):
        c = self._cache()
        prompt = np.arange(11, dtype=np.int32)     # 2 full pages + 3
        c.allocate(0, 11)
        c.advance([0], 11)
        assert c.register_prefix(0, prompt) == 2   # 4- and 8-token keys
        # exact prompt: the 8-token prefix matches, never the partial page
        assert c.probe_prefix(prompt)[0] == 8
        # a prompt sharing only 6 tokens (unaligned) falls back to the
        # 4-token page boundary
        other = np.concatenate([prompt[:6], [63, 62, 61]]).astype(np.int32)
        assert c.probe_prefix(other)[0] == 4
        # divergence inside the first page: miss
        assert c.probe_prefix(np.arange(50, 61, dtype=np.int32))[0] == 0
        # a prompt that IS the cached prefix must keep >= 1 token to
        # prefill: only the 4-token entry is usable for an 8-token prompt
        assert c.probe_prefix(prompt[:8])[0] == 4

    def test_refcounts_and_release_accounting(self):
        c = self._cache()
        prompt = np.arange(8, dtype=np.int32)
        c.allocate(0, 9)
        c.advance([0], 9)                          # 3 pages
        c.register_prefix(0, prompt)               # retains pages 0-1
        assert c.free(0) == 3                      # all pages unpinned
        assert c.cached_prefix_pages == 2 and c.free_pages == 8
        # two sharers acquire: pages pinned once each acquire
        assert c.acquire_prefix(1, np.arange(9, dtype=np.int32)) == 8
        assert c.acquire_prefix(2, np.arange(9, dtype=np.int32)) == 8
        assert c.free_pages == 6                   # 2 pages pinned
        # first sharer retires: pages still pinned by the second
        assert c.free(1) == 0
        assert c.free_pages == 6
        # second retires: pages drop back to evictable
        assert c.free(2) == 2
        assert c.free_pages == 8 and c.cached_prefix_pages == 2

    def test_eviction_lru_under_pool_pressure(self):
        c = self._cache(total_pages=4, page_size=4)
        old = np.arange(5, dtype=np.int32)
        new = np.arange(40, 45, dtype=np.int32)
        for sid, toks in ((0, old), (1, new)):
            c.allocate(sid, 5)
            c.advance([sid], 5)
            c.register_prefix(sid, toks)
            c.free(sid)
        assert c.cached_prefix_pages == 2 and len(c._free) == 2
        c.acquire_prefix(9, new)                   # LRU-touches `new`
        c.free(9)
        c.allocate(3, 12)                          # needs 3 pages: evict 1
        assert c.prefix_evictions == 1
        # the LRU victim was `old`; `new` survived
        assert c.probe_prefix(old)[0] == 0
        assert c.probe_prefix(new)[0] == 4
        c.free(3)

    def test_eviction_never_touches_pinned_pages(self):
        c = self._cache(total_pages=3, page_size=4)
        prompt = np.arange(5, dtype=np.int32)
        c.allocate(0, 5)
        c.advance([0], 5)
        c.register_prefix(0, prompt)               # page 0 retained
        # sharer pins the cached page, then the pool runs dry
        c.acquire_prefix(1, prompt)
        c.allocate(2, 4)                           # last free page
        with pytest.raises(RuntimeError, match="out of pages"):
            c.allocate(3, 4)
        # the pinned shared page was NOT reclaimed by the failed attempt
        assert c.probe_prefix(prompt)[0] == 4
        assert c.length(1) == 4

    def test_reset_pools_drops_the_index(self):
        c = self._cache()
        prompt = np.arange(9, dtype=np.int32)
        c.allocate(0, 9)
        c.advance([0], 9)
        c.register_prefix(0, prompt)
        c.free(0)
        assert c.cached_prefix_pages > 0
        c.reset_pools()                            # cached KV content lost
        assert c.cached_prefix_pages == 0
        assert c.probe_prefix(prompt)[0] == 0
        assert sorted(c._free) == list(range(8))


class TestEnginePrefixCaching:
    def test_warm_hit_matches_cold_run_and_reference(self, model):
        """A prefix-hit generation (suffix-only prefill through the
        jitted prefix program) must produce the same tokens as the cold
        full-prefill run AND the dense-KV reference generate."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        p = np.random.default_rng(0).integers(0, 64, (21,)).astype("int32")
        want = model.generate(paddle.to_tensor(p[None]), max_new_tokens=6)
        want = np.asarray(want.numpy() if hasattr(want, "numpy") else want)

        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=2) as eng:
            cold = eng.submit(p, max_new_tokens=6).result(timeout=120)
            assert eng.cache.cached_prefix_pages == 2   # 16 of 21 cached
            warm = eng.submit(p, max_new_tokens=6).result(timeout=120)
        np.testing.assert_array_equal(cold, want[0])
        np.testing.assert_array_equal(warm, cold)

    def test_hit_metrics_and_partial_prefix_reuse(self, model):
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        hits = monitor.counter("prefix_cache_hit_tokens_total")
        rng = np.random.default_rng(1)
        system = rng.integers(0, 64, (16,)).astype("int32")   # 2 pages
        a = np.concatenate([system, rng.integers(0, 64, (5,))]).astype(
            "int32")
        b = np.concatenate([system, rng.integers(0, 64, (9,))]).astype(
            "int32")
        want_b = model.generate(paddle.to_tensor(b[None]), max_new_tokens=4)
        want_b = np.asarray(want_b.numpy() if hasattr(want_b, "numpy")
                            else want_b)

        before = hits.value()
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=2) as eng:
            eng.submit(a, max_new_tokens=4).result(timeout=120)
            out_b = eng.submit(b, max_new_tokens=4).result(timeout=120)
        # b shares only the 16-token system prefix with a's cached pages
        assert hits.value() - before == 16
        np.testing.assert_array_equal(out_b, want_b[0])

    def test_sharer_retiring_mid_decode_of_another(self, model):
        """Two sharers of one cached prefix with different budgets: the
        short one retires first; the survivor keeps decoding against the
        shared pages (refcounts must keep them resident) and still
        matches the reference."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        rng = np.random.default_rng(2)
        p = rng.integers(0, 64, (17,)).astype("int32")        # 2 full pages
        # 12 tokens: the eager reference compiles every op anew at every
        # cache length, and nine steps past the short sharer's three
        # are as good as seventeen
        want = model.generate(paddle.to_tensor(p[None]), max_new_tokens=12)
        want = np.asarray(want.numpy() if hasattr(want, "numpy") else want)

        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=4) as eng:
            # seed the cache, then a long and a short sharer together
            eng.submit(p, max_new_tokens=2).result(timeout=120)
            long_r = eng.submit(p, max_new_tokens=12)
            short_r = eng.submit(p, max_new_tokens=3)
            short_r.result(timeout=120)
            out = long_r.result(timeout=120)
            # the short one retired while the long one was decoding
            assert short_r.finished_at < long_r.finished_at
            np.testing.assert_array_equal(out, want[0])
            # drained: every page free or evictable, reservations back
            # to the pad headroom
            deadline = time.time() + 30
            while time.time() < deadline and eng._reserved_pages != 1:
                time.sleep(0.02)
            assert eng._reserved_pages == 1
            assert eng.cache.free_pages == 64

    def test_eviction_under_pool_pressure_keeps_serving(self, model):
        """A request too big for the pool's free pages must evict cached
        prefixes (LRU) instead of failing, and still generate
        correctly."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        rng = np.random.default_rng(3)
        warm = rng.integers(0, 64, (17,)).astype("int32")
        big = rng.integers(0, 64, (48,)).astype("int32")
        want = model.generate(paddle.to_tensor(big[None]), max_new_tokens=8)
        want = np.asarray(want.numpy() if hasattr(want, "numpy") else want)

        # pool of 8: the warm run leaves 2 evictable prefix pages (6
        # truly free); the big request's prefill takes all 6, so the
        # 7th page (decode token 49) must reclaim the cached prefix
        # (LRU) instead of failing
        with ContinuousBatchingEngine(model, total_pages=8, page_size=8,
                                      max_batch=2) as eng:
            eng.submit(warm, max_new_tokens=8).result(timeout=120)
            assert eng.cache.cached_prefix_pages > 0
            out = eng.submit(big, max_new_tokens=8).result(timeout=120)
            np.testing.assert_array_equal(out, want[0])
            assert eng.cache.prefix_evictions > 0

    def test_prefix_cache_off_knob(self, model):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        p = np.random.default_rng(4).integers(0, 64, (17,)).astype("int32")
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      prefix_cache=False) as eng:
            a = eng.submit(p, max_new_tokens=4).result(timeout=120)
            assert eng.cache.cached_prefix_pages == 0
            b = eng.submit(p, max_new_tokens=4).result(timeout=120)
            np.testing.assert_array_equal(a, b)
