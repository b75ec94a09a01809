"""The sliding window in the paged kernels (ISSUE 33): a query at
position p sees keys p - W + 1 .. p.  The Pallas kernels (interpreted
here) against their XLA oracles for the one-query, multi-query and
ragged calls with rows shorter than, equal to and longer than the
window; the oracles against a plain numpy attention with the mask
written out; ``window=None`` bit-equal to a call without the argument;
and the host's count of what a windowed walk costs."""
import numpy as np
import pytest
import jax.numpy as jnp

import test_paged_attention as tpa
from paddle_tpu.ops.pallas.paged_attention import (
    kv_tokens_visible, kv_tokens_walked, walk_block_pages, walk_cut,
    window_first_token)

MODES = ["decode", "multi", "ragged"]
case = tpa.TestContextWalk._case
_real_queries = tpa._real_queries


def _walk_call(mode, *args, oracle=False, **window):
    """``test_paged_attention._walk_call`` with the window among the
    keyword arguments the entry is handed."""
    return tpa._walk_call(mode, *args[:-1], dict(args[-1], **window),
                          oracle=oracle)


def numpy_windowed(mode, q, kp, vp, lens, q_lens, tabs, scale, window):
    """Every real query of every row against its row's keys, the mask
    written out: 0 <= p - j < window."""
    q, kp, vp = (np.asarray(a.astype(jnp.float32)) for a in (q, kp, vp))
    n, nq, qh, d = q.shape
    kvh, _, page, _ = kp.shape
    out = np.zeros_like(q)
    for b in range(n):
        L = int(lens[b])
        ql = int(q_lens[b]) if mode == "ragged" else nq
        need = -(-L // page)
        k = kp[:, np.asarray(tabs[b, :need])].reshape(kvh, -1, d)[:, :L]
        v = vp[:, np.asarray(tabs[b, :need])].reshape(kvh, -1, d)[:, :L]
        for j in range(ql):
            p = L - ql + j
            lo = max(0, p - window + 1)
            for h in range(qh):
                g = h // (qh // kvh)
                s = k[g, lo:p + 1] @ q[b, j, h] * scale
                w = np.exp(s - s.max())
                out[b, j, h] = (w / w.sum()) @ v[g, lo:p + 1]
    return out


class TestWindowedWalk:
    @pytest.mark.parametrize("kv", ["bf16", "int8"])
    @pytest.mark.parametrize("mode", MODES)
    def test_kernel_matches_oracle_around_the_window(self, mode, kv):
        """Rows shorter than, equal to and longer than the window, and
        long enough that the walk starts blocks into the row."""
        rng = np.random.default_rng(33)
        span, window = (1 if mode == "decode" else 8), 48
        lens = [span, window - 1, window, window + 1, window + span,
                5 * window + 3, 1400]
        args = case(rng, mode, kv, lens, span=span, table=96)
        out = _walk_call(mode, *args, window=window)
        ref = _walk_call(mode, *args, oracle=True, window=window)
        tol = 2e-2 if kv == "bf16" else 2e-4
        np.testing.assert_allclose(_real_queries(mode, out, args[4]),
                                   _real_queries(mode, ref, args[4]),
                                   rtol=tol, atol=tol)
        assert not np.isnan(np.asarray(out.astype(jnp.float32))).any()

    @pytest.mark.parametrize("mode", MODES)
    def test_oracle_matches_the_written_out_mask(self, mode):
        rng = np.random.default_rng(34)
        span, window = (1 if mode == "decode" else 5), 24
        lens = [span, 23, 24, 25, 100, 7]
        q_lens = None if mode != "ragged" else [5, 3, 1, 5, 2, 4]
        args = case(rng, mode, "f32", lens, q_heads=6, kvh=2, d=64, page=8,
                    span=span, q_lens=q_lens)
        ref = _walk_call(mode, *args, oracle=True, window=window)
        ref = np.asarray(ref).reshape(len(lens), span, 6, 64)
        want = numpy_windowed(mode, *args[:7], window)
        keep = (np.arange(span)[None, :] < np.asarray(args[4])[:, None]
                if mode == "ragged" else np.ones((len(lens), span), bool))
        np.testing.assert_allclose(ref[keep], want[keep], rtol=2e-5,
                                   atol=2e-5)
        # and the window is not a no-op on the long row
        full = np.asarray(_walk_call(mode, *args, oracle=True)).reshape(
            ref.shape)
        assert np.abs(full[4] - ref[4]).max() > 1e-3

    def test_mixed_rows_group_of_six_and_eight(self):
        """The two head groups one model's layers hand one pool: 48 and 64
        query heads over 8 KV heads, chunk spans beside decode and pad
        rows."""
        rng = np.random.default_rng(35)
        lens = [700, 130, 1030, 1, 1, 64, 1, 512]
        q_lens = [16, 5, 1, 1, 1, 1, 1, 16]
        for heads in (12, 16):
            args = case(rng, "ragged", "bf16", lens, q_heads=heads, kvh=2,
                        span=16, q_lens=q_lens, table=128)
            out = _walk_call("ragged", *args, window=64)
            ref = _walk_call("ragged", *args, oracle=True, window=64)
            np.testing.assert_allclose(
                _real_queries("ragged", out, args[4]),
                _real_queries("ragged", ref, args[4]), rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("oracle", [False, True])
    @pytest.mark.parametrize("mode", MODES)
    def test_window_none_is_bit_equal_to_no_argument(self, mode, oracle):
        rng = np.random.default_rng(36)
        args = case(rng, mode, "bf16", [3, 200, 600],
                    span=1 if mode == "decode" else 4)
        a = _walk_call(mode, *args, oracle=oracle)
        b = _walk_call(mode, *args, oracle=oracle, window=None)
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b.astype(jnp.float32)))

    def test_a_window_wider_than_the_row_changes_nothing(self):
        rng = np.random.default_rng(37)
        args = case(rng, "ragged", "bf16", [3, 200, 600], span=4)
        a = _walk_call("ragged", *args)
        b = _walk_call("ragged", *args, window=4096)
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


class TestWindowedHeadGroups:
    """The window under a grid step that owns a group of kv heads
    (ISSUE 42): the walk starts at the row's first visible page for the
    whole group, and every output is one head a step's, bit for bit."""

    # name: mode, kv heads, group, storage, span, rows
    CASES = {
        "phi4_flash_one_query": ("decode", 10, 4, "bf16", 1, 32),
        "laguna_sliding_ragged": ("ragged", 8, 8, "bf16", 128, 6),
        "verify_bucket": ("multi", 8, 4, "bf16", 4, 6),
        "int8_ragged": ("ragged", 4, 4, "int8", 32, 6),
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_grouped_is_one_head_a_step_bit_for_bit(self, name):
        mode, kvh, group, kv, span, rows = self.CASES[name]
        rng = np.random.default_rng(42)
        # shorter than the window, around it, blocks past it, empty
        lens = ([0, 16, 511, 512, 513, 1300] * 6)[:rows]
        lens[-1] = 1029
        q_lens = None
        if mode == "ragged":
            q_lens = [min(n, L, span) for n, L in zip(
                [0, 1, span, 37, 1, 1], lens)]
        if mode == "multi":
            lens = [max(L, span) for L in lens]
        args = case(rng, mode, kv, lens, q_heads=kvh * group, kvh=kvh,
                    span=span, q_lens=q_lens, table=96)
        if kv == "int8":
            args = (args[0].astype(jnp.bfloat16), *args[1:])
        tpa.head_group_check(mode, args, 512, kvh)


class TestWindowedCount:
    def test_first_token_is_the_page_of_the_first_visible_key(self):
        # a decode row of 1,000 tokens under a window of 512 sees 488..999
        assert window_first_token(np.asarray([1000]), 1, 512, 16)[0] == 480
        # a chunk of 128 queries ending at 1,000: the first stands at 872
        assert window_first_token(np.asarray([1000]), 128, 512, 16)[0] == 352
        assert window_first_token(np.asarray([100]), 1, 512, 16)[0] == 0

    def test_walked_and_visible_follow_the_window(self):
        lens, ql = np.asarray([1000, 100, 5000]), np.asarray([1, 1, 128])
        assert kv_tokens_walked(lens, 256) == 1024 + 256 + 5120
        # 1000 - 480 = 520 -> 768; 100 -> 256; 5000 - 4352 = 648 -> 768
        assert kv_tokens_walked(lens, 256, window=512, q_lens=ql,
                                page_size=16) == 768 + 256 + 768
        assert kv_tokens_visible(lens, ql) == 6100
        assert kv_tokens_visible(lens, ql, window=512) == 512 + 100 + 639

    def test_block_rule_of_the_two_groups_at_the_cell_s_span(self):
        """The tile's rows cut the block (96 and 128 rows: 512 tokens; the
        sliding layers' window of 512 reaches past that), where the
        bucket's 768 and 1,024 cut it at 256.  A window's reach bounds it:
        128 + 16 positions - 1 + a page are 159 columns, blocks of 256."""
        bf16 = jnp.bfloat16
        for group, tile, window in ((6, 96, None), (8, 128, 512)):
            assert walk_cut(8, 16, 128, 128, group, bf16, bf16,
                            window=window) == (tile, 32, 8)
            assert walk_block_pages(16, 128, 128 * group, bf16) == 16
        assert walk_cut(8, 16, 128, 128, 8, bf16, bf16, window=128) \
            == (128, 16, 8)
        assert walk_block_pages(16, 128, 128, bf16, reach=159) == 16
        assert walk_block_pages(16, 128, 128, bf16, reach=100) == 8
