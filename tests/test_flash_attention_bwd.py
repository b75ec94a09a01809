"""Pallas flash-attention backward kernels (VERDICT r3 item 4a; reference:
paddle/phi/kernels/gpu/flash_attn_grad_kernel.cu).  The kernels run in
interpret mode on CPU; on TPU the same code compiles via Mosaic.  Every
path — Pallas fwd/bwd, XLA blockwise bwd, plain autodiff of the dense
reference — must agree, including bottom-right-aligned causal masking
when kv is longer than q (the KV-cache decode shape)."""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as FA


def _make(b, h, kvh, sq, sk, d=128, seed=0, dv=None):
    rng = np.random.default_rng(seed)
    dv = d if dv is None else dv
    q = jnp.asarray(rng.standard_normal((b, h, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, kvh, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, kvh, sk, dv)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((b, h, sq, dv)), jnp.float32)
    return q, k, v, do


CASES = [
    (2, 4, 4, 256, 256, False),
    (2, 4, 4, 256, 256, True),
    (1, 8, 2, 384, 384, True),      # GQA, non-block-multiple seq
    (1, 4, 4, 128, 512, True),      # causal decode: kv longer than q
]


# b, h, kvh, sq, sk, causal, block_q, block_kv, d, dv: the swept blocks'
# shapes at small lengths (256 x 512 and 512 x 1024 as 128 x 256)
BLOCK_CASES = [
    (1, 2, 2, 512, 512, True, 128, 256, 128, 128),
    (1, 2, 2, 512, 512, True, 256, 128, 128, 128),
    (1, 2, 2, 384, 768, True, 128, 256, 128, 128),    # offset diagonal
    (1, 2, 2, 256, 256, True, 128, 128, 192, 128),    # latent widths
    (1, 8, 2, 256, 256, True, 128, 256, 128, 128),    # GQA 8 -> 2
    (1, 2, 2, 256, 512, False, 128, 256, 128, 128),   # the rectangle
    (1, 2, 2, 300, 300, True, 128, 256, 128, 128),    # padded last blocks
]


def _tile_sees(sq, sk, causal, block_q, block_kv):
    """Brute force: [n_q, n_kv] whether a tile holds an unmasked score."""
    keep = np.ones((sq, sk), bool)
    if causal:
        keep = np.arange(sq)[:, None] + (sk - sq) >= np.arange(sk)[None, :]
    n_q, n_kv = -(-sq // block_q), -(-sk // block_kv)
    return np.array([[keep[i * block_q:(i + 1) * block_q,
                           j * block_kv:(j + 1) * block_kv].any()
                      for j in range(n_kv)] for i in range(n_q)])


class TestTileSchedule:
    """``causal_tile_schedule`` against the mask itself."""

    @pytest.mark.parametrize("order", ["q", "kv"])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk,block_q,block_kv", [
        (512, 512, 128, 128),
        (256, 1024, 128, 128),      # sq < sk: the diagonal's offset
        (384, 384, 128, 256),       # block_q != block_kv
        (1024, 1024, 512, 256),
        (1024, 1024, 256, 512),
        (1000, 1000, 256, 128),     # no block multiple
        (200, 456, 128, 256),
    ])
    def test_every_tile_with_a_score_once_and_no_other(
            self, sq, sk, block_q, block_kv, causal, order):
        q_idx, kv_idx, flags = FA.causal_tile_schedule(
            sq, sk, block_q, block_kv, causal, order)
        sees = _tile_sees(sq, sk, causal, block_q, block_kv)
        pairs = list(zip(q_idx.tolist(), kv_idx.tolist()))
        assert len(set(pairs)) == len(pairs)
        assert set(pairs) == set(zip(*np.nonzero(sees)))
        major = (lambda p: p) if order == "q" else (lambda p: p[::-1])
        assert pairs == sorted(pairs, key=major)
        for i, ((qi, ki), fl) in enumerate(zip(pairs, flags.tolist())):
            run = major((qi, ki))[0]
            first = i == 0 or major(pairs[i - 1])[0] != run
            last = i == len(pairs) - 1 or major(pairs[i + 1])[0] != run
            assert fl == (FA.FIRST if first else 0) | (FA.LAST if last else 0)

    def test_a_block_that_sees_nothing_keeps_one_tile(self):
        # causal with sq > sk: the first 256 rows see no key
        q_idx, kv_idx, flags = FA.causal_tile_schedule(
            512, 256, 128, 128, True, "q")
        assert list(zip(q_idx.tolist(), kv_idx.tolist())) == [
            (0, 0), (1, 0), (2, 0), (3, 0), (3, 1)]
        assert (flags[:3] == FA.FIRST | FA.LAST).all()

    @pytest.mark.parametrize("causal,block,tiles", [
        (True, 512, 36), (True, 1024, 10), (False, 512, 64)])
    def test_the_cells_counts(self, causal, block, tiles):
        # 4,096 tokens: the lower triangle's tiles, or the rectangle's
        for order in ("q", "kv"):
            _, _, flags = FA.causal_tile_schedule(4096, 4096, block, block,
                                                  causal, order)
            assert len(flags) == tiles


class TestTileCounters:
    def test_a_traced_call_counts_its_tiles(self):
        from paddle_tpu import monitor
        now = monitor.counter("flash_attn_tiles_visited_total").value

        b, h, s, d = 1, 2, 4096, 128
        x = jax.ShapeDtypeStruct((b, h, s, d), jnp.bfloat16)
        before = now()
        jax.eval_shape(lambda q, k, v: FA.flash_attention_forward(
            q, k, v, True, None, block_q=512, block_kv=512), x, x, x)
        fwd = now() - before
        assert fwd == 36 * h
        lse = jax.ShapeDtypeStruct((b, h, s), jnp.float32)
        jax.eval_shape(lambda q, k, v, o, l, do: FA.flash_attention_backward(
            q, k, v, o, l, do, True, d ** -0.5, block_q=512, block_kv=512),
            x, x, x, x, lse, x)
        assert now() - before - fwd == 72 * h
        # the rectangle: every tile visited
        before = now()
        jax.eval_shape(lambda q, k, v: FA.flash_attention_forward(
            q, k, v, False, None, block_q=512, block_kv=512), x, x, x)
        assert now() - before == 64 * h


class TestPallasBackward:
    @pytest.mark.parametrize("b,h,kvh,sq,sk,causal", CASES)
    def test_bwd_kernels_match_autodiff(self, b, h, kvh, sq, sk, causal):
        q, k, v, do = _make(b, h, kvh, sq, sk)
        scale = 1.0 / math.sqrt(q.shape[-1])
        out, lse = FA._fwd_impl(q, k, v, causal, scale)

        def loss(q_, k_, v_):
            return (FA.mha_reference(q_, k_, v_, causal, scale) * do).sum()

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        dq, dk, dv = FA.flash_attention_backward(
            q, k, v, out, lse, do, causal, scale,
            block_q=128, block_kv=128, interpret=True)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(gq),
                                   rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(gk),
                                   rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(gv),
                                   rtol=5e-3, atol=5e-3)

    @pytest.mark.parametrize(
        "b,h,kvh,sq,sk,causal,block_q,block_kv,d,dv", BLOCK_CASES)
    def test_bwd_kernels_at_unequal_blocks(self, b, h, kvh, sq, sk, causal,
                                           block_q, block_kv, d, dv):
        q, k, v, do = _make(b, h, kvh, sq, sk, d=d, dv=dv)
        scale = 1.0 / math.sqrt(d)
        out, lse = FA._fwd_impl(q, k, v, causal, scale)

        def loss(q_, k_, v_):
            return (FA.mha_reference(q_, k_, v_, causal, scale) * do).sum()

        want = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        got = FA.flash_attention_backward(
            q, k, v, out, lse, do, causal, scale, block_q=block_q,
            block_kv=block_kv, interpret=True)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=5e-3, atol=5e-3)

    @pytest.mark.parametrize(
        "b,h,kvh,sq,sk,causal,block_q,block_kv,d,dv", BLOCK_CASES)
    def test_fwd_kernel_at_unequal_blocks(self, b, h, kvh, sq, sk, causal,
                                          block_q, block_kv, d, dv):
        q, k, v, _ = _make(b, h, kvh, sq, sk, d=d, dv=dv)
        scale = 1.0 / math.sqrt(d)
        out, lse = FA.flash_attention_forward(
            q, k, v, causal, scale, block_q=block_q, block_kv=block_kv,
            interpret=True)
        want, want_lse = FA._fwd_impl(q, k, v, causal, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("b,h,kvh,sq,sk,causal", CASES)
    def test_xla_blockwise_matches_autodiff(self, b, h, kvh, sq, sk,
                                            causal):
        q, k, v, do = _make(b, h, kvh, sq, sk)
        scale = 1.0 / math.sqrt(q.shape[-1])
        out, lse = FA._fwd_impl(q, k, v, causal, scale)

        def loss(q_, k_, v_):
            return (FA.mha_reference(q_, k_, v_, causal, scale) * do).sum()

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        dq, dk, dv = FA._bwd_blockwise(q, k, v, out, lse, do, causal, scale)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(gq),
                                   rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(gk),
                                   rtol=5e-3, atol=5e-3)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(gv),
                                   rtol=5e-3, atol=5e-3)

    def test_fwd_kernel_bottom_right_causal(self):
        # decode shape: each of the 128 query rows attends to the first
        # (sk - sq + row + 1) keys — the flash-attn v2.1 convention the
        # reference wraps
        q, k, v, _ = _make(1, 2, 2, 128, 512)
        out_p, _ = FA.flash_attention_forward(q, k, v, True, None,
                                              block_q=128, block_kv=128,
                                              interpret=True)
        ref = FA.mha_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

    def test_rows_that_see_no_key(self):
        # causal with sq > sk: the last sk query rows see keys and read
        # what the reference reads, out, lse and gradients; the first
        # sq - sk see none: their output is undefined, stays finite and
        # passes no gradient on
        sq, sk = 384, 128
        q, k, v, do = _make(1, 2, 2, sq, sk)
        scale = 1.0 / math.sqrt(q.shape[-1])
        out, lse = FA.flash_attention_forward(
            q, k, v, True, scale, block_q=128, block_kv=128, interpret=True)
        want, want_lse = FA._fwd_impl(q, k, v, True, scale)
        seen = slice(sq - sk, sq)
        np.testing.assert_allclose(np.asarray(out[:, :, seen]),
                                   np.asarray(want[:, :, seen]),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse[:, :, seen]),
                                   np.asarray(want_lse[:, :, seen]),
                                   rtol=2e-4, atol=2e-4)
        assert np.isfinite(np.asarray(out)).all()
        assert np.isfinite(np.asarray(lse)).all()

        # the kernels' gradients are the reference's of the rows that see
        # a key alone
        do_seen = do.at[:, :, :sq - sk].set(0.0)

        def loss(q_, k_, v_):
            return (FA.mha_reference(q_, k_, v_, True, scale)
                    * do_seen).sum()

        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        dq, dk, dv = FA.flash_attention_backward(
            q, k, v, out, lse, do, True, scale, block_q=128, block_kv=128,
            interpret=True)
        for g, w in ((dq, gq), (dk, gk), (dv, gv)):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=5e-3, atol=5e-3)
        assert not np.asarray(dq[:, :, :sq - sk]).any()
