"""Power retention (``ops/power_retention.py``) on the CPU: phi, the three
forms against each other, and a ragged step against a pool of slots — in
XLA at a small head, and through the Pallas kernels (interpreted) at the
head of 128 they are written for.

Inputs: keys and values normal, queries a key of their group plus noise,
so that a position's weights do not sum to nearly nothing (the output is a
ratio: where its denominator is a rounding error the forms differ by
more than one, in the reference too).  Everything float32; the forms
differ in the order of float32 sums, the chunk kernel by its three
bfloat16 passes (2^-16)."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import power_retention as pr

HK, G = 2, 3


def draw(rng, t, d, lo=0.05, hi=0.999, hk=HK, g=G):
    k = rng.normal(size=(t, hk, d))
    q = np.repeat(k, g, axis=1) + 0.5 * rng.normal(size=(t, hk * g, d))
    return (jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32),
            jnp.asarray(rng.normal(size=(t, hk, d)), jnp.float32),
            jnp.log(jnp.asarray(rng.uniform(lo, hi, (t, hk)), jnp.float32)))


class TestPhi:
    @pytest.mark.parametrize("d", [8, 16, 128])
    def test_inner_product_is_the_squared_dot_product(self, d):
        rng = np.random.default_rng(d)
        x, y = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32)
                for _ in range(2))
        want = np.asarray(jnp.sum(x * y, -1) ** 2)
        for emb in (pr.phi, pr.phi_sym):
            got = np.asarray(jnp.sum(emb(x) * emb(y), -1))
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-4)

    def test_sizes_at_the_published_head(self):
        assert pr.sym_dim(128) == 8256 == pr.phi_sym(jnp.ones(128)).shape[0]
        # stored: 65 whole lane tiles, the 64 half-way pairs twice
        assert pr.store_dim(128) == 8320 == 8256 + 64
        assert pr.state_shape(8, 128, 128) == (8, 136, 8320)
        assert pr.state_bytes_symmetric(8, 128, 128) == 8 * 8256 * 129 * 4


class TestForms:
    @pytest.mark.parametrize("chunk", [1, 16, 64, 7])
    def test_recurrence_chunks_and_attention_agree(self, chunk):
        """50 tokens: chunks of 1, 16 and 64 (one ragged chunk of 50) and
        of 7 (a ragged last one), GQA groups of 3, gates over (0, 1)."""
        q, k, v, lg = draw(np.random.default_rng(0), 50, 16)
        want = np.asarray(pr.retention_attention(q, k, v, lg))
        y_rec, s_rec = pr.retention_recurrent(q, k, v, lg)
        y_chk, s_chk = pr.retention_chunked(q, k, v, lg, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y_rec), want, atol=2e-4)
        np.testing.assert_allclose(np.asarray(y_chk), want, atol=2e-4)
        np.testing.assert_allclose(np.asarray(s_chk), np.asarray(s_rec),
                                   rtol=1e-4, atol=1e-5)

    def test_long_memory(self):
        """Every gate over 0.99 for 2,048 tokens: token 0 still weighs
        e^-20 .. 1 at the end, and the chunk form carried across 32
        chunks agrees with the first form over the whole sequence."""
        q, k, v, lg = draw(np.random.default_rng(1), 2048, 8, lo=0.99,
                           hi=0.9999, hk=1, g=2)
        assert float(jnp.exp(lg).min()) > 0.99
        want = np.asarray(pr.retention_attention(q, k, v, lg))
        got, _ = pr.retention_chunked(q, k, v, lg, chunk=64)
        np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
        # it IS long memory: from the last 64 tokens alone the end reads
        # otherwise
        late, _ = pr.retention_chunked(q[-64:], k[-64:], v[-64:], lg[-64:],
                                       chunk=64)
        assert np.abs(np.asarray(late)[-1] - want[-1]).max() > 1e-2


def mixed_step(d, interpret):
    """Four rows of one ragged step against a pool of 5 slots + scratch:
    a chunk of 10 tokens into a fresh slot (which holds another
    sequence's leftovers), one token onto 20, a chunk of 5 onto 12, and a
    pad row; packed, and as the (rows, span) rectangle."""
    rng = np.random.default_rng(2)
    hq = HK * G
    shape = (6,) + pr.state_shape(HK, d, d)
    pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    _, s_b = pr.retention_recurrent(*draw(rng, 20, d, lo=0.5))
    _, s_c = pr.retention_recurrent(*draw(rng, 12, d, lo=0.5))
    pool = pool.at[0].set(s_b).at[4].set(s_c)
    q_lens, ctx = np.array([10, 1, 5, 1]), np.array([0, 20, 12, 0])
    slots, span = np.array([2, 0, 4, 5]), 16
    out = []
    for packed in (True, False):
        tokens = 32 if packed else 4 * span
        off = (np.cumsum(q_lens) - q_lens if packed
               else np.arange(4) * span).astype(np.int32)
        q, k, v, lg = draw(rng, tokens, d, lo=0.3)
        y, new = pr.retention_step(
            pool, jnp.asarray(slots, jnp.int32), jnp.asarray(ctx, jnp.int32),
            jnp.asarray(q_lens, jnp.int32),
            jnp.asarray(off) if packed else None,
            jnp.asarray([0, 2], jnp.int32), q, k, v, lg, span=span,
            interpret=interpret)
        for r, (start, slot) in enumerate([(None, 2), (s_b, 0), (s_c, 4)]):
            a, n = off[r], q_lens[r]
            y_ref, s_ref = pr.retention_recurrent(
                q[a:a + n], k[a:a + n], v[a:a + n], lg[a:a + n], start)
            out.append((np.asarray(y[a:a + n]), np.asarray(y_ref),
                        np.asarray(new[slot]), np.asarray(s_ref)))
        # the slots of no row of the step are as they were
        for idle in (1, 3):
            np.testing.assert_array_equal(np.asarray(new[idle]),
                                          np.asarray(pool[idle]))
    return out


class TestAgainstSlots:
    def test_xla_paths_at_a_small_head(self):
        for y, y_ref, s, s_ref in mixed_step(16, interpret=False):
            np.testing.assert_allclose(y, y_ref, atol=1e-4)
            np.testing.assert_allclose(s, s_ref, rtol=1e-4, atol=1e-5)

    def test_pallas_kernels_interpreted_at_128(self):
        """``retention_decode`` and ``retention_chunk`` (three bfloat16
        passes a product: 2^-16 of a state's largest entries)."""
        for y, y_ref, s, s_ref in mixed_step(128, interpret=True):
            np.testing.assert_allclose(y, y_ref, atol=2e-3)
            assert np.abs(s - s_ref).max() < 1e-4 * np.abs(s_ref).max()
