"""The selective scan and its causal convolution (``ops/selective_scan.py``)
on the CPU: the recurrence over a whole sequence (the oracle) against the
forms a ragged serving step runs — chunk rows from a slot and back into
it, one-token rows against their slots in place — with chunk boundaries
inside the sequences, the tail carried, dirty slots entered at context 0,
a pad row on the scratch slot, packed and rectangular token axes; in XLA at
64 channels and through the Pallas kernel, interpreted, at 256.

Tolerance: float32 on both sides and the same products in the same order a
token (the forms differ only in where ``h`` is kept between tokens), so
1e-5 of outputs of order ten is rounding of ``exp`` and of the sum over
``d_state`` alone; a form that dropped the carry misses it a
thousandfold (``test_the_carry_is_what_is_tested``)."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import selective_scan as ss

N, K, ROWS, L = 16, 4, 4, 21
# (what each of the three sequences is handed a step); a fourth row is pad
PLAN = ([8, 5, 1], [1, 8, 1], [1, 1, 8], [8, 1, 3], [3, 6, 8], [1, 1, 1])


def _weights(rng, d):
    a = -jnp.exp(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None]
                 * jnp.ones((1, d)))
    f32 = lambda x: jnp.asarray(x, jnp.float32)             # noqa: E731
    return dict(a=a, d=f32(rng.standard_normal(d)),
                w=f32(rng.standard_normal((K, d)) * 0.3),
                b=f32(rng.standard_normal(d) * 0.1))


def _sequence(rng, d):
    f32 = lambda x: jnp.asarray(x, jnp.float32)             # noqa: E731
    return dict(x=f32(rng.standard_normal((L, d))),
                delta=f32(rng.uniform(1e-3, 0.1, (L, d))),
                B=f32(rng.standard_normal((L, N))),
                C=f32(rng.standard_normal((L, N))))


def _oracle(seq, wt):
    u, tail = ss.conv_recurrence(seq["x"], wt["w"], wt["b"])
    m, h = ss.scan_recurrence(u, seq["delta"], wt["a"], seq["B"], seq["C"],
                              wt["d"])
    return u, m, h, tail


def _serve(seqs, wt, d, packed, interpret, carry=True):
    """The three sequences through ``PLAN``'s ragged steps against dirty
    pools; returns (u, m) a sequence and the pools."""
    rng = np.random.default_rng(9)
    hp = jnp.asarray(rng.standard_normal((ROWS + 1, N, d)), jnp.float32)
    tp = jnp.asarray(rng.standard_normal((ROWS + 1, (K - 1) * d)),
                     jnp.float32)
    slots = jnp.asarray([2, 0, 3, ROWS], jnp.int32)
    done, us, ms = [0, 0, 0], [[], [], []], [[], [], []]
    for ns in PLAN:
        ns = [min(n, L - at) for n, at in zip(ns, done)]
        ql = np.array(ns + [1], np.int32)
        ctx = np.array((done if carry else [0, 0, 0]) + [0], np.int32)
        span = 1 if ql.max() == 1 else 8
        multi = [i for i, n in enumerate(ns) if n > 1]
        rows = np.full(0 if span == 1 else 4, -1, np.int32)
        rows[:len(multi)] = multi
        off = ((np.cumsum(ql) - ql).astype(np.int32) if packed
               else np.arange(ROWS, dtype=np.int32) * span)
        total = 24 if packed else ROWS * span

        def pack(key):
            out = np.zeros((total,) + seqs[0][key].shape[1:], np.float32)
            for r in range(3):
                out[off[r]:off[r] + ns[r]] = np.asarray(
                    seqs[r][key][done[r]:done[r] + ns[r]])
            return jnp.asarray(out)

        args = (slots, jnp.asarray(ctx), jnp.asarray(ql),
                jnp.asarray(off) if packed else None)
        u, tp = ss.conv_step(tp, *args, pack("x"), wt["w"], wt["b"],
                             span=span)
        m, hp = ss.scan_step(hp, *args, jnp.asarray(rows), u, pack("delta"),
                             wt["a"], pack("B"), pack("C"), wt["d"],
                             span=span, interpret=interpret)
        for r in range(3):
            us[r].append(np.asarray(u[off[r]:off[r] + ns[r]]))
            ms[r].append(np.asarray(m[off[r]:off[r] + ns[r]]))
            done[r] += ns[r]
    assert done == [L] * 3
    return ([np.concatenate(x) for x in us], [np.concatenate(x) for x in ms],
            hp, tp)


@pytest.mark.parametrize("packed", [False, True], ids=["rectangle", "packed"])
@pytest.mark.parametrize("d, interpret", [(64, False), (256, True)],
                         ids=["xla", "pallas_interpreted"])
class TestTheThreeFormsAgree:
    def test_chunk_rows_and_one_token_rows_against_the_recurrence(
            self, d, interpret, packed):
        rng = np.random.default_rng(0)
        wt, seqs = _weights(rng, d), [_sequence(rng, d) for _ in range(3)]
        us, ms, hp, tp = _serve(seqs, wt, d, packed, interpret)
        for r, slot in enumerate((2, 0, 3)):
            u, m, h, tail = _oracle(seqs[r], wt)
            assert np.abs(us[r] - np.asarray(u)).max() < 1e-5
            assert np.abs(ms[r] - np.asarray(m)).max() < 1e-5
            # what the slot holds after the last token is the recurrence's
            assert np.abs(np.asarray(hp[slot]) - np.asarray(h)).max() < 1e-5
            np.testing.assert_array_equal(
                np.asarray(tp[slot]).reshape(K - 1, d), np.asarray(tail))

    def test_a_slot_no_row_holds_is_untouched(self, d, interpret, packed):
        rng = np.random.default_rng(1)
        wt, seqs = _weights(rng, d), [_sequence(rng, d) for _ in range(3)]
        *_, hp, tp = _serve(seqs, wt, d, packed, interpret)
        dirty = np.random.default_rng(9)
        h0 = dirty.standard_normal((ROWS + 1, N, d)).astype(np.float32)
        t0 = dirty.standard_normal((ROWS + 1, (K - 1) * d)).astype(
            np.float32)
        np.testing.assert_array_equal(np.asarray(hp[1]), h0[1])
        np.testing.assert_array_equal(np.asarray(tp[1]), t0[1])


def test_the_carry_is_what_is_tested():
    """Every row entered at context 0 in every step (no carry of ``h`` or
    of the tail): the same comparison misses by orders of magnitude."""
    rng = np.random.default_rng(0)
    wt, seqs = _weights(rng, 64), [_sequence(rng, 64) for _ in range(3)]
    us, ms, *_ = _serve(seqs, wt, 64, False, False, carry=False)
    u, m, *_ = _oracle(seqs[0], wt)
    assert np.abs(us[0] - np.asarray(u)).max() > 1e-2
    assert np.abs(ms[0] - np.asarray(m)).max() > 1e-2


@pytest.mark.parametrize("span, lens", [(8, (8, 5, 0)), (64, (64, 37, 0))],
                         ids=["one_block", "two_token_blocks"])
def test_kernel_and_xla_rows_agree_on_a_pool(span, lens):
    """``scan_rows`` alone: the kernel (interpreted) and XLA's
    gather-update-scatter over the same rows, a fresh one and a pad among
    them, leave the same pool and the same outputs; at 64 tokens a row is
    two grid steps of ``TOKEN_BLOCK`` tokens with ``h`` carried between
    them (one row ends inside its second block)."""
    rng = np.random.default_rng(2)
    d, rows = 128, 3
    assert span == 8 or span == 2 * ss.TOKEN_BLOCK
    wt = _weights(rng, d)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    pool = f32(5, N, d)
    args = (jnp.asarray([3, 1, 4]), jnp.asarray([False, True, True]),
            jnp.asarray(lens), f32(rows, span, d),
            jnp.asarray(rng.uniform(1e-3, 0.1, (rows, span, d)), jnp.float32),
            wt["a"], f32(rows, span, N), f32(rows, span, N), wt["d"])
    m_x, p_x = ss._scan_rows_xla(pool, *args)
    m_k, p_k = ss._scan_pallas(pool, *args, interpret=True)
    real = np.arange(span)[None, :] < np.asarray(lens)[:, None]
    assert np.abs(np.asarray(m_x) - np.asarray(m_k))[real].max() < 1e-5
    assert np.abs(np.asarray(p_x) - np.asarray(p_k))[:4].max() < 1e-5
    np.testing.assert_array_equal(np.asarray(p_k[0]), np.asarray(pool[0]))


def test_state_shapes_and_bytes():
    assert ss.state_shapes(5120, 16, 4) == [(16, 5120), (3 * 5120,)]
    assert ss.state_bytes(5120, 16, 4) == 327680 + 3 * 5120 * 4
