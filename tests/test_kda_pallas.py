"""The Pallas kernels of the chunkwise delta rule (``ops/pallas/
kda_chunk.py``) in interpret mode on the CPU, against both of their
oracles: ``kda._kda_chunk`` (the XLA path they replace on a TPU) and
``kda._kda_recurrent`` (the recurrence token by token).  The wrapper
takes and returns ROWS ([B, T, H * d]); the oracles see the [B, T, H, d]
view of the same arrays.  Output, final state and the gradients of all
six arguments; and the route of ``kda.kda_chunk`` and
``kda.kda_chunk_rows``, which is decided by backend and shape alone.
The compile for a described chip: tests/test_pallas_mosaic_lowering.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kimi_linear import exact_float32, rel  # noqa: F401
from test_kimi_linear_ops import kda_inputs
from test_kda_rows import rows
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import kda_chunk as kc

WIDE = dict(b=1, h=2, dk=128, dv=128)       # whole lane tiles


def kernel(q, k, v, a, beta, s0=None, call=None):
    """The rows-in / rows-out wrapper, interpreted, behind the oracles'
    [B, T, H, d] signature: the reshapes are the test's, so a gradient
    comes back in the oracle's shape."""
    call = call or functools.partial(kc.kda_chunk_pallas, interpret=True)
    o, s = call(rows(q), rows(k), rows(v), rows(a), beta, s0)
    assert o.shape == v.shape[:2] + (v.shape[2] * v.shape[3],)
    return o.reshape(v.shape), s


T = 100      # a whole chunk and 36 tokens of a second; one length, so the
             # cases of one dtype share their compiled programs


def parallel_keys(noise, shift, t=T):
    """The keys of ``test_chunkwise_kda_with_nearly_parallel_keys``:
    every key of a chunk points the same way and beta is near 1."""
    q, k, v, a, beta, s0 = kda_inputs(11, t, 0.001, **WIDE)
    base = jax.random.normal(jax.random.key(5), (1, 1, 2, 128))
    k = base + noise * k * 128 ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return k * 128 ** -0.5, k, v, a, jax.nn.sigmoid(shift + beta), s0


def in_bf16(args):
    q, k, v, a, beta, s0 = args
    bf = jnp.bfloat16
    return q.astype(bf), k.astype(bf), v.astype(bf), a, beta, s0


CASES = {
    # name: (arguments, tolerance of output and state, of the gradients)
    "t_not_whole_chunks": (lambda: kda_inputs(3, T, 0.3, **WIDE),
                           2e-5, 5e-4),
    # A_log large: a chunk decays by e^-1000, exp(-g) would be inf
    "decay_e-1000_a_chunk": (lambda: kda_inputs(5, T, 30.0, **WIDE),
                             2e-5, 5e-4),
    "parallel_keys_noisy": (lambda: parallel_keys(0.1, 2.0), 2e-5, 5e-4),
    "parallel_keys_exact": (lambda: parallel_keys(0.0, 6.0), 2e-5, 5e-4),
    # bfloat16 operands: the kernel and the XLA path round at the same
    # places, the recurrence nowhere (it reads 4e-3 against either)
    "bfloat16_inputs": (lambda: in_bf16(kda_inputs(3, T, 0.3, **WIDE)),
                        1e-2, 1.5e-2),
}


@functools.lru_cache(maxsize=None)
def grad_of(fn):
    def loss(*xs):
        out, state = fn(*xs)
        return (jnp.sum(jnp.sin(out.astype(jnp.float32)))
                + jnp.sum(state * state))
    return jax.jit(jax.grad(loss, argnums=tuple(range(6))))


def grads(fn, args):
    return grad_of(fn)(*args)


forward_of = functools.lru_cache(maxsize=None)(jax.jit)


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_both_oracles(case):
    make, tol, tol_grad = CASES[case]
    args = make()
    o, s = kernel(*args)
    got = grads(kernel, args)
    assert np.isfinite(np.asarray(o, np.float32)).all()
    for oracle in (kda._kda_chunk, kda._kda_recurrent):
        o_ref, s_ref = forward_of(oracle)(*args)
        assert o.shape == o_ref.shape and o.dtype == o_ref.dtype
        assert s.shape == s_ref.shape and s.dtype == s_ref.dtype
        assert rel(o, o_ref) < tol and rel(s, s_ref) < tol, oracle
        for g, want, x in zip(got, grads(oracle, args), args):
            assert g.shape == x.shape and g.dtype == x.dtype
            assert np.isfinite(np.asarray(g, np.float32)).all()
            assert rel(g, want) < tol_grad, oracle


def test_kernels_without_an_initial_state_and_heads_sharing_a_step(
        monkeypatch):
    """Three heads: a grid step of one head, then of three; no state
    given is a zero state."""
    q, k, v, a, beta, _ = kda_inputs(7, 70, 0.5, b=2, h=3, dk=128, dv=128)
    want = jax.jit(kda._kda_chunk)(q, k, v, a, beta)
    for heads in (1, 3):
        # the constant is read when the op is traced: trace it anew
        monkeypatch.setattr(kc, "HEADS_PER_STEP", heads)
        got = jax.jit(functools.partial(
            kernel, call=functools.partial(kc.kda_chunk_pallas.__wrapped__,
                                           interpret=True)))(q, k, v, a, beta)
        assert rel(got[0], want[0]) < 2e-5 and rel(got[1], want[1]) < 2e-5


def test_the_route_is_decided_by_backend_and_shape(monkeypatch):
    wide = kda_inputs(1, 70, 0.5, **WIDE)
    narrow = kda_inputs(1, 70, 0.5, b=1, h=2, dk=32, dv=16)
    interpreted = functools.partial(kc.kda_chunk_pallas, interpret=True)

    def in_rows(args):
        return tuple(rows(x) for x in args[:4]) + tuple(args[4:])

    def both_ops(args):
        """(kda_chunk on [B, T, H, d], kda_chunk_rows on the rows)."""
        return (kda.kda_chunk.raw_fn(*args),
                kda.kda_chunk_rows.raw_fn(*in_rows(args)))

    # here, on the CPU: the XLA path, bit for bit, under either view
    assert not kc.supported(128, 128)
    calls = []
    monkeypatch.setattr(kc, "kda_chunk_pallas",
                        lambda *xs: calls.append(xs) or interpreted(*xs))
    for args in (wide, narrow):
        want = kda._kda_chunk(*args)
        four, flat = both_ops(args)
        assert all(np.array_equal(x, y) for x, y in zip(four, want))
        assert np.array_equal(flat[0], rows(want[0]))
        assert np.array_equal(flat[1], want[1])
    assert not calls
    # on a TPU: the kernels for whole lane tiles, the XLA path otherwise
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kc.supported(128, 128) and kc.supported(128, 256)
    assert not kc.supported(32, 16)
    assert not kc.supported(128, 16)
    assert not kc.supported(256, 128)
    want = kda._kda_chunk(*narrow)
    for got in both_ops(narrow):
        assert np.array_equal(got[0].reshape(want[0].shape), want[0])
        assert np.array_equal(got[1], want[1])
    assert not calls
    # both routes: the same shapes and dtypes, and the kernels were
    # handed rows under either view
    want = kda._kda_chunk(*in_bf16(wide))
    four, flat = both_ops(in_bf16(wide))
    assert len(calls) == 2
    for xs in calls:
        assert [x.ndim for x in xs[:5]] == [3, 3, 3, 3, 3]
        assert xs[0].shape == (1, 70, 256) and xs[4].shape == (1, 70, 2)
    for x, y in zip(four, want):
        assert x.shape == y.shape and x.dtype == y.dtype
    assert flat[0].shape == (1, 70, 256) and flat[1].shape == want[1].shape
    assert np.array_equal(flat[0].reshape(want[0].shape), four[0])
    assert four[0].dtype == jnp.bfloat16 and four[1].dtype == jnp.float32
