"""The KDA mixer's XLA half in ROWS (PR 46): every stream between the
projections and the two kernels is made once as [B, T, H * d], the layout
the kernels read, and no [B, T, H, d] view of a stream exists on the
kernel path.  Held here against the [B, T, H, d] definitions the row
forms replaced (kept below as the oracles): the log-decay, the two L2
norms, the gated head norm, values and gradients; the short convolution
with and without a tail; and the jaxpr of the mixer's kernel path.

Tolerances: the row forms are the same float32 arithmetic under another
view, so float32 inputs agree to 1e-6 relative (on the CPU they come out
equal) and bfloat16 inputs to the rounding of one bfloat16 step, 2^-8.
The compile for a described chip, where the view has to be free:
tests/test_pallas_mosaic_lowering.py::TestKdaChunkLowering."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kimi_linear import exact_float32, rel  # noqa: F401
import paddle_tpu as paddle
from paddle_tpu.models import kimi_linear as KL
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import kda_chunk as kc

F32, BF16 = jnp.float32, jnp.bfloat16
SHAPES = [(2, 16), (4, 128), (32, 128)]                   # (heads, dk)
TOL = {F32: 1e-6, BF16: 2.0 ** -8}


# ------------------------------ the [B, T, H, d] forms the rows replaced
def gates_4d(q, k, f, a_log, dt_bias, b_logits, heads):
    b, t = q.shape[:2]
    dk = q.shape[-1] // heads

    def unit(x):
        x = x.reshape(b, t, heads, dk).astype(F32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    a = -jnp.exp(a_log.astype(F32))[:, None] * jax.nn.softplus(
        (f.astype(F32) + dt_bias.astype(F32)).reshape(b, t, heads, dk))
    return ((unit(q) * dk ** -0.5).astype(q.dtype), unit(k).astype(k.dtype),
            a, jax.nn.sigmoid(b_logits.astype(F32)))


def head_norm_4d(o, gate, weight, eps):
    b, t, h, dv = o.shape
    x = o.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    x = x * weight.astype(F32) * jax.nn.sigmoid(
        gate.astype(F32).reshape(b, t, h, dv))
    return x.reshape(b, t, h * dv).astype(o.dtype)


def conv_with_tail(x, w, tail):
    """``_short_conv_silu`` as it was: the tail concatenated in front."""
    k = w.shape[-1]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    t = x.shape[1]
    y = sum(xp[:, j:j + t].astype(F32) * w[:, j].astype(F32)
            for j in range(k))
    return jax.nn.silu(y).astype(x.dtype), xp[:, -(k - 1):]


def rows(x):
    return x.reshape(x.shape[:2] + (-1,))


def draw(seed, heads, dk, dtype, t):
    ks = jax.random.split(jax.random.key(seed), 8)
    wide = heads * dk
    return dict(
        q=jax.random.normal(ks[0], (2, t, wide)).astype(dtype),
        k=jax.random.normal(ks[1], (2, t, wide)).astype(dtype),
        f=jax.random.normal(ks[2], (2, t, wide)).astype(dtype),
        a_log=jax.random.uniform(ks[3], (heads,), F32, 0.0, 2.77),
        dt_bias=jax.random.normal(ks[4], (wide,), F32) - 4.6,
        b_logits=jax.random.normal(ks[5], (2, t, heads)).astype(dtype),
        gate=jax.random.normal(ks[6], (2, t, wide)).astype(dtype),
        weight=(1.0 + 0.1 * jax.random.normal(ks[7], (dk,))).astype(dtype))


def weighted(fn, cots):
    """A scalar of every output, so one gradient holds them all to
    account: sum of output x a fixed draw."""
    def loss(*xs):
        outs = fn(*xs)
        outs = outs if isinstance(outs, tuple) else (outs,)
        return sum(jnp.sum(o.astype(F32) * c) for o, c in zip(outs, cots))
    return loss


def close(got, want, tol):
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert rel(got, want) <= tol, rel(got, want)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,dk", SHAPES)
@pytest.mark.parametrize("t", [24, 13], ids=["whole_tiles", "t_odd"])
def test_gates_in_rows_are_the_head_axis_definitions(heads, dk, dtype, t):
    """q, k, the log-decay and beta; T a multiple of 8 (tokens grouped by
    the tile) and not (no grouping)."""
    d = draw(heads * dk + t, heads, dk, dtype, t)
    args = (d["q"], d["k"], d["f"], d["a_log"], d["dt_bias"], d["b_logits"])
    got = KL._kda_gates.raw_fn(*args, heads)
    want = gates_4d(*args, heads)
    assert got[2].dtype == F32 and got[3].dtype == F32
    for g, w in zip(got, want):
        assert g.ndim == 3                      # rows, beta [B, T, H]
        close(g, rows(w) if w.ndim == 4 else w, TOL[dtype])
    cots = [jax.random.normal(jax.random.key(i), g.shape) for i, g in
            enumerate(got)]
    cots_4d = [c.reshape(w.shape) for c, w in zip(cots, want)]
    grad = jax.grad(weighted(lambda *xs: KL._kda_gates.raw_fn(*xs, heads),
                             cots), argnums=tuple(range(6)))(*args)
    grad_4d = jax.grad(weighted(lambda *xs: gates_4d(*xs, heads), cots_4d),
                       argnums=tuple(range(6)))(*args)
    for g, w in zip(grad, grad_4d):
        close(g, w, 4 * TOL[dtype])


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,dv", SHAPES)
def test_gated_head_norm_in_rows_is_the_head_axis_definition(heads, dv,
                                                             dtype):
    d = draw(heads + dv, heads, dv, dtype, 16)
    o = d["q"]
    got = KL._gated_head_rms_norm.raw_fn(o, d["gate"], d["weight"], 1e-5)
    want = head_norm_4d(o.reshape(2, 16, heads, dv), d["gate"], d["weight"],
                        1e-5)
    close(got, want, TOL[dtype])
    cot = jax.random.normal(jax.random.key(3), got.shape)
    grad = jax.grad(weighted(
        lambda o, g, w: KL._gated_head_rms_norm.raw_fn(o, g, w, 1e-5),
        [cot]), argnums=(0, 1, 2))(o, d["gate"], d["weight"])
    grad_4d = jax.grad(weighted(
        lambda o, g, w: head_norm_4d(o.reshape(2, 16, heads, dv), g, w, 1e-5),
        [cot]), argnums=(0, 1, 2))(o, d["gate"], d["weight"])
    for g, w in zip(grad, grad_4d):
        close(g, w, 4 * TOL[dtype])


# ------------------------------------------------- the short convolution
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("t", [40, 2], ids=["t40", "shorter_than_a_tail"])
def test_convolution_without_a_tail_is_a_zero_tail(dtype, t):
    ks = jax.random.split(jax.random.key(t), 3)
    x = jax.random.normal(ks[0], (2, t, 48)).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (48, 4))).astype(dtype)
    zeros = jnp.zeros((2, 3, 48), dtype)
    got = KL._short_conv_silu.raw_fn(x, w)
    want = conv_with_tail(x, w, zeros)
    for g, wv in zip(got, want):
        assert g.shape == wv.shape and g.dtype == wv.dtype
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(wv, np.float32))
    cots = [jax.random.normal(ks[2], g.shape) for g in got]
    grad = jax.grad(weighted(KL._short_conv_silu.raw_fn, cots),
                    argnums=(0, 1))(x, w)
    grad_tail = jax.grad(weighted(lambda x, w: conv_with_tail(x, w, zeros),
                                  cots), argnums=(0, 1))(x, w)
    for g, wv in zip(grad, grad_tail):
        close(g, wv, TOL[dtype])


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_convolution_with_a_tail_is_bit_for_bit_what_it_was(dtype):
    ks = jax.random.split(jax.random.key(9), 3)
    x = jax.random.normal(ks[0], (2, 5, 48)).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (48, 4))).astype(dtype)
    tail = jax.random.normal(ks[2], (2, 3, 48)).astype(dtype)
    for g, wv in zip(KL._short_conv_silu.raw_fn(x, w, tail),
                     conv_with_tail(x, w, tail)):
        assert g.dtype == wv.dtype
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(wv, np.float32))


# --------------------------------------------- the mixer's kernel path
WIDE_CFG = dict(hidden_size=64, rms_norm_eps=1e-5, kda_gate_rank=16,
                linear_attn_config={"kda_layers": [1], "full_attn_layers": [],
                                    "head_dim": 128, "num_heads": 2,
                                    "short_conv_kernel_size": 4},
                num_hidden_layers=1)


def sub_jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr inside its equations' parameters."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for j in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    yield from sub_jaxprs(inner)


def stream_views(jaxpr, t, wide):
    """(reshapes of a [B, T, H * d] stream to rank 4 or from it, names of
    the Pallas calls) anywhere in ``jaxpr``."""
    views, kernels = [], []
    for j in sub_jaxprs(jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                kernels.append(eqn.params["name"])
            if eqn.primitive.name != "reshape":
                continue
            shapes = (eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape)
            for a, b in (shapes, shapes[::-1]):
                if a[1:] == (t, wide) and len(b) == 4:
                    views.append(shapes)
    return views, kernels


@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_no_head_axis_view_of_a_stream_on_the_kernel_path(monkeypatch, grad):
    """With the kernels taken (steered as on a TPU: one 128-lane tile a
    head), the mixer's jaxpr reshapes no [B, T, H * d] stream to rank 4
    and none back; on the XLA path, here, it does (the oracle's view)."""
    t, wide = 64, 256
    mixer = KL.KimiDeltaAttention(KL.KimiLinearConfig(**WIDE_CFG))
    x = jax.random.normal(jax.random.key(0), (1, t, 64))

    def trace():
        # a function of its own each time: a trace is kept by function
        def forward(x):
            with paddle.no_grad():
                return mixer(paddle.to_tensor(x))._data

        fn = jax.grad(lambda x: jnp.sum(forward(x) ** 2)) if grad else forward
        return stream_views(jax.make_jaxpr(fn)(x).jaxpr, t, wide)

    views, kernels = trace()
    assert views and not kernels
    monkeypatch.setattr(kc, "supported",
                        lambda dk, dv: dk == 128 and dv % 128 == 0)
    views, kernels = trace()
    assert "kda_chunk_fwd" in kernels
    assert ("kda_chunk_bwd" in kernels) == grad
    assert not views, views


def test_the_mixer_with_a_state_is_what_the_head_axis_forms_give():
    """``forward(x, state)`` (the XLA oracle behind the rows op) against
    the same mixer with the [B, T, H, d] forms put back in its place."""
    cfg = dict(WIDE_CFG, linear_attn_config=dict(
        WIDE_CFG["linear_attn_config"], head_dim=8, num_heads=2))
    mixer = KL.KimiDeltaAttention(KL.KimiLinearConfig(**cfg))
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (2, 11, 64))
    tails = tuple(jax.random.normal(k, (2, 3, 16)) for k in ks[1:4])
    s0 = 0.1 * jax.random.normal(ks[4], (2, 2, 8, 8))
    with paddle.no_grad():
        y, (new_tails, s) = mixer(
            paddle.to_tensor(x), (tuple(paddle.to_tensor(v) for v in tails),
                                  paddle.to_tensor(s0)))

    def weight(layer):
        return layer.weight._data

    def proj(lin):
        return x @ weight(lin)

    q, k, v = (conv_with_tail(proj(p), weight(c), tl) for p, c, tl in zip(
        (mixer.q_proj, mixer.k_proj, mixer.v_proj),
        (mixer.q_conv1d, mixer.k_conv1d, mixer.v_conv1d), tails))
    f = proj(mixer.f_a_proj) @ weight(mixer.f_b_proj)
    gate = proj(mixer.g_a_proj) @ weight(mixer.g_b_proj)
    q4, k4, a4, beta = gates_4d(q[0], k[0], f, mixer.A_log._data,
                                mixer.dt_bias._data, proj(mixer.b_proj), 2)
    o, s_want = kda._kda_chunk(q4, k4, v[0].reshape(2, 11, 2, 8), a4, beta,
                               s0)
    y_want = head_norm_4d(o, gate, mixer.o_norm.weight._data, 1e-5) \
        @ weight(mixer.o_proj)
    assert rel(y._data, y_want) <= 1e-6 and rel(s._data, s_want) <= 1e-6
    for got, want in zip(new_tails, (q[1], k[1], v[1])):
        assert np.array_equal(got._data, want)
