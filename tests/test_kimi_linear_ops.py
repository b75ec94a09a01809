"""The parts of Kimi-Linear on the CPU, against the plain reference: the
chunkwise KDA op, MLA on the flash path, the sigmoid router, the expert
layer that holds a share.  The model itself, and what the tolerances
mean: tests/test_kimi_linear.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# TINY, the reference on sys.path, and the autouse float32 fixture
from test_kimi_linear import (  # noqa: F401
    TINY, exact_float32, plain, rel)
import paddle_tpu as paddle
from paddle_tpu.incubate.distributed.models.moe import (
    MoELayer, SigmoidTopKGate, SwiGLUExperts, shard_moe_layer)
from paddle_tpu.models import kimi_linear as KL
from paddle_tpu.ops import kda
from paddle_tpu.ops.pallas import flash_attention as fa


# ------------------------------------------------------------- the KDA op
def kda_inputs(seed, t, decay, b=2, h=3, dk=32, dv=16):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    a = -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = 0.1 * jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, a, beta, s0


@pytest.mark.parametrize("t,decay", [
    (64, 1.0),       # one whole chunk
    (200, 0.05),     # weak decay, not whole chunks, two sub-blocks short
    (130, 30.0),     # A_log large: a chunk decays by e^-1000, exp(-g) = inf
    (37, 1.0),       # less than a chunk
    (1100, 0.3),     # more than two segments of 8 chunks
])
def test_chunkwise_kda_matches_the_recurrence(t, decay):
    args = kda_inputs(t, t, decay)
    o, s = jax.jit(kda._kda_chunk)(*args)
    o_ref, s_ref = jax.jit(kda._kda_recurrent)(*args)
    assert np.isfinite(np.asarray(o)).all()
    assert rel(o, o_ref) < 2e-5 and rel(s, s_ref) < 2e-5

    def loss(fn):
        def f(*xs):
            out, state = fn(*xs)
            return jnp.sum(jnp.sin(out)) + jnp.sum(state * state)
        return jax.jit(jax.grad(f, argnums=tuple(range(6))))

    for got, want in zip(loss(kda._kda_chunk)(*args),
                         loss(kda._kda_recurrent)(*args)):
        assert np.isfinite(np.asarray(got)).all()
        # the log-decay's gradient sums thousands of terms of both signs
        assert rel(got, want) < 5e-4


@pytest.mark.parametrize("noise,shift", [(0.1, 2.0), (0.0, 6.0)])
def test_chunkwise_kda_with_nearly_parallel_keys(noise, shift):
    """What one hot optimizer step does to a wide model: every key of a
    chunk points the same way and beta is near 1.  (I + A)^-1 as a power
    series loses every digit there (terms of 1e16 that cancel); forward
    substitution does not."""
    q, k, v, a, beta, s0 = kda_inputs(11, 256, 0.001, b=1, h=2)
    base = jax.random.normal(jax.random.key(5), (1, 1, 2, 32))
    k = base + noise * k * 32 ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(shift + beta)
    args = (k * 32 ** -0.5, k, v, a, beta, s0)
    o, s = jax.jit(kda._kda_chunk)(*args)
    o_ref, s_ref = jax.jit(kda._kda_recurrent)(*args)
    assert rel(o, o_ref) < 2e-5 and rel(s, s_ref) < 2e-5
    grad = lambda fn: jax.jit(jax.grad(  # noqa: E731
        lambda *xs: jnp.sum(jnp.sin(fn(*xs)[0])), argnums=(1, 2, 4)))
    for got, want in zip(grad(kda._kda_chunk)(*args),
                         grad(kda._kda_recurrent)(*args)):
        assert rel(got, want) < 5e-4


def test_kda_op_agrees_with_the_references_own_recurrence():
    q, k, v, a, beta, _ = kda_inputs(5, 150, 0.5, b=1)
    o, _ = kda.kda_chunk(*(paddle.to_tensor(x) for x in (q, k, v, a, beta)))
    want = plain.delta_rule(q[0], k[0], v[0], a[0], beta[0])
    assert rel(o._data[0], want) < 2e-5


def test_kda_state_carries_across_a_cut():
    q, k, v, a, beta, s0 = kda_inputs(9, 190, 0.2)
    whole, s_end = kda._kda_chunk(q, k, v, a, beta, s0)
    cut = 77
    first, s_mid = kda._kda_chunk(*(x[:, :cut] for x in (q, k, v, a, beta)), s0)
    rest, s_last = kda._kda_chunk(*(x[:, cut:] for x in (q, k, v, a, beta)),
                                  s_mid)
    assert rel(jnp.concatenate([first, rest], 1), whole) < 2e-5
    assert rel(s_last, s_end) < 2e-5


# -------------------------------------------------------------------- MLA
def dense_attention(q, k, v, scale):
    """q, k (b, s, h, d), v (b, s, h, dv): plain causal softmax."""
    s = q.shape[1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)


def test_mla_attention_matches_dense_softmax():
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (2, 50, 2, 12))
    kn = jax.random.normal(ks[1], (2, 50, 2, 8))
    kpe = jax.random.normal(ks[2], (2, 50, 4))
    v = jax.random.normal(ks[3], (2, 50, 2, 8))
    scale = 12 ** -0.5

    def want(q, kn, kpe, v):
        k = jnp.concatenate(
            [kn, jnp.broadcast_to(kpe[:, :, None], (2, 50, 2, 4))], -1)
        return dense_attention(q, k, v, scale)

    got_fn = lambda *xs: KL._mla_attention.raw_fn(*xs, scale)  # noqa: E731
    assert rel(got_fn(q, kn, kpe, v), want(q, kn, kpe, v)) < 1e-5
    g = jax.grad(lambda *xs: jnp.sum(jnp.cos(got_fn(*xs))), (0, 1, 2, 3))
    w = jax.grad(lambda *xs: jnp.sum(jnp.cos(want(*xs))), (0, 1, 2, 3))
    for a, b in zip(g(q, kn, kpe, v), w(q, kn, kpe, v)):
        assert rel(a, b) < 1e-4


def test_flash_kernels_take_a_value_width_of_their_own():
    """The Pallas kernels, interpreted: 24-wide scores, 8-wide values."""
    ks = jax.random.split(jax.random.key(4), 4)
    q = jax.random.normal(ks[0], (1, 2, 160, 24))
    k = jax.random.normal(ks[1], (1, 2, 160, 24))
    v = jax.random.normal(ks[2], (1, 2, 160, 8))
    do = jax.random.normal(ks[3], (1, 2, 160, 8))
    scale = 24 ** -0.5
    out, lse = fa.flash_attention_forward(q, k, v, True, scale,
                                          block_q=128, block_kv=128,
                                          interpret=True)
    want, vjp = jax.vjp(
        lambda q, k, v: fa.mha_reference(q, k, v, causal=True, scale=scale),
        q, k, v)
    assert out.shape == (1, 2, 160, 8) and rel(out, want) < 1e-5
    got = fa.flash_attention_backward(q, k, v, out, lse, do, True, scale,
                                      block_q=128, block_kv=128,
                                      interpret=True)
    for a, b in zip(got, vjp(do)):
        assert a.shape == b.shape and rel(a, b) < 1e-4


# ------------------------------------------------------ router and experts
def moe_layer(held, seed=7, d=32, width=16, experts=32, topk=4):
    first, count = held
    paddle.seed(seed)
    gate = SigmoidTopKGate(d, experts, 1, topk=topk, renormalize=True,
                           routed_scaling_factor=2.446)
    return MoELayer(d, SwiGLUExperts(count, d, width), gate=gate,
                    held_experts=held,
                    shared_expert=KL.KimiMLP(d, width))


def test_router_choice_and_weights_match_the_reference():
    layer = moe_layer((0, 32))
    rng = np.random.default_rng(0)
    layer.gate.e_score_correction_bias.set_value(
        jnp.asarray(rng.normal(0, 0.05, 32), jnp.float32))
    x = jnp.asarray(rng.normal(0, 1, (200, 32)), jnp.float32)
    idx, w = layer.gate.route_no_drop(paddle.to_tensor(x))
    cfg = dict(TINY, num_experts=32, num_experts_per_token=4)
    ref_idx, ref_w = plain.route(
        x, {"gate.gate_weight": layer.gate.gate_weight._data,
            "gate.e_score_correction_bias":
                layer.gate.e_score_correction_bias._data}, cfg, "f32")
    assert np.array_equal(np.sort(np.asarray(idx._data), -1),
                          np.sort(np.asarray(ref_idx), -1))
    order, ref_order = np.argsort(idx._data, -1), np.argsort(ref_idx, -1)
    assert rel(np.take_along_axis(np.asarray(w._data), order, -1),
               np.take_along_axis(np.asarray(ref_w), ref_order, -1)) < 1e-6
    # the weights are the chosen scores over their sum, times 2.446
    assert np.allclose(np.asarray(w._data).sum(-1), 2.446, rtol=1e-5)


def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts over 4 shares of 8: the four partial results, with the
    shared expert counted once, are the whole layer's output."""
    whole = moe_layer((0, 32))
    x = paddle.to_tensor(np.random.default_rng(1).normal(
        0, 1, (3, 50, 32)).astype(np.float32))
    want = whole(x)._data
    shared = whole.shared_expert(x)._data
    total = jnp.zeros_like(want)
    for r in range(4):
        part = moe_layer((8 * r, 8))
        part.gate.gate_weight.set_value(whole.gate.gate_weight._data)
        part.shared_expert.set_state_dict(whole.shared_expert.state_dict())
        for n in ("gate_proj", "up_proj", "down_proj"):
            getattr(part.experts, n).set_value(
                getattr(whole.experts, n)._data[8 * r:8 * r + 8])
        total = total + part(x)._data - shared
        slots, held, rows, *_ = np.asarray(part.last_routing._data)
        assert slots == 3 * 50 * 4 and rows == 3 * 50 * 8 and 0 < held < slots
    assert rel(total + shared, want) < 1e-5
    # and the uncut layer is the reference's dense sum over its experts
    cfg = dict(TINY, num_experts=32, num_experts_per_token=4,
               held_experts=(0, 32))
    w = {"gate.gate_weight": whole.gate.gate_weight._data,
         "gate.e_score_correction_bias":
             whole.gate.e_score_correction_bias._data,
         **{f"experts.{n}": getattr(whole.experts, n)._data
            for n in ("gate_proj", "up_proj", "down_proj")},
         **{f"shared_expert.{n}.weight":
            getattr(whole.shared_expert, n).weight._data
            for n in ("gate_proj", "up_proj", "down_proj")}}
    ref = plain.moe_ffn(x._data.reshape(-1, 32), w, cfg, "f32")
    assert rel(want.reshape(-1, 32), ref) < 1e-5


def test_a_share_computes_every_slot_when_the_router_collapses():
    """A selection bias that sends EVERY token to the same four experts,
    all of them held: nothing is dropped, the share is the reference's
    sum over all the slots, and so is its gradient."""
    x = np.random.default_rng(2).normal(0, 1, (256, 32)).astype(np.float32)
    layer = moe_layer((8, 8))
    bias = np.zeros(32, np.float32)
    bias[[8, 10, 11, 15]] = 10.0
    layer.gate.e_score_correction_bias.set_value(jnp.asarray(bias))
    xt = paddle.to_tensor(x, stop_gradient=False)
    y = layer(xt)
    slots, held, rows, most, touched = np.asarray(layer.last_routing._data)
    assert (slots, held, rows, most, touched) == (1024, 1024, 2048, 256, 4)
    cfg = dict(TINY, num_experts=32, num_experts_per_token=4,
               held_experts=(8, 8))
    w = {"gate.gate_weight": layer.gate.gate_weight._data,
         "gate.e_score_correction_bias": jnp.asarray(bias),
         **{f"experts.{n}": getattr(layer.experts, n)._data
            for n in ("gate_proj", "up_proj", "down_proj")},
         **{f"shared_expert.{n}.weight":
            getattr(layer.shared_expert, n).weight._data
            for n in ("gate_proj", "up_proj", "down_proj")}}
    assert rel(y._data, plain.moe_ffn(jnp.asarray(x), w, cfg, "f32")) < 1e-5
    y.sum().backward()
    want = jax.grad(lambda a: jnp.sum(plain.moe_ffn(a, w, cfg, "f32")))(
        jnp.asarray(x))
    assert rel(xt.grad._data, want) < 1e-4


def test_share_contract_is_stated_and_a_share_is_not_sharded():
    gate = SigmoidTopKGate(32, 32, 1, topk=4)
    with pytest.raises(AssertionError, match="held_experts=\\(first, count\\)"):
        MoELayer(32, SwiGLUExperts(8, 32, 16), gate=gate)
    with pytest.raises(AssertionError, match="inside the 32"):
        MoELayer(32, SwiGLUExperts(8, 32, 16), gate=gate,
                 held_experts=(28, 8))
    from paddle_tpu.distributed.auto_parallel.process_mesh import ProcessMesh
    mesh = ProcessMesh(np.arange(2), ["ep"])
    with pytest.raises(ValueError, match="already holds a share"):
        shard_moe_layer(moe_layer((8, 8)), mesh)
