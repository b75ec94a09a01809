"""Kimi-Linear on the CPU at a tiny size, against the plain reference
(``benchmark/reference/kimi_linear_plain.py``, float32, the delta rule
token by token): the model's logits, loss, first gradients and three
``TrainStep`` steps.  (Its parts — the chunkwise KDA op, MLA on the
flash path, the sigmoid router, the expert layer that holds a share —
are in tests/test_kimi_linear_ops.py.)

Tolerances: everything here runs in float32 on the CPU, where the
program and the reference differ only in the ORDER of float32 sums
(chunked against token by token, sorted rows against dense experts), so
values agree to about 1e-6 relative and gradients to about 1e-5; the
limits below leave a factor of ten to a hundred over what was read when
the tests were written and are still a thousand times tighter than a
bfloat16 product (4e-3)."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
from paddle_tpu.models import kimi_linear as KL  # noqa: E402
from reference import kimi_linear_plain as plain  # noqa: E402

TINY = dict(
    vocab_size=96, hidden_size=32, intermediate_size=48, num_hidden_layers=3,
    num_attention_heads=2, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-5, kv_lora_rank=16, q_lora_rank=None,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, mla_use_nope=True,
    linear_attn_config={"kda_layers": [1, 3], "full_attn_layers": [2],
                        "head_dim": 8, "num_heads": 2,
                        "short_conv_kernel_size": 4},
    first_k_dense_replace=1, moe_layer_freq=1, moe_intermediate_size=16,
    num_experts=32, num_experts_per_token=4, num_shared_experts=1,
    moe_renormalize=True, moe_router_activation_func="sigmoid",
    routed_scaling_factor=2.446, num_expert_group=1, topk_group=1,
    tie_word_embeddings=False, held_experts=(8, 8))
SEED = 2147483659


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


@pytest.fixture(autouse=True)
def exact_float32():
    with jax.default_matmul_precision("highest"):
        yield


def build(cfg=TINY, seed=SEED, **extra):
    """The program's model with the reference's float32 leaves in it."""
    model = KL.KimiLinearForCausalLM(KL.KimiLinearConfig(**cfg, **extra))
    leaves = dict(model.named_parameters())
    specs = plain.param_specs(cfg)
    assert [(n, tuple(p.shape)) for n, p in model.named_parameters()
            if p.trainable] == [(n, tuple(s)) for n, s in specs]
    for n, s in specs + plain.buffer_specs(cfg):
        leaves[n].set_value(plain.make_leaf(seed, n, s))
    return model


def batch(seed, rows=2, seq=70, vocab=TINY["vocab_size"]):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vocab, (rows, seq + 1)).astype(np.int32)
    return x[:, :-1], x[:, 1:]


# --------------------------------------------------------------- the model
def test_model_logits_match_the_reference():
    model = build()
    ids, _ = batch(0)
    got = model(paddle.to_tensor(ids))._data
    leaves = plain.all_leaves(TINY, SEED)
    want = plain.logits_jit(leaves, jnp.asarray(ids), plain._freeze(TINY))
    assert got.shape == (2, 70, 96) and rel(got, want) < 2e-5


def test_loss_and_every_leafs_first_gradient_match_the_reference():
    model = build()
    ids, labels = batch(1)
    loss = F.cross_entropy(
        model(paddle.to_tensor(ids)).reshape([-1, 96]).astype("float32"),
        paddle.to_tensor(labels).reshape([-1]))
    loss.backward()
    params = {n: plain.make_leaf(SEED, n, s)
              for n, s in plain.param_specs(TINY)}
    buffers = {n: plain.make_leaf(SEED, n, s)
               for n, s in plain.buffer_specs(TINY)}
    ref_loss, ref = plain._loss_and_grads(
        params, buffers, jnp.asarray(ids), jnp.asarray(labels),
        plain._freeze(TINY), "f32")
    assert abs(float(loss) - float(ref_loss)) < 1e-5 * float(ref_loss)
    for n, p in model.named_parameters():
        if not p.trainable:                  # the routers' selection bias
            assert p.grad is None and n not in ref
            continue
        assert p.grad is not None, n
        assert rel(p.grad._data, ref[n]) < 2e-4, n


def test_three_train_steps_match_the_reference():
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    hyper = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.01)
    model = build(recompute_mixers=True)
    opt = optim.AdamW(learning_rate=hyper["lr"], beta1=hyper["beta1"],
                      beta2=hyper["beta2"], epsilon=hyper["eps"],
                      weight_decay=hyper["weight_decay"],
                      parameters=model.parameters(), multi_precision=True)
    step = TrainStep(model, lambda lo, la: F.cross_entropy(
        lo.reshape([-1, 96]).astype("float32"), la.reshape([-1])), opt)
    batches = [batch(10 + i) for i in range(3)]
    losses = [float(np.asarray(step(paddle.to_tensor(x),
                                    paddle.to_tensor(y))._data))
              for x, y in batches]
    ref = plain.train_reference(TINY, SEED, batches, hyper)
    assert np.allclose(losses, ref["losses"], rtol=2e-5)
    step.sync()
    for n, p in model.named_parameters():
        if not p.trainable:
            continue
        s = dict(plain.param_specs(TINY))[n]
        moved = float(jnp.linalg.norm(p._data - plain.make_leaf(SEED, n, s)))
        # Adam's first steps are lr * sign-like: a leaf's change is its
        # size's root times 3e-3, whatever the gradient's scale
        assert abs(moved - ref["delta_norm"][n]) < 2e-3 * ref["delta_norm"][n], n


def test_states_carry_the_model_across_a_cut():
    """Prefill in two pieces through the mixers' stated states (KDA: conv
    tails + S; MLA: c_kv + k_pe) gives the whole sequence's logits."""
    model = build()
    model.eval()
    ids, _ = batch(3, rows=1, seq=90)
    whole = model(paddle.to_tensor(ids))._data
    states = model.empty_states(1)
    a, states = model(paddle.to_tensor(ids[:, :50]), states=states)
    b, states = model(paddle.to_tensor(ids[:, 50:]), states=states)
    assert rel(jnp.concatenate([a._data, b._data], 1), whole) < 2e-5
    kda_state, mla_state = states[0], states[1]
    assert tuple(kda_state[1].shape) == (1, 2, 8, 8)
    assert tuple(mla_state[0].shape) == (1, 90, 16)


def test_routing_counts_reach_the_monitor():
    from paddle_tpu import monitor
    model = build()
    ids, _ = batch(4)
    before = {n: monitor.counter(n).value() for n in KL.ROUTING_COUNTS}
    totals = KL.record_routing_counts(model, [ids, ids])
    moe_layers = 2
    assert totals["moe_slots_total"] == 2 * moe_layers * 2 * 70 * 4
    assert 0 < totals["moe_held_slots_total"] < totals["moe_slots_total"]
    # every token through each of the 8 held experts
    assert totals["moe_rows_computed_total"] == 2 * moe_layers * 2 * 70 * 8
    for n in KL.ROUTING_COUNTS[:-1]:
        assert monitor.counter(n).value() - before[n] == totals[n]
    assert monitor.counter(KL.ROUTING_COUNTS[-1]).value() \
        >= totals["moe_held_slots_max_per_expert"] > 0


def test_published_config_names_its_layers():
    cfg = KL.KimiLinearConfig()
    kinds = [cfg.mixer_kind(i) for i in range(27)]
    assert kinds.count("mla") == 7 and kinds[3] == kinds[26] == "mla"
    assert kinds[:3] == ["kda"] * 3
    assert [cfg.ffn_kind(i) for i in range(3)] == ["dense", "moe", "moe"]
    with pytest.raises(NotImplementedError):
        KL.KimiLinearConfig(mla_use_nope=False)
    from_file = KL.KimiLinearConfig.from_dict(
        dict(TINY, driver="x", reduced=["num_hidden_layers"]))
    assert from_file.held_experts == (8, 8) and from_file.hidden_size == 32
