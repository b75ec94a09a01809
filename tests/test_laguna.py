"""Laguna on the CPU at a tiny size (hidden 64, query heads [3, 4, 4, 4,
3] over 1 KV head of 16, 8 experts top-2 with one shared, window 8)
against the plain reference (``benchmark/reference/laguna_plain.py``,
float32, precision highest): the full forward's logits; chunked prefill
and then decoding through ``ContinuousBatchingEngine`` at contexts
several windows long, each served token's reference logit held against
the reference's best there (logits, not tokens); what the step ring and
the registry say of the expert and sliding layers; and every path with no
window refusing.

Tolerances: float32 on both sides, so the program and the reference
differ in the ORDER of float32 sums only (paged blocks against one
softmax, grouped rows against a loop over experts): logits agree to about
1e-6.  The limits leave a factor of a hundred, and each test with a limit
also shows the limit has teeth: the reference with the window off, or
with one expert fewer, misses it a hundredfold."""
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.framework.tape import no_grad  # noqa: E402
from paddle_tpu.inference.continuous import (  # noqa: E402
    ContinuousBatchingEngine)
from paddle_tpu.inference.paged import (  # noqa: E402
    JittedPagedDecoder, PagedGenerator, _tp_plan)
from paddle_tpu.models.laguna import (  # noqa: E402
    LagunaConfig, LagunaForCausalLM, rope_tables)
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache  # noqa: E402
from drivers import serve_laguna as driver  # noqa: E402
from reference import laguna_plain as plain  # noqa: E402

TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=3, num_key_value_heads=1,
    head_dim=16, num_attention_heads_per_layer=[3, 4, 4, 4, 3],
    max_position_embeddings=256, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    sliding_window=8)
SEED = 2147483659


def model_cfg():
    """The reference's view of TINY: a configuration file's keys."""
    c = LagunaConfig(**TINY)
    return plain.model_cfg({k: getattr(c, k) for k in plain.MODEL_KEYS})


@pytest.fixture(scope="module")
def model():
    """The program with the benchmark's weights for SEED, in float32."""
    m = driver.build_model(model_cfg(), SEED)
    for _, p in m.named_parameters():
        p._data = p._data.astype(jnp.float32)
    return m


class TestFullForward:
    def test_logits_match_the_reference(self, model):
        ids = np.random.default_rng(0).integers(0, 96, 50).astype(np.int32)
        with no_grad():
            got = np.asarray(model(paddle.to_tensor(ids[None]))._data[0])
        ref = np.asarray(plain.forward_logits(model_cfg(), SEED, ids))
        assert np.abs(got - ref).max() < 1e-4 * np.abs(ref).max()
        # the window, the gate and the eighth... second expert all matter
        off = np.asarray(plain.forward_logits(model_cfg(), SEED, ids,
                                              window=None))
        one = np.asarray(plain.forward_logits(model_cfg(), SEED, ids,
                                              top_k=1))
        assert np.abs(off - ref).max() > 1e-2 * np.abs(ref).max()
        assert np.abs(one - ref).max() > 1e-2 * np.abs(ref).max()

    def test_parameters_are_the_reference_s_by_name_shape_and_dtype(self):
        m = driver.build_model(model_cfg(), 3)
        bias = dict(m.named_parameters())[
            "model.layers.1.mlp.gate.e_score_correction_bias"]
        assert bias._data.dtype == jnp.float32 and not bias._data.any()
        assert dict(m.named_parameters())[
            "lm_head.weight"]._data.dtype == jnp.bfloat16
        assert paddle.get_default_dtype() == np.float32     # put back

    def test_weight_attr_reaches_every_matrix(self):
        """The initialiser a caller hands in makes the embedding, every
        projection, the experts and the head; gains, the router's weights
        and its bias keep their own."""
        class Sevens(paddle.nn.initializer.Initializer):
            def __call__(self, shape, dtype):
                return jnp.full(shape, 7, dtype)

        m = LagunaForCausalLM(LagunaConfig(**TINY), weight_attr=Sevens())
        own = ("layernorm.weight", "norm.weight", "gate.gate_weight",
               "e_score_correction_bias")
        for name, p in m.named_parameters():
            assert bool((p._data == 7).all()) != name.endswith(own), name

    def test_yarn_tables(self):
        """Channels that turn often keep their frequency, slow ones are
        divided by the factor, and both carry the attention factor."""
        p = LagunaConfig().rope_parameters["full_attention"]
        cos, sin = rope_tables(p, 128, 8)
        assert cos.shape == (8, 32)
        np.testing.assert_allclose(np.asarray(cos[0]),
                                   p["attention_factor"], rtol=1e-6)
        inv = 1.0 / 500000 ** (np.arange(0, 64, 2) / 64)
        np.testing.assert_allclose(
            np.asarray(sin[1, 0]), np.sin(inv[0]) * p["attention_factor"],
            rtol=1e-5)
        np.testing.assert_allclose(
            np.asarray(sin[1, -1]),
            np.sin(inv[-1] / 64) * p["attention_factor"], rtol=1e-5)
        ref_cos, ref_sin = plain.rope_tables(p, 128, 8)
        np.testing.assert_allclose(np.asarray(cos), np.asarray(ref_cos),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(sin), np.asarray(ref_sin),
                                   rtol=1e-6, atol=1e-7)


class TestServedThroughTheEngine:
    @pytest.fixture(scope="class")
    def served(self, model):
        """8 requests, prompts 3 to 8 windows long, chunked 16 tokens a
        step under a decode batch of up to 4; the ring captured."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n in (25, 64, 33, 41, 50, 27, 61, 38)]
        engine = ContinuousBatchingEngine(
            model, total_pages=64, page_size=16, max_batch=4,
            prefill_chunk_tokens=16, min_table_pages=8)
        before = monitor.snapshot()
        monitor.start_capture(max_requests=64, max_steps=4096,
                              host_events=False)
        try:
            reqs = [engine.submit(p, max_new_tokens=12) for p in prompts]
            outs = [r.result(timeout=600) for r in reqs]
        finally:
            # the engine first: a step's ``dispatch`` record is written
            # when the iteration that committed it ends
            engine.stop()
            monitor.stop_capture()
        steps = monitor.get_tracer().step_records()
        seqs = [(p, np.asarray(o[len(p):], np.int32))
                for p, o in zip(prompts, outs)]
        return seqs, steps, before, monitor.snapshot()

    def test_served_logits_match_the_reference_s_full_forward(self, served):
        seqs, *_ = served
        assert all(len(s) == 12 for _, s in seqs)
        gaps, _, _ = plain.served_gaps(model_cfg(), SEED, seqs)
        assert np.concatenate(gaps).max() < 1e-4
        for switch in ({"window": None}, {"top_k": 1}):
            off, _, _ = plain.served_gaps(model_cfg(), SEED, seqs, **switch)
            assert np.concatenate(off).max() > 1e-2, switch

    def test_the_ring_counts_experts_and_windows(self, served):
        _, steps, *_ = served
        recs = [r for r in steps if r["kind"] == "dispatch"]
        assert recs
        for r in recs:
            # 2 experts a token in each of the 4 sparse layers
            assert r["moe_slots"] == r["tokens"] * 2 * 4
            assert r["moe_expert_layers"] == 8 * 4
            assert 0 < r["moe_experts_touched"] <= min(
                r["moe_expert_layers"], r["moe_slots"])
            assert r["moe_slots"] <= r["moe_rows_computed"] \
                <= r["moe_slots"] + r["moe_experts_touched"] * 15
            assert r["ctx_tokens_window"] <= r["ctx_tokens"] \
                <= r["kv_tokens_walked"]
            assert r["kv_tokens_walked_window"] \
                <= r["kv_tokens_walked_nowindow"]
        # a row 3 windows deep: its sliding layers see 8 + span - 1 keys
        deep = max(recs, key=lambda r: r["ctx_tokens"])
        assert deep["ctx_tokens_window"] < deep["ctx_tokens"]
        assert max(r["kv_window_dead_pages"] for r in recs) >= 2

    def test_the_registry_sums_what_the_ring_says(self, served):
        _, steps, before, after = served

        def total(snap, name):
            return sum(s["value"] for s in
                       snap.get(name, {"series": []})["series"])

        recs = [r for r in steps if r["kind"] == "dispatch"]
        for name in ("moe_slots", "moe_rows_computed", "moe_experts_touched",
                     "moe_expert_layers", "kv_tokens_walked_window",
                     "kv_tokens_walked_nowindow", "ctx_tokens_window"):
            counter = f"serve_{name}_total"
            assert total(after, counter) - total(before, counter) \
                == sum(r[name] for r in recs), name
        assert "kv_window_dead_pages" in after


class TestPathsWithNoWindowRefuse:
    def test_from_model_takes_the_explicit_head_dim(self, model):
        cache = PagedKVCache.from_model(model, total_pages=4, page_size=16)
        assert cache.head_dim == 16 != 64 // 3
        assert cache.k_pages[0].shape == (1, 4, 16, 16)
        assert len(cache.k_pages) == 5

    def test_eager_generator_prefill(self, model):
        gen = PagedGenerator(model, total_pages=8, page_size=16)
        with pytest.raises(NotImplementedError, match="window"):
            gen.generate(np.arange(12, dtype=np.int32)[None],
                         max_new_tokens=2)

    @pytest.mark.parametrize("path", ["prefill", "chunk_prefill",
                                      "batch_context_prefill"])
    def test_compiled_prefill_modes(self, model, path):
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16)
        dec = JittedPagedDecoder(model)
        ids = np.arange(12, dtype=np.int32)[None]
        with pytest.raises(NotImplementedError, match="window"):
            if path == "prefill":
                dec.prefill(cache, [0], ids)
            elif path == "chunk_prefill":
                cache.allocate(0, 16)
                cache.advance([0], 16)
                dec.chunk_prefill(cache, [0], ids, 16)
            else:
                dec.batch_context_prefill(cache, [0], [ids[0]], [0])
        assert cache.length(0) in (0, 16)               # rolled back

    def test_the_decode_step_applies_it(self, model):
        """The non-ragged decode program is windowed, not refused: one
        token a step after a ragged prefill agrees with the reference."""
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16)
        dec = JittedPagedDecoder(model)
        ids = np.random.default_rng(2).integers(0, 96, 30).astype(np.int32)
        dec.ragged_step(cache, [0], [ids[:29]], [0])
        logits = dec.step(cache, [0], ids[29:30][None],
                          np.asarray([29], np.int32))
        ref = np.asarray(plain.forward_logits(model_cfg(), SEED, ids))[-1]
        assert np.abs(np.asarray(logits)[0] - ref).max() < 1e-4

    def test_tp_plan_names_what_it_lacks(self, model):
        class Mesh:
            size = 2
        with pytest.raises(ValueError, match="g_proj"):
            _tp_plan(model, Mesh())
        # past the gate and the dense first layer: the expert block
        plain_heads = LagunaForCausalLM(LagunaConfig(**dict(
            TINY, num_hidden_layers=2, gating=False, num_key_value_heads=2,
            num_attention_heads_per_layer=[2, 2])))
        with pytest.raises(ValueError, match="layer 1 .*expert block"):
            _tp_plan(plain_heads, Mesh())
