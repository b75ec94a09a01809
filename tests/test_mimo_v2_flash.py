"""MiMo-V2-Flash on the CPU at a tiny size (hidden 64, 8 query heads over 2
KV heads in a full layer and 4 in a sliding one, K heads of 24 beside V
heads of 16, window 8, a sink a sliding query head, 4 of 16 experts held at
top-2) against the plain reference
(``benchmark/reference/mimo_v2_flash_plain.py``, float32, precision
highest): the full forward's logits; chunked prefill and then decoding
through ``ContinuousBatchingEngine`` at contexts several windows long, each
served token's reference logit held against the reference's best there
(logits, not tokens); the same at the PUBLISHED head widths (192 beside 128:
two K heads a row of the pool, logits through the ragged and the decode
programs); what the step ring and the registry say of
the pools' bytes; the shares of one expert layer adding up to the uncut
layer; and every path that cannot take the model refusing in words.

Tolerances: float32 on both sides, so the program and the reference differ
in the ORDER of float32 sums only (paged blocks against one softmax, a dense
product against a loop over experts): logits agree to about 1e-6.  The
limits leave a factor of a hundred, and each test with a limit also shows
the limit has teeth: the reference with the sink off, K cut to V's width,
half the sliding KV heads or the value scale off misses it a hundredfold."""
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
    JittedPagedDecoder, PagedGenerator)
from paddle_tpu.models.mimo_v2_flash import (  # noqa: E402
    MiMoV2FlashConfig, _moe_block)
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    PagedKVCache, k_pack, paged_layout)
import flops_mimo  # noqa: E402
from drivers import serve_mimo as driver  # noqa: E402
from reference import mimo_v2_flash_plain as plain  # noqa: E402

TINY = dict(
    vocab_size=96, hidden_size=64, intermediate_size=128,
    num_hidden_layers=7, num_attention_heads=8, num_key_value_heads=2,
    head_dim=24, v_head_dim=16, swa_num_attention_heads=8,
    swa_num_key_value_heads=4, swa_head_dim=24, swa_v_head_dim=16,
    max_position_embeddings=256, sliding_window=8, sliding_window_size=8,
    attention_chunk_size=8, moe_intermediate_size=32, n_routed_experts=16,
    num_experts_per_tok=2)
#: the published head widths over a narrow stream: two K heads a pool row
WIDE = dict(TINY, num_hidden_layers=3, num_attention_heads=4,
            swa_num_attention_heads=4, head_dim=192, swa_head_dim=192,
            v_head_dim=128, swa_v_head_dim=128,
            hybrid_layer_pattern=[0, 1, 0], moe_layer_freq=[0, 1, 1])
SEED = 2147483659


def model_cfg(sizes=TINY, held=(4, 4)):
    """The reference's view of ``sizes``: a configuration file's keys, of
    whose 16 experts this rank holds ``held``."""
    c = MiMoV2FlashConfig(**sizes)
    file = {k: getattr(c, k) for k in plain.MODEL_KEYS}
    file.update(n_routed_experts=held[1], held_experts_first=held[0],
                routed_experts_published=sizes["n_routed_experts"])
    return plain.model_cfg(file)


def build(sizes=TINY):
    """The program with the benchmark's weights for SEED, in float32."""
    m = driver.build_model(model_cfg(sizes), SEED)
    for _, p in m.named_parameters():
        p._data = p._data.astype(jnp.float32)
    return m


@pytest.fixture(scope="module")
def model():
    return build()


def serve(model, prompts, new=12, **engine):
    opts = dict(total_pages=64, page_size=16, max_batch=4,
                prefill_chunk_tokens=16, min_table_pages=8)
    eng = ContinuousBatchingEngine(model, **dict(opts, **engine))
    try:
        reqs = [eng.submit(p, max_new_tokens=new) for p in prompts]
        outs = [r.result(timeout=600) for r in reqs]
    finally:
        eng.stop()
    return [(p, np.asarray(o[len(p):], np.int32))
            for p, o in zip(prompts, outs)]


class TestFullForward:
    def test_logits_match_the_reference(self, model):
        ids = np.random.default_rng(0).integers(0, 96, 40).astype(np.int32)
        with no_grad():
            got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
        ref = np.asarray(plain.forward_logits(model_cfg(), SEED, ids))
        assert np.abs(got - ref).max() < 1e-5 < 0.1 < np.abs(ref).max()

    @pytest.mark.parametrize("window", [None, 8, 128])
    def test_the_reference_s_blocks_of_queries_are_one_attention(self,
                                                                 window):
        """The reference holds a block of queries against the keys it can
        reach only (with a window: the block's own and the window's worth
        before them): three blocks of 128 are one block of 384."""
        rng = np.random.default_rng(4)
        q, k, v = (jnp.asarray(rng.normal(size=(384, h, d)), jnp.float32)
                   for h, d in ((4, 24), (2, 24), (2, 16)))
        sinks = jnp.asarray(rng.normal(size=4), jnp.float32)
        whole, took = plain.attention(q, k, v, sinks, window, 384)
        parts, took_parts = plain.attention(q, k, v, sinks, window, 128)
        assert np.abs(np.asarray(whole - parts)).max() < 1e-5
        assert np.abs(np.asarray(took - took_parts)).max() < 1e-5
        assert 0 < float(took.min()) and float(took.max()) < 1

    def test_what_the_engine_reads_of_the_model(self, model):
        layout = paged_layout(model)
        assert layout["pools"] == 7 and layout["state"] is None
        assert layout["kv_heads"] is None and layout["head_dim"] is None
        assert layout["pool_shapes"] == [
            (2, 24, 16) if i in (0, 5) else (4, 24, 16) for i in range(7)]
        assert layout["calls"] == [
            (8, None if i in (0, 5) else 8, i, False) for i in range(7)]
        assert layout["sinks"] == [i not in (0, 5) for i in range(7)]
        cache = PagedKVCache.from_model(model, total_pages=4, page_size=16)
        assert [a.shape for a in cache.k_pages[:2]] \
            == [(2, 4, 16, 24), (4, 4, 16, 24)]
        assert [a.shape for a in cache.v_pages[:2]] \
            == [(2, 4, 16, 16), (4, 4, 16, 16)]
        assert [cache.page_bytes(p) for p in (0, 1)] \
            == [2 * 16 * 40 * 4, 4 * 16 * 40 * 4]
        assert cache.kv_pool_bytes == 4 * sum(
            cache.page_bytes(p) for p in range(7))

    def test_the_published_widths_count_4_52_billion(self):
        file = __import__("json").loads(
            (ROOT / "benchmark/configs/mimo-v2-flash.serve-ep16-d7.json")
            .read_text())
        cfg = plain.model_cfg(file)
        leaves = sum(int(np.prod(s)) for _, s in plain.param_specs(cfg))
        small = leaves - flops_mimo.model_params(cfg)
        assert leaves == 4_523_620_160
        # gains (15 x 4,096), sinks (5 x 64), selection biases (6 x 256)
        assert small == 15 * 4096 + 5 * 64 + 6 * 256
        assert flops_mimo.expert_params(cfg) * 2 == 50_331_648
        assert [flops_mimo.kv_bytes_per_token(cfg, i) for i in (0, 1)] \
            == [2560, 5120]
        assert flops_mimo.kv_bytes_per_token_all(cfg) == 30720
        assert k_pack(192) == 2 and k_pack(128) == k_pack(64) == 1

    @pytest.mark.parametrize("kw,lacks", [
        ({"n_shared_experts": 1}, "a shared expert"),
        ({"scoring_func": "softmax"}, "no sigmoid"),
        ({"attention_chunk_size": 64}, "attention chunk"),
        ({"swa_head_dim": 32}, "head widths"),
        ({"tie_word_embeddings": True}, "a tied head")])
    def test_a_config_the_model_is_not(self, kw, lacks):
        with pytest.raises(NotImplementedError, match=lacks):
            MiMoV2FlashConfig(**dict(TINY, **kw))


class TestServedThroughTheEngine:
    @pytest.fixture(scope="class")
    def served(self, model):
        """8 requests, prompts 3 to 8 windows long, chunked 16 tokens a
        step under a decode batch of up to 4; the ring captured."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n in (25, 64, 33, 41, 50, 27, 61, 38)]
        before = monitor.snapshot()
        monitor.start_capture(max_requests=64, max_steps=4096,
                              host_events=False)
        try:
            seqs = serve(model, prompts)    # stops the engine first
        finally:
            monitor.stop_capture()
        return (seqs, monitor.get_tracer().step_records(), before,
                monitor.snapshot())

    def test_served_logits_match_the_reference_s_full_forward(self, served):
        seqs, *_ = served
        assert all(len(s) == 12 for _, s in seqs)
        gaps, _, _ = plain.served_gaps(model_cfg(), SEED, seqs)
        assert np.concatenate(gaps).max() < 1e-4

    @pytest.mark.parametrize("what,switch,over", [
        ("the sink", {"sink": False}, 100),
        ("the 192 / 128 split", {"k_as_wide_as_v": True}, 10),
        ("8 against 4 KV heads", {"swa_kv_heads": 2}, 100),
        ("the value scale", {"value_scale": False}, 100),
        ("the window", {"window": 7}, 100),
        ("top-k", {"top_k": 1}, 100)])
    def test_the_tolerance_catches(self, served, what, switch, over):
        """The reference with ONE mechanism otherwise than the program's
        misses the limit ``over``-fold: K cut to V's width (the scores over
        a head's first 16 of 24 channels, which hold the 8 rotated ones:
        tenfold), a sliding layer's first 2 KV heads serving all 8 query
        heads, and so on."""
        seqs, *_ = served
        off, _, _ = plain.served_gaps(model_cfg(), SEED, seqs, **switch)
        assert np.concatenate(off).max() > over * 1e-4, what

    def test_the_ring_counts_pools_of_unequal_bytes(self, served):
        _, steps, *_ = served
        recs = [r for r in steps if r["kind"] == "dispatch"]
        assert recs
        full, sliding = 2 * 16 * 40 * 4, 4 * 16 * 40 * 4    # bytes a page
        for r in recs:
            # 2 experts a token in each of the 6 sparse layers, 4 held
            assert r["moe_slots"] == r["tokens"] * 2 * 6
            assert r["moe_expert_layers"] == 4 * 6
            assert 0 <= r["moe_experts_touched"] <= 24
            assert r["moe_max_expert_pairs"] <= r["moe_slots"]
            assert r["kv_pinned_bytes"] % (2 * full + 5 * sliding) == 0
            assert r["kv_dead_bytes"] % (5 * sliding) == 0
            assert r["kv_dead_bytes"] == r["kv_window_dead_pages"] * 5 \
                * sliding
            assert 0 <= r["kv_dead_bytes"] < r["kv_pinned_bytes"]
            assert r["kv_bytes_copied_full"] % (2 * full) == 0
            assert r["kv_bytes_copied_sliding"] % (5 * sliding) == 0
            # a sliding call copies at most two pages a row (window 8,
            # spans up to 16): fewer than a full call at these contexts
            assert r["kv_bytes_copied_sliding"] / (5 * sliding) \
                <= r["kv_bytes_copied_full"] / (2 * full)
        deep = max(recs, key=lambda r: r["ctx_tokens"])
        assert deep["kv_dead_bytes"] > 0

    def test_the_registry_sums_what_the_ring_says(self, served):
        _, steps, before, after = served

        def total(snap, name, **labels):
            return sum(s["value"] for s in
                       snap.get(name, {"series": []})["series"]
                       if all(s["labels"].get(k) == v
                              for k, v in labels.items()))

        recs = [r for r in steps if r["kind"] == "dispatch"]
        for name in ("kv_pinned_bytes", "kv_dead_bytes", "moe_slots",
                     "moe_experts_touched", "moe_max_expert_pairs"):
            counter = f"serve_{name}_total"
            assert total(after, counter) - total(before, counter) \
                == sum(r[name] for r in recs), name
        for kind in ("full", "sliding"):
            name = "serve_kv_bytes_copied_total"
            assert total(after, name, kind=kind) \
                - total(before, name, kind=kind) \
                == sum(r[f"kv_bytes_copied_{kind}"] for r in recs), kind

    def test_the_published_head_widths_two_k_heads_a_row(self):
        """K heads of 192 lie two to a row of 384 in the pool, beside V
        pages of 128; the program's logits and what the engine serves are
        the reference's."""
        wide = build(WIDE)
        cache = PagedKVCache.from_model(wide, total_pages=4, page_size=16)
        assert [a.shape for a in cache.k_pages] == [
            (1, 4, 16, 384), (2, 4, 16, 384), (1, 4, 16, 384)]
        assert [a.shape for a in cache.v_pages] == [
            (2, 4, 16, 128), (4, 4, 16, 128), (2, 4, 16, 128)]
        assert cache.page_bytes(1) == 4 * 16 * 320 * 4
        # chunks of 16 through the ragged program, then one decode step:
        # the logits, against the reference's full forward
        dec = JittedPagedDecoder(wide)
        ids = np.random.default_rng(3).integers(0, 96, 41).astype(np.int32)
        for k in (0, 16, 32):
            dec.ragged_step(cache, [0], [ids[k:min(k + 16, 40)]], [k])
        logits = np.asarray(dec.step(cache, [0], ids[40:41][None],
                                     np.asarray([40], np.int32)))[0]
        cfg = model_cfg(WIDE)
        ref = np.asarray(plain.forward_logits(cfg, SEED, ids))[-1]
        assert np.abs(logits - ref).max() < 1e-4
        off = np.asarray(plain.forward_logits(cfg, SEED, ids,
                                              k_as_wide_as_v=True))[-1]
        assert np.abs(logits - off).max() > 1e-3


class TestTheSharesAddUp:
    @pytest.mark.parametrize("count", [16, 4, 2])
    def test_the_shares_add_up_to_the_uncut_layer(self, count):
        """16 experts over 16 / count shares ``(count r, count)`` of one
        expert layer (no shared expert: nothing is counted twice): the
        partial results are the whole layer's output, and the whole layer
        is the reference's sum over its experts."""
        c = MiMoV2FlashConfig(**TINY)
        whole = _moe_block(c, None)
        rng = np.random.default_rng(1)
        for p in whole.parameters():
            if p.shape != [16]:                     # the zero bias stays
                p.set_value(jnp.asarray(
                    rng.normal(0, 0.2, p.shape), jnp.float32))
        x = paddle.to_tensor(rng.normal(0, 1, (50, 64)).astype(np.float32))
        want = whole(x)._data
        total = jnp.zeros_like(want)
        for r in range(16 // count):
            c.held_experts = (count * r, count)
            part = _moe_block(c, None)
            part.gate.gate_weight.set_value(whole.gate.gate_weight._data)
            for n in ("gate_proj", "up_proj", "down_proj"):
                getattr(part.experts, n).set_value(getattr(
                    whole.experts, n)._data[count * r:count * (r + 1)])
            total = total + part(x)._data
            slots, held, *_ = np.asarray(part.last_routing._data)
            assert slots == 50 * 2 and 0 <= held <= slots
        assert np.abs(total - want).max() < 1e-5 * np.abs(want).max()
        w = {"mlp.gate.gate_weight": whole.gate.gate_weight._data,
             "mlp.gate.e_score_correction_bias":
                 whole.gate.e_score_correction_bias._data,
             **{f"mlp.experts.{n}": getattr(whole.experts, n)._data
                for n in ("gate_proj", "up_proj", "down_proj")}}
        idx, wts = plain.route(x._data, w, 2, 1.0)
        ref = plain.experts(x._data, idx, wts, w, 0)
        assert np.abs(ref - want).max() < 1e-5 * np.abs(want).max()


class TestWhatCannotTakeTheModelRefuses:
    @pytest.mark.parametrize("kw,reason", [
        ({"kv_quant": "int8"}, "kv_quant='int8'.*pools differ in shape"),
        ({"tp": 2}, "expert block|pools differ in shape"),
        ({"draft_model": "itself"}, "draft_model.*pools differ in shape"),
        ({"prefill_chunk_tokens": None},
         "prefill_chunk_tokens=None.*unequal")])
    def test_at_construction(self, model, kw, reason):
        if kw.get("draft_model"):
            kw = {"draft_model": model}
        opts = dict(total_pages=16, page_size=16, max_batch=2,
                    prefill_chunk_tokens=16)
        with pytest.raises(ValueError, match=reason):
            ContinuousBatchingEngine(model, **dict(opts, **kw))

    def test_a_cache_of_unlike_pools_in_int8(self):
        with pytest.raises(ValueError, match="pools differ in shape"):
            PagedKVCache(2, None, None, total_pages=4, kv_dtype="int8",
                         pool_shapes=[(2, 24, 16), (4, 24, 16)])

    def test_the_paged_generator(self, model):
        gen = PagedGenerator(model, total_pages=8, page_size=16)
        with pytest.raises(NotImplementedError, match="wider than its V"):
            gen.generate(np.arange(12, dtype=np.int32)[None],
                         max_new_tokens=2)

    @pytest.mark.parametrize("path", ["prefill", "chunk_prefill",
                                      "batch_context_prefill"])
    def test_the_whole_prompt_prefill_programs(self, model, path):
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16)
        dec = JittedPagedDecoder(model)
        ids = np.arange(12, dtype=np.int32)[None]
        with pytest.raises(NotImplementedError, match="wider than its V"):
            if path == "prefill":
                dec.prefill(cache, [0], ids)
            elif path == "chunk_prefill":
                cache.allocate(0, 16)
                cache.advance([0], 16)
                dec.chunk_prefill(cache, [0], ids, 16)
            else:
                dec.batch_context_prefill(cache, [0], [ids[0]], [0])
        assert cache.length(0) in (0, 16)               # rolled back

    def test_the_decode_step_takes_it(self, model):
        """The non-ragged decode program takes sinks and unequal widths:
        one token a step after a ragged prefill agrees with the
        reference."""
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16)
        dec = JittedPagedDecoder(model)
        ids = np.random.default_rng(2).integers(0, 96, 30).astype(np.int32)
        dec.ragged_step(cache, [0], [ids[:29]], [0])
        logits = dec.step(cache, [0], ids[29:30][None],
                          np.asarray([29], np.int32))
        ref = np.asarray(plain.forward_logits(model_cfg(), SEED, ids))[-1]
        assert np.abs(np.asarray(logits)[0] - ref).max() < 1e-4
