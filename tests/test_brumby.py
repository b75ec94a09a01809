"""Brumby on the CPU at a tiny size (hidden 64, 4 query heads over 2 KV
heads of 16, 3 layers) against the plain reference
(``benchmark/reference/brumby_plain.py``: float32, precision highest, the
first form over the whole sequence, no state): the full forward's logits;
chunk rows and decode rows mixed through ``ContinuousBatchingEngine`` with
more requests than slots, each served token's reference logit held against
the reference's best there; what the step ring and the registry say of the
slots; a slot taken again starting from zero; preemption and resume, and
the replay after the pools are lost, into a zeroed slot; and what cannot
hold for a recurrent state refusing with its reason.

Tolerances: float32 on both sides, so the program (a recurrence over a
state) and the reference (the attention form) differ in the order of
float32 sums: served tokens are the reference's own first choice or within
1e-4 of it.  Each limit has teeth: the reference with the plain dot
product, or with the state zeroed every 16 positions, misses it a
hundredfold."""
import sys
import time
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
from paddle_tpu.models.brumby import (  # noqa: E402
    BrumbyConfig, BrumbyForCausalLM)
from paddle_tpu.ops import power_retention as pr  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache  # noqa: E402
from paddle_tpu.testing import faults  # noqa: E402
from drivers import serve_brumby as driver  # noqa: E402
from reference import brumby_plain as plain  # noqa: E402

TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=128,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, max_position_embeddings=256)
SEED = 2147483659
STATE_BYTES = 2 * 136 * 17 * 4          # a layer's: 2 heads x D x (d + 1)


def model_cfg():
    c = BrumbyConfig(**TINY)
    return plain.model_cfg({k: getattr(c, k) for k in plain.MODEL_KEYS})


@pytest.fixture(scope="module")
def model():
    """The program with the benchmark's weights for SEED, in float32."""
    m = driver.build_model(model_cfg(), SEED)
    for _, p in m.named_parameters():
        p._data = p._data.astype(jnp.float32)
    return m


def engine(model, **kw):
    kw = dict(dict(total_pages=64, page_size=16, max_batch=4,
                   prefill_chunk_tokens=16), **kw)
    return ContinuousBatchingEngine(model, **kw)


def gap(prompt, out):
    """The widest served-logit gap of one request against the reference."""
    seq = [(prompt, np.asarray(out[len(prompt):], np.int32))]
    return float(np.concatenate(plain.served_gaps(model_cfg(), SEED,
                                                  seq)).max())


def wait_for(cond, what, timeout=120.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {what}")


def total(snap, name):
    return sum(s["value"] for s in snap.get(name, {"series": []})["series"])


class TestFullForward:
    def test_logits_match_the_reference(self, model):
        ids = np.random.default_rng(0).integers(0, 96, 50).astype(np.int32)
        with no_grad():
            got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
        ref = np.asarray(plain.forward_logits(model_cfg(), SEED, ids))
        assert np.abs(got - ref).max() < 1e-4
        for switch in ({"power": 1}, {"reset_every": 16}):
            off = np.asarray(plain.forward_logits(model_cfg(), SEED, ids,
                                                  **switch))
            assert np.abs(got - off).max() > 1e-2, switch

    def test_parameters_are_the_reference_s_by_name_and_shape(self):
        m = BrumbyForCausalLM(BrumbyConfig(**TINY))
        assert [(n, tuple(p.shape)) for n, p in m.named_parameters()] \
            == [(n, tuple(s)) for n, s in plain.param_specs(model_cfg())]

    def test_what_the_engine_reads_of_the_model(self, model):
        assert model.attention_kinds() == []
        state = model.recurrent_state()
        assert state == {"layers": 3, "shape": (2, 24, 144),
                         "bytes": STATE_BYTES}
        assert state["shape"] == pr.state_shape(2, 16, 16)
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16,
                                        state_slots=3)
        assert cache.k_pages == [] and cache.num_layers == 0
        assert [tuple(a.shape) for a in cache.state_pools] \
            == [(4, 2, 24, 144)] * 3
        assert cache.kv_pool_bytes == cache.state_pool_bytes \
            == 3 * 4 * 2 * 24 * 144 * 4 == cache.kv_pool_bytes_per_chip


class TestServedThroughTheEngine:
    @pytest.fixture(scope="class")
    def served(self, model):
        """8 requests over 4 slots, chunked 16 tokens a step under a
        decode batch of up to 4; the ring captured."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n in (25, 64, 33, 41, 50, 27, 61, 38)]
        eng = engine(model)
        assert eng.prefix_cache is False        # turned off, not refused
        assert eng.cache.state_slots == 4
        before = monitor.snapshot()
        monitor.start_capture(max_requests=64, max_steps=4096,
                              host_events=False)
        try:
            reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
            outs = [r.result(timeout=600) for r in reqs]
            assert eng.cache.slots_in_use == 0 and eng.cache.free_slots == 4
        finally:
            # the engine first: a step's ``dispatch`` record is written
            # when the iteration that committed it ends
            eng.stop()
            monitor.stop_capture()
        steps = monitor.get_tracer().step_records()
        seqs = [(p, np.asarray(o[len(p):], np.int32))
                for p, o in zip(prompts, outs)]
        return seqs, steps, before, monitor.snapshot()

    def test_served_logits_match_the_reference_s_full_forward(self, served):
        seqs, *_ = served
        assert all(len(s) == 12 for _, s in seqs)
        gaps = plain.served_gaps(model_cfg(), SEED, seqs)
        assert np.concatenate(gaps).max() < 1e-4
        for switch in ({"power": 1}, {"reset_every": 16}):
            off = plain.served_gaps(model_cfg(), SEED, seqs, **switch)
            assert np.concatenate(off).max() > 1e-2, switch

    def test_the_ring_counts_rows_bytes_and_zeroed_slots(self, served):
        _, steps, *_ = served
        recs = [r for r in steps if r["kind"] == "dispatch"]
        assert recs and any(r["span_padded"] > 1 and r["rows"] > 1
                            for r in recs)      # chunk and decode rows mixed
        for r in recs:
            assert r["state_rows"] == r["rows"] <= r["state_slots"] == 4
            assert r["state_bytes"] == 2 * r["rows"] * 3 * STATE_BYTES
            assert r["table_pages"] == 1        # no table follows a context
            assert r["chunk_rows_padded"] == (0 if r["span_padded"] == 1
                                              else 2)
            assert r["state_chunk_tokens"] >= 2 * r["state_chunk_rows"]
            assert "ctx_tokens" not in r        # no K/V layer is walked
        assert sum(r["slots_zeroed"] for r in recs) == 8

    def test_the_registry_sums_what_the_ring_says(self, served):
        _, steps, before, after = served
        recs = [r for r in steps if r["kind"] == "dispatch"]

        def moved(name):
            return total(after, name) - total(before, name)

        assert moved("serve_state_bytes_total") \
            == sum(r["state_bytes"] for r in recs)
        assert moved("recurrent_slots_taken_total") == 8
        assert moved("recurrent_slots_zeroed_total") == 8
        assert total(after, "recurrent_slots_in_use") == 0


    def test_the_ragged_program_audits_clean(self, model):
        """The auditor rebuilds the program with its slot pools among the
        donated operands: no hazard (a donated pool that is not aliased,
        a constant baked in) is found."""
        from paddle_tpu.analysis import audit_engine
        eng = engine(model)
        try:
            audit = audit_engine(eng, mode="ragged")
        finally:
            eng.stop()
        assert not audit.findings, [f.rule for f in audit.findings]


class TestSlots:
    def test_a_slot_taken_again_starts_from_zero(self, model):
        """One slot: the second request enters what the first left, and
        worse (every pool overwritten with 1e3 between the two)."""
        rng = np.random.default_rng(3)
        a, b = (rng.integers(0, 96, n).astype(np.int32) for n in (40, 35))
        eng = engine(model, max_batch=1)
        try:
            out_a = eng.submit(a, max_new_tokens=6).result(timeout=300)
            wait_for(lambda: eng.cache.slots_in_use == 0, "the slot's return")
            eng.cache.state_pools = [jnp.full_like(p, 1e3)
                                     for p in eng.cache.state_pools]
            out_b = eng.submit(b, max_new_tokens=6).result(timeout=300)
        finally:
            eng.stop()
        assert gap(a, out_a) < 1e-4 and gap(b, out_b) < 1e-4

    def test_a_pad_row_s_slot_is_untouched(self, model):
        """Three rows padded to four: the pad row writes the scratch slot
        and the slot no row holds stays as it was."""
        cache = PagedKVCache.from_model(model, total_pages=16, page_size=16,
                                        state_slots=4)
        dec = JittedPagedDecoder(model)
        rng = np.random.default_rng(4)
        cache.state_pools = [jnp.asarray(rng.normal(size=p.shape),
                                         jnp.float32)
                             for p in cache.state_pools]
        held = [np.asarray(p) for p in cache.state_pools]
        rows = [rng.integers(0, 96, n).astype(np.int32) for n in (9, 1, 1)]
        dec.ragged_step(cache, [10, 11, 12], rows, [0, 0, 0])
        idle = (set(range(4)) - {cache.slot_of(s) for s in (10, 11, 12)}).pop()
        for before, pool in zip(held, cache.state_pools):
            np.testing.assert_array_equal(np.asarray(pool)[idle],
                                          before[idle])
            assert not np.array_equal(np.asarray(pool)[cache.slot_of(10)],
                                      before[cache.slot_of(10)])


class TestPreemptResumeAndReplay:
    @pytest.mark.parametrize("when", ["mid_prefill", "mid_decode"])
    def test_preempt_and_resume_reproduce_the_logits(self, model, when):
        """One slot; a batch-class request is paused for an interactive
        one, gives its slot up, and resumes by running its tokens so far
        through chunk rows into a zeroed slot."""
        rng = np.random.default_rng(5)
        p = rng.integers(0, 96, 70).astype(np.int32)
        site = "prefill_chunk" if when == "mid_prefill" else "decode_step"
        plan = faults.FaultPlan([{"site": site, "kind": "delay",
                                  "delay_s": 0.03}])
        before = monitor.snapshot()
        with faults.installed(plan):
            eng = engine(model, max_batch=1)
            try:
                rb = eng.submit(p, max_new_tokens=10, priority="batch")
                wait_for(lambda: (rb.prefill_pos > 0
                                  if when == "mid_prefill"
                                  else len(rb.generated) >= 3), "the victim")
                assert not rb.done.is_set()
                ri = eng.submit(rng.integers(0, 96, 5).astype(np.int32),
                                max_new_tokens=3, priority="interactive")
                out_i = ri.result(timeout=300)
                out_b = rb.result(timeout=300)
            finally:
                eng.stop()
        assert ri.finished_at < rb.finished_at and rb.paused_total > 0
        assert len(out_b) == 80 and gap(p, out_b) < 1e-4
        assert gap(out_i[:5], out_i) < 1e-4
        # batch, interactive, batch again; each entered at context 0
        after = monitor.snapshot()
        for name in ("recurrent_slots_taken_total",
                     "recurrent_slots_zeroed_total"):
            assert total(after, name) - total(before, name) == 3, name

    def test_replay_after_the_pools_are_lost(self, model):
        """A device fault consumes the donated pools mid-stream: they are
        rebuilt zeroed and every survivor's tokens so far run through
        chunk rows again, each into its own (zeroed) slot."""
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (30, 45)]
        plan = faults.FaultPlan([{"site": "buffer_loss", "nth": 9}])
        before = monitor.snapshot()
        with faults.installed(plan):
            eng = engine(model, max_batch=2)
            try:
                outs = [r.result(timeout=300) for r in
                        [eng.submit(p, max_new_tokens=10) for p in prompts]]
            finally:
                eng.stop()
        assert plan.fired
        after = monitor.snapshot()
        assert total(after, "engine_rebuilds_total") \
            > total(before, "engine_rebuilds_total")
        for p, o in zip(prompts, outs):
            assert len(o) == len(p) + 10 and gap(p, o) < 1e-4

    @pytest.mark.parametrize("site", ["decode_step", "prefill_chunk"])
    def test_one_poisoned_request_among_16_fails_alone(self, model, site):
        """A fault that follows one sequence fails every step it is a row
        of: the ladder over the ragged step (whole once more, then by
        halves) ends at that row alone, the other 15 are served as if it
        had never been there."""
        rng = np.random.default_rng(8)
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n in rng.integers(5, 31, 16)]
        plan = faults.FaultPlan([{"site": site, "seq_id": 5}])
        before = monitor.snapshot()
        with faults.installed(plan):
            eng = engine(model, max_batch=16, total_pages=128)
            try:
                reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
                with pytest.raises(faults.FaultError):
                    reqs[5].result(timeout=300)
                assert reqs[5].seq_id == 5      # admitted in order
                outs = [r.result(timeout=300)
                        for i, r in enumerate(reqs) if i != 5]
                assert eng.cache.slots_in_use == 0
            finally:
                eng.stop()
        # every shot held the poisoned row, the last one it alone
        assert plan.fired and all(5 in ids for _, _, ids in plan.fired)
        assert plan.fired[-1][2] == [5]
        after = monitor.snapshot()
        assert total(after, "quarantined_requests_total") \
            - total(before, "quarantined_requests_total") == 1
        for p, o in zip([q for i, q in enumerate(prompts) if i != 5], outs):
            assert len(o) == len(p) + 6 and gap(p, o) < 1e-4

    def test_snapshot_and_restore_continue_the_stream(self, model):
        """A journal entry (prompt, generated, the pending token) resumes
        through the admission path: its tokens re-run into a fresh slot."""
        rng = np.random.default_rng(7)
        p = rng.integers(0, 96, 40).astype(np.int32)
        eng = engine(model, max_batch=1)
        try:
            want = eng.submit(p, max_new_tokens=8).result(timeout=300)
        finally:
            eng.stop()
        entry = {"prompt": p.tolist(), "generated": want[40:44].tolist(),
                 "next_token": int(want[44]), "max_new_tokens": 8}
        eng = engine(model, max_batch=1)
        try:
            got = eng.restore({"version": 1, "requests": [entry]})[0] \
                .result(timeout=300)
        finally:
            eng.stop()
        np.testing.assert_array_equal(got, want)


class TestWhatCannotHoldRefuses:
    @pytest.mark.parametrize("kw, reason", [
        (dict(draft_model="model"), "rolled out of it"),
        (dict(kv_quant="int8"), "no K/V page to quantise"),
        (dict(tp=2), "g_proj"),
        (dict(prefill_chunk_tokens=None), "only the ragged unified step"),
    ])
    def test_at_construction(self, model, kw, reason):
        if kw.get("draft_model"):
            kw = dict(kw, draft_model=model)
        with pytest.raises(ValueError, match=reason):
            engine(model, **kw)

    def test_the_paged_generator(self, model):
        gen = PagedGenerator(model, total_pages=8, page_size=16)
        with pytest.raises(NotImplementedError, match="retention layer"):
            gen.generate(np.arange(12, dtype=np.int32)[None],
                         max_new_tokens=2)

    @pytest.mark.parametrize("path", ["prefill", "chunk_prefill",
                                      "batch_context_prefill", "step"])
    def test_the_programs_that_carry_no_slots(self, model, path):
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16,
                                        state_slots=2)
        dec = JittedPagedDecoder(model)
        ids = np.arange(12, dtype=np.int32)[None]
        with pytest.raises(NotImplementedError, match="retention layer"):
            if path == "prefill":
                dec.prefill(cache, [0], ids)
            elif path == "chunk_prefill":
                cache.allocate(0, 16)
                cache.advance([0], 16)
                dec.chunk_prefill(cache, [0], ids, 16)
            elif path == "step":
                dec.step(cache, [0], ids[:, :1], np.asarray([0], np.int32))
            else:
                dec.batch_context_prefill(cache, [0], [ids[0]], [0])
        assert cache.length(0) in (0, 16)               # rolled back
