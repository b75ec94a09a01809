"""Phi-4-mini-flash on the CPU at a tiny size (hidden 64, 4 query heads
over 2 KV heads of 16, ``d_inner`` 128, window 24, 8 layers: Mamba, sliding,
Mamba, sliding, Mamba that hands m on, full, GMU, cross: every row of the
mixer table with the split at n / 2) against the plain reference
(``benchmark/reference/phi4_flash_plain.py``: float32, precision highest,
the scan token by token, dense masked attention, no cache, no slot): the
full forward's logits; chunked prefill then decode through
``ContinuousBatchingEngine`` with more requests than slots and contexts past
the window, each served token's reference logit held against the
reference's best there; 4 paged calls on 3 pools and what the cache says it
holds; a cross layer appends nothing; slots and pages counted at admission
and returned at ``free``; pause and resume; what the step ring and the
registry say; and what cannot hold refusing with its reason.

Tolerances: float32 on both sides; the program (slots, pages, the paged
kernels' XLA form, a page's head a pair of KV heads) and the reference
(whole sequences) differ in the order of float32 sums only: logits of order
one agree to 1e-5, and a served token is the reference's own first choice
or within 1e-5 of it.  Each has teeth: the reference with plain attention
(lambda = 0), with the cross layers reading a sliding layer's K/V, with a
window one shorter or with the convolution's tail dropped every 16
positions misses it a hundredfold; the scan's carry, which at these widths
moves a logit by 1e-5 only, is held by ``tests/test_selective_scan.py``."""
import sys
import time
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
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.framework.tape import no_grad  # noqa: E402
from paddle_tpu.inference.continuous import (  # noqa: E402
    ContinuousBatchingEngine)
from paddle_tpu.inference.paged import (  # noqa: E402
    JittedPagedDecoder, PagedGenerator)
from paddle_tpu.models.phi4_flash import (  # noqa: E402
    Phi4FlashConfig, Phi4FlashForCausalLM)
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    PagedKVCache, paged_layout)
from paddle_tpu.testing import faults  # noqa: E402
from drivers import serve_phi4_flash as driver  # noqa: E402
from reference import phi4_flash_plain as plain  # noqa: E402

TINY = dict(vocab_size=96, hidden_size=64, intermediate_size=128,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=2, sliding_window=24,
            max_position_embeddings=256)
SEED = 2147483659
STATE_BYTES = 4 * 128 * (16 + 3)        # a layer's: h and the tail
FAULTS = ({"lam_zero": True}, {"cross_from": 3}, {"window": 23},
          {"drop_tail_every": 16})


def model_cfg():
    c = Phi4FlashConfig(**TINY)
    return {k: getattr(c, k) for k in plain.MODEL_KEYS}


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
        ids = np.random.default_rng(0).integers(0, 96, 70).astype(np.int32)
        with no_grad():
            got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
        ref = np.asarray(plain.forward_logits(model_cfg(), SEED, ids))
        assert np.abs(got - ref).max() < 1e-5
        for switch in FAULTS:
            off = np.asarray(plain.forward_logits(model_cfg(), SEED, ids,
                                                  **switch))
            assert np.abs(got - off).max() > 1e-3, switch

    def test_parameters_are_the_reference_s_by_name_and_shape(self):
        m = Phi4FlashForCausalLM(Phi4FlashConfig(**TINY))
        assert [(n, tuple(p.shape)) for n, p in m.named_parameters()] \
            == [(n, tuple(s)) for n, s in plain.param_specs(model_cfg())]

    def test_a_group_s_weights_are_the_leaves_own_bit_for_bit(self):
        """The reference makes a group's leaves in one program
        (``group_weights``); a leaf alone (``make_leaf``, a program a leaf)
        holds the same bits, type and range."""
        cfg = model_cfg()
        for group in plain.param_groups(cfg)[:3]:
            made = plain.group_weights(SEED, group)
            for name, shape in group:
                alone = plain.make_leaf(SEED, name, shape)
                assert made[name].dtype == alone.dtype \
                    == plain.leaf_dtype(name)
                np.testing.assert_array_equal(
                    np.asarray(made[name].astype(jnp.float32)),
                    np.asarray(alone.astype(jnp.float32)))
        a_log = made["model.layers.1.mixer.lambda_q1"]
        assert float(jnp.abs(a_log.astype(jnp.float32)).max()) < 0.2

    def test_the_published_widths_count_3_85_billion(self):
        """Counted from the shapes (nothing is built): the published
        model's 3.8 B."""
        cfg = dict(model_cfg(), vocab_size=200064, hidden_size=2560,
                   intermediate_size=10240, num_hidden_layers=32,
                   num_attention_heads=40, num_key_value_heads=20)
        count = sum(int(np.prod(s)) for _, s in plain.param_specs(cfg))
        assert abs(count / 3.85e9 - 1) < 0.01, count
        kinds = [plain.mixer(cfg, i) for i in range(32)]
        assert [kinds.count(k) for k in ("mamba", "sliding", "full", "gmu",
                                         "cross")] == [9, 8, 1, 7, 7]
        c = Phi4FlashConfig(**{k: cfg[k] for k in plain.MODEL_KEYS})
        assert [c.mixer(i) for i in range(32)] == kinds
        assert (c.head_dim, c.d_inner, c.dt_rank) == plain.sizes(cfg)

    def test_what_the_engine_reads_of_the_model(self, model):
        # 4 paged calls on 3 pools: the cross layer names the full one's
        assert model.attention_kinds() == [(4, 24, 0), (4, 24, 1),
                                           (4, None, 2), (4, None, 2)]
        layout = paged_layout(model)
        assert [c[3] for c in layout["calls"]] == [False, False, False, True]
        assert (layout["pools"], layout["kv_heads"], layout["head_dim"]) \
            == (3, 1, 32)
        state = model.recurrent_state()
        assert state == {"layers": 3, "shapes": [(16, 128), (3 * 128,)],
                         "bytes": STATE_BYTES}
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16,
                                        state_slots=3)
        assert cache.num_layers == len(cache.k_pages) == 3
        assert cache.k_pages[0].shape == (1, 8, 16, 32)     # a PAIR a head
        assert [tuple(a.shape) for a in cache.state_pools] \
            == [(4, 16, 128), (4, 3 * 128)] * 3
        # a pool once, however many layers walk it; and the slots
        pages = 3 * 2 * 8 * 16 * 32 * 4
        assert cache.state_pool_bytes == 4 * STATE_BYTES * 3
        assert cache.kv_pool_bytes == pages + cache.state_pool_bytes \
            == cache.kv_pool_bytes_per_chip

    def test_a_model_without_the_extended_description_is_as_it_was(self):
        """(heads, window) pairs name a pool each; no ``shapes`` key means
        one array a slot (``models/laguna.py``, ``models/brumby.py``)."""
        class Config:
            num_attention_heads, num_key_value_heads = 8, 2
            hidden_size, num_hidden_layers, head_dim = 64, 3, 16

        class Plain:
            config = Config()

        class Mixed(Plain):
            def attention_kinds(self):
                return [(8, None), (4, 512)]

            def recurrent_state(self):
                return {"layers": 2, "shape": (2, 8, 128), "bytes": 1}

        assert paged_layout(Plain())["calls"] == [(8, None, i, False)
                                                  for i in range(3)]
        got = paged_layout(Mixed())
        assert got["calls"] == [(8, None, 0, False), (4, 512, 1, False)]
        assert (got["pools"], got["kv_heads"], got["head_dim"]) == (2, 2, 16)
        assert got["state"]["shapes"] == [(2, 8, 128)]


class TestServedThroughTheEngine:
    @pytest.fixture(scope="class")
    def served(self, model):
        """8 requests over 4 slots, chunked 16 tokens a step under a
        decode batch of up to 4, contexts up to 3 windows; the ring
        captured."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n in (25, 64, 33, 41, 50, 27, 61, 38)]
        eng = engine(model)
        assert eng.prefix_cache is False        # turned off, not refused
        assert eng.cache.state_slots == 4 and eng.cache.num_layers == 3
        before = monitor.snapshot()
        monitor.start_capture(max_requests=64, max_steps=4096,
                              host_events=False)
        try:
            reqs = [eng.submit(p, max_new_tokens=14) for p in prompts]
            wait_for(lambda: eng.cache.slots_in_use == 4, "four admitted")
            # slots and pages are both held by the admitted four
            assert eng.cache.free_slots == 0 and eng.cache.pinned_pages > 0
            outs = [r.result(timeout=600) for r in reqs]
            wait_for(lambda: eng.cache.slots_in_use == 0, "the slots' return")
            assert eng.cache.free_slots == 4 and eng.cache.pinned_pages == 0
            assert eng.cache.free_pages == 64
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
        assert all(len(s) == 14 for _, s in seqs)
        assert max(len(p) + len(s) for p, s in seqs) > 3 * 24
        gaps = plain.served_gaps(model_cfg(), SEED, seqs)
        assert np.concatenate(gaps).max() < 1e-5

    def test_the_ring_counts_walks_rows_and_bytes(self, served):
        _, steps, *_ = served
        recs = [r for r in steps if r["kind"] == "dispatch"]
        assert recs and any(r["span_padded"] > 1 and r["rows"] > 1
                            for r in recs)      # chunk and decode rows mixed
        for r in recs:
            assert r["state_rows"] == r["rows"] <= r["state_slots"] == 4
            assert r["state_bytes"] == 2 * r["rows"] * 3 * STATE_BYTES
            assert r["chunk_rows_padded"] == (0 if r["span_padded"] == 1
                                              else 2)
            assert r["state_chunk_tokens"] >= 2 * r["state_chunk_rows"]
            # one call of four walks a pool it does not own, and walks what
            # the full layer walks: the two sliding calls walk no more
            assert 0 < r["kv_tokens_walked_shared"] \
                <= r["kv_tokens_walked"] / 2
            assert r["kv_tokens_walked_window"] \
                <= r["kv_tokens_walked_nowindow"] \
                == 4 * r["kv_tokens_walked_shared"]
        assert sum(r["slots_zeroed"] for r in recs) == 8

    def test_the_registry_sums_what_the_ring_says(self, served):
        _, steps, before, after = served
        recs = [r for r in steps if r["kind"] == "dispatch"]

        def moved(name):
            return total(after, name) - total(before, name)

        assert moved("serve_state_bytes_total") \
            == sum(r["state_bytes"] for r in recs)
        assert moved("serve_kv_tokens_walked_shared_total") \
            == pytest.approx(sum(r["kv_tokens_walked_shared"] for r in recs))
        assert moved("recurrent_slots_taken_total") == 8
        assert moved("recurrent_slots_zeroed_total") == 8
        assert total(after, "recurrent_slots_in_use") == 0

    def test_the_ragged_program_audits_clean(self, model):
        """The auditor rebuilds the program with page pools AND slot pools
        among the donated operands: no hazard is found."""
        from paddle_tpu.analysis import audit_engine
        eng = engine(model)
        try:
            audit = audit_engine(eng, mode="ragged")
        finally:
            eng.stop()
        assert not audit.findings, [f.rule for f in audit.findings]


class TestLogitsThroughTheRaggedStep:
    def test_chunked_prefill_then_decode_against_the_full_forward(
            self, model):
        """Two sequences through the ragged program's logits escape hatch:
        prompts in chunks of 16 (the second entering while the first
        decodes), then one-token rows to 80 positions, past three
        windows.  Every step's logits are the reference's at that position
        to float32 rounding, and no planted fault's."""
        cache = PagedKVCache.from_model(model, total_pages=32, page_size=16,
                                        state_slots=4)
        dec = JittedPagedDecoder(model)
        rng = np.random.default_rng(2)
        seqs = [rng.integers(0, 96, 80).astype(np.int32) for _ in range(2)]
        prompt, at, got = [41, 30], [0, 0], [[], []]
        while min(at) < 80:
            ids, rows = [], []
            for i in (0, 1):
                if at[i] >= 80 or (i == 1 and at[0] < 32):
                    continue
                n = min(16, prompt[i] - at[i]) if at[i] < prompt[i] else 1
                ids.append(i)
                rows.append(seqs[i][at[i]:at[i] + n])
            out, _ = dec.ragged_step(cache, ids, rows, [at[i] for i in ids])
            for i, row, lg in zip(ids, rows, np.asarray(out)):
                at[i] += len(row)
                got[i].append((at[i] - 1, lg))
        for i in (0, 1):
            pos = np.asarray([p for p, _ in got[i]])
            mine = np.stack([lg for _, lg in got[i]])
            ref = np.asarray(plain.forward_logits(model_cfg(), SEED,
                                                  seqs[i]))[pos]
            assert np.abs(mine - ref).max() < 1e-5
            for switch in FAULTS:
                off = np.asarray(plain.forward_logits(
                    model_cfg(), SEED, seqs[i], **switch))[pos]
                assert np.abs(mine - off).max() > 1e-3, switch


class TestPoolsAndSlots:
    def test_a_cross_layer_appends_nothing(self, model):
        """One ragged step over three rows: every pool's pages change at
        the rows' write targets ONCE; the scopes a program names."""
        cache = PagedKVCache.from_model(model, total_pages=16, page_size=16,
                                        state_slots=4)
        dec = JittedPagedDecoder(model)
        rng = np.random.default_rng(4)
        rows = [rng.integers(0, 96, n).astype(np.int32) for n in (9, 1, 1)]
        dec.ragged_step(cache, [10, 11, 12], rows, [0, 0, 0])
        assert len(cache.k_pages) == 3
        full = np.asarray(cache.k_pages[2])
        first = cache._seq_pages[10][0]
        assert np.abs(full[:, first, :9]).min() > 0      # layer 5 wrote
        assert not full[:, first, 9:].any()              # and no one else
        # the same rows again, one token each: layer 7 reads what layer 5
        # appends in the same program, 10 + 1 positions, and leaves them
        out, _ = dec.ragged_step(cache, [10, 11, 12],
                                 [r[:1] for r in rows], [9, 1, 1])
        full = np.asarray(cache.k_pages[2])
        assert np.abs(full[:, first, :10]).min() > 0
        assert not full[:, first, 10:].any()

    def test_a_slot_taken_again_starts_from_zero(self, model):
        """One slot: the second request enters what the first left, and
        worse (every slot pool overwritten with 1e3 between the two)."""
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
        assert gap(a, out_a) < 1e-5 and gap(b, out_b) < 1e-5

    def test_a_pad_row_s_slot_is_untouched(self, model):
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
        assert len(cache.state_pools) == 6
        for before, pool in zip(held, cache.state_pools):
            np.testing.assert_array_equal(np.asarray(pool)[idle],
                                          before[idle])
            assert not np.array_equal(np.asarray(pool)[cache.slot_of(10)],
                                      before[cache.slot_of(10)])


class TestPreemptResumeAndReplay:
    @pytest.mark.parametrize("when", ["mid_prefill", "mid_decode"])
    def test_preempt_and_resume_give_the_same_tokens(self, model, when):
        """One slot; a batch-class request is paused for an interactive
        one, gives its slot up and its pages' content with it, and resumes
        by running its tokens so far through chunk rows into a zeroed slot
        and over its pages again: the tokens an undisturbed run gives."""
        rng = np.random.default_rng(5)
        p = rng.integers(0, 96, 70).astype(np.int32)
        eng = engine(model, max_batch=1)
        try:
            want = eng.submit(p, max_new_tokens=10).result(timeout=300)
        finally:
            eng.stop()
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
                wait_for(lambda: eng.cache.slots_in_use == 0, "the return")
                assert eng.cache.pinned_pages == 0
            finally:
                eng.stop()
        assert ri.finished_at < rb.finished_at and rb.paused_total > 0
        np.testing.assert_array_equal(out_b, want)
        assert gap(p, out_b) < 1e-5 and gap(out_i[:5], out_i) < 1e-5
        # batch, interactive, batch again; each entered at context 0
        after = monitor.snapshot()
        for name in ("recurrent_slots_taken_total",
                     "recurrent_slots_zeroed_total"):
            assert total(after, name) - total(before, name) == 3, name

    def test_replay_after_the_pools_are_lost(self, model):
        """A device fault consumes the donated pools mid-stream: pages and
        slots are rebuilt zeroed and every survivor's tokens so far run
        through chunk rows again."""
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, 96, n).astype(np.int32) for n in (30, 45)]
        plan = faults.FaultPlan([{"site": "buffer_loss", "nth": 9}])
        with faults.installed(plan):
            eng = engine(model, max_batch=2)
            try:
                outs = [r.result(timeout=300) for r in
                        [eng.submit(p, max_new_tokens=10) for p in prompts]]
            finally:
                eng.stop()
        assert plan.fired
        for p, o in zip(prompts, outs):
            assert len(o) == len(p) + 10 and gap(p, o) < 1e-5


class TestWhatCannotHoldRefuses:
    @pytest.mark.parametrize("kw, reason", [
        (dict(draft_model="model"), "rolled out of it"),
        (dict(kv_quant="int8"), "not been held to a reference in int8"),
        (dict(tp=2), "Phi4FlashMamba: the plan has no placement"),
        (dict(prefill_chunk_tokens=None), "only the ragged unified step"),
    ])
    def test_at_construction(self, model, kw, reason):
        if kw.get("draft_model"):
            kw = dict(kw, draft_model=model)
        with pytest.raises(ValueError, match=reason):
            engine(model, **kw)

    def test_the_paged_generator(self, model):
        gen = PagedGenerator(model, total_pages=8, page_size=16)
        with pytest.raises(NotImplementedError, match="Mamba layer"):
            gen.generate(np.arange(12, dtype=np.int32)[None],
                         max_new_tokens=2)

    @pytest.mark.parametrize("path", ["prefill", "chunk_prefill",
                                      "batch_context_prefill", "step"])
    def test_the_programs_that_carry_no_slots(self, model, path):
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16,
                                        state_slots=2)
        dec = JittedPagedDecoder(model)
        ids = np.arange(12, dtype=np.int32)[None]
        with pytest.raises(NotImplementedError, match="Mamba layer"):
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

    def test_a_shared_walk_outside_the_ragged_step(self, model):
        """``attend(q, None, None)`` reaching a context that appends in
        every call says so (the Mamba layers ahead of it refuse first in a
        whole model: asked of the context alone)."""
        from paddle_tpu.inference.paged import _TracedPagedContext
        ctx = _TracedPagedContext([jnp.zeros((1, 4, 16, 32))] * 3,
                                  [jnp.zeros((1, 4, 16, 32))] * 3,
                                  jnp.zeros(2, jnp.int32),
                                  jnp.zeros(2, jnp.int32), prefill=True)
        q = paddle.to_tensor(np.zeros((2, 1, 4, 32), np.float32))
        with pytest.raises(NotImplementedError, match="another layer's"):
            ctx.attend(q, None, None)


class TestTheStateProbe:
    def test_carry_gap_reads_rounding_when_sound(self):
        """The driver's probe of the state ops alone at this size: float32
        rounding when sound."""
        assert driver.carry_gap(SEED, model_cfg(), 16, steps=8) < 1e-5

    def test_carry_gap_sees_a_state_stored_in_bfloat16(self, monkeypatch):
        from paddle_tpu.ops import selective_scan as ss
        sound = ss.scan_rows

        def rounded(pool, *a, **kw):
            m, pool = sound(pool, *a, **kw)
            return m, pool.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(ss, "scan_rows", rounded)
        jax.clear_caches()      # ``scan_step`` is jitted over the old one
        try:
            assert driver.carry_gap(SEED + 1, model_cfg(), 16,
                                    steps=8) > 1e-4
        finally:
            monkeypatch.undo()
            jax.clear_caches()
