"""ZAYA1 on the CPU at a tiny size (hidden 64, 4 query heads over 2 KV
heads of 16, 4 experts of 64, a router of 16, 4 layers) against the plain
reference (``benchmark/reference/zaya_plain.py``: float32, precision
highest, the convolutions and the value shift as padded whole-sequence
operations, dense attention, the experts by a loop, no cache, no slot): the
full forward's logits; chunked prefill then decode through the ragged step
and through ``ContinuousBatchingEngine`` with more requests than slots, a
chunk boundary inside every prompt and rows entering on used slots; a slot
AND a page pool in every layer and what the cache says it holds; slots and
pages counted at admission and returned at ``free``; pause and resume; the
router's state reaching the next layer and no further; the chosen experts
against the reference's; the shift primitive; what the step ring and the
registry say; and what cannot hold refusing with its reason.

Tolerances: float32 on both sides; the program (slots, pages, the paged
kernels' XLA form, the grouped expert product) and the reference (whole
sequences, an expert at a time) differ in the order of float32 sums only:
logits of order one agree to 1e-5, and a served token is the reference's
own first choice or within 1e-5 of it.  Each has teeth: the reference with
the tails zeroed every 16 positions, without the value shift, without the
q-k mean, with tau = 1, without the previous layer's router state or with
the front pad after the first convolution misses it a hundredfold."""
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
from paddle_tpu.models.zaya import ZayaConfig, ZayaForCausalLM  # noqa: E402
from paddle_tpu.ops import selective_scan as ss  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import (  # noqa: E402
    PagedKVCache, paged_layout)
from paddle_tpu.testing import faults  # noqa: E402
from drivers import serve_zaya as driver  # noqa: E402
from reference import zaya_plain as plain  # noqa: E402

TINY = dict(vocab_size=96, hidden_size=64, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            num_experts=4, moe_intermediate_size=64, router_hidden_size=16,
            max_position_embeddings=256)
SEED = 2147483659
LATENT, VALUE_HALF = 6 * 16, 16
STATE_BYTES = 4 * (2 * LATENT + VALUE_HALF)     # a layer's three tails
#: every planted fault, the tails zeroed at every 16th position (every
#: chunk boundary of these tests) for the cell's every 128th, each with how
#: far a logit must move: the front pad after the first convolution moves
#: ONE token's a_{-1} (position 0's), which later positions see through one
#: key and one value of their context
FAULTS = {"reset16": 1e-3, "no_shift": 1e-3, "no_qk_mean": 1e-3,
          "tau_one": 1e-3, "no_depth": 1e-3, "late_pad": 1e-4}


def model_cfg():
    c = ZayaConfig(**TINY)
    return {k: getattr(c, k) for k in plain.MODEL_KEYS}


@pytest.fixture(scope="module")
def beta():
    b, fullest = plain.balancing_biases(model_cfg(), SEED, sequences=4,
                                        tokens=64)
    assert b.shape == (4, 4) and np.abs(b).max() > 0
    # the rule settled: no expert of a layer over 1.25 of its even share
    assert max(fullest) <= 1.25
    return b


@pytest.fixture(scope="module")
def model(beta):
    """The program with the benchmark's weights for SEED, in float32."""
    m = driver.build_model(model_cfg(), SEED, beta)
    for _, p in m.named_parameters():
        p._data = p._data.astype(jnp.float32)
    return m


def engine(model, **kw):
    kw = dict(dict(total_pages=64, page_size=16, max_batch=4,
                   prefill_chunk_tokens=16), **kw)
    return ContinuousBatchingEngine(model, **kw)


def gaps_of(seqs, beta, **kw):
    return plain.served_gaps(model_cfg(), SEED, seqs, beta=beta, **kw)


def gap(prompt, out, beta):
    """The widest served-logit gap of one request against the reference."""
    seq = [(prompt, np.asarray(out[len(prompt):], np.int32))]
    return float(np.concatenate(gaps_of(seq, beta)[0]).max())


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
    def test_logits_match_the_reference(self, model, beta):
        ids = np.random.default_rng(0).integers(0, 96, 70).astype(np.int32)
        with no_grad():
            got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
        ref = np.asarray(plain.forward_logits(model_cfg(), SEED, ids,
                                              beta=beta))
        assert np.abs(got - ref).max() < 1e-5
        for fault, moves in FAULTS.items():
            off = np.asarray(plain.forward_logits(model_cfg(), SEED, ids,
                                                  beta=beta, fault=fault))
            assert np.abs(got - off).max() > moves, fault

    def test_parameters_are_the_reference_s_by_name_and_shape(self):
        m = ZayaForCausalLM(ZayaConfig(**TINY))
        assert [(n, tuple(p.shape)) for n, p in m.named_parameters()] \
            == [(n, tuple(s)) for n, s in plain.param_specs(model_cfg())]
        # layer 0 has no state before it: no gamma
        names = [n for n, _ in m.named_parameters()]
        assert "model.layers.0.mlp.gate.gamma" not in names
        assert "model.layers.1.mlp.gate.gamma" in names

    def test_the_seeded_leaves_are_shaped_as_assumed(self):
        cfg = model_cfg()
        w = plain.group_weights(SEED, plain.param_groups(cfg)[2])
        f = lambda n: np.asarray(                           # noqa: E731
            w["model.layers.1." + n].astype(jnp.float32))
        tau = f("self_attn.k_scale")
        assert tau.dtype == np.float32 and (0.5 <= tau).all() \
            and (tau <= 2).all()
        assert not f("mlp.gate.balancing_bias").any()
        assert abs(f("attn_merge.res_scale").mean() - 1) < 0.05
        # the merges' biases: a twenty-fifth of the vector draw's 0.05
        assert 0.001 < f("attn_merge.out_bias").std() < 0.003
        assert abs(f("mlp.gate.gamma").mean() - 1) < 0.05
        assert abs(f("self_attn.conv0_weight").std() / 0.32 - 1) < 0.2
        assert w["model.layers.1.mlp.gate.w1"].dtype == jnp.float32
        assert w["model.layers.1.mlp.gate.down_weight"].dtype == jnp.bfloat16

    @pytest.mark.parametrize("group", [0, 2, -1],
                             ids=["embedding", "layer1", "norm"])
    def test_a_group_s_leaves_are_the_benchmark_s_bit_for_bit(self, group):
        """The reference makes a group's leaves in one program with the
        names' folds traced (a program a NAME was most of the check's time
        on the chip): every value is ``weights.make_leaf``'s, which is
        what the program's own leaves are made from."""
        g = plain.param_groups(model_cfg())[group]
        w = plain.group_weights(SEED, g)
        assert list(w) == [n for n, _ in g]
        for n, s in g:
            want = plain.make_leaf(SEED, n, s)
            assert w[n].dtype == want.dtype and w[n].shape == tuple(s)
            assert np.array_equal(np.asarray(w[n].astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32))), n

    def test_the_published_widths_count_4_69_billion(self):
        """Counted from the shapes (nothing is built): 20 of the 40
        layers with all 16 experts and the whole vocabulary; and the
        whole model's active parameters a token, the published A0.76B."""
        cfg = dict(model_cfg(), vocab_size=262272, hidden_size=2048,
                   num_hidden_layers=20, num_attention_heads=8,
                   num_key_value_heads=2, head_dim=128, num_experts=16,
                   moe_intermediate_size=2048, router_hidden_size=256)
        count = sum(int(np.prod(s)) for _, s in plain.param_specs(cfg))
        assert abs(count / 4.69e9 - 1) < 0.01, count
        layer = sum(int(np.prod(s)) for n, s in plain.layer_specs(cfg, 1))
        expert = 3 * 2048 * 2048
        active = 40 * (layer - 15 * expert)
        assert abs(active / 0.76e9 - 1) < 0.02, active

    def test_what_the_engine_reads_of_the_model(self, model):
        # a pool of its own in EVERY layer, and a slot in every layer
        assert model.attention_kinds() == [(4, None)] * 4
        layout = paged_layout(model)
        assert layout["calls"] == [(4, None, i, False) for i in range(4)]
        assert (layout["pools"], layout["kv_heads"], layout["head_dim"]) \
            == (4, 2, 16)
        state = model.recurrent_state()
        assert state == {"layers": 4, "bytes": STATE_BYTES,
                         "shapes": [(LATENT + VALUE_HALF,), (LATENT,)]}
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16,
                                        state_slots=3)
        assert cache.num_layers == len(cache.k_pages) == 4
        assert cache.k_pages[0].shape == (2, 8, 16, 16)
        assert [tuple(a.shape) for a in cache.state_pools] \
            == [(4, LATENT + VALUE_HALF), (4, LATENT)] * 4
        pages = 4 * 2 * (2 * 8 * 16 * 16) * 4
        assert cache.state_pool_bytes == 4 * STATE_BYTES * 4
        assert cache.kv_pool_bytes == pages + cache.state_pool_bytes


class TestTheRouterState:
    def test_it_reaches_the_next_layer_and_no_further(self, model):
        """Layer l's gate is handed layer l - 1's state, the very array,
        and layer 0's None; what a gate returns is its own
        ``W_d h + b_d + gamma . state``."""
        seen = []
        gates = [layer.mlp.gate for layer in model.model.layers]
        for i, gate in enumerate(gates):
            def route(x, state=None, _route=type(gate).route_no_drop,
                      _gate=gate, _i=i):
                idx, w, r = _route(_gate, x, state)
                seen.append((_i, state, r, x))
                return idx, w, r
            gate.route_no_drop = route
        try:
            ids = np.arange(12, dtype=np.int32)[None]
            with no_grad():
                model(paddle.to_tensor(ids))
        finally:
            for gate in gates:
                del gate.route_no_drop
        assert [i for i, *_ in seen] == [0, 1, 2, 3]
        assert seen[0][1] is None
        for (_, state, r, x), (_, _, before, _) in zip(seen[1:], seen):
            assert state is before
        _, state, r, x = seen[2]
        g = gates[2]
        own = x._data @ g.down_weight._data + g.down_bias._data
        np.testing.assert_allclose(
            np.asarray(r._data), np.asarray(own + g.gamma._data
                                            * state._data), atol=1e-6)
        assert np.abs(np.asarray(r._data - own)).max() > 0.1

    def test_the_layer_hands_it_out_only_for_a_gate_that_carries_one(self):
        from paddle_tpu.incubate.distributed.models.moe import (
            DepthAveragedMLPGate, MoELayer, SigmoidTopKGate, SwiGLUExperts)
        x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
            (6, 64)).astype(np.float32))
        carried = MoELayer(64, SwiGLUExperts(4, 64, 32),
                           gate=DepthAveragedMLPGate(64, 4, 16),
                           held_experts=(0, 4))
        y, r = carried(x)
        assert tuple(y.shape) == (6, 64) and tuple(r.shape) == (6, 16)
        y2, r2 = carried(x, router_state=r)
        assert np.abs(np.asarray(r2._data - r._data)).max() > 0.1
        plain_gate = MoELayer(64, SwiGLUExperts(4, 64, 32),
                              gate=SigmoidTopKGate(64, 4, topk=1),
                              held_experts=(0, 4))
        assert tuple(plain_gate(x).shape) == (6, 64)


def ragged_logits(model, seqs, prompt, start_second_at=32):
    """Two sequences through the ragged program's logits escape hatch:
    prompts in chunks of 16 (the second entering while the first
    decodes), then one-token rows to the end.  [(position, logits)]."""
    cache = PagedKVCache.from_model(model, total_pages=32, page_size=16,
                                    state_slots=4)
    dec = JittedPagedDecoder(model)
    n = len(seqs[0])
    at, got = [0, 0], [[], []]
    while min(at) < n:
        ids, rows = [], []
        for i in (0, 1):
            if at[i] >= n or (i == 1 and at[0] < start_second_at):
                continue
            k = min(16, prompt[i] - at[i]) if at[i] < prompt[i] else 1
            ids.append(i)
            rows.append(seqs[i][at[i]:at[i] + k])
        out, _ = dec.ragged_step(cache, ids, rows, [at[i] for i in ids])
        for i, row, lg in zip(ids, rows, np.asarray(out)):
            at[i] += len(row)
            got[i].append((at[i] - 1, lg))
    return got, cache


class TestLogitsThroughTheRaggedStep:
    def test_chunked_prefill_then_decode_against_the_full_forward(
            self, model, beta):
        """Every step's logits are the reference's at that position to
        float32 rounding — across the chunk boundaries at 16 and 32 (the
        tails carry z, a and the shifted value over them, and the K the
        pages hold is a function of the tails) and across prefill ->
        decode — and no planted fault's, the tails zeroed at every 16th
        position (every chunk boundary) among them."""
        rng = np.random.default_rng(2)
        seqs = [rng.integers(0, 96, 60).astype(np.int32) for _ in range(2)]
        got, _ = ragged_logits(model, seqs, [41, 30])
        for i in (0, 1):
            pos = np.asarray([p for p, _ in got[i]])
            mine = np.stack([lg for _, lg in got[i]])
            ref = np.asarray(plain.forward_logits(model_cfg(), SEED, seqs[i],
                                                  beta=beta))[pos]
            assert np.abs(mine - ref).max() < 1e-5
            for fault, moves in FAULTS.items():
                off = np.asarray(plain.forward_logits(
                    model_cfg(), SEED, seqs[i], beta=beta, fault=fault))[pos]
                assert np.abs(mine - off).max() > moves, fault

    def test_the_chosen_experts_are_the_reference_s(self, model, beta):
        """At float32 the program's top-1 of every token of every layer is
        the reference's own, through chunk rows and one-token rows."""
        rng = np.random.default_rng(7)
        seqs = [rng.integers(0, 96, n).astype(np.int32) for n in (45, 37)]
        eng = engine(model)
        eng.stop()
        routed = driver.program_routing(eng, model, seqs, 16, 4)
        _, bounds, chosen, _ = plain.hidden_states(model_cfg(), SEED, seqs,
                                                   beta=beta)
        assert driver.flip_share(routed, chosen, bounds) == 0.0
        assert sorted(routed) == [0, 1, 2, 3]
        assert all(routed[i].shape == (82, 1) for i in routed)
        # and the bias did its work: no expert of the sample goes unused
        assert all(len(np.unique(routed[i])) == 4 for i in routed)


class TestTheShiftPrimitive:
    """``shift_step`` (``conv_step`` at two taps (1, 0)): a token's
    predecessor in its own sequence, the slot's tail moving on."""

    @staticmethod
    def run(pool, slots, ctx, q_lens, x, span, packed=True):
        i32 = lambda v: jnp.asarray(v, jnp.int32)           # noqa: E731
        off = i32(np.cumsum(q_lens) - q_lens) if packed else None
        y, pool = ss.shift_step(jnp.asarray(pool), i32(slots), i32(ctx),
                                i32(q_lens), off, jnp.asarray(x), span=span)
        return np.asarray(y), np.asarray(pool)

    @pytest.mark.parametrize("case", ["context_0_on_a_dirty_slot",
                                      "one_token_rows",
                                      "a_chunk_row_of_one_token",
                                      "a_pad_row_on_the_scratch_slot"])
    def test_rows(self, case):
        rng = np.random.default_rng(3)
        d = 8
        pool = rng.standard_normal((4, d)).astype(np.float32)   # 3 + scratch
        if case == "context_0_on_a_dirty_slot":
            # a row of 5 entering slot 2 at context 0 beside a row of 3
            # continuing slot 0: the first reads zero whatever slot 2 held
            x = rng.standard_normal((8, d)).astype(np.float32)
            y, new = self.run(pool, [2, 0], [0, 7], [5, 3], x, span=8)
            np.testing.assert_array_equal(y[0], 0)
            np.testing.assert_array_equal(y[1:5], x[0:4])
            np.testing.assert_array_equal(y[5], pool[0])
            np.testing.assert_array_equal(y[6:8], x[5:7])
            np.testing.assert_array_equal(new[2], x[4])
            np.testing.assert_array_equal(new[0], x[7])
            np.testing.assert_array_equal(new[[1, 3]], pool[[1, 3]])
        elif case == "one_token_rows":
            x = rng.standard_normal((3, d)).astype(np.float32)
            y, new = self.run(pool, [1, 0, 2], [4, 0, 9], [1, 1, 1], x,
                              span=1, packed=False)
            np.testing.assert_array_equal(y[0], pool[1])
            np.testing.assert_array_equal(y[1], 0)      # context 0
            np.testing.assert_array_equal(y[2], pool[2])
            np.testing.assert_array_equal(new[[1, 0, 2]], x)
        elif case == "a_chunk_row_of_one_token":
            # a chunk row shorter than the tail's reach: ONE token in a
            # step whose span is 4 reads its slot and replaces it
            x = rng.standard_normal((5, d)).astype(np.float32)
            y, new = self.run(pool, [1, 2], [6, 3], [1, 4], x, span=4)
            np.testing.assert_array_equal(y[0], pool[1])
            np.testing.assert_array_equal(y[1], pool[2])
            np.testing.assert_array_equal(y[2:5], x[1:4])
            np.testing.assert_array_equal(new[1], x[0])
            np.testing.assert_array_equal(new[2], x[4])
        else:
            x = rng.standard_normal((2, d)).astype(np.float32)
            y, new = self.run(pool, [0, 3], [2, 0], [1, 1], x, span=1,
                              packed=False)
            np.testing.assert_array_equal(new[[1, 2]], pool[[1, 2]])
            np.testing.assert_array_equal(y[0], pool[0])

    def test_it_is_the_convolution_at_two_taps(self):
        rng = np.random.default_rng(4)
        x = rng.standard_normal((20, 8)).astype(np.float32)
        taps = np.stack([np.ones(8), np.zeros(8)]).astype(np.float32)
        want, tail = ss.conv_recurrence(jnp.asarray(x), jnp.asarray(taps),
                                        jnp.zeros(8))
        np.testing.assert_array_equal(np.asarray(want)[1:], x[:-1])
        y1, pool = self.run(np.ones((2, 8), np.float32), [0], [0], [12],
                            x[:12], span=16)
        y2, pool = self.run(pool, [0], [12], [8], x[12:], span=8)
        np.testing.assert_array_equal(np.concatenate([y1, y2]),
                                      np.asarray(want))
        np.testing.assert_array_equal(pool[0], np.asarray(tail)[0])


class TestServedThroughTheEngine:
    @pytest.fixture(scope="class")
    def served(self, model):
        """8 requests over 4 slots, chunked 16 tokens a step under a
        decode batch of up to 4: every prompt crosses a chunk boundary,
        the later four enter slots the first four used; the ring
        captured."""
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 96, n).astype(np.int32)
                   for n in (25, 64, 33, 41, 50, 27, 61, 38)]
        eng = engine(model)
        assert eng.prefix_cache is False        # turned off, not refused
        assert eng.cache.state_slots == 4 and eng.cache.num_layers == 4
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
            eng.stop()
            monitor.stop_capture()
        steps = monitor.get_tracer().step_records()
        seqs = [(p, np.asarray(o[len(p):], np.int32))
                for p, o in zip(prompts, outs)]
        return seqs, steps, before, monitor.snapshot()

    def test_served_logits_match_the_reference_s_full_forward(self, served,
                                                               beta):
        seqs, *_ = served
        assert all(len(s) == 14 for _, s in seqs)
        gaps, _, _ = gaps_of(seqs, beta)
        assert np.concatenate(gaps).max() < 1e-5

    def test_the_ring_counts_experts_rows_and_bytes(self, served):
        _, steps, *_ = served
        recs = [r for r in steps if r["kind"] == "dispatch"]
        assert recs and any(r["span_padded"] > 1 and r["rows"] > 1
                            for r in recs)      # chunk and decode rows mixed
        for r in recs:
            # a slot a row in every one of the 4 layers, three tails each
            assert r["state_rows"] == r["rows"] <= r["state_slots"] == 4
            assert r["state_bytes"] == 2 * r["rows"] * 4 * STATE_BYTES
            # top-1: a pair a real token a layer; pads routed nowhere
            assert r["moe_slots"] == 4 * r["tokens"]
            assert r["moe_expert_layers"] == 4 * 4
            assert 4 <= r["moe_experts_touched"] <= min(16, r["moe_slots"])
            assert r["moe_slots"] / 4 <= r["moe_max_expert_pairs"] \
                <= r["moe_slots"]
            assert r["moe_rows_computed"] % 16 == 0 \
                and r["moe_rows_computed"] >= r["moe_slots"]
            assert r["kv_tokens_walked"] > 0
        assert sum(r["slots_zeroed"] for r in recs) == 8

    def test_the_registry_sums_what_the_ring_says(self, served):
        _, steps, before, after = served
        recs = [r for r in steps if r["kind"] == "dispatch"]

        def moved(name):
            return total(after, name) - total(before, name)

        for field in ("moe_max_expert_pairs", "moe_slots",
                      "moe_experts_touched", "state_bytes"):
            assert moved(f"serve_{field}_total") \
                == sum(r[field] for r in recs), field
        assert moved("recurrent_slots_taken_total") == 8
        assert moved("recurrent_slots_zeroed_total") == 8
        assert total(after, "recurrent_slots_in_use") == 0

    def test_the_ragged_program_audits_clean(self, model):
        from paddle_tpu.analysis import audit_engine
        eng = engine(model)
        try:
            audit = audit_engine(eng, mode="ragged")
        finally:
            eng.stop()
        assert not audit.findings, [f.rule for f in audit.findings]


class TestPoolsAndSlots:
    def test_every_layer_appends_to_its_own_pool_and_moves_its_slot(
            self, model):
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
        first = cache._seq_pages[10][0]
        for layer in range(4):
            k = np.asarray(cache.k_pages[layer])
            assert np.abs(k[:, first, :9]).min() > 0
            assert not k[:, first, 9:].any()
        idle = (set(range(4)) - {cache.slot_of(s) for s in (10, 11, 12)}).pop()
        assert len(cache.state_pools) == 8
        for before, pool in zip(held, cache.state_pools):
            np.testing.assert_array_equal(np.asarray(pool)[idle],
                                          before[idle])     # and the scratch
            assert not np.array_equal(np.asarray(pool)[cache.slot_of(10)],
                                      before[cache.slot_of(10)])

    def test_a_slot_taken_again_starts_from_zero(self, model, beta):
        """One slot: the second request enters what the first left, and
        worse (every slot pool overwritten with 1e3 between the two): its
        first token's convolutions, value and so its PAGES read zero."""
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
        assert gap(a, out_a, beta) < 1e-5 and gap(b, out_b, beta) < 1e-5


class TestPreemptAndResume:
    @pytest.mark.parametrize("when", ["mid_prefill", "mid_decode"])
    def test_preempt_and_resume_give_the_same_tokens(self, model, beta,
                                                     when):
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
        assert gap(p, out_b, beta) < 1e-5
        assert gap(out_i[:5], out_i, beta) < 1e-5
        after = monitor.snapshot()
        for name in ("recurrent_slots_taken_total",
                     "recurrent_slots_zeroed_total"):
            assert total(after, name) - total(before, name) == 3, name


class TestWhatCannotHoldRefuses:
    @pytest.mark.parametrize("kw, reason", [
        (dict(draft_model="model"), "rolled out of it"),
        (dict(kv_quant="int8"), "not been held to a reference in int8"),
        (dict(tp=2), "an expert block: the plan has no placement"),
        (dict(prefill_chunk_tokens=None), "only the ragged unified step"),
    ])
    def test_at_construction(self, model, kw, reason):
        if kw.get("draft_model"):
            kw = dict(kw, draft_model=model)
        with pytest.raises(ValueError, match=reason):
            engine(model, **kw)

    def test_the_paged_generator(self, model):
        gen = PagedGenerator(model, total_pages=8, page_size=16)
        with pytest.raises(NotImplementedError,
                           match="convolutional-attention layer"):
            gen.generate(np.arange(12, dtype=np.int32)[None],
                         max_new_tokens=2)

    @pytest.mark.parametrize("path", ["prefill", "chunk_prefill",
                                      "batch_context_prefill", "step"])
    def test_the_programs_that_carry_no_slots(self, model, path):
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=16,
                                        state_slots=2)
        dec = JittedPagedDecoder(model)
        ids = np.arange(12, dtype=np.int32)[None]
        with pytest.raises(NotImplementedError,
                           match="convolutional-attention layer"):
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

    @pytest.mark.parametrize("kw, reason", [
        (dict(layer_types=["hybrid", "full", "hybrid", "hybrid"]),
         "all 'hybrid'"),
        (dict(sliding_window=512), "none is windowed"),
        (dict(cca_time0=4), "two taps wide"),
        (dict(num_experts_per_tok=2), "one expert a token"),
        (dict(tie_word_embeddings=False), "a tied head"),
        (dict(num_key_value_heads=1), "an even number of KV heads"),
    ])
    def test_a_config_the_model_is_not(self, kw, reason):
        with pytest.raises(NotImplementedError, match=reason):
            ZayaConfig(**dict(TINY, **kw))
