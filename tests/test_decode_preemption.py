"""Closed-loop overload protection (ISSUE 19): decode-time preemption
bit-exactness, the TPOT feedback trigger, SLO-aware admission shedding,
and the brownout ladder's engine-visible state.

The acceptance spine: a DECODING row paused for urgent traffic resumes
bit-identical to an uninterrupted run — greedy, sampled, on a
prefix-cache hit, and with a draft model attached, composed with
chunked prefill and the unified ragged step — and the admission
controller sheds doomed work on arrival with a truthful Retry-After
instead of queueing it to time out.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults
from paddle_tpu.inference.continuous import (ContinuousBatchingEngine,
                                             EngineSaturated)
from paddle_tpu.inference.scheduler import PriorityClass

import time


def tiny_model(vocab=64, layers=1, seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=layers,
                      num_attention_heads=2, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def model():
    return tiny_model()


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.clear()


def reference(model, prompt, max_new_tokens):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=max_new_tokens)
    out = out.numpy() if hasattr(out, "numpy") else np.asarray(out)
    return out[0]


def wait_for(cond, timeout=120.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


def make_engine(model, **kw):
    kw.setdefault("total_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    return ContinuousBatchingEngine(model, **kw)


def counter_value(name, **labels):
    m = monitor.get_registry().get(name)
    return 0.0 if m is None else m.value(**labels)


class TestDecodePreemptBitExact:
    def _decode_preempt_run(self, model, prompt, max_new,
                            submit_kw=None, **engine_kw):
        """Drive one batch-class request INTO decode, preempt it
        mid-decode with interactive traffic (max_batch=1 guarantees the
        only possible victim is the decoding row), and return its
        output.  Asserts the preemption actually happened via the
        decode_preemptions_total counter."""
        before = counter_value("decode_preemptions_total")
        rng = np.random.default_rng(11)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.02}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1, **engine_kw) as eng:
                rb = eng.submit(prompt, max_new_tokens=max_new,
                                priority="batch", **(submit_kw or {}))
                wait_for(lambda: len(rb.generated) >= 2,
                         msg="victim decoding")
                ri = eng.submit(rng.integers(0, 64, (5,)),
                                max_new_tokens=2, priority="interactive")
                ri.result(timeout=300)
                got_b = rb.result(timeout=300)
                wait_for(lambda: eng.cache.free_pages
                         == eng.cache.total_pages, msg="pool reclaim")
        assert counter_value("decode_preemptions_total") > before
        assert ri.finished_at < rb.finished_at
        return got_b

    def test_greedy_bit_identical(self, model):
        rng = np.random.default_rng(20)
        p = rng.integers(0, 64, (24,)).astype("int32")
        want = reference(model, p, 10)
        got = self._decode_preempt_run(model, p, 10)
        np.testing.assert_array_equal(got, want)

    def test_sampled_bit_identical(self, model):
        """The on-device sampler is keyed by (seed, absolute position),
        so a mid-decode pause cannot perturb the sample stream."""
        rng = np.random.default_rng(21)
        p = rng.integers(0, 64, (16,)).astype("int32")
        with make_engine(model, max_batch=1) as eng:
            want = eng.submit(p, max_new_tokens=10, do_sample=True,
                              temperature=0.8,
                              seed=123).result(timeout=300)
        got = self._decode_preempt_run(
            model, p, 10,
            submit_kw=dict(do_sample=True, temperature=0.8, seed=123))
        np.testing.assert_array_equal(got, want)

    def test_prefix_hit_bit_identical(self, model):
        """A victim admitted ON a prefix-cache hit keeps the shared
        pages across the pause (hits are output-invariant)."""
        rng = np.random.default_rng(22)
        system = rng.integers(0, 64, (16,)).astype("int32")
        sharer = np.concatenate(
            [system, rng.integers(0, 64, (9,))]).astype("int32")
        want = reference(model, sharer, 10)
        before = counter_value("decode_preemptions_total")
        irng = np.random.default_rng(23)
        with make_engine(model, max_batch=1) as eng:
            seed_p = np.concatenate(
                [system, rng.integers(0, 64, (3,))]).astype("int32")
            eng.submit(seed_p, max_new_tokens=2).result(timeout=300)
            plan = faults.FaultPlan([
                {"site": "decode_step", "kind": "delay",
                 "delay_s": 0.02}])
            with faults.installed(plan):
                rb = eng.submit(sharer, max_new_tokens=10,
                                priority="batch")
                wait_for(lambda: len(rb.generated) >= 2,
                         msg="sharer decoding")
                ri = eng.submit(irng.integers(0, 64, (5,)),
                                max_new_tokens=2, priority="interactive")
                ri.result(timeout=300)
                got = rb.result(timeout=300)
            assert rb.prefix_tokens == 16
        assert counter_value("decode_preemptions_total") > before
        np.testing.assert_array_equal(got, want)

    def test_draft_attached_bit_identical(self, model):
        """A speculating victim pauses mid-decode with BOTH caches
        (target + draft) kept and resumes still speculating."""
        draft = tiny_model(seed=0)       # clone: accept ~1.0
        rng = np.random.default_rng(24)
        p = rng.integers(0, 64, (20,)).astype("int32")
        want = reference(model, p, 12)
        got = self._decode_preempt_run(
            model, p, 12, submit_kw=dict(draft=True),
            draft_model=draft, spec_tokens=2, draft_total_pages=64)
        np.testing.assert_array_equal(got, want)

    def test_composes_with_chunked_prefill(self, model):
        """ISSUE 7's chunked prefill and ISSUE 19's decode preemption
        are orthogonal: a victim that prefilled in chunks still pauses
        mid-decode and resumes bit-exactly."""
        rng = np.random.default_rng(25)
        p = rng.integers(0, 64, (40,)).astype("int32")
        want = reference(model, p, 8)
        got = self._decode_preempt_run(model, p, 8,
                                       prefill_chunk_tokens=8)
        np.testing.assert_array_equal(got, want)

    def test_host_sampling_path(self, model):
        """With sampling on the host (logits rows cross, nothing is in
        flight) a victim pauses mid-decode and resumes identically."""
        rng = np.random.default_rng(26)
        p = rng.integers(0, 64, (24,)).astype("int32")
        want = reference(model, p, 8)
        got = self._decode_preempt_run(model, p, 8,
                                       sample_on_device=False)
        np.testing.assert_array_equal(got, want)

    def test_decode_preempt_off_preserves_run_to_completion(self, model):
        """The opt-out: with decode_preempt=False a decoding row is
        never a victim — interactive traffic waits for it (the pre-
        ISSUE-19 behavior)."""
        rng = np.random.default_rng(27)
        p = rng.integers(0, 64, (16,)).astype("int32")
        before = counter_value("decode_preemptions_total")
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.02}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1,
                             decode_preempt=False) as eng:
                rb = eng.submit(p, max_new_tokens=8, priority="batch")
                wait_for(lambda: len(rb.generated) >= 2,
                         msg="victim decoding")
                ri = eng.submit(rng.integers(0, 64, (5,)),
                                max_new_tokens=2, priority="interactive")
                ri.result(timeout=300)
                rb.result(timeout=300)
                assert rb.finished_at < ri.finished_at
        assert counter_value("decode_preemptions_total") == before


class TestTpotTrigger:
    def test_tpot_breach_pauses_least_urgent_decoder(self, model):
        """At full occupancy, an interactive row whose measured TPOT
        breaches its budget evicts the least-urgent decoding row; the
        victim stays parked while the breach persists and resumes
        bit-exactly once the urgent row retires."""
        classes = (
            PriorityClass("interactive", rank=0, weight=8,
                          tpot_budget_s=1e-4),
            PriorityClass("standard", rank=1, weight=4),
            PriorityClass("batch", rank=2, weight=1, preemptible=True),
        )
        rng = np.random.default_rng(30)
        p = rng.integers(0, 64, (16,)).astype("int32")
        want = reference(model, p, 10)
        before = counter_value("decode_preemptions_total")
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.02}])
        with faults.installed(plan):
            with make_engine(model, max_batch=2,
                             scheduler_classes=classes,
                             default_class="standard",
                             tpot_preempt_cooldown_s=0.0) as eng:
                rb = eng.submit(p, max_new_tokens=10, priority="batch")
                wait_for(lambda: len(rb.generated) >= 2,
                         msg="victim decoding")
                # admits into the FREE slot -> occupancy 2/2; only the
                # TPOT trigger, not slot pressure, can evict the victim
                ri = eng.submit(rng.integers(0, 64, (5,)),
                                max_new_tokens=6, priority="interactive")
                ri.result(timeout=300)
                got = rb.result(timeout=300)
        assert counter_value("decode_preemptions_total") > before
        np.testing.assert_array_equal(got, want)


class TestSLOAdmission:
    def test_doomed_arrival_sheds_with_truthful_retry_after(self, model):
        """A class whose projected queue wait (depth x decode p50)
        already exceeds its deadline budget sheds ON ARRIVAL: the
        request never holds pages, the 429 carries a Retry-After, and
        the shed is counted per class."""
        classes = (
            PriorityClass("interactive", rank=0, weight=8),
            PriorityClass("standard", rank=1, weight=4),
            PriorityClass("batch", rank=2, weight=1, preemptible=True,
                          deadline_s=1e-9),
        )
        rng = np.random.default_rng(31)
        shed_before = counter_value("sched_shed_on_arrival_total",
                                    cls="batch")
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.02}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1,
                             scheduler_classes=classes,
                             default_class="standard") as eng:
                # one completed request guarantees the process-global
                # decode-step histogram has a p50 for the projection
                eng.submit(rng.integers(0, 64, (6,)),
                           max_new_tokens=3).result(timeout=300)
                r1 = eng.submit(rng.integers(0, 64, (8,)),
                                max_new_tokens=8, priority="batch")
                wait_for(lambda: len(r1.generated) >= 1,
                         msg="slot occupied")
                # depth 0 at check time -> projected wait 0 -> admitted
                r2 = eng.submit(rng.integers(0, 64, (8,)),
                                max_new_tokens=2, priority="batch")
                # depth 1 -> projected = 1 x p50 > 1ns budget -> shed
                with pytest.raises(EngineSaturated) as ei:
                    eng.submit(rng.integers(0, 64, (8,)),
                               max_new_tokens=2, priority="batch")
                assert ei.value.priority_class == "batch"
                assert 1 <= ei.value.retry_after_s <= 30
                # admitted work is untouched by the shed
                r1.result(timeout=300)
                r2.result(timeout=300)
        assert counter_value("sched_shed_on_arrival_total",
                             cls="batch") > shed_before

    def test_budgetless_classes_never_shed(self, model):
        """No deadline budget, no brownout -> the controllers are off
        and deep queues behave exactly as before ISSUE 19."""
        rng = np.random.default_rng(32)
        shed_before = counter_value("sched_shed_on_arrival_total",
                                    cls="batch")
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.02}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1) as eng:
                reqs = [eng.submit(rng.integers(0, 64, (6,)),
                                   max_new_tokens=2, priority="batch")
                        for _ in range(4)]
                for r in reqs:
                    r.result(timeout=300)
        assert counter_value("sched_shed_on_arrival_total",
                             cls="batch") == shed_before


class TestBrownoutLadder:
    def test_ladder_escalates_under_pressure_and_recovers(self, model):
        """Queue pressure climbs the ladder (gauge + /health state);
        an idle engine de-escalates back to rung 0 so a latched level
        can never shed the NEXT burst's first arrivals."""
        rng = np.random.default_rng(33)
        trans_before = counter_value("engine_brownout_transitions_total")
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.03}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1, max_queue=8,
                             brownout_thresholds=(0.25, 0.5, 0.75, 0.95),
                             brownout_patience=2) as eng:
                assert eng.scheduler_info()["brownout_enabled"]
                reqs = [eng.submit(rng.integers(0, 64, (6,)),
                                   max_new_tokens=4,
                                   priority="interactive")
                        for _ in range(5)]
                wait_for(lambda: eng.scheduler_info()["brownout_level"]
                         >= 1, msg="ladder escalation")
                assert counter_value(
                    "engine_brownout_transitions_total") > trans_before
                for r in reqs:
                    r.result(timeout=300)
                # drained + idle -> the loop resets the ladder
                wait_for(lambda: eng.scheduler_info()["brownout_level"]
                         == 0, timeout=10.0, msg="ladder recovery")

    def test_brownout_band_sheds_lower_ranks_only(self, model):
        """Rung 1 sheds the least-urgent rank band on arrival while the
        top class still admits (degrade, don't fail)."""
        rng = np.random.default_rng(34)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.03}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1, max_queue=4,
                             brownout_thresholds=(0.25, 2.0, 2.0, 2.0),
                             brownout_patience=64) as eng:
                reqs = [eng.submit(rng.integers(0, 64, (6,)),
                                   max_new_tokens=4,
                                   priority="interactive")
                        for _ in range(3)]
                wait_for(lambda: eng.scheduler_info()["brownout_level"]
                         >= 1, msg="rung 1")
                with pytest.raises(EngineSaturated) as ei:
                    eng.submit(rng.integers(0, 64, (6,)),
                               max_new_tokens=2, priority="batch")
                assert ei.value.priority_class == "batch"
                # the top rank band still admits at rung 1
                ok = eng.submit(rng.integers(0, 64, (6,)),
                                max_new_tokens=2, priority="interactive")
                for r in reqs + [ok]:
                    r.result(timeout=300)
