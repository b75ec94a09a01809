"""Serving resilience (ISSUE 4): request lifecycle, graceful drain,
failure isolation (quarantine instead of fail-all), stall detection,
and the deterministic fault-injection harness driving them.

The acceptance scenario: with a fault plan injecting one prefill
exception and one decode-step exception into a 6-request mixed
workload, exactly the poisoned request(s) error; everyone else
completes with outputs equal to the reference generate, the pool
drains to fully reclaimed, and ``monitor.snapshot()`` carries matching
quarantine/retry counters.  SIGTERM under load drains in-flight
requests to completion while new submissions get 429/503.
"""
import json
import signal
import threading
import time
import urllib.error
import urllib.request
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults


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


def counter_value(name):
    m = monitor.get_registry().get(name)
    return 0.0 if m is None else m.value()


def reference(model, prompt, max_new_tokens):
    out = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                         max_new_tokens=max_new_tokens)
    out = out.numpy() if hasattr(out, "numpy") else np.asarray(out)
    return out[0]


def wait_for(cond, timeout=60.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def make_engine(model, **kw):
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    kw.setdefault("total_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    return ContinuousBatchingEngine(model, **kw)


class TestFaultPlan:
    def test_nth_fires_exactly_once(self):
        plan = faults.FaultPlan([{"site": "prefill", "nth": 2}])
        with faults.installed(plan):
            faults.maybe_fire("prefill", seq_ids=[0])
            with pytest.raises(faults.FaultError):
                faults.maybe_fire("prefill", seq_ids=[1])
            faults.maybe_fire("prefill", seq_ids=[2])      # spent
        faults.maybe_fire("prefill")                       # plan cleared

    def test_seq_targeted_rule_is_sticky(self):
        plan = faults.FaultPlan([
            {"site": "decode_step", "seq_id": 3, "kind": "error"}])
        with faults.installed(plan):
            faults.maybe_fire("decode_step", seq_ids=[0, 1])   # no match
            for _ in range(3):                                 # sticky
                with pytest.raises(faults.FaultError):
                    faults.maybe_fire("decode_step", seq_ids=[2, 3])

    def test_delay_rule_sleeps_without_raising(self):
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.05,
             "nth": 1}])
        with faults.installed(plan):
            t0 = time.monotonic()
            faults.maybe_fire("decode_step", seq_ids=[0])
            assert time.monotonic() - t0 >= 0.05

    def test_probability_rule_is_seed_deterministic(self):
        def shots(seed):
            plan = faults.FaultPlan(
                [{"site": "page_alloc", "probability": 0.5}], seed=seed)
            out = []
            for _ in range(32):
                try:
                    plan.fire("page_alloc")
                    out.append(0)
                except faults.FaultError:
                    out.append(1)
            return out

        assert shots(7) == shots(7)
        assert 0 < sum(shots(7)) < 32

    def test_json_roundtrip_and_validation(self):
        plan = faults.FaultPlan.from_json(
            json.dumps({"seed": 3, "rules": [{"site": "http_handler"}]}))
        assert plan.seed == 3 and plan.rules[0].site == "http_handler"
        with pytest.raises(ValueError, match="site"):
            faults.FaultPlan([{"site": "nope"}])
        with pytest.raises(ValueError, match="kind"):
            faults.FaultPlan([{"site": "prefill", "kind": "explode"}])

    def test_journal_sites_registered_and_free_when_disabled(self):
        # ISSUE 13 satellite: the durability fault sites exist, accept
        # rules, and cost one global None check when no plan is active
        for site in ("journal_write", "journal_fsync"):
            assert site in faults.SITES
            faults.FaultPlan([{"site": site, "nth": 1}])
        assert faults.active() is None
        faults.maybe_fire("journal_write")      # no plan: pure no-op
        faults.maybe_fire("journal_fsync")


class TestLifecycle:
    def test_deadline_expiry_frees_reserved_pages(self, model):
        rng = np.random.default_rng(0)
        with make_engine(model, total_pages=16, max_batch=2) as eng:
            # worst case 8 pages reserved at admission; the TTL expires
            # long before 60 tokens decode
            r = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=60,
                           ttl_s=0.3)
            with pytest.raises(Exception, match="TTL"):
                r.result(timeout=120)
            from paddle_tpu.inference.continuous import DeadlineExceeded
            assert isinstance(r.error, DeadlineExceeded)
            # it WAS decoding: the first token was sampled (prefill
            # completed, TTFT stamped) but the budget was far from
            # exhausted.  Under the unified step (ISSUE 17) expiry can
            # land between prefill completion and the first decode
            # iteration, when `generated` is still empty — so the
            # progress evidence is the stamped first token, not a
            # non-empty `generated`.
            assert r.first_token_at is not None
            assert len(r.generated) < 60
            # its worst-case reservation and pages came back
            wait_for(lambda: eng.cache.free_pages == 16,
                     msg="pool reclaim after TTL expiry")
            assert eng._reserved_pages == 1
            # ... so a blocked successor can now admit and finish
            ok = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4)
            assert len(ok.result(timeout=120)) == 8

    def test_queue_wait_deadline_rejects_unadmitted(self, model):
        rng = np.random.default_rng(1)
        with make_engine(model, max_batch=1) as eng:
            r1 = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=40)
            wait_for(lambda: r1.seq_id is not None, msg="r1 admission")
            r2 = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4,
                            queue_timeout_s=0.2)
            with pytest.raises(Exception, match="queue-wait"):
                r2.result(timeout=60)
            assert r2.seq_id is None             # never admitted
            r1.cancel()

    def test_cancel_mid_decode_frees_pages(self, model):
        rng = np.random.default_rng(2)
        with make_engine(model) as eng:
            r = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=60)
            wait_for(lambda: r.first_token_at is not None,
                     msg="decode start")
            assert r.cancel()
            from paddle_tpu.inference.continuous import RequestCancelled
            with pytest.raises(RequestCancelled):
                r.result(timeout=60)
            assert len(r.generated) < 60
            wait_for(lambda: eng.cache.free_pages == 64,
                     msg="pool reclaim after cancel")
            assert eng._reserved_pages == 1

    def test_result_timeout_cancels_by_default(self, model):
        """Satellite: a timed-out ``result()`` must not leave the
        sequence decoding (and holding pool pages) forever."""
        rng = np.random.default_rng(3)
        with make_engine(model) as eng:
            r = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=100)
            with pytest.raises(TimeoutError, match="cancelled"):
                r.result(timeout=0.02)
            # the scheduler reaps the cancelled request and reclaims
            wait_for(r.done.is_set, msg="reap after timeout-cancel")
            wait_for(lambda: eng.cache.free_pages == 64,
                     msg="pool reclaim after timeout-cancel")
            # opt-out keeps the request running to completion
            r2 = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=24)
            with pytest.raises(TimeoutError):
                r2.result(timeout=0.02, cancel_on_timeout=False)
            assert len(r2.result(timeout=120)) == 28

    def test_bounded_queue_saturation(self, model):
        from paddle_tpu.inference.continuous import EngineSaturated
        rng = np.random.default_rng(4)
        with make_engine(model, max_batch=1, max_queue=1) as eng:
            r1 = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=60)
            wait_for(lambda: r1.seq_id is not None, msg="r1 admission")
            eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4)
            before = counter_value("engine_saturated_total")
            with pytest.raises(EngineSaturated):
                eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4)
            assert counter_value("engine_saturated_total") == before + 1
            r1.cancel()


class TestDrain:
    def test_drain_under_load_completes_all_admitted(self, model):
        from paddle_tpu.inference.continuous import EngineDraining
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 64, (4,)).astype("int32")
                   for _ in range(4)]
        eng = make_engine(model, max_batch=2)
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        assert eng.drain(timeout=300)
        # every already-submitted request (queued AND active at drain
        # time) ran to completion — full budget, no error (output
        # correctness under faults is locked by TestChaosRegression)
        for r in reqs:
            assert len(r.result(timeout=1)) == 12
        assert eng.cache.free_pages == 64
        assert eng._reserved_pages == 1
        with pytest.raises(EngineDraining):
            eng.submit(prompts[0], max_new_tokens=4)

    def test_drain_timeout_returns_false_but_keeps_draining(self, model):
        rng = np.random.default_rng(6)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.02}])
        with faults.installed(plan):
            eng = make_engine(model, max_batch=2)
            r = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=32)
            assert eng.drain(timeout=0.05) is False
            assert eng.draining
            assert eng.drain(timeout=300) is True
            assert len(r.result(timeout=1)) == 36

    def test_drain_reject_queued_fails_fast_keeps_admitted(self, model):
        # ROADMAP PR 4 follow-up (b): the hard-preemption fast path —
        # queued-but-unadmitted requests error immediately with
        # EngineDraining while the admitted request finishes its full
        # budget
        from paddle_tpu.inference.continuous import EngineDraining
        rng = np.random.default_rng(21)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.01}])
        before = counter_value("drain_rejected_requests_total")
        with faults.installed(plan):
            eng = make_engine(model, max_batch=1)
            r1 = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=24)
            wait_for(lambda: r1.seq_id is not None, msg="r1 admission")
            queued = [eng.submit(rng.integers(0, 64, (4,)),
                                 max_new_tokens=4) for _ in range(2)]
            assert eng.drain(timeout=300, reject_queued=True)
            for q in queued:                  # failed fast, never admitted
                with pytest.raises(EngineDraining):
                    q.result(timeout=1)
                assert q.seq_id is None
            assert len(r1.result(timeout=1)) == 28   # full budget
        assert counter_value("drain_rejected_requests_total") == before + 2
        assert eng.cache.free_pages == 64             # pool reclaimed


def dispatch_counts():
    """engine_dispatches_total by mode, every label the counter has
    ever had (a mode that is gone must stay at zero)."""
    m = monitor.get_registry().get("engine_dispatches_total")
    return {mode: (0.0 if m is None else m.value(mode=mode))
            for mode in ("ragged", "prefill", "chunk", "decode", "verify")}


def moved(before, after):
    return {m for m in after if after[m] != before[m]}


class TestQuarantine:
    @pytest.mark.parametrize("chunk", [None, 8],
                             ids=["whole_prompt", "chunked"])
    def test_poisoned_prefill_errors_only_that_request(self, model, chunk):
        """A prefill fault that FOLLOWS a request (seq 1 = the second
        admission) quarantines exactly it, whichever way its prompt
        reaches the device: whole, on the prefill program (first
        failure quarantines), or a chunk row of the ragged step (the
        step is retried whole, then by halves, and the row that fails
        alone goes)."""
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(3)]
        expects = [reference(model, p, 6) for p in prompts]
        before_q = counter_value("quarantined_requests_total")
        before_d = dispatch_counts()
        plan = faults.FaultPlan([{"site": "prefill", "seq_id": 1}])
        with faults.installed(plan):
            with make_engine(model, prefill_chunk_tokens=chunk) as eng:
                reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
                with pytest.raises(faults.FaultError):
                    reqs[1].result(timeout=120)
                for i in (0, 2):
                    np.testing.assert_array_equal(
                        reqs[i].result(timeout=120), expects[i])
                wait_for(lambda: eng.cache.free_pages == 64,
                         msg="pool reclaim")
                assert eng._reserved_pages == 1
        assert counter_value("quarantined_requests_total") == before_q + 1
        assert moved(before_d, dispatch_counts()) == (
            {"ragged"} if chunk else {"ragged", "prefill"})

    def test_one_shot_prefill_fault_recovers_and_quarantines_nobody(
            self, model):
        """A chunk row's transient fault (the 2nd admission's first
        chunk, once) fails its step; the step is retried whole on the
        ragged program and everyone finishes with the reference's
        tokens."""
        rng = np.random.default_rng(14)
        prompts = [rng.integers(0, 64, (n,)).astype("int32")
                   for n in (5, 20, 9)]
        expects = [reference(model, p, 6) for p in prompts]
        before_q = counter_value("quarantined_requests_total")
        before_r = counter_value("decode_retries_total")
        before_f = counter_value("engine_unified_fallbacks_total")
        before_d = dispatch_counts()
        plan = faults.FaultPlan([{"site": "prefill", "nth": 2}])
        with faults.installed(plan):
            with make_engine(model, prefill_chunk_tokens=8) as eng:
                reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
                for r, want in zip(reqs, expects):
                    np.testing.assert_array_equal(
                        r.result(timeout=120), want)
                wait_for(lambda: eng.cache.free_pages == 64,
                         msg="pool reclaim")
                assert eng._reserved_pages == 1
        assert [f[0] for f in plan.fired] == ["prefill"]
        assert counter_value("quarantined_requests_total") == before_q
        assert counter_value("decode_retries_total") == before_r + 1
        assert counter_value("engine_unified_fallbacks_total") \
            == before_f + 1
        assert moved(before_d, dispatch_counts()) == {"ragged"}

    def test_sticky_chunk_row_is_quarantined_alone_beside_decode_rows(
            self, model):
        """A fault that follows ONE chunk row of a step that also holds
        decode rows: the ladder bisects the step's rows, chunk and
        decode alike; the chunk row fails alone and goes with the error
        that killed it, the decode rows' tokens are exact."""
        rng = np.random.default_rng(15)
        short = [rng.integers(0, 64, (n,)).astype("int32")
                 for n in (5, 7)]
        long = rng.integers(0, 64, (30,)).astype("int32")
        expects = [reference(model, p, 12) for p in short]
        before_q = counter_value("quarantined_requests_total")
        before_r = counter_value("decode_retries_total")
        before_d = dispatch_counts()
        # seqs 0 and 1 decode; seq 2 is the long prompt, poisoned for
        # good.  The delay holds the decoders in flight while it is
        # admitted
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.01},
            {"site": "prefill_chunk", "seq_id": 2}])
        with faults.installed(plan):
            with make_engine(model, prefill_chunk_tokens=8) as eng:
                reqs = [eng.submit(p, max_new_tokens=12) for p in short]
                wait_for(lambda: all(len(r.generated) >= 1 for r in reqs),
                         msg="decode rows in flight")
                assert not any(r.done.is_set() for r in reqs)
                bad = eng.submit(long, max_new_tokens=4)
                with pytest.raises(faults.FaultError):
                    bad.result(timeout=120)
                for r, want in zip(reqs, expects):
                    np.testing.assert_array_equal(
                        r.result(timeout=120), want)
                wait_for(lambda: eng.cache.free_pages == 64,
                         msg="pool reclaim")
                assert eng._reserved_pages == 1
        assert counter_value("quarantined_requests_total") == before_q + 1
        # it failed BESIDE decode rows: a chunk row alone is one retry,
        # with one decode row it is the whole retry and two halves
        assert counter_value("decode_retries_total") >= before_r + 3
        assert moved(before_d, dispatch_counts()) == {"ragged"}

    def test_decode_bisection_ejects_poisoned_sharer(self, model):
        """A sticky mid-decode fault on one prefix-cache sharer: the
        bisection ejects exactly it; the healthy sharers keep their
        refcounted prefix pages and finish with correct outputs."""
        rng = np.random.default_rng(8)
        system = rng.integers(0, 64, (16,)).astype("int32")

        def sharer_prompt():
            return np.concatenate(
                [system, rng.integers(0, 64, (5,))]).astype("int32")

        prompts = [sharer_prompt() for _ in range(3)]
        expects = [reference(model, p, 6) for p in prompts]
        before_q = counter_value("quarantined_requests_total")
        before_r = counter_value("decode_retries_total")
        # seq 0 seeds the prefix; sharers are seqs 1..3 — poison seq 2
        plan = faults.FaultPlan([
            {"site": "decode_step", "seq_id": 2, "kind": "error"}])
        with faults.installed(plan):
            with make_engine(model) as eng:
                seed_prompt = np.concatenate(
                    [system, rng.integers(0, 64, (5,))]).astype("int32")
                eng.submit(seed_prompt, max_new_tokens=2).result(
                    timeout=120)
                reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
                with pytest.raises(faults.FaultError):
                    reqs[1].result(timeout=120)       # seq 2 = reqs[1]
                for i in (0, 2):
                    np.testing.assert_array_equal(
                        reqs[i].result(timeout=120), expects[i])
                # healthy sharers actually shared the cached prefix
                assert reqs[0].prefix_tokens == 16
                assert reqs[2].prefix_tokens == 16
                # all sequence refs released; the prefix KV survived the
                # quarantine (no pool reset) and stays reclaimable
                wait_for(lambda: not eng.cache._seq_refs,
                         msg="all sequence refs released")
                assert eng.cache.cached_prefix_pages > 0
                assert eng.cache.free_pages == 64
                assert eng._reserved_pages == 1
        assert counter_value("quarantined_requests_total") == before_q + 1
        assert counter_value("decode_retries_total") > before_r

    def test_transient_decode_fault_retries_and_recovers(self, model):
        rng = np.random.default_rng(9)
        p = rng.integers(0, 64, (5,)).astype("int32")
        want = reference(model, p, 8)
        before_r = counter_value("decode_retries_total")
        before_q = counter_value("quarantined_requests_total")
        plan = faults.FaultPlan([{"site": "decode_step", "nth": 3}])
        with faults.installed(plan):
            with make_engine(model) as eng:
                got = eng.submit(p, max_new_tokens=8).result(timeout=120)
        np.testing.assert_array_equal(got, want)
        assert counter_value("decode_retries_total") == before_r + 1
        assert counter_value("quarantined_requests_total") == before_q


class TestStallDetection:
    def test_injected_stall_fires_watchdog_counter(self, model):
        from paddle_tpu.distributed.watchdog import CommTaskManager
        rng = np.random.default_rng(10)
        mgr = CommTaskManager.instance()
        mgr._scan_interval = 0.05
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.8,
             "nth": 2}])
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with make_engine(model, max_batch=2,
                                 step_timeout_s=0.25) as eng:
                    # warm the compiled programs BEFORE arming the
                    # plan: a fresh engine's first step pays a
                    # trace/compile that can itself exceed the 0.25s
                    # heartbeat, firing a wedge of its own and making
                    # the nth=2 delay land on the recovery retry — a
                    # single-row batch then quarantines instead of
                    # recovering (order-dependent flake)
                    eng.submit(rng.integers(0, 64, (4,)),
                               max_new_tokens=2).result(timeout=120)
                    before = counter_value("comm_timeouts_total")
                    with faults.installed(plan):
                        r = eng.submit(rng.integers(0, 64, (4,)),
                                       max_new_tokens=6)
                        assert len(r.result(timeout=120)) == 10
                    assert counter_value("comm_timeouts_total") > before
                # heartbeat unregistered on stop: no stale probes
                assert not mgr._heartbeats
        finally:
            mgr.stop()

    def test_heartbeat_gauge_advances(self, model):
        rng = np.random.default_rng(11)
        with make_engine(model) as eng:
            t0 = time.time()
            eng.submit(rng.integers(0, 64, (4,)),
                       max_new_tokens=4).result(timeout=120)
        g = monitor.get_registry().get(
            "engine_last_step_timestamp_seconds")
        assert g is not None and g.value() >= t0 - 1.0


class TestChaosRegression:
    """The ISSUE 4 acceptance scenario, end to end."""

    def test_six_request_mixed_workload_isolates_the_poison(self, model):
        rng = np.random.default_rng(12)
        system = rng.integers(0, 64, (16,)).astype("int32")
        prompts = []
        for i in range(6):
            if i % 2 == 0:    # sharers
                prompts.append(np.concatenate(
                    [system, rng.integers(0, 64, (5,))]).astype("int32"))
            else:             # uniques
                prompts.append(
                    rng.integers(0, 64, (12,)).astype("int32"))
        expects = [reference(model, p, 6) for p in prompts]
        before_q = counter_value("quarantined_requests_total")
        before_r = counter_value("decode_retries_total")
        # one prefill exception (2nd admission = prompts[1]) and one
        # transient decode-step exception (absorbed by the retry)
        plan = faults.FaultPlan([
            {"site": "prefill", "nth": 2},
            {"site": "decode_step", "nth": 4},
        ])
        with faults.installed(plan):
            with make_engine(model, total_pages=128) as eng:
                reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
                errored = []
                for i, r in enumerate(reqs):
                    try:
                        np.testing.assert_array_equal(
                            r.result(timeout=300), expects[i])
                    except faults.FaultError:
                        errored.append(i)
                # exactly the poisoned request errored; everyone else
                # already compared equal to the reference above
                assert errored == [1]
                # the pool drains to fully reclaimed
                wait_for(lambda: eng.cache.free_pages == 128,
                         msg="pool reclaim")
                assert eng._reserved_pages == 1
        # matching counters in monitor.snapshot()
        assert counter_value("quarantined_requests_total") == before_q + 1
        assert counter_value("decode_retries_total") == before_r + 1

    def test_sigterm_under_load_drains_while_rejecting_new(self, model):
        """SIGTERM -> PreemptionHandler -> server drain: in-flight
        requests complete (200, correct outputs); new submissions are
        rejected with 429/503; /health reports the drain."""
        from paddle_tpu.inference import GenerationServer
        from paddle_tpu.distributed.fault_tolerance import \
            PreemptionHandler

        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, 64, (1, 5)).astype("int32")
                   for _ in range(2)]
        expects = [reference(model, p[0], 12) for p in prompts]
        # a sticky per-step delay keeps the engine busy long enough for
        # the signal to land mid-generation, deterministically
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.04}])
        handler = PreemptionHandler(signals=())
        results = [None, None]

        def client(i, srv):
            req = urllib.request.Request(
                f"http://{srv.host}:{srv.port}/generate",
                data=json.dumps({"input_ids": prompts[i].tolist(),
                                 "max_new_tokens": 12}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as resp:
                results[i] = (resp.status, json.loads(resp.read()))

        with faults.installed(plan):
            with GenerationServer(model, total_pages=64, page_size=8,
                                  max_batch=2) as srv:
                srv.attach_preemption(handler)
                threads = [threading.Thread(target=client, args=(i, srv))
                           for i in range(2)]
                for t in threads:
                    t.start()
                wait_for(lambda: len(srv._engine._active) >= 1,
                         msg="load admitted")
                # the preemption notice (SIGTERM path, delivered via the
                # handler seam so pytest's main thread stays signal-free)
                handler._on_signal(signal.SIGTERM, None)
                wait_for(lambda: srv.draining, msg="drain begin")
                # new submission while draining -> 429/503
                req = urllib.request.Request(
                    f"http://{srv.host}:{srv.port}/generate",
                    data=json.dumps({"input_ids": [[1, 2, 3]],
                                     "max_new_tokens": 4}).encode())
                with pytest.raises(urllib.error.HTTPError) as ei:
                    urllib.request.urlopen(req, timeout=60)
                assert ei.value.code in (429, 503)
                with urllib.request.urlopen(
                        f"http://{srv.host}:{srv.port}/health",
                        timeout=30) as resp:
                    health = json.loads(resp.read())
                assert health["draining"] is True
                assert health["status"] == "draining"
                for t in threads:
                    t.join(timeout=300)
                assert srv.wait_drained(timeout=300)
        for (status, body), want in zip(results, expects):
            assert status == 200
            np.testing.assert_array_equal(
                np.asarray(body["output_ids"][0]), want)


class TestServerErrorMapping:
    """Satellite: ValueError from submit (rope-table overflow) is the
    CLIENT's fault -> 400; page-pool exhaustion is capacity -> 503;
    queue overflow -> 429 + Retry-After."""

    def _post(self, srv, body, timeout=120):
        req = urllib.request.Request(
            f"http://{srv.host}:{srv.port}/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, json.loads(resp.read()), dict(
                    resp.headers)
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read()), dict(e.headers)

    def test_rope_overflow_400_pool_overflow_503(self, model):
        from paddle_tpu.inference import GenerationServer

        with GenerationServer(model, total_pages=8, page_size=8) as srv:
            # prompt + max_new_tokens past max_position_embeddings: the
            # request itself is invalid -> 400
            code, body, _ = self._post(
                srv, {"input_ids": [[1] * 40], "max_new_tokens": 100})
            assert code == 400
            assert "max_position" in body["error"]
            # fits the rope table but not this replica's page pool:
            # capacity -> 503 (retry elsewhere)
            code, body, _ = self._post(
                srv, {"input_ids": [[1] * 40], "max_new_tokens": 64})
            assert code == 503
            assert "pages" in body["error"]
            # the engine survived both rejections
            code, body, _ = self._post(
                srv, {"input_ids": [[1] * 4], "max_new_tokens": 2})
            assert code == 200 and body["new_tokens"] == 2

    def test_queue_overflow_429_with_retry_after(self, model):
        from paddle_tpu.inference import GenerationServer

        rng = np.random.default_rng(14)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.03}])
        results = []

        def client(srv, max_new):
            results.append(self._post(
                srv, {"input_ids":
                      rng.integers(0, 64, (1, 4)).tolist(),
                      "max_new_tokens": max_new}, timeout=300))

        with faults.installed(plan):
            with GenerationServer(model, total_pages=64, page_size=8,
                                  max_batch=1, max_queue=1) as srv:
                t1 = threading.Thread(target=client, args=(srv, 32))
                t1.start()
                wait_for(lambda: len(srv._engine._active) == 1,
                         msg="first request active")
                t2 = threading.Thread(target=client, args=(srv, 4))
                t2.start()
                wait_for(lambda: len(srv._engine._sched) == 1,
                         msg="second request queued")
                code, body, headers = self._post(
                    srv, {"input_ids": [[5, 6, 7]],
                          "max_new_tokens": 4})
                assert code == 429
                assert "Retry-After" in headers
                # ROADMAP PR 4 follow-up (c): derived from queue depth
                # x measured decode-step p50, clamped to [1, 30] —
                # never the old constant string with no basis
                assert 1 <= int(headers["Retry-After"]) <= 30
                assert (int(headers["Retry-After"])
                        == srv._engine.retry_after_hint())
                t1.join(timeout=300)
                t2.join(timeout=300)
        assert all(code == 200 for code, _, _ in results)

    def test_retry_after_is_class_aware(self, model):
        """ISSUE 7 satellite: the 429 hint derives from the REQUESTING
        class's queue depth x step p50 — a deep batch backlog must not
        inflate what an interactive client is told, and the header must
        match the engine's per-class hint."""
        from paddle_tpu.inference import GenerationServer

        rng = np.random.default_rng(24)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.03}])
        results = []

        def client(srv, max_new, priority):
            results.append(self._post(
                srv, {"input_ids": rng.integers(0, 64, (1, 4)).tolist(),
                      "max_new_tokens": max_new, "priority": priority},
                timeout=300))

        with faults.installed(plan):
            with GenerationServer(model, total_pages=64, page_size=8,
                                  max_batch=1, max_queue=1) as srv:
                t1 = threading.Thread(target=client,
                                      args=(srv, 32, "batch"))
                t1.start()
                wait_for(lambda: len(srv._engine._active) == 1,
                         msg="first request active")
                t2 = threading.Thread(target=client,
                                      args=(srv, 4, "batch"))
                t2.start()
                wait_for(lambda: srv._engine._sched.depth("batch") == 1,
                         msg="batch queue full")
                code, body, headers = self._post(
                    srv, {"input_ids": [[5, 6, 7]], "max_new_tokens": 4,
                          "priority": "batch"})
                assert code == 429
                assert "batch" in body["error"]
                assert 1 <= int(headers["Retry-After"]) <= 30
                # derived from the BATCH queue, and equal to the
                # engine's own per-class hint
                assert (int(headers["Retry-After"])
                        == srv._engine.retry_after_hint("batch"))
                # the interactive queue is empty: its hint is the floor
                assert srv._engine.retry_after_hint("interactive") == 1
                # ... and an interactive submission still ADMITS (its
                # class queue has room even while batch is saturated)
                code, body, _ = self._post(
                    srv, {"input_ids": [[1, 2, 3]], "max_new_tokens": 2,
                          "priority": "interactive"}, timeout=300)
                assert code == 200
                t1.join(timeout=300)
                t2.join(timeout=300)
        assert all(code == 200 for code, _, _ in results)

    def test_request_body_ttl_maps_to_504(self, model):
        from paddle_tpu.inference import GenerationServer

        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.05}])
        with faults.installed(plan):
            with GenerationServer(model, total_pages=64,
                                  page_size=8) as srv:
                code, body, _ = self._post(
                    srv, {"input_ids": [[1, 2, 3, 4]],
                          "max_new_tokens": 60, "timeout_s": 0.2})
                assert code == 504
                assert "TTL" in body["error"]

    def test_http_handler_fault_is_500(self, model):
        from paddle_tpu.inference import GenerationServer

        with GenerationServer(model, total_pages=32, page_size=8) as srv:
            with faults.installed(faults.FaultPlan(
                    [{"site": "http_handler", "nth": 1}])):
                code, body, _ = self._post(
                    srv, {"input_ids": [[1, 2]], "max_new_tokens": 2})
            assert code == 500
            assert "injected fault" in body["error"]


class TestRetryAfterDerivation:
    """ROADMAP PR 4 follow-up (c): Retry-After = queue depth x measured
    decode-step p50, clamped to [1, 30] seconds."""

    def test_clamps_and_formula(self):
        from paddle_tpu.inference.continuous import retry_after_seconds
        assert retry_after_seconds(0, 0.5) == 1          # empty queue
        assert retry_after_seconds(5, None) == 1         # nothing measured
        assert retry_after_seconds(3, 0.001) == 1        # floor clamp
        assert retry_after_seconds(10, 0.5) == 5         # ceil(10 x 0.5)
        assert retry_after_seconds(7, 0.33) == 3         # ceil(2.31)
        assert retry_after_seconds(1000, 0.5) == 30      # ceiling clamp

    def test_engine_hint_uses_live_queue_depth(self, model):
        rng = np.random.default_rng(23)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.01}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1, max_queue=8) as eng:
                assert eng.retry_after_hint() >= 1       # idle: floor
                r1 = eng.submit(rng.integers(0, 64, (4,)),
                                max_new_tokens=16)
                wait_for(lambda: r1.seq_id is not None, msg="admission")
                qs = [eng.submit(rng.integers(0, 64, (4,)),
                                 max_new_tokens=2) for _ in range(3)]
                hint = eng.retry_after_hint()
                assert 1 <= hint <= 30
                for r in (r1, *qs):
                    r.cancel()
