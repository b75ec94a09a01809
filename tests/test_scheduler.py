"""Heterogeneous-workload scheduler (ISSUE 7): chunked prefill,
priority classes + weighted-fair queueing, preempt-and-resume, and the
per-class SLO surface.

The acceptance spine: chunked and PREEMPTED prefill are greedy-bit-
identical to the monolithic path (including on prefix-cache hits and
with a draft model attached), interactive traffic overtakes batch-class
prefill without ever costing it re-prefill work, and a poisoned chunk
quarantines exactly its own request with earlier chunks' pages
reclaimed.
"""
import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults
from paddle_tpu.inference.continuous import (ContinuousBatchingEngine,
                                             _Request)
from paddle_tpu.inference.scheduler import (DEFAULT_CLASSES,
                                            PriorityClass, QueueFull,
                                            WorkloadScheduler)


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


def mkreq(priority=None, tenant="default", tokens=4):
    return _Request(np.arange(tokens, dtype=np.int32), 4, None, False,
                    1.0, 0, priority=priority, tenant=tenant)


class TestWorkloadSchedulerPolicy:
    """Pure policy unit tests — no model, no engine thread."""

    def test_interactive_pops_before_earlier_batch(self):
        s = WorkloadScheduler()
        rb = mkreq("batch")
        ri = mkreq("interactive")
        s.push(rb)
        s.push(ri)                     # submitted LATER
        assert s.pop_next(lambda r: 1) is ri
        assert s.pop_next(lambda r: 1) is rb
        assert s.pop_next(lambda r: 1) is None

    def test_tenant_drr_alternates_within_class(self):
        s = WorkloadScheduler()
        a = [mkreq("standard", "tenant-a") for _ in range(3)]
        b = [mkreq("standard", "tenant-b") for _ in range(3)]
        for r in a:                    # tenant-a's burst arrives first
            s.push(r)
        for r in b:
            s.push(r)
        got = [s.pop_next(lambda r: 1) for _ in range(6)]
        tenants = [r.tenant for r in got]
        # equal-quantum DRR: the burst cannot monopolize the class
        assert tenants == ["tenant-a", "tenant-b"] * 3

    def test_class_weights_set_service_share(self):
        s = WorkloadScheduler()
        for _ in range(12):
            s.push(mkreq("interactive"))
            s.push(mkreq("batch"))
        first9 = [s.pop_next(lambda r: 1).priority for _ in range(9)]
        # weights 8:1 -> each replenish round serves 8 interactive then
        # 1 batch; batch is metered, not starved
        assert first9.count("interactive") == 8
        assert first9.count("batch") == 1

    def test_head_that_does_not_fit_skips_to_other_class(self):
        s = WorkloadScheduler()
        big = mkreq("interactive")
        small = mkreq("batch")
        s.push(big)
        s.push(small)
        # the interactive head doesn't fit -> batch is served instead
        # of head-of-line blocking the whole engine
        got = s.pop_next(lambda r: None if r is big else 1)
        assert got is small
        assert s.pop_next(lambda r: None) is None    # nothing fits
        assert len(s) == 1

    def test_per_class_bound_raises_class_aware(self):
        s = WorkloadScheduler(max_queue=2)
        s.push(mkreq("batch"))
        s.push(mkreq("batch"))
        with pytest.raises(QueueFull) as ei:
            s.push(mkreq("batch"))
        assert ei.value.priority_class == "batch"
        assert "batch" in str(ei.value)
        s.push(mkreq("interactive"))   # other classes unaffected
        assert s.depth("interactive") == 1
        assert s.depth("batch") == 2

    def test_resolve_validates_and_defaults(self):
        s = WorkloadScheduler()
        assert s.resolve(None).name == "standard"
        assert s.resolve("interactive").rank == 0
        with pytest.raises(ValueError, match="unknown priority class"):
            s.resolve("platinum")
        with pytest.raises(ValueError, match="duplicate"):
            WorkloadScheduler(classes=(
                PriorityClass("a", 0), PriorityClass("a", 1)))

    def test_large_cost_head_still_affords(self):
        """Regression: costs are PAGES but deficits replenish in
        WEIGHT quanta — a lone weight-1 class with a request costing
        more than the deficit cap must still be served, not spin
        pop_next forever (the engine thread holds the lock there)."""
        s = WorkloadScheduler()
        big = mkreq("batch")           # batch: weight 1, cap 16 rounds
        s.push(big)
        assert s.pop_next(lambda r: 64) is big     # cost >> 16

    def test_max_rank_excludes_less_urgent_banked_deficit(self):
        """Regression: a slot freed by preempting FOR interactive must
        not be consumed by batch's banked deficit."""
        s = WorkloadScheduler()
        for _ in range(9):             # bank batch credit: 8 int pops
            s.push(mkreq("interactive"))
            s.push(mkreq("batch"))
        for _ in range(8):
            assert s.pop_next(lambda r: 1).priority == "interactive"
        # batch now affords (deficit 1 >= 1) and interactive is at 0 —
        # unrestricted, batch would win; rank-capped, interactive must
        assert s.pop_next(lambda r: 1, max_rank=0).priority \
            == "interactive"
        assert s.pop_next(lambda r: 1, max_rank=0) is None  # int empty
        assert s.pop_next(lambda r: 1).priority == "batch"

    def test_emptied_tenant_queues_are_pruned(self):
        """Regression: tenant entries are keyed by a client-supplied
        string — emptied queues must be dropped, not accumulate."""
        s = WorkloadScheduler()
        for i in range(20):
            s.push(mkreq("standard", f"tenant-{i}"))
        while s.pop_next(lambda r: 1) is not None:
            pass
        cs = s._classes["standard"]
        assert cs.tenants == {}
        # reap-driven removal prunes too
        dead = _Request(np.arange(4, dtype=np.int32), 4, None, False,
                        1.0, 0, queue_timeout_s=0.0, priority="standard",
                        tenant="ephemeral")
        s.push(dead)
        time.sleep(0.01)
        s.reap(time.perf_counter())
        assert cs.tenants == {}

    def test_reap_removes_expired_queued(self):
        s = WorkloadScheduler()
        live = mkreq("standard")
        dead = _Request(np.arange(4, dtype=np.int32), 4, None, False,
                        1.0, 0, queue_timeout_s=0.0,
                        priority="standard")
        s.push(live)
        s.push(dead)
        time.sleep(0.01)
        reaped = s.reap(time.perf_counter())
        assert reaped == [dead]
        assert len(s) == 1
        assert s.pop_next(lambda r: 1) is live

    def test_policy_surface(self):
        s = WorkloadScheduler()
        s.push(mkreq("batch", "offline"))
        pol = s.policy()
        assert set(pol) == {c.name for c in DEFAULT_CLASSES}
        assert pol["batch"]["queued"] == 1
        assert pol["batch"]["preemptible"] is True
        assert pol["interactive"]["rank"] == 0
        assert s.tenant_depths()["batch"] == {"offline": 1}


class TestChunkedPrefillExactness:
    def test_chunked_matches_unchunked_greedy(self, model):
        """The tentpole exactness bound: any chunk size — page-aligned
        or not — produces bit-identical greedy output to monolithic
        prefill."""
        rng = np.random.default_rng(0)
        p = rng.integers(0, 64, (41,)).astype("int32")
        want = reference(model, p, 6)
        for chunk in (8, 7, 16, 64):
            with make_engine(model, prefill_chunk_tokens=chunk) as eng:
                got = eng.submit(p, max_new_tokens=6).result(timeout=300)
            np.testing.assert_array_equal(got, want), chunk

    def test_chunked_sampled_draws_replay_identically(self, model):
        """Sampling counters are (seed, absolute position): chunking
        the prefill must not shift a single draw."""
        rng = np.random.default_rng(1)
        p = rng.integers(0, 64, (20,)).astype("int32")
        with make_engine(model) as eng:
            want = eng.submit(p, max_new_tokens=8, do_sample=True,
                              temperature=0.8,
                              seed=77).result(timeout=300)
        with make_engine(model, prefill_chunk_tokens=6) as eng:
            got = eng.submit(p, max_new_tokens=8, do_sample=True,
                             temperature=0.8, seed=77).result(timeout=300)
        np.testing.assert_array_equal(got, want)

    def test_chunked_prefill_on_prefix_hit(self, model):
        """Prefix-cache acquire still happens ONCE at admission; the
        chunked suffix continues from the shared pages bit-exactly."""
        rng = np.random.default_rng(2)
        system = rng.integers(0, 64, (16,)).astype("int32")
        sharer = np.concatenate(
            [system, rng.integers(0, 64, (21,))]).astype("int32")
        want = reference(model, sharer, 5)
        with make_engine(model, prefill_chunk_tokens=8) as eng:
            seed_p = np.concatenate(
                [system, rng.integers(0, 64, (3,))]).astype("int32")
            eng.submit(seed_p, max_new_tokens=2).result(timeout=300)
            r = eng.submit(sharer, max_new_tokens=5)
            got = r.result(timeout=300)
            assert r.prefix_tokens == 16       # acquired, not re-prefilled
            assert r.chunks_done == 3          # 21-token suffix / 8
        np.testing.assert_array_equal(got, want)

    def test_chunk_budget_interleaves_decode(self, model):
        """The Sarathi property: while a long batch-class prompt is
        still mid-prefill, interactive requests prefill AND decode to
        completion — a monolithic prefill would have blocked them."""
        rng = np.random.default_rng(3)
        long_p = rng.integers(0, 64, (96,)).astype("int32")
        plan = faults.FaultPlan([
            {"site": "prefill_chunk", "seq_id": 0, "kind": "delay",
             "delay_s": 0.04}])
        with faults.installed(plan):
            with make_engine(model, max_batch=2,
                             prefill_chunk_tokens=8) as eng:
                rb = eng.submit(long_p, max_new_tokens=4,
                                priority="batch")
                wait_for(lambda: rb.prefill_pos > 0, msg="first chunk")
                ri = eng.submit(rng.integers(0, 64, (5,)),
                                max_new_tokens=4, priority="interactive")
                ri.result(timeout=300)
                # the chat request finished while the flood was STILL
                # prefilling — the stall the subsystem removes
                assert rb.prefill_pos < len(long_p)
                assert not rb.done.is_set()
                rb.result(timeout=300)


class TestPreemptResume:
    def _preempt_run(self, model, prompt, max_new, **engine_kw):
        """Drive one batch-class request, preempt it mid-prefill with
        interactive traffic, and return (batch_out, interactive_req,
        batch_req)."""
        rng = np.random.default_rng(4)
        plan = faults.FaultPlan([
            {"site": "prefill_chunk", "kind": "delay", "delay_s": 0.04}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1,
                             prefill_chunk_tokens=8, **engine_kw) as eng:
                rb = eng.submit(prompt, max_new_tokens=max_new,
                                priority="batch")
                wait_for(lambda: rb.prefill_pos > 0, msg="first chunk")
                pos_then = rb.prefill_pos
                ri = eng.submit(rng.integers(0, 64, (5,)),
                                max_new_tokens=4, priority="interactive")
                got_i = ri.result(timeout=300)
                got_b = rb.result(timeout=300)
                # pool fully reclaimed afterwards (cached prefix pages
                # are evictable and count as free)
                wait_for(lambda: eng.cache.free_pages
                         == eng.cache.total_pages, msg="pool reclaim")
        assert ri.finished_at < rb.finished_at
        assert pos_then > 0
        return got_b, got_i

    def test_preempted_batch_output_bit_identical(self, model):
        rng = np.random.default_rng(5)
        p = rng.integers(0, 64, (40,)).astype("int32")
        want = reference(model, p, 6)
        before = counter_value("sched_preemptions_total", cls="batch")
        before_res = counter_value("sched_resumed_total", cls="batch")
        got_b, _ = self._preempt_run(model, p, 6)
        np.testing.assert_array_equal(got_b, want)
        assert counter_value("sched_preemptions_total",
                             cls="batch") > before
        assert counter_value("sched_resumed_total",
                             cls="batch") > before_res

    def test_preempted_prefix_hit_sharer_bit_identical(self, model):
        rng = np.random.default_rng(6)
        system = rng.integers(0, 64, (16,)).astype("int32")
        sharer = np.concatenate(
            [system, rng.integers(0, 64, (25,))]).astype("int32")
        want = reference(model, sharer, 6)
        # seed the prefix OUTSIDE the preemption run so the sharer
        # acquires at admission and chunks only its suffix
        with make_engine(model, prefill_chunk_tokens=8,
                         max_batch=1) as eng:
            seed_p = np.concatenate(
                [system, rng.integers(0, 64, (3,))]).astype("int32")
            eng.submit(seed_p, max_new_tokens=2).result(timeout=300)
            plan = faults.FaultPlan([
                {"site": "prefill_chunk", "kind": "delay",
                 "delay_s": 0.04}])
            with faults.installed(plan):
                rb = eng.submit(sharer, max_new_tokens=6,
                                priority="batch")
                wait_for(lambda: rb.prefill_pos > rb.prefix_tokens,
                         msg="first suffix chunk")
                ri = eng.submit(rng.integers(0, 64, (5,)),
                                max_new_tokens=4, priority="interactive")
                ri.result(timeout=300)
                got = rb.result(timeout=300)
            assert rb.prefix_tokens == 16
        np.testing.assert_array_equal(got, want)

    def test_preempted_with_draft_attached_bit_identical(self, model):
        """Spec decode rides along (PR 6 semantics): the draft ingests
        the whole prompt at prefill COMPLETION, so a preempted target
        resumes cleanly and still speculates."""
        draft = tiny_model(seed=0)     # clone: accept ~1.0
        rng = np.random.default_rng(7)
        p = rng.integers(0, 64, (40,)).astype("int32")
        want = reference(model, p, 8)
        spec_before = counter_value("spec_accepted_tokens_total")
        got_b, _ = self._preempt_run(model, p, 8, draft_model=draft,
                                     spec_tokens=2, draft_total_pages=64)
        np.testing.assert_array_equal(got_b, want)
        # the preempted request actually decoded speculatively
        assert counter_value("spec_accepted_tokens_total") > spec_before


class TestChunkFaultIsolation:
    def test_poisoned_chunk_quarantines_only_its_request(self, model):
        """A fault that follows the batch request from its 3rd chunk
        on errors only it: the step is retried whole, then by halves,
        the chunk row fails alone; pages from its earlier chunks are
        reclaimed, its batchmate (another tenant) finishes bit-exact,
        and the engine keeps serving."""
        rng = np.random.default_rng(8)
        long_p = rng.integers(0, 64, (40,)).astype("int32")
        mate_p = rng.integers(0, 64, (6,)).astype("int32")
        want_mate = reference(model, mate_p, 6)
        before_q = counter_value("quarantined_requests_total")
        # sticky FROM the third chunk: every rule counts seq 0's chunk
        # dispatches, so nth = 3, 4, 5, ... fire on the third chunk, on
        # its whole retry and on every probe of the ladder after it (a
        # one-shot fault would be absorbed by the retry)
        plan = faults.FaultPlan([
            {"site": "prefill_chunk", "seq_id": 0, "nth": n}
            for n in range(3, 12)])
        with faults.installed(plan):
            with make_engine(model, max_batch=2,
                             prefill_chunk_tokens=8) as eng:
                rb = eng.submit(long_p, max_new_tokens=6,
                                priority="batch", tenant="offline")
                # pin the poisoned request to seq 0 before the
                # batchmate joins
                wait_for(lambda: rb.seq_id is not None, msg="admission")
                rm = eng.submit(mate_p, max_new_tokens=6,
                                priority="interactive", tenant="acme")
                with pytest.raises(faults.FaultError):
                    rb.result(timeout=300)
                np.testing.assert_array_equal(
                    rm.result(timeout=300), want_mate)
                # the poisoned request died on its 3rd chunk — the two
                # completed chunks' pages must come back
                assert rb.chunks_done == 2
                wait_for(lambda: eng.cache.free_pages
                         == eng.cache.total_pages, msg="pool reclaim")
                assert eng._reserved_pages == eng._pad_pages
                # engine still serves
                ok = eng.submit(mate_p, max_new_tokens=2)
                assert len(ok.result(timeout=300)) == 8
        assert counter_value("quarantined_requests_total") == before_q + 1


def counter_value(name, **labels):
    m = monitor.get_registry().get(name)
    return 0.0 if m is None else m.value(**labels)


class TestClassSLOSurface:
    def test_labeled_series_populated(self, model):
        rng = np.random.default_rng(9)
        with make_engine(model, prefill_chunk_tokens=8) as eng:
            for cls in ("interactive", "standard", "batch"):
                eng.submit(rng.integers(0, 64, (6,)), max_new_tokens=3,
                           priority=cls,
                           tenant=f"t-{cls}").result(timeout=300)
        snap = monitor.snapshot()
        for name in ("sched_ttft_seconds", "sched_queue_wait_seconds",
                     "sched_tpot_seconds"):
            labels = {tuple(sorted(s["labels"].items()))
                      for s in snap[name]["series"] if s["count"]}
            for cls in ("interactive", "standard", "batch"):
                assert (("cls", cls),) in labels, (name, cls)
        admitted = {s["labels"]["cls"]: s["value"]
                    for s in snap["sched_admitted_total"]["series"]}
        for cls in ("interactive", "standard", "batch"):
            assert admitted.get(cls, 0) >= 1

    def test_retry_after_hint_is_class_aware(self, model):
        rng = np.random.default_rng(10)
        plan = faults.FaultPlan([
            {"site": "decode_step", "kind": "delay", "delay_s": 0.01}])
        with faults.installed(plan):
            with make_engine(model, max_batch=1, max_queue=8) as eng:
                r1 = eng.submit(rng.integers(0, 64, (4,)),
                                max_new_tokens=24)
                wait_for(lambda: r1.seq_id is not None, msg="admission")
                qs = [eng.submit(rng.integers(0, 64, (4,)),
                                 max_new_tokens=2, priority="batch")
                      for _ in range(4)]
                # the interactive queue is EMPTY: its hint is the
                # floor, whatever the batch backlog looks like
                assert eng.retry_after_hint("interactive") == 1
                assert eng.retry_after_hint("batch") >= \
                    eng.retry_after_hint("interactive")
                for r in (r1, *qs):
                    r.cancel()

    def test_generation_server_scheduler_surface(self, model):
        from paddle_tpu.inference import GenerationServer

        rng = np.random.default_rng(11)
        p = rng.integers(0, 64, (5,)).astype("int32")
        want = reference(model, p, 4)
        with GenerationServer(model, total_pages=64, page_size=8,
                              max_batch=2,
                              prefill_chunk_tokens=8) as srv:
            url = f"http://{srv.host}:{srv.port}"
            req = urllib.request.Request(
                url + "/generate", data=json.dumps(
                    {"input_ids": p[None].tolist(), "max_new_tokens": 4,
                     "priority": "interactive",
                     "tenant": "acme"}).encode())
            with urllib.request.urlopen(req, timeout=300) as resp:
                body = json.loads(resp.read())
            np.testing.assert_array_equal(
                np.asarray(body["output_ids"][0]), want)
            with urllib.request.urlopen(url + "/health",
                                        timeout=60) as resp:
                health = json.loads(resp.read())
            sched = health["scheduler"]
            # the satellite contract: queue depths + the active policy
            # knobs are readable off a live replica
            assert sched["prefill_chunk_tokens"] == 8
            assert sched["default_class"] == "standard"
            for cls in ("interactive", "standard", "batch"):
                assert "weight" in sched["classes"][cls]
                assert "queued" in sched["classes"][cls]
            # unknown class is the client's mistake -> 400, not 429/503
            req = urllib.request.Request(
                url + "/generate", data=json.dumps(
                    {"input_ids": [[1, 2]], "max_new_tokens": 2,
                     "priority": "platinum"}).encode())
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=60)
            assert ei.value.code == 400
            assert "priority class" in json.loads(ei.value.read())["error"]
