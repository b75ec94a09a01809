"""Crash-consistent serving (ISSUE 8): the survivor-KV replay
primitive and its three consumers — device-failure (donated-buffer
loss) recovery, watchdog-driven restart, and engine snapshot/restore —
plus the satellites: preempted-prefill resume TTL, drain × chunked ×
preempted interaction, and the checkpoint-layer races.

The acceptance scenario: a REAL donated-buffer loss mid-decode on a
4-row batch quarantines exactly the poisoned row while every survivor
completes bit-identically to a fault-free run (greedy and sampled,
with and without a draft model); a snapshot→restore round trip across
a fresh engine resumes mid-stream requests exactly.
"""
import json
import os
import threading
import time
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


def wait_for(cond, timeout=60.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


def make_engine(model, **kw):
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    kw.setdefault("total_pages", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_batch", 4)
    return ContinuousBatchingEngine(model, **kw)


def engine_reference(model, prompts, max_new_tokens, submit_kw=None,
                     engine_kw=None):
    """Fault-free engine outputs for ``prompts`` — the bit-exactness
    oracle (the engine's own fused sampler, so sampled rows compare
    draw-for-draw)."""
    submit_kw = submit_kw or [{} for _ in prompts]
    with make_engine(model, **(engine_kw or {})) as eng:
        reqs = [eng.submit(p, max_new_tokens=max_new_tokens, **kw)
                for p, kw in zip(prompts, submit_kw)]
        return [r.result(timeout=120) for r in reqs]


def install_at_step_boundary(eng, plan):
    """Install a fault plan BETWEEN engine steps (the snapshot quiesce
    barrier), so per-site nth counting starts at a deterministic point
    instead of racing a step already in flight."""
    with eng._cond:
        eng._snap_waiters += 1
        try:
            while eng._stepping:
                eng._cond.wait(0.1)
            faults.install(plan)
        finally:
            eng._snap_waiters -= 1
            eng._cond.notify_all()


def submit_and_ripen(eng, prompts, max_new_tokens, submit_kw=None,
                     min_generated=2, delay_s=0.01):
    """Submit every prompt and wait until ALL rows are mid-decode
    (>= min_generated tokens, none finished) — the deterministic
    setup point for injecting a mid-decode device fault.  A mild
    decode delay is installed first so the mid-decode window is wide
    enough that the poll below cannot miss it on a fast machine; the
    caller's own plan (or the autouse clear) replaces it.  A caller
    whose next move takes longer than a few steps (stopping a server)
    passes the ``delay_s`` that keeps the rows mid-decode meanwhile."""
    faults.install(faults.FaultPlan(
        [{"site": "decode_step", "kind": "delay", "delay_s": delay_s}]))
    submit_kw = submit_kw or [{} for _ in prompts]
    reqs = [eng.submit(p, max_new_tokens=max_new_tokens, **kw)
            for p, kw in zip(prompts, submit_kw)]
    wait_for(lambda: all(len(r.generated) >= min_generated
                         for r in reqs),
             msg="all rows mid-decode")
    assert not any(r.done.is_set() for r in reqs)
    return reqs


class TestSurvivorReplay:
    """Tentpole consumer 1: device-failure recovery."""

    def test_transient_buffer_loss_all_rows_bit_exact(self, model):
        rng = np.random.default_rng(20)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(4)]
        want = engine_reference(model, prompts, 10)
        b_rebuild = counter_value("engine_rebuilds_total")
        b_replay = counter_value("survivor_replays_total")
        b_quar = counter_value("quarantined_requests_total")
        with make_engine(model) as eng:
            reqs = submit_and_ripen(eng, prompts, 10)
            # one REAL donated-buffer loss on the next decode step
            install_at_step_boundary(eng, faults.FaultPlan(
                [{"site": "buffer_loss", "nth": 1}]))
            outs = [r.result(timeout=120) for r in reqs]
            faults.clear()
            wait_for(lambda: eng.cache.free_pages == 64,
                     msg="pool reclaim")
        for o, e in zip(outs, want):
            np.testing.assert_array_equal(o, e)
        assert counter_value("engine_rebuilds_total") >= b_rebuild + 1
        assert counter_value("survivor_replays_total") >= b_replay + 4
        assert counter_value("quarantined_requests_total") == b_quar

    def test_sticky_buffer_loss_quarantines_exactly_the_poison(
            self, model):
        """The acceptance scenario: a sticky device fault tied to one
        sequence — bisect ejects exactly it while every batchmate's KV
        survives the pool rebuilds via replay."""
        rng = np.random.default_rng(21)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(4)]
        want = engine_reference(model, prompts, 10)
        b_quar = counter_value("quarantined_requests_total")
        with make_engine(model) as eng:
            reqs = submit_and_ripen(eng, prompts, 10)
            install_at_step_boundary(eng, faults.FaultPlan(
                [{"site": "buffer_loss", "seq_id": 2}]))
            with pytest.raises(faults.FaultError):
                reqs[2].result(timeout=120)
            outs = {i: reqs[i].result(timeout=120) for i in (0, 1, 3)}
            faults.clear()
            wait_for(lambda: eng.cache.free_pages == 64,
                     msg="pool reclaim")
            assert eng._reserved_pages == 1
        for i in (0, 1, 3):
            np.testing.assert_array_equal(outs[i], want[i])
        assert counter_value("quarantined_requests_total") == b_quar + 1

    def test_sampled_rows_replay_bit_exact(self, model):
        rng = np.random.default_rng(22)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(4)]
        kw = [dict(do_sample=True, temperature=0.8, seed=100 + i)
              for i in range(4)]
        want = engine_reference(model, prompts, 10, submit_kw=kw)
        with make_engine(model) as eng:
            reqs = submit_and_ripen(eng, prompts, 10, submit_kw=kw)
            install_at_step_boundary(eng, faults.FaultPlan(
                [{"site": "buffer_loss", "nth": 1}]))
            outs = [r.result(timeout=120) for r in reqs]
            faults.clear()
        for o, e in zip(outs, want):
            # the fused sampler draws by (seed, absolute position):
            # replayed KV -> identical logits -> identical draws
            np.testing.assert_array_equal(o, e)

    def test_buffer_loss_with_draft_attached(self, model):
        draft = tiny_model()            # same seed -> identical weights
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(4)]
        ekw = dict(draft_model=draft, spec_tokens=3)
        want = engine_reference(model, prompts, 10, engine_kw=ekw)
        b_down = counter_value("spec_draft_failures_total")
        with make_engine(model, **ekw) as eng:
            reqs = submit_and_ripen(eng, prompts, 10)
            # nth=2 skips the draft propose scan (match 1) and lands
            # on the TARGET's ragged dispatch of the verify rows — both
            # pools then replay in lockstep
            install_at_step_boundary(eng, faults.FaultPlan(
                [{"site": "buffer_loss", "nth": 2}]))
            outs = [r.result(timeout=120) for r in reqs]
            faults.clear()
        for o, e in zip(outs, want):
            np.testing.assert_array_equal(o, e)
        # lockstep survived: no request was downgraded to plain decode
        assert counter_value("spec_draft_failures_total") == b_down

    def test_prefix_entries_reregistered_after_loss(self, model):
        rng = np.random.default_rng(24)
        system = rng.integers(0, 64, (16,)).astype("int32")

        def sharer():
            return np.concatenate(
                [system, rng.integers(0, 64, (5,))]).astype("int32")

        seed_p, prompts = sharer(), [sharer() for _ in range(3)]
        late = sharer()
        want = engine_reference(model, prompts + [late], 8)
        with make_engine(model) as eng:
            eng.submit(seed_p, max_new_tokens=2).result(timeout=120)
            reqs = submit_and_ripen(eng, prompts, 8)
            assert all(r.prefix_tokens == 16 for r in reqs)
            faults.install(faults.FaultPlan(
                [{"site": "buffer_loss", "nth": 1}]))
            outs = [r.result(timeout=120) for r in reqs]
            faults.clear()
            # the loss dropped the prefix index; survivor replay
            # re-registered it — a late sharer still hits, bit-exactly
            r_late = eng.submit(late, max_new_tokens=8)
            out_late = r_late.result(timeout=120)
            assert r_late.prefix_tokens == 16
            assert eng.cache.cached_prefix_pages > 0
        for o, e in zip(outs + [out_late], want):
            np.testing.assert_array_equal(o, e)


class TestWatchdogRestart:
    """Tentpole consumer 2: a wedged step triggers a bounded rebuild +
    survivor replay instead of only incrementing the timeout counter."""

    def test_wedged_step_rebuilds_and_stays_exact(self, model):
        from paddle_tpu.distributed.watchdog import CommTaskManager
        rng = np.random.default_rng(25)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(2)]
        want = engine_reference(model, prompts, 8,
                                engine_kw=dict(max_batch=2))
        mgr = CommTaskManager.instance()
        mgr._scan_interval = 0.05
        b_rebuild = counter_value("engine_rebuilds_total")
        b_timeout = counter_value("comm_timeouts_total")
        plan = faults.FaultPlan([
            {"site": "engine_wedge", "kind": "delay", "delay_s": 0.8,
             "nth": 3}])
        try:
            with faults.installed(plan), warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with make_engine(model, max_batch=2,
                                 step_timeout_s=0.25) as eng:
                    reqs = [eng.submit(p, max_new_tokens=8)
                            for p in prompts]
                    outs = [r.result(timeout=120) for r in reqs]
                assert not mgr._heartbeats
        finally:
            mgr.stop()
        for o, e in zip(outs, want):
            np.testing.assert_array_equal(o, e)
        assert counter_value("comm_timeouts_total") > b_timeout
        assert counter_value("engine_rebuilds_total") > b_rebuild


class TestSnapshotRestore:
    """Tentpole consumer 3: journal in-flight state, resume exactly."""

    def test_round_trip_bit_exact_greedy_and_sampled(self, model):
        rng = np.random.default_rng(26)
        prompts = [rng.integers(0, 64, (6,)).astype("int32")
                   for _ in range(3)]
        kw = [dict(), dict(priority="batch", tenant="offline"),
              dict(do_sample=True, temperature=0.8, seed=7)]
        want = engine_reference(model, prompts, 10, submit_kw=kw)
        b_snap = counter_value("snapshot_requests_total")
        engA = make_engine(model)
        reqs = submit_and_ripen(engA, prompts, 10, submit_kw=kw,
                                min_generated=3)
        snap = engA.snapshot()
        engA.stop()                          # the "crashed" process
        snap = json.loads(json.dumps(snap))  # journal is JSON-able
        assert len(snap["requests"]) == 3
        for e in snap["requests"]:
            assert 3 <= len(e["generated"]) < 10
            assert e["next_token"] is not None
        assert counter_value("snapshot_requests_total") == b_snap + 3
        with make_engine(model) as engB:     # fresh pools, zero state
            restored = engB.restore(snap)
            outs = [r.result(timeout=120) for r in restored]
            # class/tenant survive the journal
            offline = [r for r in restored if r.tenant == "offline"]
            assert len(offline) == 1 and offline[0].priority == "batch"
        # journal order is admission order, not submission order:
        # match outputs to references by prompt
        want_by_prompt = {tuple(p.tolist()): w
                          for p, w in zip(prompts, want)}
        assert len(outs) == 3
        for r, o in zip(restored, outs):
            np.testing.assert_array_equal(
                o, want_by_prompt[tuple(r.prompt.tolist())])

    def test_snapshot_on_idle_engine_is_empty(self, model):
        with make_engine(model) as eng:
            snap = eng.snapshot()
        assert snap["requests"] == []

    def test_restore_nonstrict_skips_unplaceable_entries(self, model):
        rng = np.random.default_rng(27)
        good = {"prompt": [int(t) for t in rng.integers(0, 64, (5,))],
                "generated": [], "next_token": None,
                "max_new_tokens": 4, "seed": 1}
        bad = dict(good, max_new_tokens=10_000)   # past the rope table
        snap = {"version": 1, "requests": [bad, good]}
        with make_engine(model) as eng:
            with pytest.raises(ValueError):
                eng.restore(snap)                 # strict default
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                restored = eng.restore(snap, strict=False)
            assert len(restored) == 1
            assert len(restored[0].result(timeout=120)) == 9

    def test_ttl_remaining_carries_into_restore(self, model):
        rng = np.random.default_rng(28)
        engA = make_engine(model)
        reqs = submit_and_ripen(
            engA, [rng.integers(0, 64, (5,)).astype("int32")], 10,
            submit_kw=[dict(ttl_s=600.0, queue_timeout_s=5.0)],
            min_generated=1)
        snap = engA.snapshot()
        engA.stop()
        remaining = snap["requests"][0]["ttl_remaining_s"]
        assert 0 < remaining < 600.0
        # an ADMITTED request satisfied its queue-wait contract: the
        # journal must not re-impose the (spent) deadline on restore
        assert snap["requests"][0]["queue_timeout_remaining_s"] is None
        # ... and the restoring engine's DEFAULT deadlines must not
        # leak onto journaled requests either — the journal is verbatim
        with make_engine(model, default_ttl_s=0.5,
                         default_queue_timeout_s=0.001) as engB:
            r = engB.restore(snap)[0]
            assert r.ttl_s == pytest.approx(remaining)
            assert r.queue_timeout_s is None
            assert r.queue_deadline is None
            r.result(timeout=120)
        assert reqs[0] is not r     # a new handle on a new engine

    def test_server_snapshot_path_restart_resumes(self, model, tmp_path):
        from paddle_tpu.inference.server import GenerationServer
        path = str(tmp_path / "engine.snap")
        rng = np.random.default_rng(29)
        srvA = GenerationServer(model, total_pages=64, page_size=8,
                                max_batch=4, snapshot_path=path).start()
        try:
            eng = srvA._engine
            reqs = submit_and_ripen(
                eng, [rng.integers(0, 64, (5,)).astype("int32")
                      for _ in range(2)], 12)
            assert srvA.save_snapshot() == 2
            assert os.path.exists(path)
        finally:
            srvA.stop()
        srvB = GenerationServer(model, total_pages=64, page_size=8,
                                max_batch=4, snapshot_path=path).start()
        try:
            assert srvB._restored_requests == 2
            assert not os.path.exists(path)          # consumed...
            assert os.path.exists(path + ".restored")   # ...and kept
            import urllib.request
            with urllib.request.urlopen(
                    f"http://{srvB.host}:{srvB.port}/health",
                    timeout=30) as r:
                health = json.loads(r.read())
            assert health["snapshot_path"] == path
            assert health["restored_requests"] == 2
            # the restored streams run to completion in the new process
            wait_for(lambda: not srvB._engine._active
                     and not srvB._engine._prefilling,
                     msg="restored requests complete")
        finally:
            srvB.stop()

    def test_server_tolerates_malformed_journal(self, model, tmp_path):
        from paddle_tpu.inference.server import GenerationServer
        path = str(tmp_path / "bad.snap")
        with open(path, "w") as f:
            f.write('{"requests": 1}')     # valid JSON, wrong shape
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            srv = GenerationServer(model, total_pages=64, page_size=8,
                                   max_batch=4,
                                   snapshot_path=path).start()
        try:
            # startup survived, journal consumed, nothing restored
            assert srv._restored_requests == 0
            assert not os.path.exists(path)
        finally:
            srv.stop()

    def test_sigterm_snapshots_then_drains(self, model, tmp_path):
        from paddle_tpu.inference.server import GenerationServer
        from paddle_tpu.distributed.fault_tolerance import \
            PreemptionHandler
        path = str(tmp_path / "preempt.snap")
        rng = np.random.default_rng(30)
        srv = GenerationServer(model, total_pages=64, page_size=8,
                               max_batch=4, snapshot_path=path).start()
        try:
            handler = PreemptionHandler(signals=())
            srv.attach_preemption(handler)
            reqs = submit_and_ripen(
                srv._engine,
                [rng.integers(0, 64, (5,)).astype("int32")], 24)
            handler._on_signal(None, None)    # the preemption notice
            wait_for(lambda: os.path.exists(path), msg="journal write")
            assert srv.draining
            with open(path) as f:
                snap = json.load(f)
            # crash floor: the in-flight request is journaled at once
            assert len(snap["requests"]) == 1
            assert len(snap["requests"][0]["generated"]) >= 2
            assert srv.wait_drained(timeout=120)
            reqs[0].result(timeout=1)         # drain completed it too
            # ... and the post-drain refresh drops it from the journal
            # so a restarted server will not re-execute it
            wait_for(lambda: json.load(open(path))["requests"] == [],
                     msg="journal refresh after drain")
        finally:
            srv.stop()


class TestPreemptResumeTTL:
    """Satellite (scheduler follow-up d): a paused preempted prefill
    must be forcibly resumed (aging boost) or reaped (resume TTL) —
    never hold its page reservation indefinitely."""

    def _slow_batch_then_interactive(self, model, ttl, interactive_new,
                                     step_delay=0.02,
                                     also_queue_standard=False):
        from paddle_tpu.inference.continuous import DeadlineExceeded
        rng = np.random.default_rng(31)
        plan = faults.FaultPlan([
            # slow chunked prefill for the batch prompt, so it is
            # reliably mid-prefill when interactive traffic arrives
            {"site": "prefill_chunk", "seq_id": 1, "kind": "delay",
             "delay_s": 0.05},
            # ... and slow interactive decode, so the slot stays busy
            # well past the TTL/aging thresholds
            {"site": "decode_step", "kind": "delay",
             "delay_s": step_delay, "seq_id": 2}])
        # the pause is paced by the injected delays alone: the page
        # table is pinned at its widest and a first request (sequence
        # 0) builds the chunk and decode programs, so nothing compiles
        # while the TTL clock runs (three programs used to, which on a
        # cold compile cache outlasted the TTL by themselves)
        eng = make_engine(model, max_batch=1, prefill_chunk_tokens=4,
                          preempt_resume_ttl_s=ttl, min_table_pages=16)
        out = {}
        try:
            eng.submit(rng.integers(0, 64, (4,)),
                       max_new_tokens=2).result(timeout=120)
            with faults.installed(plan):
                rb = eng.submit(rng.integers(0, 64, (24,)),
                                max_new_tokens=4, priority="batch")
                wait_for(lambda: rb.prefill_pos > 0,
                         msg="batch prefill started")
                ri = eng.submit(rng.integers(0, 64, (4,)),
                                max_new_tokens=interactive_new,
                                priority="interactive")
                wait_for(lambda: rb in eng._preempted,
                         msg="batch preempted")
                rs = None
                if also_queue_standard:
                    rs = eng.submit(rng.integers(0, 64, (4,)),
                                    max_new_tokens=4,
                                    priority="standard")
                out = dict(rb=rb, ri=ri, rs=rs, eng=eng,
                           DeadlineExceeded=DeadlineExceeded)
                ri.result(timeout=120)
            return out
        except BaseException:
            eng.stop()
            raise

    def test_expired_preempted_request_reaped_with_pages(self, model):
        before = 0.0
        m = monitor.get_registry().get("sched_preempt_expired_total")
        if m is not None:
            before = sum(s["value"] for s in
                         monitor.snapshot()
                         ["sched_preempt_expired_total"]["series"])
        # interactive decodes ~25 x 0.02s = 0.5s >> the 0.25s TTL:
        # no slot ever frees, so the paused batch request must be
        # reaped, not parked forever
        out = self._slow_batch_then_interactive(
            model, ttl=0.25, interactive_new=25)
        eng, rb = out["eng"], out["rb"]
        try:
            with pytest.raises(out["DeadlineExceeded"]):
                rb.result(timeout=120)
            wait_for(lambda: eng.cache.free_pages == 64,
                     msg="preempted pages reclaimed")
            assert eng._reserved_pages == 1
            assert not eng._preempted
        finally:
            eng.stop()
        after = sum(s["value"] for s in
                    monitor.snapshot()
                    ["sched_preempt_expired_total"]["series"])
        assert after >= before + 1

    def test_aged_preempted_request_resumes_before_queued_class(
            self, model):
        # interactive holds the slot ~3s (12 x 0.25s delayed steps);
        # aging boost kicks in at half the 5s TTL, so when the slot
        # frees the aged BATCH request must resume ahead of the queued
        # STANDARD request — without the boost, standard (rank 1)
        # always beats batch (rank 2).  Generous margins on both sides
        # (pause >= 2.5s aging, << 5s reap) absorb scheduler jitter.
        out = self._slow_batch_then_interactive(
            model, ttl=5.0, interactive_new=13, step_delay=0.25,
            also_queue_standard=True)
        eng, rb, rs = out["eng"], out["rb"], out["rs"]
        try:
            np.testing.assert_array_equal(
                rb.result(timeout=120)[:24], rb.prompt)
            rs.result(timeout=120)
            assert rb.first_token_at < rs.admitted_at
        finally:
            eng.stop()


class TestDrainChunkedPreempted:
    """Satellite: the PR 7 x PR 4 interaction — drain() while chunked
    prefills are mid-flight and a preempted request is parked."""

    def test_drain_completes_prefilling_and_preempted(self, model):
        rng = np.random.default_rng(32)
        plan = faults.FaultPlan([
            {"site": "prefill_chunk", "seq_id": 0, "kind": "delay",
             "delay_s": 0.05}])
        with faults.installed(plan):
            eng = make_engine(model, max_batch=1,
                              prefill_chunk_tokens=4)
            rb = eng.submit(rng.integers(0, 64, (24,)),
                            max_new_tokens=4, priority="batch")
            wait_for(lambda: rb.prefill_pos > 0,
                     msg="batch prefill started")
            ri = eng.submit(rng.integers(0, 64, (8,)),
                            max_new_tokens=4, priority="interactive")
            wait_for(lambda: rb in eng._preempted,
                     msg="batch preempted")
            # drain with one request mid-chunked-prefill and one
            # parked: BOTH must complete, pages reclaimed, scheduler
            # state empty
            assert eng.drain(timeout=120)
            assert len(ri.result(timeout=1)) == 12
            assert len(rb.result(timeout=1)) == 28
        info = eng.scheduler_info()
        assert info["prefilling"] == 0 and info["preempted"] == 0
        assert not info["tenants_queued"] or all(
            not v for v in info["tenants_queued"].values())
        assert eng.cache.free_pages == 64
        assert eng._reserved_pages == 1

    def test_drain_reject_queued_with_parked_preempted(self, model):
        from paddle_tpu.inference.continuous import EngineDraining
        rng = np.random.default_rng(33)
        plan = faults.FaultPlan([
            {"site": "prefill_chunk", "seq_id": 0, "kind": "delay",
             "delay_s": 0.05}])
        with faults.installed(plan):
            eng = make_engine(model, max_batch=1,
                              prefill_chunk_tokens=4)
            rb = eng.submit(rng.integers(0, 64, (24,)),
                            max_new_tokens=4, priority="batch")
            wait_for(lambda: rb.prefill_pos > 0,
                     msg="batch prefill started")
            ri = eng.submit(rng.integers(0, 64, (8,)),
                            max_new_tokens=4, priority="interactive")
            wait_for(lambda: rb in eng._preempted,
                     msg="batch preempted")
            rq = eng.submit(rng.integers(0, 64, (4,)),
                            max_new_tokens=4, priority="batch")
            assert eng.drain(timeout=120, reject_queued=True)
            # queued-but-unadmitted rejected; admitted (prefilling AND
            # parked-preempted) completed
            with pytest.raises(EngineDraining):
                rq.result(timeout=1)
            assert len(ri.result(timeout=1)) == 12
            assert len(rb.result(timeout=1)) == 28
        assert eng.cache.free_pages == 64


class TestCheckpointSatellites:
    def test_wait_async_save_surfaces_write_errors(self, tmp_path):
        from paddle_tpu.distributed import checkpoint as ckpt
        # a lambda cannot pickle: the WRITER thread fails, and that
        # failure must surface at wait_async_save — not vanish with
        # the thread (a failed checkpoint must never look durable)
        ckpt.save_state_dict({"fn": (lambda: 0)}, str(tmp_path),
                             async_save=True)
        with pytest.raises(Exception):
            ckpt.wait_async_save()
        # the queue is drained: a second wait is a clean no-op
        ckpt.wait_async_save()

    def test_concurrent_async_saves_and_waits(self, tmp_path):
        from paddle_tpu.distributed import checkpoint as ckpt
        errs = []

        def worker(i):
            try:
                d = str(tmp_path / f"d{i}")
                for _ in range(3):
                    ckpt.save_state_dict(
                        {"step": i}, d, async_save=True)
                    ckpt.wait_async_save()
            except BaseException as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        ckpt.wait_async_save()
        for i in range(4):
            assert os.path.exists(
                str(tmp_path / f"d{i}" / "rank_0.pkl"))

    def test_prune_skips_inuse_checkpoint(self, tmp_path):
        from paddle_tpu.distributed.fault_tolerance import (
            _inuse_path, latest_checkpoint, save_checkpoint)
        d = str(tmp_path)
        for step in (1, 2, 3):
            save_checkpoint({"step": step}, d, step, keep_last_n=5)
        # a concurrent reader resolved step 3 and is mid-load
        marker = _inuse_path(d, 3)
        with open(marker, "w") as f:
            f.write("reader")
        save_checkpoint({"step": 9}, d, 9, keep_last_n=1)
        # steps 1-2 pruned; the in-use step 3 SURVIVES
        assert sorted(os.path.basename(p) for p in
                      [latest_checkpoint(d)]) == ["step_9"]
        assert os.path.exists(os.path.join(d, "step_3"))
        assert not os.path.exists(os.path.join(d, "step_1"))
        assert not os.path.exists(os.path.join(d, "step_2"))
        # reader done: the marker no longer protects it
        os.remove(marker)
        save_checkpoint({"step": 10}, d, 10, keep_last_n=1)
        assert not os.path.exists(os.path.join(d, "step_3"))

    def test_stale_inuse_marker_does_not_block_prune(self, tmp_path):
        from paddle_tpu.distributed.fault_tolerance import (
            _inuse_path, save_checkpoint)
        d = str(tmp_path)
        save_checkpoint({"s": 1}, d, 1, keep_last_n=5)
        marker = _inuse_path(d, 1)
        with open(marker, "w") as f:
            f.write("crashed reader")
        old = time.time() - 7200
        os.utime(marker, (old, old))
        save_checkpoint({"s": 2}, d, 2, keep_last_n=1)
        assert not os.path.exists(os.path.join(d, "step_1"))

    def test_load_checkpoint_marks_and_cleans(self, tmp_path):
        import glob as _glob
        from paddle_tpu.distributed.fault_tolerance import (
            load_checkpoint, save_checkpoint)
        d = str(tmp_path)
        save_checkpoint({"step": 5}, d, 5)
        state, step = load_checkpoint(d)
        assert step == 5 and state["step"] == 5
        assert not _glob.glob(os.path.join(d, "*.inuse"))


class TestJournalRecovery:
    """ISSUE 13 tentpole: the write-ahead request journal makes crash
    recovery SIGKILL-grade — the engine journals every state
    transition as it happens, a HARD stop journals nothing (that is
    exactly the state a kill -9 leaves), and a fresh process
    reconstructs the live set and resumes bit-exactly through the
    replay admission path.  The subprocess SIGKILL acceptance scenario
    is tools/chaos_smoke.py's hard-kill lane; journal-file mechanics
    are tests/test_journal.py."""

    def _journal(self, tmp_path, name="j", **kw):
        from paddle_tpu.inference.journal import RequestJournal
        kw.setdefault("fsync", "always")
        return RequestJournal(str(tmp_path / name), **kw)

    def test_hard_stop_recovery_bit_exact_greedy_and_sampled(
            self, model, tmp_path):
        rng = np.random.default_rng(40)
        prompts = [rng.integers(0, 64, (6,)).astype("int32")
                   for _ in range(3)]
        kw = [dict(), dict(priority="batch", tenant="offline"),
              dict(do_sample=True, temperature=0.8, seed=7)]
        want = engine_reference(model, prompts, 10, submit_kw=kw)
        j = self._journal(tmp_path)
        engA = make_engine(model, journal=j)
        reqs = submit_and_ripen(engA, prompts, 10, submit_kw=kw,
                                min_generated=3)
        rids = [r.request_id for r in reqs]
        engA.stop()          # HARD stop: no retire records (kill -9)
        j.close()
        j2 = self._journal(tmp_path)
        entries = j2.recovered_requests()
        assert sorted(e["request_id"] for e in entries) == sorted(rids)
        for e in entries:
            # the WAL held the mid-stream cut: tokens + pending sample
            assert len(e["generated"]) >= 3
            assert e["next_token"] is not None
            assert e["ttl_remaining_s"] is None      # verbatim: none set
            assert e["queue_timeout_remaining_s"] is None   # admitted
        with make_engine(model, journal=j2) as engB:
            restored = engB.restore({"version": 1, "requests": entries})
            outs = {r.request_id: r.result(timeout=120)
                    for r in restored}
            # class/tenant survive; journaled ids re-attach via the
            # result cache on the NEW engine (the /result contract)
            offline = [r for r in restored if r.tenant == "offline"]
            assert len(offline) == 1 and offline[0].priority == "batch"
            for rid in rids:
                assert engB.result_for(rid)["status"] == "done"
        j2.close()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(outs[r.request_id], w)

    def test_completed_requests_are_not_resurrected(self, model,
                                                    tmp_path):
        rng = np.random.default_rng(41)
        j = self._journal(tmp_path)
        with make_engine(model, journal=j) as eng:
            eng.submit(rng.integers(0, 64, (5,)),
                       max_new_tokens=4).result(timeout=120)
        j.close()
        j2 = self._journal(tmp_path)
        assert j2.recovered_requests() == []
        j2.close()

    def test_double_crash_recovery_is_idempotent(self, model, tmp_path):
        """A restart that dies mid-recovery (here: after resubmitting,
        before finishing the streams) must itself be recoverable — the
        re-admission records carry the restored state, so a THIRD
        process still resumes bit-exactly."""
        rng = np.random.default_rng(42)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(2)]
        want = engine_reference(model, prompts, 12)
        j = self._journal(tmp_path)
        engA = make_engine(model, journal=j)
        reqs = submit_and_ripen(engA, prompts, 12, min_generated=2)
        rids = [r.request_id for r in reqs]
        engA.stop()
        j.close()
        # crash 2: restart, resume, die again mid-stream
        j2 = self._journal(tmp_path)
        engB = make_engine(model, journal=j2)
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": 0.01}]))
        restored = engB.restore({"version": 1,
                                 "requests": j2.recovered_requests()})
        wait_for(lambda: all(len(r.generated) >= 4 for r in restored),
                 msg="second process mid-stream")
        faults.clear()
        engB.stop()
        j2.close()
        # process 3 completes everything, still bit-exact
        j3 = self._journal(tmp_path)
        entries = j3.recovered_requests()
        assert sorted(e["request_id"] for e in entries) == sorted(rids)
        assert all(len(e["generated"]) >= 4 for e in entries)
        with make_engine(model, journal=j3) as engC:
            outs = {r.request_id: r.result(timeout=120)
                    for r in engC.restore({"version": 1,
                                           "requests": entries})}
        j3.close()
        for r, w in zip(reqs, want):
            np.testing.assert_array_equal(outs[r.request_id], w)

    def test_server_journal_dir_restart_resumes(self, model, tmp_path):
        from paddle_tpu.inference.server import GenerationServer
        import urllib.request
        jdir = str(tmp_path / "journal")
        rng = np.random.default_rng(43)
        prompts = [rng.integers(0, 64, (5,)).astype("int32")
                   for _ in range(2)]
        want = engine_reference(model, prompts, 12)
        srvA = GenerationServer(model, total_pages=64, page_size=8,
                                max_batch=4, journal_dir=jdir).start()
        try:
            # stop() closes the HTTP listener BEFORE it stops the
            # engine; at 0.01 s a step the ten tokens left were done
            # (and retired in the journal) by then on a loaded host,
            # and srvB found nothing to restore.  0.25 s a step holds
            # both rows mid-decode for two seconds and more.
            reqs = submit_and_ripen(srvA._engine, prompts, 12,
                                    delay_s=0.25)
            rids = [r.request_id for r in reqs]
        finally:
            srvA.stop()     # engine hard-stops: journals no retirement
            faults.clear()
        assert not any(r.error is None and r.done.is_set() for r in reqs), \
            "a row finished before the stop: nothing left to resume"
        srvB = GenerationServer(model, total_pages=64, page_size=8,
                                max_batch=4, journal_dir=jdir).start()
        try:
            assert srvB._restored_requests == 2
            with urllib.request.urlopen(
                    f"http://{srvB.host}:{srvB.port}/health",
                    timeout=30) as r:
                health = json.loads(r.read())
            assert health["journal"]["path"] == jdir
            assert health["journal"]["segments"] >= 1
            assert health["journal"]["fsync_policy"] == "interval_ms"
            assert health["restored_requests"] == 2
            # /result/<id> re-attaches across the HARD restart with
            # the journaled ids — same contract as across SIGTERM
            outs = {}
            for rid in rids:
                def done(rid=rid):
                    with urllib.request.urlopen(
                            f"http://{srvB.host}:{srvB.port}"
                            f"/result/{rid}", timeout=30) as r:
                        outs[rid] = json.loads(r.read())
                    return outs[rid].get("status") == "done"
                wait_for(done, msg=f"re-attach {rid}")
        finally:
            srvB.stop()
        for r, w in zip(reqs, want):
            assert outs[r.request_id]["output_ids"] \
                == [int(t) for t in w]

    def test_sigterm_with_journal_flushes_then_compacts(self, model,
                                                        tmp_path):
        """The SIGTERM snapshot collapses onto the journal: the
        preemption path durably flushes the WAL (crash floor), the
        drain completes the requests, and the post-drain compaction
        shrinks the live set to empty — a relaunch resumes nothing."""
        from paddle_tpu.inference.server import GenerationServer
        from paddle_tpu.distributed.fault_tolerance import \
            PreemptionHandler
        jdir = str(tmp_path / "journal")
        rng = np.random.default_rng(44)
        srv = GenerationServer(model, total_pages=64, page_size=8,
                               max_batch=4, journal_dir=jdir).start()
        try:
            handler = PreemptionHandler(signals=())
            srv.attach_preemption(handler)
            reqs = submit_and_ripen(
                srv._engine,
                [rng.integers(0, 64, (5,)).astype("int32")], 24)
            wait_for(lambda: srv._journal.live_count == 1,
                     msg="admit record applied by the writer")
            handler._on_signal(None, None)    # the preemption notice
            assert srv.draining
            assert srv.wait_drained(timeout=120)
            reqs[0].result(timeout=1)         # drain completed it
            # post-drain refresh: live set compacted to empty
            wait_for(lambda: srv._journal.live_count == 0,
                     msg="post-drain journal compaction")
        finally:
            srv.stop()
        j = self._journal(tmp_path, name="journal")
        assert j.recovered_requests() == []
        j.close()

    def test_journal_dir_and_snapshot_path_mutually_exclusive(
            self, model, tmp_path):
        from paddle_tpu.inference.server import GenerationServer
        with pytest.raises(ValueError, match="mutually exclusive"):
            GenerationServer(model, total_pages=64, page_size=8,
                             journal_dir=str(tmp_path / "j"),
                             snapshot_path=str(tmp_path / "s"))

    def test_stale_restored_file_does_not_block_snapshot_restore(
            self, model, tmp_path):
        """Crash-loop satellite (legacy snapshot path): a stale
        ``<path>.restored`` left by an earlier generation must be
        overwritten by the next consume, never wedge the restart."""
        from paddle_tpu.inference.server import GenerationServer
        path = str(tmp_path / "engine.snap")
        with open(path + ".restored", "w") as f:
            f.write('{"version": 1, "requests": '
                    '[{"prompt": [1], "stale": true}]}')
        rng = np.random.default_rng(45)
        snap = {"version": 1, "requests": [{
            "request_id": "fresh-1",
            "prompt": [int(t) for t in rng.integers(0, 64, (5,))],
            "generated": [], "next_token": None,
            "max_new_tokens": 4, "seed": 1}]}
        with open(path, "w") as f:
            json.dump(snap, f)
        srv = GenerationServer(model, total_pages=64, page_size=8,
                               max_batch=4, snapshot_path=path).start()
        try:
            assert srv._restored_requests == 1
            assert not os.path.exists(path)
            with open(path + ".restored") as f:
                consumed = json.load(f)
            assert consumed["requests"][0].get("request_id") == "fresh-1"
            wait_for(lambda: srv._engine.result_for("fresh-1")
                     is not None and srv._engine.result_for(
                         "fresh-1")["status"] == "done",
                     msg="fresh journal entry completes")
        finally:
            srv.stop()

    def test_quarantined_request_is_retired_in_journal(self, model,
                                                       tmp_path):
        """Retirement records cover EVERY terminal path — a poisoned
        request ejected by failure isolation must not come back from
        the dead on restart."""
        rng = np.random.default_rng(46)
        j = self._journal(tmp_path)
        # the fault follows the second admission (seq 1): whichever way
        # its prompt reaches the device, it is the one that goes
        plan = faults.FaultPlan(
            [{"site": "prefill", "seq_id": 1}])
        with faults.installed(plan):
            with make_engine(model, journal=j) as eng:
                ok = eng.submit(rng.integers(0, 64, (5,)),
                                max_new_tokens=4)
                bad = eng.submit(rng.integers(0, 64, (5,)),
                                 max_new_tokens=4)
                ok.result(timeout=120)
                with pytest.raises(faults.FaultError):
                    bad.result(timeout=120)
        j.close()
        j2 = self._journal(tmp_path)
        assert j2.recovered_requests() == []
        j2.close()
