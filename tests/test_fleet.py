"""Fault-tolerant serving fleet (ISSUE 14): replica supervisor +
health-gated router with journal-backed failover.

The acceptance scenario here is the IN-PROCESS half: kill one of two
replicas mid-decode with greedy + sampled + prefix-hit + draft streams
in flight — the supervisor recovers the corpse's write-ahead journal
and migrates every stream to the survivor through the
``restore(strict=False)`` admission path, all four completing
bit-identically to a single-replica oracle, with ``/result/<id>``
re-attaching through the router.  (The in-process ``kill()`` emulation
leaves exactly the PR 13 crash floor on disk — hard engine stop, no
journal retirements; the REAL subprocess SIGKILL runs in
``tools/chaos_smoke.py --fleet``, gated in tests/test_tools.py.)

Also covered: circuit-breaker open/half-open/close transitions, router
retry dedup on ``request_id`` (a retried admit that landed re-attaches
instead of re-running), replica-labeled monitor series staying
separated with two engines in one process, drain-aware routing,
backpressure aggregation, journal page-provenance records, the
port-0 readiness signal, and the heartbeat-deregistration fixes
(engine stop + server bind failure must leave no watchdog probe
behind)."""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.distributed.watchdog import CommTaskManager
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.inference.fleet import (CircuitBreaker, FleetRouter,
                                        Replica, ReplicaSupervisor)
from paddle_tpu.inference.journal import RequestJournal
from paddle_tpu.inference.server import GenerationServer
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.testing import faults


def tiny_model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=1,
                      num_attention_heads=2, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.clear()


def wait_for(cond, timeout=60.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(
        f"timed out waiting for {msg() if callable(msg) else msg}")


def http_json(url, body=None, timeout=60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data,
        headers={} if body is None else
        {"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read() or b"{}"), dict(
                r.headers)
    except urllib.error.HTTPError as e:
        try:
            return e.code, json.loads(e.read() or b"{}"), dict(
                e.headers or {})
        except ValueError:
            return e.code, {}, {}


def gauge_value(name, **labels):
    m = monitor.get_registry().get(name)
    return None if m is None else m.value(**labels)


class TestCircuitBreaker:
    """closed -> open after N consecutive failures -> half-open after
    the cooldown admits ONE probe -> close on success / reopen on
    failure."""

    def test_transitions(self):
        br = CircuitBreaker("cb-test", threshold=3, reset_s=0.05)
        assert br.state == CircuitBreaker.CLOSED
        br.record_failure()
        br.record_failure()
        assert br.state == CircuitBreaker.CLOSED and br.allow()
        br.record_failure()              # threshold crossed
        assert br.state == CircuitBreaker.OPEN
        assert not br.allow()            # open: no traffic
        assert gauge_value("router_circuit_open", replica="cb-test") \
            == 1
        time.sleep(0.06)                 # cooldown elapsed
        assert br.allow()                # the half-open probe
        assert br.state == CircuitBreaker.HALF_OPEN
        assert not br.allow()            # ... exactly ONE probe
        br.record_failure()              # probe failed -> reopen
        assert br.state == CircuitBreaker.OPEN
        time.sleep(0.06)
        assert br.allow()
        br.record_success()              # probe succeeded -> close
        assert br.state == CircuitBreaker.CLOSED
        assert br.allow()
        assert gauge_value("router_circuit_open", replica="cb-test") \
            == 0

    def test_success_resets_consecutive_count(self):
        br = CircuitBreaker("cb-reset", threshold=2, reset_s=1.0)
        br.record_failure()
        br.record_success()
        br.record_failure()              # 1 consecutive, not 2
        assert br.state == CircuitBreaker.CLOSED


class TestHeartbeatHygiene:
    """ISSUE 14 satellites: a stopped engine / failed server bind must
    deregister its watchdog heartbeats — a supervisor restarting
    replicas in-process must not accumulate probes firing
    comm_timeouts_total against corpses."""

    def test_engine_stop_deregisters_heartbeat(self):
        mgr = CommTaskManager.instance()
        eng = ContinuousBatchingEngine(tiny_model(), total_pages=32,
                                       page_size=8, max_batch=2,
                                       step_timeout_s=30.0)
        assert "engine/decode_step" in mgr.heartbeat_names()
        eng.stop()
        assert "engine/decode_step" not in mgr.heartbeat_names()

    def test_bind_failure_leaks_no_heartbeat_or_journal(self, tmp_path):
        mgr = CommTaskManager.instance()
        blocker = GenerationServer(tiny_model(), total_pages=32,
                                   page_size=8, max_batch=2)
        try:
            def writer_threads():
                return sum(1 for t in threading.enumerate()
                           if t.name == "journal-writer"
                           and t.is_alive())
            before = mgr.heartbeat_names()
            jw_before = writer_threads()
            with pytest.raises(OSError):
                GenerationServer(
                    tiny_model(), port=blocker.port, total_pages=32,
                    page_size=8, max_batch=2, step_timeout_s=30.0,
                    journal_dir=str(tmp_path / "j"),
                    journal_fsync_timeout_s=30.0)
            # neither the engine's step heartbeat nor the journal's
            # fsync heartbeat survived the failed construction
            assert mgr.heartbeat_names() == before
            # and the failed server's journal writer thread is gone (a
            # relaunch over the same dir would contend otherwise)
            assert writer_threads() == jw_before
        finally:
            blocker.stop()

    def test_port0_readiness_signal(self):
        srv = GenerationServer(tiny_model(), port=0, total_pages=32,
                               page_size=8, max_batch=2)
        try:
            host, port = srv.address
            assert port > 0                  # ephemeral bind resolved
            assert not srv.wait_ready(0.01)  # not started yet
            srv.start()
            assert srv.wait_ready(5.0)
            status, payload, _ = http_json(
                f"http://{host}:{port}/health")
            assert status == 200 and payload["status"] == "ok"
        finally:
            srv.stop()


class TestPageProvenance:
    """ISSUE 14 satellite: the journal records which prefix-cache
    pages a request acquired/registered, keyed by the prefix's stable
    content hash — recovery exposes it for failover grouping and
    disaggregated re-attach."""

    def test_pages_records_survive_recovery(self, tmp_path):
        model = tiny_model()
        rng = np.random.default_rng(0)
        shared = rng.integers(0, 64, (16,))    # 2 full pages
        jdir = str(tmp_path / "wal")
        jr = RequestJournal(jdir, fsync="always")
        eng = ContinuousBatchingEngine(model, total_pages=64,
                                       page_size=8, max_batch=2,
                                       journal=jr)
        try:
            first = eng.submit(np.concatenate([shared, [1, 2, 3]]),
                               max_new_tokens=2, request_id="pp-reg")
            first.result(timeout=600)
            # the sharer acquires the registered prefix, then stalls
            # mid-decode so its admit + pages records are the live set
            faults.install(faults.FaultPlan(
                [{"site": "decode_step", "kind": "delay",
                  "delay_s": 0.02}]))
            second = eng.submit(np.concatenate([shared, [4, 5, 6]]),
                                max_new_tokens=16, request_id="pp-acq")
            wait_for(lambda: len(second.generated) >= 1,
                     msg="sharer mid-decode")
        finally:
            eng.stop()
            jr.close()
            faults.clear()
        jr2 = RequestJournal(jdir, fsync="os")
        entries = {e["request_id"]: e
                   for e in jr2.recovered_requests()}
        jr2.close()
        assert "pp-acq" in entries
        prov = entries["pp-acq"].get("prefix")
        assert prov is not None
        # latest record wins: admission journaled "acquired", prefill
        # completion superseded it with "registered" (same key/pages)
        assert prov["event"] == "registered"
        assert prov["tokens"] == 16            # the page-aligned share
        assert len(prov["pages"]) == 2
        key = PagedKVCache_key(model, shared)
        assert prov["key"] == key              # content hash, stable
        # the registering request retired, so its record is gone with
        # it — only live provenance migrates
        assert "pp-reg" not in entries

    def test_pages_record_roundtrip(self, tmp_path):
        """Journal-level contract: a pages record attaches to its
        admit entry, unknown ids are ignored, retire drops it."""
        jdir = str(tmp_path / "wal")
        jr = RequestJournal(jdir, fsync="always")
        jr.append_admit({"request_id": "a", "prompt": [1, 2, 3],
                         "max_new_tokens": 4, "seed": 0})
        jr.append_pages("a", "acquired", 16, [3, 4], "ff00")
        jr.append_pages("ghost", "acquired", 8, [5], "aa")  # ignored
        jr.append_admit({"request_id": "b", "prompt": [4],
                         "max_new_tokens": 4, "seed": 0})
        jr.append_pages("b", "registered", 8, [6], "bb")
        jr.append_retire("b")
        jr.flush(sync=True)
        jr.close()
        jr2 = RequestJournal(jdir, fsync="os")
        entries = {e["request_id"]: e
                   for e in jr2.recovered_requests()}
        jr2.close()
        assert entries["a"]["prefix"] == {
            "event": "acquired", "tokens": 16, "pages": [3, 4],
            "key": "ff00"}
        assert "b" not in entries
        assert "ghost" not in entries

    def test_prefix_key_is_content_addressed(self):
        model = tiny_model()
        rng = np.random.default_rng(1)
        toks = rng.integers(0, 64, (16,))
        assert PagedKVCache_key(model, toks) \
            == PagedKVCache_key(tiny_model(), toks)


def PagedKVCache_key(model, tokens):
    from paddle_tpu.ops.pallas.paged_attention import PagedKVCache
    cache = PagedKVCache.from_model(model, total_pages=8, page_size=8)
    return cache.prefix_key_hex(np.asarray(tokens, np.int32),
                                len(tokens))


# ---------------------------------------------------------------- fleet
@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """A 2-replica in-process fleet (each replica with its own journal
    dir and a same-seed draft model, so draft-opted streams speculate)
    plus a single-engine oracle builder."""
    root = str(tmp_path_factory.mktemp("fleet-journals"))

    def factory(name, jdir):
        return GenerationServer(
            tiny_model(), draft_model=tiny_model(), spec_tokens=2,
            total_pages=128, page_size=8, max_batch=4,
            journal_dir=jdir, journal_fsync="always")

    sup = ReplicaSupervisor(factory=factory, replicas=2,
                            journal_root=root, probe_interval_s=0.1,
                            probe_failure_threshold=2,
                            probe_timeout_s=2.0,
                            heartbeat_timeout_s=5.0)
    router = FleetRouter(sup, attach_timeout_s=300.0)
    sup.start()
    router.start()
    wait_for(lambda: len(sup.routable_replicas()) == 2,
             msg="both replicas up")
    yield sup, router
    router.stop()
    sup.stop()


def router_url(router):
    return f"http://{router.host}:{router.port}"


def post_async(router, body, outs):
    def go():
        try:
            status, payload, _ = http_json(
                router_url(router) + "/generate", body=body,
                timeout=600)
            payload["_status"] = status
            outs[body["request_id"]] = payload
        except Exception as e:   # noqa: BLE001
            outs[body["request_id"]] = {"error": repr(e)}
    t = threading.Thread(target=go, daemon=True)
    t.start()
    return t


class TestFleetFailover:
    """THE tentpole acceptance (in-process half): kill one of two
    replicas mid-decode; greedy + sampled + prefix-hit + draft streams
    all complete bit-identical to a single-replica oracle via
    journal-backed migration, and /result/<id> re-attaches through
    the router."""

    def test_kill_mid_decode_migrates_bit_exact(self, fleet):
        sup, router = fleet
        rng = np.random.default_rng(7)
        shared = rng.integers(0, 64, (16,)).tolist()
        prompts = {
            "fo-greedy": shared + rng.integers(0, 64, (6,)).tolist(),
            "fo-sampled": rng.integers(0, 64, (7,)).tolist(),
            "fo-prefix": shared + rng.integers(0, 64, (5,)).tolist(),
            "fo-draft": rng.integers(0, 64, (6,)).tolist(),
        }
        bodies = {
            rid: {"input_ids": [prompts[rid]], "max_new_tokens": 24,
                  "request_id": rid, "seed": 100 + i}
            for i, rid in enumerate(prompts)}
        bodies["fo-sampled"].update({"do_sample": True,
                                     "temperature": 0.8})
        bodies["fo-greedy"]["draft"] = False
        bodies["fo-prefix"]["draft"] = False
        bodies["fo-draft"]["draft"] = True
        bodies["fo-draft"]["max_new_tokens"] = 32

        # single-replica oracle over the same seeded weights
        refs = {}
        with ContinuousBatchingEngine(
                tiny_model(), draft_model=tiny_model(), spec_tokens=2,
                total_pages=128, page_size=8, max_batch=4) as eng:
            for rid, b in bodies.items():
                refs[rid] = [int(t) for t in eng.submit(
                    np.asarray(b["input_ids"][0], np.int32),
                    max_new_tokens=b["max_new_tokens"],
                    do_sample=b.get("do_sample", False),
                    temperature=b.get("temperature", 1.0),
                    seed=b["seed"],
                    draft=b.get("draft")).result(timeout=600)]

        # warm BOTH replicas: the shared prefix registers in each
        # prefix cache (hits are output-invariant) and a draft-opted
        # warm request compiles the speculative propose/verify
        # programs — cold spec compiles inside the kill window would
        # stall the mid-decode wait below
        warm_outs: dict = {}
        warm = [dict(bodies["fo-greedy"], request_id=f"fo-warm-{i}",
                     max_new_tokens=2, draft=False) for i in range(2)]
        warm += [dict(bodies["fo-draft"], request_id=f"fo-dwarm-{i}",
                      max_new_tokens=2, draft=True) for i in range(2)]
        for t in [post_async(router, b, warm_outs) for b in warm]:
            t.join(timeout=300)

        # HOLD "all four mid-decode at once": every decode step of
        # either replica sleeps 1 s, so the shortest stream (fo-draft:
        # 32 tokens, at most spec_tokens + 1 a step) stays pending for
        # some ten seconds after its second token — the four sequential
        # polls below need a fraction of that even on a loaded host.
        # The plan is cleared right after the kill.
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": 1.0}]))
        outs: dict = {}
        threads = [post_async(router, bodies[rid], outs)
                   for rid in bodies]

        def result(rid):
            _, payload, _ = http_json(
                router_url(router) + f"/result/{rid}", timeout=30)
            return payload

        states: dict = {}

        def all_mid_decode():
            for rid in bodies:
                states[rid] = {k: v for k, v in result(rid).items()
                               if k != "output_ids"}
            # a stream that finished can never read "pending, two
            # tokens in" again: fail now, not after the timeout
            done = [rid for rid in bodies
                    if states[rid].get("status") == "done"]
            assert not done, (
                f"{done} finished before all four were mid-decode "
                f"(the hold is too short): {states}")
            return all(s.get("status") == "pending"
                       and s.get("generated_tokens", 0) >= 2
                       for s in states.values())

        wait_for(all_mid_decode, timeout=120,
                 msg=lambda: f"all 4 mid-decode: {states}")
        owners = [states[rid]["replica"] for rid in bodies]
        victim = max(set(owners), key=owners.count)
        fo_before = monitor.get_registry().get(
            "fleet_failovers_total").value(replica=victim)
        sup.kill(victim)
        faults.clear()
        for t in threads:
            t.join(timeout=600)

        for rid in bodies:
            assert outs[rid].get("_status") == 200, outs[rid]
            assert outs[rid]["output_ids"][0] == refs[rid], rid
        # at least one stream lived on the victim and was migrated
        migrated = monitor.get_registry().get(
            "fleet_migrated_requests_total").value(replica=victim)
        assert migrated >= 1
        assert monitor.get_registry().get(
            "fleet_failovers_total").value(replica=victim) \
            == fo_before + 1
        # /result/<id> re-attaches through the router for every id,
        # wherever the stream ended up
        for rid in bodies:
            final = result(rid)
            assert final.get("status") == "done"
            assert final["output_ids"] == refs[rid]
        # replica-labeled series separated: victim down, survivor up
        survivor = next(n for n in ("r0", "r1") if n != victim)
        assert gauge_value("fleet_replica_up", replica=victim) == 0
        assert gauge_value("fleet_replica_up", replica=survivor) == 1

    def test_fleet_health_reports_dead_replica(self, fleet):
        sup, router = fleet
        # its own corpse: the kill test above leaves one when it ran
        # and got that far, and this test must not lean on that
        def a_corpse():
            return any(r.state == Replica.DEAD
                       for r in sup.replicas.values())

        if not a_corpse():
            sup.kill(sup.routable_replicas()[0].name)
        wait_for(a_corpse,
                 msg="the probes to declare the killed replica dead")
        status, payload, _ = http_json(router_url(router) + "/health")
        assert status == 200
        states = {name: r["state"]
                  for name, r in payload["replicas"].items()}
        assert "dead" in states.values()
        assert payload["routable"] >= 1
        assert payload["status"] == "ok"

    def test_metrics_exposition_carries_fleet_series(self, fleet):
        _, router = fleet
        req = urllib.request.Request(router_url(router) + "/metrics")
        with urllib.request.urlopen(req, timeout=30) as r:
            text = r.read().decode()
        for series in ("fleet_replica_up", "fleet_failovers_total",
                       "fleet_migrated_requests_total",
                       "router_circuit_open"):
            assert series in text
        assert 'replica="' in text             # labeled exposition

    def test_retry_dedup_reattaches_instead_of_rerunning(self, fleet):
        """A retried admit whose first attempt actually landed must
        NOT run twice: the far engine rejects the duplicate id as
        already-live and the router re-attaches to the live stream."""
        sup, router = fleet
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, 64, (6,)).tolist()
        body = {"input_ids": [prompt], "max_new_tokens": 16,
                "request_id": "dedup-1", "seed": 42, "draft": False}
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": 0.02}]))
        outs: dict = {}
        t1 = post_async(router, body, outs)
        wait_for(lambda: http_json(
            router_url(router) + "/result/dedup-1")[0] in (200, 202),
            msg="first admit landed")
        # the "retry": the same id again while the original is live
        status, payload, _ = http_json(
            router_url(router) + "/generate", body=body, timeout=600)
        faults.clear()
        t1.join(timeout=300)
        assert status == 200
        assert payload.get("reattached") is True
        assert outs["dedup-1"]["_status"] == 200
        assert payload["output_ids"] == outs["dedup-1"]["output_ids"]
        # exactly ONE generation ran: the engine would have emitted
        # two different streams under two seeds if it ran twice —
        # instead both replies carry the same id and bytes
        assert payload["request_ids"] == ["dedup-1"]

    def test_drain_aware_routing(self, fleet):
        """A draining replica receives no new work while in-flight
        generations keep completing."""
        sup, router = fleet
        live = [r for r in sup.routable_replicas()]
        assert live, "no routable replica left"
        rep = live[0]
        rep.server.begin_drain()
        try:
            wait_for(lambda: rep.state == Replica.DRAINING,
                     msg="probe sees draining")
            rng = np.random.default_rng(13)
            for i in range(3):
                status, payload, _ = http_json(
                    router_url(router) + "/generate",
                    body={"input_ids":
                          [rng.integers(0, 64, (5,)).tolist()],
                          "max_new_tokens": 2, "draft": False,
                          "request_id": f"drain-{i}"}, timeout=600)
                if len(live) > 1:
                    assert status == 200
                    # the draining replica got none of them
                    assert router._owner_of(f"drain-{i}") != rep.name
                else:
                    # nothing else routable: the fleet refuses rather
                    # than feeding a draining replica
                    assert status in (429, 503)
        finally:
            rep.server.wait_drained(300)
            # drained replicas stay down for the remaining tests (the
            # module fixture tears the whole fleet down at the end)


class TestBackpressureAggregation:
    """Fleet 429 Retry-After = min over healthy replicas' hints."""

    def test_min_retry_after_when_all_saturated(self):
        sup = ReplicaSupervisor(probe_interval_s=3600.0)
        router = FleetRouter(sup, admit_attempts=1)
        # two fake "replicas" that always 429 with different hints
        class _Stub(threading.Thread):
            def __init__(self, hint):
                super().__init__(daemon=True)
                from http.server import (BaseHTTPRequestHandler,
                                         ThreadingHTTPServer)
                stub = self

                class H(BaseHTTPRequestHandler):
                    def log_message(self, *a):
                        pass

                    def do_POST(self):
                        body = json.dumps(
                            {"error": "saturated"}).encode()
                        self.send_response(429)
                        self.send_header("Retry-After", str(hint))
                        self.send_header("Content-Length",
                                         str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
                self.port = self.httpd.server_address[1]

            def run(self):
                self.httpd.serve_forever()
        stubs = [_Stub(7), _Stub(3)]
        for s in stubs:
            s.start()
        try:
            for i, s in enumerate(stubs):
                rep = sup.add_replica(f"stub{i}",
                                      f"http://127.0.0.1:{s.port}")
                rep.state = Replica.UP      # probe-free unit test
            status, payload, headers = router.route_generate(
                {"input_ids": [[1, 2, 3]], "max_new_tokens": 2})
            assert status == 429
            assert headers["Retry-After"] == "3"   # min(7, 3)
        finally:
            for s in stubs:
                s.httpd.shutdown()
                s.httpd.server_close()
            sup.stop(stop_replicas=False)

    def test_route_admit_fault_site_drives_retries(self):
        """An injected route_admit error counts router_retries and the
        bounded ladder still fails over to 503 when nothing lands."""
        sup = ReplicaSupervisor(probe_interval_s=3600.0)
        rep = sup.add_replica("ghost", "http://127.0.0.1:9")  # refused
        rep.state = Replica.UP
        router = FleetRouter(sup, admit_attempts=2,
                             backoff_base_s=0.005)
        before = monitor.get_registry().get(
            "router_retries_total").value(replica="ghost")
        faults.install(faults.FaultPlan(
            [{"site": "route_admit", "nth": 1}]))
        status, payload, _ = router.route_generate(
            {"input_ids": [[1, 2, 3]], "max_new_tokens": 2})
        assert status == 503
        after = monitor.get_registry().get(
            "router_retries_total").value(replica="ghost")
        assert after > before

    def test_replica_probe_fault_site_opens_the_gate(self):
        """Sticky replica_probe errors make a healthy replica look
        dead: probes fail, the replica leaves the routable set, and
        failover fires — without killing anything."""
        srv = GenerationServer(tiny_model(), total_pages=32,
                               page_size=8, max_batch=2).start()
        sup = ReplicaSupervisor(probe_interval_s=3600.0,
                                probe_failure_threshold=2)
        try:
            rep = sup.add_replica(
                "probed", f"http://{srv.host}:{srv.port}")
            assert sup.probe_once(rep)           # healthy
            assert rep.routable
            faults.install(faults.FaultPlan(
                [{"site": "replica_probe"}]))    # sticky error
            assert not sup.probe_once(rep)
            assert not sup.probe_once(rep)       # threshold crossed
            wait_for(lambda: rep.state == Replica.DEAD,
                     msg="failover marked the replica dead")
            assert not rep.routable
        finally:
            faults.clear()
            sup.stop(stop_replicas=False)
            srv.stop()
