"""Resilience-counter smoke gate (ISSUE 4 CI satellite; ISSUE 8
crash-consistency scenarios; ISSUE 13 SIGKILL hard-kill scenario).

Runs a tiny chaos scenario end to end — a fault plan injecting one
prefill exception and one sticky decode-step poison into a mixed
engine workload, one failing preemption callback, and a graceful
drain — then asserts every resilience series the README documents
actually exists in ``monitor.snapshot()`` with the values the scenario
implies, and that the pool drained to fully reclaimed.  The ISSUE 8
lanes add (a) a REAL donated-buffer loss mid-decode on a 4-row batch —
every survivor must complete bit-identically to a fault-free run with
``survivor_replays_total``/``engine_rebuilds_total`` counted and an
``engine_recovery_seconds`` MTTR sample — and (b) a snapshot→restore
round trip across a fresh engine resuming mid-stream requests
bit-exactly.

The ISSUE 13 hard-kill lane (``run_hard_kill``; part of the standalone
``python tools/chaos_smoke.py`` run and its own gate in
tests/test_tools.py) is the acceptance scenario for the write-ahead
request journal: a SUBPROCESS GenerationServer with ``journal_dir``
set serves 4 in-flight requests (greedy + sampled + prefix-hit +
draft-opted), is SIGKILLed mid-decode, and is relaunched over the same
journal — the restarted server must complete ALL of them with outputs
bit-identical to an uninterrupted run, and ``/result/<request_id>``
must re-attach for every journaled id across the hard restart.
``--child`` is the subprocess entry point.

The ISSUE 14 fleet lane (``--fleet`` / ``run_fleet_kill``) is the
acceptance scenario for the replica supervisor + router: TWO
subprocess replicas behind an in-parent ``ReplicaSupervisor`` +
``FleetRouter``, 4 in-flight streams (greedy + sampled + prefix-hit +
draft-opted) round-robined across them, SIGKILL of the replica owning
the most streams mid-decode — journal-backed failover must migrate its
streams to the survivor bit-exactly (zero failed requests),
``/result/<id>`` must re-attach through the router for every id, and
the ``fleet_*``/``router_*`` series must exist and fire.

The ISSUE 19 overload lane (``--overload-only`` / ``run_overload_kill``)
composes overload with a replica kill: two in-process replicas with
SLO-budgeted classes and the brownout ladder enabled take a
decode-delayed batch flood plus interactive traffic, one replica is
hard-killed mid-flood, and the gate demands zero failed interactive
requests, >= 1 shed batch arrival, a failover, and the existence of
every OVERLOAD_SERIES metric.

Exit 0 = healthy, 1 = broken; tests/test_tools.py runs main() in the
tier-1 lane, `python tools/chaos_smoke.py` is the standalone CI lane.
"""
from __future__ import annotations

import os
import sys
import warnings

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: every series the resilience layer must publish (README "Resilience")
REQUIRED_SERIES = (
    "decode_retries_total",
    "quarantined_requests_total",
    "requests_expired_total",
    "requests_cancelled_total",
    "engine_saturated_total",
    "engine_last_step_timestamp_seconds",
    "engine_draining",
    "preemption_callback_errors_total",
    # crash consistency (ISSUE 8)
    "survivor_replays_total",
    "engine_rebuilds_total",
    "engine_recovery_seconds",
    "snapshot_requests_total",
    # quantized serving + batched replay (ISSUE 9)
    "quant_enabled",
    "kv_quant_enabled",
    "kv_quant_pool_bytes",
    "kv_quant_scale_bytes",
    "replay_dispatches_total",
    # request tracing + cost/MFU accounting (ISSUE 10)
    "trace_captures_total",
    "trace_events_total",
    "trace_dropped_events_total",
    "mfu",
    "program_flops_total",
    "program_hbm_bytes",
    # write-ahead request journal (ISSUE 13)
    "journal_records_total",
    "journal_bytes",
    "journal_fsync_seconds",
    "journal_compactions_total",
    "journal_torn_records_total",
    "journal_recovered_requests_total",
    "journal_degraded",
)

#: fleet series (ISSUE 14, README "Fleet") — replica-labeled; the
#: --fleet replica-kill scenario must populate each
FLEET_SERIES = (
    "fleet_replica_up",
    "fleet_failovers_total",
    "fleet_migrated_requests_total",
    "router_retries_total",
    "router_circuit_open",
)

#: overload-protection series (ISSUE 19, README "Overload & graceful
#: degradation") — the --overload-only replica-kill-under-flood
#: scenario existence-gates each
OVERLOAD_SERIES = (
    "sched_shed_on_arrival_total",
    "engine_brownout_level",
    "decode_preemptions_total",
    "fleet_scale_events_total",
)

#: scheduler series (ISSUE 7, README "Scheduling & multi-tenancy") —
#: per-class labeled; the chunked preemption scenario below must
#: populate each
SCHEDULER_SERIES = (
    "sched_admitted_total",
    "sched_preemptions_total",
    "sched_resumed_total",
    "sched_prefill_chunks_total",
    "sched_queue_depth",
    "sched_queue_wait_seconds",
    "sched_ttft_seconds",
)


def _value(snap: dict, name: str):
    m = snap.get(name)
    if not m or not m["series"]:
        return None
    s = m["series"][0]
    return s.get("value", s.get("count"))   # counter/gauge, histogram


def _series_total(snap: dict, name: str):
    """Sum across a metric's labeled series (counter/gauge values, or
    histogram observation counts); None when the series never fired."""
    m = snap.get(name)
    if not m or not m["series"]:
        return None
    return sum(s.get("value", s.get("count", 0)) for s in m["series"])


def run_chaos() -> dict:
    """Drive the scenario; return {name: value} for the gate."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.distributed.fault_tolerance import PreemptionHandler
    from paddle_tpu.testing import faults

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)

    # one poisoned prefill (2nd admission) + one poisoned sequence
    # (sticky decode fault on seq 3) in a 5-request workload — run
    # inside a trace capture window (ISSUE 10): a quarantined request's
    # timeline must record the quarantine event, so a post-mortem can
    # see WHICH request the isolation machinery ejected and when
    plan = faults.FaultPlan([
        {"site": "prefill", "nth": 2},
        {"site": "decode_step", "seq_id": 3, "kind": "error"},
    ])
    errors = 0
    monitor.start_capture()
    try:
        with faults.installed(plan):
            with ContinuousBatchingEngine(model, total_pages=64,
                                          page_size=8,
                                          max_batch=4) as eng:
                reqs = [eng.submit(rng.integers(0, 64, (4,)),
                                   max_new_tokens=6, ttl_s=300.0)
                        for _ in range(5)]
                for r in reqs:
                    try:
                        r.result(timeout=600)
                    except faults.FaultError:
                        errors += 1
                pool_clean = (eng.cache.free_pages == 64
                              and eng._reserved_pages == 1)
                # cost/MFU accounting over the live engine: publishes
                # mfu + program_flops_total + program_hbm_bytes, the
                # series the existence gate requires
                from paddle_tpu.analysis import cost as _cost
                _cost.publish_engine_cost(eng)
    finally:
        monitor.stop_capture()
    quarantine_traced = True
    for r in reqs:
        if r.error is None:
            continue
        tl = monitor.request_timeline(r.request_id)
        kinds = [] if tl is None else [e["kind"] for e in tl["events"]]
        if "quarantine" not in kinds:
            quarantine_traced = False

    # lifecycle + drain path: a worker request, a cancelled request, an
    # expired request and a saturated submission, then a graceful drain
    # (touches every lifecycle counter + engine_draining)
    eng = ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                   max_batch=1, max_queue=2)
    r1 = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=24)
    import time as _time
    t0 = _time.time()
    while r1.seq_id is None and _time.time() - t0 < 120:
        _time.sleep(0.005)         # r1 admitted -> the queue is ours
    r_cancel = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4)
    r_cancel.cancel()
    r_expire = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4,
                          ttl_s=0.005)
    saturated = False
    try:
        eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4)
    except Exception:  # noqa: BLE001 — EngineSaturated (queue of 2 full)
        saturated = True
    drained = eng.drain(timeout=300) and r1.done.is_set() and saturated

    # heterogeneous-workload scenario (ISSUE 7): a chunk-delayed
    # batch-class prefill is preempted by an interactive request, then
    # resumes — touches every scheduler series the README documents
    plan2 = faults.FaultPlan([
        {"site": "prefill_chunk", "seq_id": 0, "kind": "delay",
         "delay_s": 0.05}])
    preempted_ok = False
    with faults.installed(plan2):
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=1,
                                      prefill_chunk_tokens=4) as eng:
            rb = eng.submit(rng.integers(0, 64, (16,)), max_new_tokens=4,
                            priority="batch", tenant="offline")
            t0 = _time.monotonic()
            while rb.prefill_pos == 0 and _time.monotonic() - t0 < 120:
                _time.sleep(0.005)
            ri = eng.submit(rng.integers(0, 64, (4,)), max_new_tokens=4,
                            priority="interactive", tenant="chat")
            ri.result(timeout=600)
            rb.result(timeout=600)
            preempted_ok = (ri.finished_at is not None
                            and rb.finished_at is not None
                            and ri.finished_at < rb.finished_at)

    # crash consistency (ISSUE 8a): a REAL donated-buffer loss
    # mid-decode on a full 4-row batch — the pools rebuild zeroed,
    # every survivor's KV replays, and all four outputs must be
    # bit-identical to a fault-free run of the same prompts
    loss_prompts = [rng.integers(0, 64, (5,)) for _ in range(4)]
    with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                  max_batch=4) as eng:
        loss_refs = [eng.submit(p, max_new_tokens=6).result(timeout=600)
                     for p in loss_prompts]
    plan_loss = faults.FaultPlan([{"site": "buffer_loss", "nth": 8}])
    with faults.installed(plan_loss):
        with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                      max_batch=4) as eng:
            reqs4 = [eng.submit(p, max_new_tokens=6)
                     for p in loss_prompts]
            got = [r.result(timeout=600) for r in reqs4]
    buffer_loss_exact = all(
        np.array_equal(g, e) for g, e in zip(got, loss_refs))
    buffer_loss_fired = any(s["fires"] for s in plan_loss.snapshot())

    # quantized serving (ISSUE 9): the same donated-buffer loss on an
    # int8-KV + w8 engine — the BATCHED survivor replay must rewrite
    # the int8 pages AND their scale pools bit-identically (scales
    # re-register with the pages), with fewer compiled dispatches than
    # survivors (the batching win)
    def run_quant(fault_plan=None):
        import contextlib
        ctx = (faults.installed(fault_plan) if fault_plan is not None
               else contextlib.nullcontext())
        # replay_batch explicit: this scenario gates the BATCHED
        # machinery (dispatch_d < replays_d), which the engine's unset
        # default disables on TPU; running it there exercises — and is
        # the hardware check for — the ROADMAP bit-exactness item
        with ctx, ContinuousBatchingEngine(
                model, total_pages=64, page_size=8, max_batch=4,
                quantize="w8", kv_quant="int8",
                replay_batch=True) as eng:
            reqs = [eng.submit(p, max_new_tokens=6) for p in loss_prompts]
            return [r.result(timeout=600) for r in reqs]

    quant_refs = run_quant()
    snap0 = monitor.snapshot()
    plan_qloss = faults.FaultPlan([{"site": "buffer_loss", "nth": 10}])
    quant_got = run_quant(plan_qloss)
    snap1 = monitor.snapshot()
    quant_loss_exact = (
        any(s["fires"] for s in plan_qloss.snapshot())
        and all(np.array_equal(g, e)
                for g, e in zip(quant_got, quant_refs)))
    replays_d = (_value(snap1, "survivor_replays_total")
                 - _value(snap0, "survivor_replays_total"))
    dispatch_d = (_value(snap1, "replay_dispatches_total")
                  - _value(snap0, "replay_dispatches_total"))
    batched_replay_won = replays_d >= 2 and 0 < dispatch_d < replays_d

    # crash consistency (ISSUE 8b): snapshot mid-stream, restore onto
    # a FRESH engine, outputs bit-identical to an uninterrupted run
    snap_prompts = [rng.integers(0, 64, (5,)) for _ in range(2)]
    with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                  max_batch=4) as eng:
        snap_refs = [eng.submit(p, max_new_tokens=8).result(timeout=600)
                     for p in snap_prompts]
    engA = ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                    max_batch=4)
    try:
        # slow the decode so the 5ms poll below cannot miss the
        # mid-stream window on a fast machine (the journal itself is
        # timing-free); installed() + try/finally keep the plan and
        # the engine thread from leaking into later lanes on failure
        with faults.installed(faults.FaultPlan(
                [{"site": "decode_step", "kind": "delay",
                  "delay_s": 0.01}])):
            live = [engA.submit(p, max_new_tokens=8)
                    for p in snap_prompts]
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < 120 and not all(
                    len(r.generated) >= 2 for r in live):
                _time.sleep(0.005)
            journal = engA.snapshot()
    finally:
        engA.stop()                   # the "crashed" process
    with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                  max_batch=4) as engB:
        resumed = engB.restore(journal)
        got = [r.result(timeout=600) for r in resumed]
    restore_exact = (len(journal["requests"]) == 2
                     and all(len(e["generated"]) >= 2
                             for e in journal["requests"])
                     and all(np.array_equal(g, e)
                             for g, e in zip(got, snap_refs)))

    # SIGKILL-grade durability (ISSUE 13), in-process half: mid-stream
    # requests survive a HARD engine stop — which journals NOTHING
    # (that is the crash floor a kill -9 leaves) — recover onto a
    # fresh engine bit-exactly through the write-ahead journal, and
    # the recovery pass compacts + consumes the crashed generation's
    # segments.  The subprocess SIGKILL half is run_hard_kill().
    import tempfile
    from paddle_tpu.inference.journal import RequestJournal
    jdir = tempfile.mkdtemp(prefix="chaos-journal-")
    jrnl = RequestJournal(jdir, fsync="always")
    engJ = ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                    max_batch=4, journal=jrnl)
    try:
        with faults.installed(faults.FaultPlan(
                [{"site": "decode_step", "kind": "delay",
                  "delay_s": 0.01}])):
            jl = [engJ.submit(p, max_new_tokens=8) for p in snap_prompts]
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < 120 and not all(
                    len(r.generated) >= 2 for r in jl):
                _time.sleep(0.005)
    finally:
        engJ.stop()
        jrnl.close()
    jrnl2 = RequestJournal(jdir, fsync="always")
    entries = jrnl2.recovered_requests()
    jref = {r.request_id: ref for r, ref in zip(jl, snap_refs)}
    with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                  max_batch=4, journal=jrnl2) as engJ2:
        restored = engJ2.restore({"version": 1, "requests": entries})
        jgot = {r.request_id: r.result(timeout=600) for r in restored}
    jrnl2.close()
    journal_exact = (
        len(entries) == 2
        and all(len(e["generated"]) >= 2 for e in entries)
        and all(np.array_equal(jgot[rid], ref)
                for rid, ref in jref.items()))

    # a failing preemption callback must be counted, not swallowed
    handler = PreemptionHandler(signals=())

    def bad_callback():
        raise RuntimeError("chaos probe")

    handler.on_preemption(bad_callback)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        handler._on_signal(None, None)

    snap = monitor.snapshot()
    out = {name: _value(snap, name) for name in REQUIRED_SERIES}
    for name in SCHEDULER_SERIES:
        out[name] = _series_total(snap, name)
    out["_poisoned_errors"] = errors
    out["_quarantine_traced"] = quarantine_traced
    out["_pool_clean"] = pool_clean
    out["_drained"] = drained
    out["_preempted_ok"] = preempted_ok
    out["_buffer_loss_fired"] = buffer_loss_fired
    out["_buffer_loss_exact"] = buffer_loss_exact
    out["_restore_exact"] = restore_exact
    out["_quant_loss_exact"] = quant_loss_exact
    out["_batched_replay_won"] = batched_replay_won
    out["_journal_exact"] = journal_exact
    return out


# --------------------------------------------------------------------
# hard-kill scenario (ISSUE 13 acceptance): subprocess server, SIGKILL
# mid-decode, restart over the same journal, zero lost admitted
# requests, bit-exact streams, /result re-attach across the restart
# --------------------------------------------------------------------

def _hk_model():
    """The hard-kill scenario's model — seeded, so the parent's
    reference engine, child A and child B all hold IDENTICAL weights
    across process boundaries."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    return LlamaForCausalLM(cfg)


def serve_child(argv) -> int:
    """Subprocess entry (``--child``): a GenerationServer with a
    write-ahead journal, its port published through ``--portfile``
    (atomic rename so the parent never reads a partial write), an
    optional decode delay widening the parent's mid-decode kill
    window.  Runs until killed."""
    import time as _time
    from paddle_tpu.inference.server import GenerationServer
    from paddle_tpu.testing import faults

    def arg(name, default=None):
        return next((a.split("=", 1)[1] for a in argv
                     if a.startswith(f"--{name}=")), default)

    journal_dir = arg("journal-dir")
    portfile = arg("portfile")
    delay = float(arg("decode-delay", "0"))
    tp = int(arg("tp", "1"))
    if tp > 1:
        # TP replica (ISSUE 20): the virtual CPU devices must exist
        # BEFORE the model build initializes the backend
        from paddle_tpu.framework.jax_compat import pin_cpu_devices
        pin_cpu_devices(max(tp, 2))
    if delay:
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": delay}]))
    model = _hk_model()
    draft = _hk_model()      # same seed -> identical weights, accept ~1
    srv = GenerationServer(model, draft_model=draft, spec_tokens=2,
                           total_pages=128, page_size=8, max_batch=4,
                           journal_dir=journal_dir,
                           journal_fsync="always", tp=tp).start()
    with open(portfile + ".tmp", "w") as f:
        f.write(str(srv.port))
    os.replace(portfile + ".tmp", portfile)
    while True:          # parent SIGKILLs/SIGTERMs us; never exit early
        _time.sleep(1.0)


def run_hard_kill() -> dict:
    """Drive the SIGKILL scenario; return {check_name: ok} plus
    observed details for the failure message."""
    import json
    import subprocess
    import tempfile
    import threading
    import time as _time
    import urllib.request
    import numpy as np

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    work = tempfile.mkdtemp(prefix="chaos-hardkill-")
    journal_dir = os.path.join(work, "journal")
    portfile = os.path.join(work, "port")
    logf = open(os.path.join(work, "child.log"), "ab")
    # CPU-only children that are killed mid-run: never on the chip, and
    # no persistent compile cache (the server's start() would place one)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")

    def spawn(delay):
        if os.path.exists(portfile):
            os.remove(portfile)
        return subprocess.Popen(
            [sys.executable,
             os.path.join(repo, "tools", "chaos_smoke.py"), "--child",
             f"--journal-dir={journal_dir}", f"--portfile={portfile}",
             f"--decode-delay={delay}"],
            env=env, cwd=repo, stdout=logf, stderr=logf)

    def wait_port(proc, timeout=300.0):
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < timeout:
            if os.path.exists(portfile):
                with open(portfile) as f:
                    return int(f.read())
            if proc.poll() is not None:
                raise RuntimeError(
                    f"hard-kill child died at startup "
                    f"(rc={proc.returncode}); see {logf.name}")
            _time.sleep(0.05)
        raise RuntimeError("hard-kill child never published its port")

    def get(port, path, timeout=30):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}",
                    timeout=timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            # /result/<id> 404s until the async POST lands — "not
            # yet", not a failure; the poll loops keep waiting
            try:
                return json.loads(e.read())
            except Exception:   # noqa: BLE001
                return {"error": f"http {e.code}"}

    def post_async(port, body):
        """POST /generate on a background thread; the connection dies
        with the SIGKILL, which is the point."""
        def _go():
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=600).read()
            except Exception:   # noqa: BLE001 — killed mid-stream
                pass
        t = threading.Thread(target=_go, daemon=True)
        t.start()
        return t

    rng = np.random.default_rng(7)
    shared = rng.integers(0, 64, (16,)).tolist()   # 2 full pages
    prompts = {
        "hk-greedy": shared + rng.integers(0, 64, (6,)).tolist(),
        "hk-sampled": rng.integers(0, 64, (7,)).tolist(),
        "hk-prefix": shared + rng.integers(0, 64, (5,)).tolist(),
        "hk-draft": rng.integers(0, 64, (6,)).tolist(),
    }
    bodies = {
        rid: {"input_ids": [prompts[rid]], "max_new_tokens": 12,
              "request_id": rid, "seed": 100 + i}
        for i, rid in enumerate(prompts)}
    bodies["hk-sampled"].update({"do_sample": True, "temperature": 0.8})
    bodies["hk-greedy"]["draft"] = False
    bodies["hk-prefix"]["draft"] = False
    bodies["hk-draft"]["draft"] = True
    # the speculative row advances ~spec_k+1 tokens per step: a longer
    # budget keeps it mid-decode at the kill instant
    bodies["hk-draft"]["max_new_tokens"] = 24

    # the uninterrupted-run oracle: an in-process engine over the SAME
    # seeded weights and submit parameters (prefix hits and greedy
    # speculation are output-invariant, locked by the PR 2/6 suites)
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    refs = {}
    with ContinuousBatchingEngine(_hk_model(), total_pages=128,
                                  page_size=8, max_batch=4) as eng:
        for rid, b in bodies.items():
            refs[rid] = eng.submit(
                np.asarray(b["input_ids"][0], np.int32),
                max_new_tokens=b["max_new_tokens"],
                do_sample=b.get("do_sample", False),
                temperature=b.get("temperature", 1.0),
                seed=b["seed"]).result(timeout=600)

    checks, details = {}, {}
    # 0.25 s a decode step HOLDS "all four mid-decode" for seconds (12
    # tokens, the draft row's 24 at three a step): the polls below need
    # a fraction of that on a loaded host, and the child is killed
    # anyway, so the delay costs the lane nothing
    proc = spawn(delay=0.25)
    try:
        port = wait_port(proc)
        # greedy first: its prefill registers the shared prefix, so
        # the prefix request's admission actually HITS the cache
        post_async(port, bodies["hk-greedy"])
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 120:
            res = get(port, "/result/hk-greedy")
            if res.get("generated_tokens", 0) >= 1 \
                    or res.get("status") == "done":
                break
            _time.sleep(0.02)
        for rid in ("hk-sampled", "hk-prefix", "hk-draft"):
            post_async(port, bodies[rid])
        # kill when every stream is mid-decode: >= 2 tokens, none done
        deadline = _time.monotonic() + 120
        while _time.monotonic() < deadline:
            states = {rid: get(port, f"/result/{rid}")
                      for rid in bodies}
            if any(s.get("status") == "done" for s in states.values()):
                break                     # window missed — fail below
            if all(s.get("generated_tokens", 0) >= 2
                   for s in states.values()):
                break
            _time.sleep(0.02)
        checks["all 4 mid-decode at kill time"] = all(
            s.get("status") == "pending"
            and s.get("generated_tokens", 0) >= 2
            for s in states.values())
        details["states_at_kill"] = states
    finally:
        proc.kill()                       # SIGKILL: no cleanup runs
        proc.wait(timeout=30)

    proc = spawn(delay=0)
    try:
        port = wait_port(proc)
        got = {}
        deadline = _time.monotonic() + 300
        for rid in bodies:
            while _time.monotonic() < deadline:
                res = get(port, f"/result/{rid}")
                if res.get("status") == "done":
                    got[rid] = res["output_ids"]
                    break
                if res.get("status") == "error":
                    details[f"error_{rid}"] = res
                    break
                _time.sleep(0.05)
        checks["zero lost admitted requests"] = len(got) == len(bodies)
        checks["streams bit-identical to the uninterrupted run"] = all(
            rid in got and got[rid] == [int(t) for t in refs[rid]]
            for rid in bodies)
        health = get(port, "/health")
        jinfo = health.get("journal", {})
        checks["/health reports the journal"] = (
            jinfo.get("path") == journal_dir
            and jinfo.get("segments", 0) >= 1
            and jinfo.get("fsync_policy") == "always")
        checks["restart recovered every journaled id"] = (
            health.get("restored_requests", 0) >= len(bodies))
        details["health"] = health
    finally:
        proc.kill()
        proc.wait(timeout=30)
        logf.close()
    return {"checks": checks, "details": details}


# --------------------------------------------------------------------
# fleet replica-kill scenario (ISSUE 14 acceptance): 2 subprocess
# replicas behind an in-parent supervisor + router, SIGKILL one
# mid-decode, journal-backed failover migrates its streams to the
# survivor bit-exactly, /result/<id> re-attaches through the router
# --------------------------------------------------------------------

def run_fleet_kill() -> dict:
    import json
    import subprocess
    import tempfile
    import threading
    import time as _time
    import urllib.error
    import urllib.request
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.inference.fleet import FleetRouter, ReplicaSupervisor

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    work = tempfile.mkdtemp(prefix="chaos-fleet-")
    logf = open(os.path.join(work, "children.log"), "ab")
    # CPU-only children that are killed mid-run: never on the chip, and
    # no persistent compile cache (the server's start() would place one)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")

    def spawn(name, delay, tp=1):
        jdir = os.path.join(work, name, "journal")
        portfile = os.path.join(work, name, "port")
        os.makedirs(os.path.dirname(portfile), exist_ok=True)
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(repo, "tools", "chaos_smoke.py"), "--child",
             f"--journal-dir={jdir}", f"--portfile={portfile}",
             f"--decode-delay={delay}", f"--tp={tp}"],
            env=env, cwd=repo, stdout=logf, stderr=logf)
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 300:
            if os.path.exists(portfile):
                with open(portfile) as f:
                    return proc, jdir, int(f.read())
            if proc.poll() is not None:
                raise RuntimeError(f"fleet child {name} died at "
                                   f"startup; see {logf.name}")
            _time.sleep(0.05)
        raise RuntimeError(f"fleet child {name} never published a port")

    def get(port_or_url, path, timeout=30):
        url = (port_or_url if isinstance(port_or_url, str)
               else f"http://127.0.0.1:{port_or_url}")
        try:
            with urllib.request.urlopen(url + path, timeout=timeout) as r:
                return json.loads(r.read())
        except urllib.error.HTTPError as e:
            try:
                return json.loads(e.read())
            except Exception:   # noqa: BLE001
                return {"error": f"http {e.code}"}

    rng = np.random.default_rng(11)
    shared = rng.integers(0, 64, (16,)).tolist()
    prompts = {
        "fk-greedy": shared + rng.integers(0, 64, (6,)).tolist(),
        "fk-sampled": rng.integers(0, 64, (7,)).tolist(),
        "fk-prefix": shared + rng.integers(0, 64, (5,)).tolist(),
        "fk-draft": rng.integers(0, 64, (6,)).tolist(),
    }
    # budgets are WIDE (vs the hard-kill lane's 12) and a decode step
    # takes 0.25 s: the two replicas decode independently, so the kill
    # window must stay open until the SLOWEST replica's streams have
    # >= 2 tokens while the fastest has not finished — four seconds
    # and more of "all four pending", where four polls through the
    # router take a fraction of one.  Speculative rows advance
    # ~spec_k+1 per step, so the draft row gets the widest budget
    bodies = {
        rid: {"input_ids": [prompts[rid]], "max_new_tokens": 24,
              "request_id": rid, "seed": 200 + i}
        for i, rid in enumerate(prompts)}
    bodies["fk-sampled"].update({"do_sample": True, "temperature": 0.8})
    bodies["fk-greedy"]["draft"] = False
    bodies["fk-prefix"]["draft"] = False
    bodies["fk-draft"]["draft"] = True
    bodies["fk-draft"]["max_new_tokens"] = 48

    # the uninterrupted-run oracle over the same seeded weights
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    refs = {}
    with ContinuousBatchingEngine(_hk_model(), total_pages=128,
                                  page_size=8, max_batch=4) as eng:
        for rid, b in bodies.items():
            refs[rid] = eng.submit(
                np.asarray(b["input_ids"][0], np.int32),
                max_new_tokens=b["max_new_tokens"],
                do_sample=b.get("do_sample", False),
                temperature=b.get("temperature", 1.0),
                seed=b["seed"]).result(timeout=600)

    checks, details = {}, {}
    snap0 = monitor.snapshot()
    procs = {}
    sup = ReplicaSupervisor(probe_interval_s=0.1,
                            probe_failure_threshold=2,
                            probe_timeout_s=2.0,
                            heartbeat_timeout_s=10.0)
    router = FleetRouter(sup)
    try:
        # r1 is a TP=2 replica (ISSUE 20): a sharded engine is one
        # replica to the fleet — probes, migration and bit-exact
        # failover must not notice the mesh behind it
        for name, tp in (("r0", 1), ("r1", 2)):
            proc, jdir, port = spawn(name, delay=0.25, tp=tp)
            procs[name] = proc
            sup.add_replica(name, f"http://127.0.0.1:{port}",
                            journal_dir=jdir, proc=proc)
        sup.start()
        router.start()
        rurl = f"http://{router.host}:{router.port}"
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 300 \
                and len(sup.routable_replicas()) < 2:
            _time.sleep(0.05)
        checks["both replicas probed up"] = \
            len(sup.routable_replicas()) == 2

        # warm BOTH replicas' prefix caches so fk-prefix hits wherever
        # round-robin lands it (hits are output-invariant — this only
        # makes the scenario exercise the prefix path, like the
        # hard-kill lane does on its single server)
        def post(body, out):
            def _go():
                try:
                    req = urllib.request.Request(
                        rurl + "/generate",
                        data=json.dumps(body).encode(),
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=600) as r:
                        out[body["request_id"]] = json.loads(r.read())
                except Exception as e:   # noqa: BLE001
                    out[body["request_id"]] = {"error": repr(e)}
            t = threading.Thread(target=_go, daemon=True)
            t.start()
            return t

        warm_out: dict = {}
        warm = [dict(bodies["fk-greedy"], request_id=f"warm-{i}",
                     max_new_tokens=2, draft=False) for i in range(2)]
        for t in [post(b, warm_out) for b in warm]:
            t.join(timeout=300)

        outs: dict = {}
        threads = [post(bodies[rid], outs) for rid in bodies]
        deadline = _time.monotonic() + 300
        while _time.monotonic() < deadline:
            states = {rid: get(rurl, f"/result/{rid}") for rid in bodies}
            if any(s.get("status") == "done" for s in states.values()):
                break
            if all(s.get("generated_tokens", 0) >= 2
                   for s in states.values()):
                break
            _time.sleep(0.02)
        checks["all 4 mid-decode at kill time"] = all(
            s.get("status") == "pending"
            and s.get("generated_tokens", 0) >= 2
            for s in states.values())
        details["states_at_kill"] = states
        # SIGKILL the replica owning the most in-flight streams
        owners = [states[rid].get("replica") for rid in bodies]
        victim = max(set(owners), key=owners.count)
        details["victim"] = victim
        details["owners"] = dict(zip(bodies, owners))
        procs[victim].kill()
        procs[victim].wait(timeout=30)

        for t in threads:
            t.join(timeout=300)
        checks["zero failed requests"] = all(
            "output_ids" in outs.get(rid, {}) for rid in bodies)
        checks["streams bit-identical to the uninterrupted run"] = all(
            outs.get(rid, {}).get("output_ids", [[]])[0]
            == [int(t) for t in refs[rid]] for rid in bodies)
        reattach = {rid: get(rurl, f"/result/{rid}") for rid in bodies}
        checks["/result re-attaches through the router for every id"] \
            = all(r.get("status") == "done"
                  and r["output_ids"] == [int(t) for t in refs[rid]]
                  for rid, r in reattach.items())
        details["migrated_ids"] = [rid for rid, o in outs.items()
                                   if o.get("reattached")]
        snap1 = monitor.snapshot()
        fo = _series_total(snap1, "fleet_failovers_total") or 0
        mig = _series_total(snap1, "fleet_migrated_requests_total") or 0
        checks["fleet_failovers_total fired"] = fo >= 1
        checks["fleet_migrated_requests_total fired"] = mig >= 1
        missing = [n for n in FLEET_SERIES
                   if _series_total(snap1, n) is None]
        checks["fleet/router series all exist"] = not missing
        details["missing_series"] = missing
        details["failovers"] = fo
        details["migrated"] = mig
        details["snap0_failovers"] = _series_total(
            snap0, "fleet_failovers_total")
    finally:
        try:
            router.stop()
            sup.stop()
        except Exception:   # noqa: BLE001 — teardown best-effort
            pass
        for proc in procs.values():
            proc.kill()
            proc.wait(timeout=30)
        logf.close()
    return {"checks": checks, "details": details}


def run_overload_kill() -> dict:
    """ISSUE 19 satellite: overload AND a replica kill at once.  Two
    in-process replicas with SLO-budgeted priority classes and the
    brownout ladder enabled take a decode-delayed batch flood several
    times their capacity plus a handful of interactive requests; one
    replica is hard-killed mid-flood.  The gate: every interactive
    request still completes (batch shedding absorbed the overload,
    journal-backed failover absorbed the kill), at least one batch
    arrival was shed with ``sched_shed_on_arrival_total`` ticking,
    failover fired, and every OVERLOAD_SERIES metric exists in
    ``monitor.snapshot()``."""
    import json
    import tempfile
    import threading
    import time as _time
    import urllib.error
    import urllib.request
    from paddle_tpu import monitor
    from paddle_tpu.testing import faults
    from paddle_tpu.inference.fleet import FleetRouter, ReplicaSupervisor
    from paddle_tpu.inference.scheduler import PriorityClass
    from paddle_tpu.inference.server import GenerationServer

    work = tempfile.mkdtemp(prefix="chaos-overload-")
    classes = (
        PriorityClass("interactive", rank=0, weight=8),
        PriorityClass("standard", rank=1, weight=4),
        # a deliberately tight budget: once the delayed flood drags the
        # decode p50 up, queued batch arrivals are doomed-on-arrival,
        # and the brownout band shed covers the rest
        PriorityClass("batch", rank=2, weight=1, preemptible=True,
                      deadline_s=0.05),
    )

    def factory(name, jdir):
        return GenerationServer(
            _hk_model(), total_pages=128, page_size=8, max_batch=2,
            max_queue=8, journal_dir=jdir, journal_fsync="os",
            scheduler_classes=classes,
            brownout_thresholds=(0.2, 0.5, 0.75, 0.95),
            brownout_patience=2)

    checks, details = {}, {}
    snap0 = monitor.snapshot()
    shed0 = _series_total(snap0, "sched_shed_on_arrival_total") or 0.0
    fo0 = _series_total(snap0, "fleet_failovers_total") or 0.0
    sup = ReplicaSupervisor(factory=factory, replicas=2,
                            journal_root=work, probe_interval_s=0.1,
                            probe_failure_threshold=2,
                            probe_timeout_s=2.0,
                            heartbeat_timeout_s=10.0)
    router = FleetRouter(sup, attach_timeout_s=300.0)
    outs, threads = {}, []

    def post(body):
        def _go():
            try:
                req = urllib.request.Request(
                    f"http://{router.host}:{router.port}/generate",
                    data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=600) as r:
                    payload = json.loads(r.read())
                    payload["_status"] = 200
            except urllib.error.HTTPError as e:
                try:
                    payload = json.loads(e.read())
                except Exception:   # noqa: BLE001
                    payload = {}
                payload["_status"] = e.code
            except Exception as e:   # noqa: BLE001
                payload = {"_status": -1, "error": repr(e)}
            outs[body["request_id"]] = payload
        t = threading.Thread(target=_go, daemon=True)
        t.start()
        threads.append(t)

    inter = [f"ov-inter-{i}" for i in range(4)]
    try:
        sup.start()
        router.start()
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 300 \
                and len(sup.routable_replicas()) < 2:
            _time.sleep(0.05)
        checks["both replicas up"] = len(sup.routable_replicas()) == 2

        # warm/compile outside the overload window (standard class, so
        # the interactive SLO window starts clean)
        for i in range(2):
            post({"input_ids": [[3 + i, 5, 7, 11]],
                  "max_new_tokens": 4, "priority": "standard",
                  "request_id": f"ov-warm-{i}"})
        for t in threads:
            t.join(timeout=600)

        # the flood decodes slowly, so its queue pressure is real
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": 0.03}]))
        try:
            for i in range(8):
                post({"input_ids": [[13 + i, 17, 19, 23, 29]],
                      "max_new_tokens": 12, "priority": "batch",
                      "request_id": f"ov-batch-{i}"})
            _time.sleep(1.0)     # let the ladder see the depth
            # second batch wave arrives INTO the brownout: shed fodder
            for i in range(8, 16):
                post({"input_ids": [[13 + i, 17, 19, 23, 29]],
                      "max_new_tokens": 12, "priority": "batch",
                      "request_id": f"ov-batch-{i}"})
            for i, rid in enumerate(inter):
                post({"input_ids": [[31 + i, 37, 41]],
                      "max_new_tokens": 4,
                      "priority": "interactive", "request_id": rid})
            _time.sleep(0.5)     # streams in flight on both replicas
            victims = sup.routable_replicas()
            victim = victims[0].name if victims else "r0"
            sup.kill(victim)
            details["victim"] = victim
            for t in threads:
                t.join(timeout=600)
        finally:
            faults.clear()

        snap1 = monitor.snapshot()
        inter_bad = [rid for rid in inter
                     if outs.get(rid, {}).get("_status") != 200
                     or not outs[rid].get("output_ids")]
        details["interactive_failed"] = inter_bad
        details["batch_statuses"] = sorted(
            str(v.get("_status")) for k, v in outs.items()
            if k.startswith("ov-batch-"))
        shed = (_series_total(snap1, "sched_shed_on_arrival_total")
                or 0.0) - shed0
        fo = (_series_total(snap1, "fleet_failovers_total")
              or 0.0) - fo0
        details["sheds"] = shed
        details["failovers"] = fo
        missing = [n for n in OVERLOAD_SERIES
                   if _series_total(snap1, n) is None]
        details["missing_series"] = missing
        checks["every interactive request completed despite the "
               "flood and the kill"] = not inter_bad
        checks["batch arrivals shed under pressure"] = shed >= 1
        checks["failover fired on the killed replica"] = fo >= 1
        checks["overload series all published"] = not missing
    finally:
        try:
            router.stop()
            sup.stop()
        except Exception:   # noqa: BLE001 — teardown best-effort
            pass
    return {"checks": checks, "details": details}


def overload_main() -> int:
    out = run_overload_kill()
    bad = [name for name, ok in out["checks"].items() if not ok]
    if bad:
        print(f"FAIL (overload): {bad}; observed {out['details']}",
              file=sys.stderr)
        return 1
    print(f"OK: replica {out['details']['victim']} killed under a 4x "
          f"batch flood — every interactive request completed, "
          f"{int(out['details']['sheds'])} batch arrivals shed with "
          "truthful 429s, and failover recovered the rest")
    return 0


def fleet_main() -> int:
    out = run_fleet_kill()
    bad = [name for name, ok in out["checks"].items() if not ok]
    if bad:
        print(f"FAIL (fleet): {bad}; observed {out['details']}",
              file=sys.stderr)
        return 1
    print(f"OK: SIGKILL'd replica {out['details']['victim']} lost "
          f"nothing — {int(out['details']['migrated'])} streams "
          "migrated to the survivor bit-exactly and /result "
          "re-attached through the router")
    return 0


def hard_kill_main() -> int:
    out = run_hard_kill()
    bad = [name for name, ok in out["checks"].items() if not ok]
    if bad:
        print(f"FAIL (hard-kill): {bad}; observed {out['details']}",
              file=sys.stderr)
        return 1
    print("OK: SIGKILL mid-decode lost nothing — 4/4 streams resumed "
          "bit-exactly across the hard restart")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--child" in argv:
        return serve_child(argv)
    if "--hard-kill-only" in argv:
        return hard_kill_main()
    if "--fleet-only" in argv or "--fleet" in argv:
        return fleet_main()
    if "--overload-only" in argv:
        return overload_main()
    rc = _counters_main()
    if rc == 0 and "--skip-hard-kill" not in argv:
        rc = hard_kill_main()
    if rc == 0 and "--skip-fleet" not in argv \
            and "--skip-hard-kill" not in argv:
        # the fleet lane spawns subprocess replicas like the hard-kill
        # lane; --skip-hard-kill marks a run that wants no subprocess
        # scenarios (each gets its own gate in tests/test_tools.py)
        rc = fleet_main()
    if rc == 0 and "--skip-overload" not in argv \
            and "--skip-hard-kill" not in argv:
        # overload + replica-kill (ISSUE 19) rides the standalone CI
        # run; its tier-1 gate is separate like the two lanes above
        rc = overload_main()
    return rc


def _counters_main() -> int:
    out = run_chaos()
    missing = [n for n in REQUIRED_SERIES + SCHEDULER_SERIES
               if out.get(n) is None]
    if missing:
        print(f"FAIL: monitor.snapshot() missing resilience/scheduler "
              f"series {missing}", file=sys.stderr)
        return 1
    checks = [
        ("interactive preempted the batch prefill and both finished",
         out["_preempted_ok"]),
        ("sched_preemptions_total counted the slot pause",
         out["sched_preemptions_total"] >= 1),
        ("sched_resumed_total counted the resume",
         out["sched_resumed_total"] >= 1),
        ("sched_prefill_chunks_total counted chunked prefill",
         out["sched_prefill_chunks_total"] >= 4),
        ("sched_admitted_total counted admissions",
         out["sched_admitted_total"] >= 2),
        ("exactly the 2 poisoned requests errored",
         out["_poisoned_errors"] == 2),
        ("quarantined requests' trace timelines record the quarantine "
         "event", out["_quarantine_traced"]),
        ("trace capture recorded events", out["trace_events_total"] >= 1),
        ("cost analyzer published program FLOPs",
         out["program_flops_total"] > 0),
        ("pool fully reclaimed after quarantine", out["_pool_clean"]),
        ("drain completed", out["_drained"]),
        ("quarantined_requests_total counted both poisons",
         out["quarantined_requests_total"] >= 2),
        ("decode_retries_total counted the replay",
         out["decode_retries_total"] >= 1),
        ("preemption_callback_errors_total counted the bad callback",
         out["preemption_callback_errors_total"] >= 1),
        ("engine heartbeat advanced",
         out["engine_last_step_timestamp_seconds"] > 0),
        ("buffer_loss fault actually fired", out["_buffer_loss_fired"]),
        ("survivors bit-identical after donated-buffer loss",
         out["_buffer_loss_exact"]),
        ("survivor_replays_total counted the replays",
         out["survivor_replays_total"] >= 4),
        ("engine_rebuilds_total counted the pool rebuild",
         out["engine_rebuilds_total"] >= 1),
        ("engine_recovery_seconds observed an MTTR sample",
         out["engine_recovery_seconds"] >= 1),
        ("snapshot->restore resumed mid-stream requests bit-exactly",
         out["_restore_exact"]),
        ("snapshot_requests_total counted the journal entries",
         out["snapshot_requests_total"] >= 2),
        ("int8-KV survivors bit-identical after loss (scales "
         "re-registered with the pages)", out["_quant_loss_exact"]),
        ("batched replay amortized survivors per dispatch",
         out["_batched_replay_won"]),
        ("write-ahead journal resumed a hard-stopped engine's "
         "mid-stream requests bit-exactly", out["_journal_exact"]),
        ("journal_records_total counted the WAL appends",
         out["journal_records_total"] >= 4),
        ("journal_recovered_requests_total counted the resume",
         out["journal_recovered_requests_total"] >= 2),
        ("journal_compactions_total counted the recovery compaction",
         out["journal_compactions_total"] >= 1),
        ("journal fsync cost was measured",
         out["journal_fsync_seconds"] >= 1),
    ]
    bad = [name for name, ok in checks if not ok]
    if bad:
        print(f"FAIL: {bad}; observed {out}", file=sys.stderr)
        return 1
    print(f"OK: {len(REQUIRED_SERIES)} resilience series present; "
          f"quarantined={int(out['quarantined_requests_total'])} "
          f"retries={int(out['decode_retries_total'])}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
