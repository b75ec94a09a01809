"""HTTP model server over the Predictor (reference: the C++ fluid
inference server / Paddle Serving's role — here a dependency-free
stdlib implementation fronting the StableHLO Predictor).

Endpoints (JSON; arrays as nested lists with dtype strings):
  GET  /health          -> {"status": "ok", "model": prefix,
                            "uptime_s": ..., "requests_total": ...}
  GET  /metadata        -> input/output names
  GET  /metrics         -> Prometheus text exposition (paddle_tpu.monitor)
  POST /predict         -> {"inputs": {name: {"data": [...], "dtype": ...,
                            "shape": [...]}}} -> {"outputs": {...}}

A PredictorPool serves concurrent requests; the ThreadingHTTPServer
dispatches each request to a pool slot.  Every request is measured into
the process-wide metrics registry (``requests_total`` counter,
``request_latency_seconds`` histogram, tagged by server and route).
"""
from __future__ import annotations

import json
import os
import threading
import time
import warnings
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional

import numpy as np

from .. import monitor
from . import Config, Predictor, PredictorPool

__all__ = ["InferenceServer", "GenerationServer", "serve"]


_requests_total = monitor.counter(
    "requests_total", "HTTP requests served", ("server", "route"))
_request_latency = monitor.histogram(
    "request_latency_seconds", "HTTP request wall latency",
    ("server", "route"))


class _JsonHandler(BaseHTTPRequestHandler):
    """Shared HTTP plumbing: quiet logs (opt-in via access_log=True) +
    JSON replies + per-route telemetry."""

    server_kind = "http"     # overridden per server class

    def log_message(self, fmt, *args):
        if getattr(self, "_outer", None) is not None \
                and self._outer._access_log:
            BaseHTTPRequestHandler.log_message(self, fmt, *args)

    def _reply(self, code, payload, headers=None):
        body = json.dumps(payload).encode()
        self._reply_bytes(code, body, "application/json", headers)

    def _reply_text(self, code, text,
                    content_type="text/plain; version=0.0.4"):
        self._reply_bytes(code, text.encode(), content_type)

    def _reply_bytes(self, code, body, content_type, headers=None):
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _read_json(self):
        n = int(self.headers.get("Content-Length", 0))
        return json.loads(self.rfile.read(n))

    def _track(self, route):
        """Count the request (registry + per-server cumulative count)
        and return a latency span for the handling block."""
        _requests_total.inc(server=self.server_kind, route=route)
        self._outer._bump_requests()
        return monitor.span(f"http/{self.server_kind}{route}",
                            histogram=_request_latency,
                            server=self.server_kind, route=route)


class _ServerLifecycle:
    """start/stop/context-manager + uptime/request accounting shared by
    both servers."""

    def _init_stats(self, access_log: bool):
        self._access_log = bool(access_log)
        self._started_at = time.monotonic()
        self._requests_lock = threading.Lock()
        self._requests_served = 0
        # readiness (ISSUE 14 satellite): set once serve_forever is
        # live — a supervisor starting replicas on port 0 waits on
        # this instead of sleep-and-polling the socket.  The listener
        # is BOUND at construction (``port`` is final then, even for
        # an ephemeral port-0 bind, and any journal/snapshot restore
        # has completed), so connections made after wait_ready() are
        # served, never refused.
        self._ready = threading.Event()

    @property
    def address(self):
        """``(host, port)`` of the bound listener — final at
        construction, port-0 binds resolved to the ephemeral port."""
        return (self.host, self.port)

    def wait_ready(self, timeout=None) -> bool:
        """Block until :meth:`start`'s serving thread is live (True),
        or ``timeout`` elapsed (False)."""
        return self._ready.wait(timeout)

    def _bump_requests(self):
        with self._requests_lock:
            self._requests_served += 1

    @property
    def requests_served(self) -> int:
        with self._requests_lock:
            return self._requests_served

    @property
    def uptime_s(self) -> float:
        return time.monotonic() - self._started_at

    def start(self):
        # programs compile on the first request after this: place the
        # persistent compilation cache before any of them
        from ..framework.compile_cache import configure_compile_cache
        configure_compile_cache()
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        self._ready.set()
        return self

    def stop(self):
        self._ready.clear()
        if self._thread is not None:
            # shutdown() handshakes with the serve_forever loop — on a
            # never-started server it would wait forever, so only the
            # socket close applies there
            self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False


class InferenceServer(_ServerLifecycle):
    """Serve a jit.save artifact over HTTP.

    Usage::

        server = InferenceServer("ckpt/model", device="cpu", pool_size=2)
        server.start()              # non-blocking; .port has the port
        ...
        server.stop()
    """

    def __init__(self, model_prefix: str, host: str = "127.0.0.1",
                 port: int = 0, pool_size: int = 1, device: str = "",
                 access_log: bool = False):
        config = Config(model_prefix)
        if device == "cpu":
            config.disable_gpu()
        elif device not in ("", "tpu", "gpu"):
            raise ValueError(
                f"device must be '', 'cpu', 'tpu' or 'gpu', got {device!r}")
        self._prefix = model_prefix
        self._pool = PredictorPool(config, pool_size)
        self._pool_lock = threading.Lock()
        self._next = [0]
        self._size = pool_size
        self._init_stats(access_log)
        outer = self

        class Handler(_JsonHandler):
            server_kind = "inference"
            _outer = outer

            def do_GET(self):
                if self.path == "/health":
                    with self._track("/health"):
                        self._reply(200, {
                            "status": "ok", "model": outer._prefix,
                            "uptime_s": round(outer.uptime_s, 3),
                            "requests_total": outer.requests_served})
                elif self.path == "/metadata":
                    with self._track("/metadata"):
                        p = outer._pool.retrieve(0)
                        self._reply(200, {
                            "inputs": p.get_input_names(),
                            "outputs": p.get_output_names()})
                elif self.path == "/metrics":
                    with self._track("/metrics"):
                        self._reply_text(200, monitor.prometheus_text())
                elif self.path == "/debug/trace":
                    with self._track("/debug/trace"):
                        self._reply(200, monitor.export_chrome_trace())
                else:
                    self._reply(404, {"error": f"no route {self.path}"})

            def do_POST(self):
                if self.path != "/predict":
                    self._reply(404, {"error": f"no route {self.path}"})
                    return
                with self._track("/predict"):
                    try:
                        out = outer._predict(self._read_json())
                        self._reply(200, out)
                    except Exception as e:   # noqa: BLE001
                        self._reply(400, {"error": str(e)})

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------
    def _predict(self, req):
        inputs = req.get("inputs", {})
        with self._pool_lock:
            idx = self._next[0] % self._size
            self._next[0] += 1
        pred = self._pool.retrieve(idx)
        names = pred.get_input_names()
        missing = [n for n in names if n not in inputs]
        if missing:
            raise ValueError(f"missing inputs: {missing}")
        arrays = []
        for name in names:
            spec = inputs[name]
            arr = np.asarray(spec["data"],
                             dtype=spec.get("dtype", "float32"))
            if "shape" in spec:
                arr = arr.reshape(spec["shape"])
            arrays.append(arr)
        # handle-free run: inputs are passed per call and outputs returned
        # directly, so concurrent requests sharing a pool slot never race
        # through the copy_from_cpu/run/copy_to_cpu handle state
        results = pred.run(arrays)
        outputs = {}
        for name, out in zip(pred.get_output_names(), results):
            a = np.asarray(out)
            outputs[name] = {"data": a.tolist(), "dtype": str(a.dtype),
                             "shape": list(a.shape)}
        return {"outputs": outputs}


class GenerationServer(_ServerLifecycle):
    """Serve a causal LM's paged-KV decode path over HTTP (the serving
    role of the reference's block_multihead_attention deployment stack).

    POST /generate  {"input_ids": [[...], ...], "max_new_tokens": N,
                     "eos_token_id": id?, "do_sample": bool?,
                     "temperature": float?, "draft": bool?}
        -> {"output_ids": [[...], ...], "new_tokens": N}

    Requests are CONTINUOUSLY BATCHED: every row of every in-flight HTTP
    request is its own sequence in one shared ContinuousBatchingEngine —
    concurrent requests decode together per step instead of queueing
    behind a server lock, and short generations retire without waiting
    for long ones.  Sampled requests draw a fresh per-request seed
    unless the request pins one.  The engine's hot-path knobs plumb
    through: ``sample_on_device`` (fused in-step sampling) and
    ``prefix_cache`` (shared-prompt-prefix KV reuse) — both on by
    default; so do the resilience knobs ``max_queue`` /
    ``default_ttl_s`` / ``step_timeout_s`` (ISSUE 4), and a request
    body may set ``timeout_s`` as its own total TTL.

    Speculative decoding (ISSUE 6): construct with ``draft_model`` and
    greedy requests decode speculatively (``spec_tokens`` draft
    proposals per step, bit-exact vs target-only greedy); a request
    body may set ``"draft": false`` to opt out, or ``true`` to demand
    it (400 if the server has no draft model).  ``/health`` reports
    the draft pool; acceptance counters land in ``/metrics``
    (``spec_*`` series).

    Error mapping (the resilience HTTP contract):
      400 = malformed request (bad JSON/shape, or prompt +
            max_new_tokens past the model's rope table);
      429 = admission queue full (``EngineSaturated``) — retry after
            the ``Retry-After`` header;
      503 = pool/capacity exhaustion or draining (retry elsewhere);
      504 = the request's deadline (TTL / queue-wait) expired;
      500 = unexpected server fault.

    Graceful drain: ``begin_drain()`` (or SIGTERM via
    ``attach_preemption``) stops new admissions — fresh /generate
    requests get 503 with ``"draining": true`` while in-flight
    generations run to completion; /health reports the drain state.

    Scheduling & multi-tenancy (ISSUE 7): a request body may set
    ``"priority"`` (scheduling class: ``interactive`` / ``standard`` /
    ``batch`` by default; unknown -> 400) and ``"tenant"`` (fair-queued
    within the class).  ``prefill_chunk_tokens`` caps per-step prefill
    so long prompts interleave with decode instead of stalling it;
    ``min_table_pages`` pins the compiled programs' page-table width
    for recompile-free mixed-length serving.  429 responses carry a
    class-aware ``Retry-After``; ``/health`` reports per-class queue
    depths and the active policy knobs under ``"scheduler"``.

    Quantized serving (ISSUE 9): ``quantize="w8"|"w8a8"`` runs the
    compiled decode/prefill/chunk/verify programs with int8 weights
    (scales traced, calibrated through the PTQ observers);
    ``kv_quant="int8"`` stores KV pages int8 with fused
    quantize-on-append / dequant-in-kernel — roughly 4x (f32) or 2x
    (bf16) the concurrent sequences per pool byte.  ``/health``
    reports both modes plus resident KV byte accounting.

    Crash consistency (ISSUE 8): with ``snapshot_path`` set, SIGTERM
    (via ``attach_preemption``) first journals every in-flight request
    — ``engine.snapshot()`` written atomically to the path — and THEN
    begins the graceful drain; a restarted server finding the journal
    consumes it (renamed to ``<path>.restored`` so a crash loop cannot
    double-resume) and resubmits each request through the engine's
    replay primitive, so mid-stream generations continue bit-exactly
    in the new process.  ``save_snapshot()`` is also callable directly
    (an operator checkpoint before risky maintenance).  ``/health``
    reports ``snapshot_path`` and the restored-request count when the
    knob is set.

    SIGKILL-grade durability (ISSUE 13): ``journal_dir`` supersedes
    the cooperative snapshot with a WRITE-AHEAD request journal —
    every admission/step/retirement is CRC-framed to disk as it
    happens (``journal_fsync``: ``always`` / ``interval_ms`` / ``os``),
    so a ``kill -9``, OOM-kill or power loss mid-decode loses nothing:
    the restarted server scans the segments, reconstructs the live set
    (admitted minus retired, journal deadlines verbatim) and resumes
    every request bit-exactly before the listener opens, with
    ``/result/<request_id>`` re-attaching across the hard restart
    exactly as it does across SIGTERM.  The SIGTERM path collapses
    onto the same format: the pre-drain "snapshot" is just
    ``journal.flush(sync=True)`` (the WAL already holds everything)
    and the post-drain refresh a final compaction.  ``/health``
    reports the journal path, segment count and fsync policy;
    ``journal_dir`` and ``snapshot_path`` are mutually exclusive.

    Observability (ISSUE 10): a request body may pin ``"request_id"``
    (multi-row bodies get ``<id>/<row>`` per row); the reply always
    carries ``"request_ids"``, and ``GET /result/<id>`` re-attaches to
    a finished (200) or in-flight (202) generation — including after a
    snapshot/restore restart, where journaled ids are preserved.
    ``POST /debug/trace/start`` / ``/debug/trace/stop`` bracket a
    capture window; ``GET /debug/trace`` exports it as chrome-trace
    JSON (engine-step track + per-request flow events + profiler host
    spans) and ``GET /debug/requests/<id>`` returns one request's raw
    event timeline.  ``GET /debug/cost`` runs the analytical cost model
    over the decode program and publishes ``program_flops_total`` /
    ``program_hbm_bytes`` / ``mfu`` to ``/metrics``; its ``spmd``
    group (ISSUE 11) adds the tier-3 distributed audit — static peak
    HBM, priced collective bytes and analytic ICI seconds, sharding
    hazard count — publishing ``program_peak_hbm_bytes`` /
    ``collective_bytes_total`` / ``ici_time_seconds`` alongside.
    """

    def __init__(self, model, host: str = "127.0.0.1", port: int = 0,
                 total_pages: int = 512, page_size: int = 16,
                 max_batch: int = 8, sample_on_device: bool = True,
                 prefix_cache: bool = True, access_log: bool = False,
                 max_queue: int = 256,
                 default_ttl_s: Optional[float] = None,
                 step_timeout_s: Optional[float] = None,
                 draft_model=None, spec_tokens: int = 4,
                 draft_total_pages: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 scheduler_classes=None,
                 min_table_pages: int = 1,
                 snapshot_path: Optional[str] = None,
                 preempt_resume_ttl_s: Optional[float] = None,
                 quantize: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 replay_batch: Optional[bool] = None,
                 journal_dir: Optional[str] = None,
                 journal_fsync: str = "interval_ms",
                 journal_fsync_interval_ms: float = 50.0,
                 journal_segment_bytes: int = 1 << 20,
                 journal_fsync_timeout_s: Optional[float] = None,
                 brownout_thresholds=None,
                 brownout_patience: int = 3,
                 decode_preempt: bool = True,
                 tpot_preempt_cooldown_s: float = 0.25,
                 tp: int = 1,
                 tp_quant_collectives: bool = False):
        from .continuous import (ContinuousBatchingEngine,
                                 DeadlineExceeded, EngineDraining,
                                 EngineSaturated)
        from ..testing import faults as _faults

        if journal_dir and snapshot_path:
            raise ValueError(
                "journal_dir and snapshot_path are mutually exclusive: "
                "the write-ahead journal supersedes the cooperative "
                "snapshot (one persistence format, ISSUE 13)")
        self._journal = None
        self._journal_entries = []
        if journal_dir:
            from .journal import RequestJournal
            # constructing the journal RECOVERS a predecessor's
            # segments (crash-loop-safe: the live set is re-compacted
            # into a fresh durable segment before the old ones are
            # consumed) — the entries are resubmitted after the
            # listener socket binds, mirroring the snapshot path
            self._journal = RequestJournal(
                journal_dir, fsync=journal_fsync,
                fsync_interval_ms=journal_fsync_interval_ms,
                segment_bytes=journal_segment_bytes,
                fsync_timeout_s=journal_fsync_timeout_s)
            self._journal_entries = self._journal.recovered_requests()
        try:
            self._engine = ContinuousBatchingEngine(
                model, total_pages=total_pages, page_size=page_size,
                max_batch=max_batch, sample_on_device=sample_on_device,
                prefix_cache=prefix_cache, max_queue=max_queue,
                default_ttl_s=default_ttl_s,
                step_timeout_s=step_timeout_s,
                draft_model=draft_model, spec_tokens=spec_tokens,
                draft_total_pages=draft_total_pages,
                prefill_chunk_tokens=prefill_chunk_tokens,
                scheduler_classes=scheduler_classes,
                min_table_pages=min_table_pages,
                preempt_resume_ttl_s=preempt_resume_ttl_s,
                quantize=quantize, kv_quant=kv_quant,
                replay_batch=replay_batch, journal=self._journal,
                brownout_thresholds=brownout_thresholds,
                brownout_patience=brownout_patience,
                decode_preempt=decode_preempt,
                tpot_preempt_cooldown_s=tpot_preempt_cooldown_s,
                tp=tp, tp_quant_collectives=tp_quant_collectives)
        except BaseException:
            # a rejected engine knob must not leak the journal's
            # writer thread / open segment / watchdog heartbeat (the
            # live set stays on disk for the next attempt)
            if self._journal is not None:
                self._journal.close()
            raise
        self._count_lock = threading.Lock()
        self._request_count = 0
        self._drain_thread: Optional[threading.Thread] = None
        self._drain_result: Optional[bool] = None
        self._snapshot_path = snapshot_path
        self._restored_requests = 0
        self._init_stats(access_log)
        outer = self

        class Handler(_JsonHandler):
            server_kind = "generation"
            _outer = outer

            def do_GET(self):
                if self.path == "/health":
                    with self._track("/health"):
                        cache = outer._engine.cache
                        draining = outer._engine.draining
                        payload = {
                            "status": "draining" if draining else "ok",
                            "draining": draining,
                            "uptime_s": round(outer.uptime_s, 3),
                            "requests_total": outer.requests_served,
                            "free_pages": cache.free_pages,
                            "total_pages": cache.total_pages,
                            "page_size": cache.page_size,
                            "cached_prefix_pages":
                                cache.cached_prefix_pages,
                            "sampling_on_device":
                                outer._engine.sample_on_device,
                            "active_sequences": len(outer._engine._active),
                            "queued_sequences": len(outer._engine._sched),
                            # fleet routing (ISSUE 14): the same
                            # backoff hint a 429 would carry, scraped
                            # per probe so the router can aggregate
                            # fleet Retry-After = min over healthy
                            # replicas without a rejected request
                            "retry_after_hint":
                                outer._engine.retry_after_hint(),
                            # scheduling & multi-tenancy (ISSUE 7):
                            # per-class queue depths + the active
                            # policy knobs, so an operator can read
                            # the WFQ/chunking configuration off a
                            # live replica
                            "scheduler": outer._engine.scheduler_info(),
                            # quantized serving (ISSUE 9): the modes an
                            # operator reads off a live replica, plus
                            # the resident-KV byte accounting capacity
                            # planning needs
                            "quantize": outer._engine.quantize,
                            "kv_quant": outer._engine.kv_quant,
                            "kv_pool_bytes": cache.kv_pool_bytes,
                            "kv_scale_bytes": cache.kv_scale_bytes,
                            # tensor-parallel serving (ISSUE 20): the
                            # mesh this replica's programs compile onto
                            # plus PER-CHIP resident-KV bytes — the
                            # number capacity planning divides by, and
                            # how a fleet operator tells a TP replica
                            # from a 1-chip one at a glance
                            "tp": outer._engine.tp,
                            "mesh_shape": (
                                dict(outer._engine.mesh.shape)
                                if outer._engine.mesh is not None
                                else None),
                            "tp_quant_collectives":
                                outer._engine.tp_quant_collectives,
                            "kv_pool_bytes_per_chip":
                                cache.kv_pool_bytes_per_chip,
                            "speculative": outer._engine._spec}
                        if outer._snapshot_path:
                            payload.update({
                                "snapshot_path": outer._snapshot_path,
                                "restored_requests":
                                    outer._restored_requests})
                        if outer._journal is not None:
                            # ISSUE 13: the durability posture an
                            # operator reads off a live replica —
                            # journal path, segment count, fsync
                            # policy (and whether a hung fsync
                            # degraded it)
                            payload.update({
                                "journal": outer._journal.info(),
                                "restored_requests":
                                    outer._restored_requests})
                        if outer._engine._spec:
                            dc = outer._engine.draft_cache
                            # capacity accounting must include the
                            # draft cache (ISSUE 6 monitor satellite)
                            payload.update({
                                "spec_tokens": outer._engine.spec_k,
                                "draft_free_pages": dc.free_pages,
                                "draft_total_pages": dc.total_pages,
                                "draft_pinned_pages": dc.pinned_pages})
                        self._reply(200, payload)
                elif self.path == "/metrics":
                    with self._track("/metrics"):
                        self._reply_text(200, monitor.prometheus_text())
                elif self.path == "/debug/trace":
                    # the capture buffer as chrome-trace JSON — load it
                    # in Perfetto (ISSUE 10; tools/trace_capture.py is
                    # the CLI driver of start -> load -> stop -> GET)
                    with self._track("/debug/trace"):
                        self._reply(200, monitor.export_chrome_trace())
                elif self.path.startswith("/debug/requests/"):
                    # one request's event timeline by its stable id
                    # (route label is collapsed so ids can't explode
                    # the metrics cardinality)
                    with self._track("/debug/requests"):
                        rid = self.path[len("/debug/requests/"):]
                        tl = monitor.request_timeline(rid)
                        if tl is None:
                            self._reply(404, {
                                "error": f"no timeline for request "
                                         f"{rid!r} (tracing off, or "
                                         "evicted from the bounded "
                                         "buffer)"})
                        else:
                            self._reply(200, tl)
                elif self.path == "/debug/cost":
                    # analytical decode-program cost + process-lifetime
                    # MFU, published to /metrics as a side effect
                    # (program_flops_total / program_hbm_bytes / mfu)
                    with self._track("/debug/cost"):
                        try:
                            from ..analysis.cost import \
                                publish_engine_cost
                            self._reply(200,
                                        publish_engine_cost(outer._engine))
                        except Exception as e:  # noqa: BLE001
                            self._reply(500, {"error": str(e)})
                elif self.path.startswith("/result/"):
                    # request-id re-attach (ISSUE 10 satellite): a
                    # client that lost its stream — timeout, server
                    # restart — polls the bounded result cache; a
                    # restored request keeps its journaled id, so the
                    # SAME id works across the restart
                    with self._track("/result"):
                        rid = self.path[len("/result/"):]
                        res = outer._engine.result_for(rid)
                        if res is None:
                            self._reply(404, {
                                "error": f"unknown request id {rid!r} "
                                         "(never seen, or evicted from "
                                         "the bounded result cache)"})
                        elif res.get("status") == "pending":
                            self._reply(202, res)
                        else:
                            self._reply(200, res)
                else:
                    self._reply(404, {"error": f"no route {self.path}"})

            def do_POST(self):
                if self.path == "/debug/trace/start":
                    with self._track("/debug/trace/start"):
                        monitor.start_capture()
                        self._reply(200, {"capturing": True})
                    return
                if self.path == "/debug/trace/stop":
                    with self._track("/debug/trace/stop"):
                        monitor.stop_capture()
                        self._reply(200, {"capturing": False})
                    return
                if self.path == "/admin/migrate":
                    with self._track("/admin/migrate"):
                        self._do_migrate()
                    return
                if self.path != "/generate":
                    self._reply(404, {"error": f"no route {self.path}"})
                    return
                with self._track("/generate"):
                    self._do_generate()

            def _do_migrate(self):
                """Journal-backed failover's far side (ISSUE 14): the
                replica supervisor POSTs a dead replica's recovered
                live set here; each snapshot-format entry flows through
                the engine's replay-admission path (``strict=False`` —
                one unplaceable request must not abort the batch; ids
                ALREADY live here dedup into ``rejected``, which makes
                a supervisor that crashed between migrate and
                source-retire safely re-runnable).  Replies with the
                ids that landed so the caller retires exactly those in
                the source journal."""
                if outer._engine.draining:
                    self._reply(503, {"error": "replica draining; "
                                      "migrate elsewhere",
                                      "draining": True})
                    return
                try:
                    body = self._read_json()
                    entries = body.get("requests", [])
                    if not isinstance(entries, list):
                        raise ValueError("requests must be a list")
                except (ValueError, TypeError,
                        json.JSONDecodeError) as e:
                    self._reply(400, {"error": str(e)})
                    return
                # ids this replica ALREADY knows (live now, or finished
                # in the result cache) are the dedup outcome, not a
                # migration failure: a router retry landed them here
                # first, or an earlier crashed failover got this far —
                # report them as "live" so the supervisor retires them
                # in the source journal instead of leaving zombies
                live, todo = [], []
                for e in entries:
                    rid = e.get("request_id")
                    if rid is not None \
                            and outer._engine.result_for(rid) is not None:
                        live.append(rid)
                    else:
                        todo.append(e)
                try:
                    with warnings.catch_warnings(record=True) as wlog:
                        warnings.simplefilter("always")
                        reqs = outer._engine.restore(
                            {"version": 1, "requests": todo},
                            strict=False)
                except Exception as e:  # noqa: BLE001 — server fault
                    self._reply(500, {"error": str(e)})
                    return
                ok = [r.request_id for r in reqs]
                landed = set(ok) | set(live)
                self._reply(200, {
                    "restored": ok,
                    "live": live,
                    "rejected": [e.get("request_id") for e in entries
                                 if e.get("request_id") not in landed],
                    # per-entry skip reasons (restore warns one line
                    # per rejected entry) — the supervisor logs these,
                    # so a failed placement is diagnosable from the
                    # router side
                    "warnings": [str(w.message) for w in wlog]})

            def _do_generate(self):
                try:
                    _faults.maybe_fire("http_handler")
                    try:
                        req = self._read_json()
                        if not isinstance(req, dict):
                            raise ValueError("request body must be a "
                                             "JSON object")
                        ids = np.asarray(req["input_ids"], np.int32)
                        if ids.ndim != 2:
                            raise ValueError("input_ids must be 2-D "
                                             "(batch, seq)")
                        max_new = int(req.get("max_new_tokens", 32))
                        eos = req.get("eos_token_id")
                        do_sample = bool(req.get("do_sample", False))
                        temperature = float(req.get("temperature", 1.0))
                        ttl = req.get("timeout_s")
                        ttl = None if ttl is None else float(ttl)
                        draft = req.get("draft")
                        draft = None if draft is None else bool(draft)
                        priority = req.get("priority")
                        priority = (None if priority is None
                                    else str(priority))
                        tenant = str(req.get("tenant", "default"))
                        request_id = req.get("request_id")
                        request_id = (None if request_id is None
                                      else str(request_id))
                        with outer._count_lock:
                            outer._request_count += 1
                            seed = int(req.get("seed",
                                               outer._request_count))
                    except (KeyError, ValueError, TypeError,
                            json.JSONDecodeError) as e:
                        self._reply(400, {"error": str(e)})
                        return
                    try:
                        out, rows = outer._engine.generate_with_requests(
                            ids, max_new_tokens=max_new, eos_token_id=eos,
                            do_sample=do_sample, temperature=temperature,
                            seed=seed, ttl_s=ttl, draft=draft,
                            priority=priority, tenant=tenant,
                            request_id=request_id)
                    except ValueError as e:      # request-shape problems
                        # e.g. prompt + max_new_tokens past the rope
                        # table: the CLIENT's request is wrong — 400,
                        # never the retryable 503 (regression-locked in
                        # tests/test_engine_faults.py)
                        self._reply(400, {"error": str(e)})
                        return
                    self._reply(200, {
                        "output_ids": out.tolist(),
                        "new_tokens": int(out.shape[1] - ids.shape[1]),
                        # the stable per-row ids (ISSUE 10): the
                        # /result/<id> and /debug/requests/<id> handles
                        "request_ids": [r.request_id for r in rows]})
                except EngineSaturated as e:
                    # bounded-queue overflow: retryable — the hint is
                    # the REQUESTING CLASS's backlog's estimated
                    # service time (its queue depth x measured
                    # decode-step p50, clamped to [1, 30]s): a chat
                    # client is never told to back off for the batch
                    # queue's sins.  An admission SHED (ISSUE 19)
                    # carries its own projected-wait hint, computed
                    # at the decision — prefer it over re-deriving
                    hint = getattr(e, "retry_after_s", None)
                    cls = getattr(e, "priority_class", None) or priority
                    if hint is None:
                        hint = outer._engine.retry_after_hint(cls)
                    self._reply(429, {"error": str(e)}, headers={
                        "Retry-After": str(hint)})
                except EngineDraining as e:
                    self._reply(503, {"error": str(e), "draining": True})
                except DeadlineExceeded as e:
                    self._reply(504, {"error": str(e)})
                except RuntimeError as e:
                    # capacity (page-pool) exhaustion: retryable
                    self._reply(503, {"error": str(e)})
                except Exception as e:   # noqa: BLE001 — server fault
                    self._reply(500, {"error": str(e)})

        try:
            self._httpd = ThreadingHTTPServer((host, port), Handler)
        except BaseException:
            # heartbeat-leak fix (ISSUE 14 satellite): a bind failure —
            # a supervisor restarting a replica in-process on a port
            # its predecessor is still releasing hits exactly this —
            # must not leak the already-running engine: its scheduler
            # thread, its step_timeout_s watchdog heartbeat (which
            # would fire comm_timeouts_total against a dead engine
            # forever) and the journal's writer thread + fsync
            # heartbeat all deregister here
            self._engine.stop()
            if self._journal is not None:
                self._journal.close()
            raise
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None
        # crash consistency (ISSUE 8): consume a predecessor's journal
        # AFTER the listener socket bound (a bind failure — e.g. the
        # predecessor still releasing the port — must not have eaten
        # the journal) but before serve_forever starts: restored
        # requests are decoding by the time the first request arrives
        if self._journal is not None:
            self._restored_requests = self._restore_journal()
        elif snapshot_path and os.path.exists(snapshot_path):
            self._restored_requests = self._restore_snapshot(snapshot_path)

    # ------------------------------------- write-ahead journal (ISSUE 13)
    def _restore_journal(self) -> int:
        """Resubmit the live set the journal recovered — each entry
        flows through the engine's replay-admission path exactly like
        a snapshot restore (``strict=False``: one unplaceable request
        must not abort the whole resume).  Entries the engine rejected
        are retired in the journal as ``unrestorable`` so they cannot
        zombie through every future compaction."""
        entries = self._journal_entries
        if not entries:
            return 0
        try:
            reqs = self._engine.restore({"version": 1,
                                         "requests": entries},
                                        strict=False)
        except Exception as e:  # noqa: BLE001 — degrade, never block
            warnings.warn(f"journal restore failed: {e!r}")  # startup
            return 0
        ok = {r.request_id for r in reqs}
        for e in entries:
            rid = e.get("request_id")
            if rid is not None and rid not in ok:
                self._journal.append_retire(rid, why="unrestorable")
        return len(reqs)

    # ----------------------------------------------- snapshot (ISSUE 8)
    def _restore_snapshot(self, path: str) -> int:
        """Consume a predecessor's journal: rename first (a crash
        mid-restore must not double-resume), then resubmit every entry
        through the engine's replay primitive — per-entry failures are
        warned about, never fatal (strict=False)."""
        consumed = path + ".restored"
        try:
            os.replace(path, consumed)
            with open(consumed) as f:
                snap = json.load(f)
        except (OSError, ValueError) as e:
            warnings.warn(f"snapshot restore skipped: {e!r}")
            return 0
        try:
            return len(self._engine.restore(snap, strict=False))
        except Exception as e:  # noqa: BLE001 — a malformed journal
            # (valid JSON, wrong shape) must degrade to an empty
            # resume, never keep the server from starting
            warnings.warn(f"snapshot restore failed: {e!r}")
            return 0

    def save_snapshot(self, path: Optional[str] = None) -> int:
        """Journal every in-flight request to ``path`` (default: the
        configured ``snapshot_path``) atomically; returns the request
        count.  The engine quiesces at a step boundary first, so the
        journal is a consistent cut a restarted process resumes
        bit-exactly."""
        path = path or self._snapshot_path
        if not path:
            raise ValueError("no snapshot_path configured")
        snap = self._engine.snapshot()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(snap, f)
        # durability bugfix (ISSUE 13 satellite): a bare os.replace
        # never fsyncs the file or the parent directory, so the rename
        # itself could be lost on power failure — the journal's shared
        # helper syncs both
        from .journal import durable_replace
        durable_replace(tmp, path)
        return len(snap["requests"])

    # ------------------------------------------------- graceful shutdown
    @property
    def draining(self) -> bool:
        return self._engine.draining

    def begin_drain(self, timeout: Optional[float] = None,
                    reject_queued: bool = False) -> None:
        """Start a graceful drain WITHOUT blocking (idempotent): the
        engine stops admitting — new /generate requests get 503 with
        ``"draining": true`` and /health flips to ``"draining"`` —
        while every in-flight generation runs to completion.  The HTTP
        listener stays up throughout so clients can still poll /health
        and /metrics.  ``reject_queued=True`` is the hard-preemption
        fast path: queued-but-unadmitted requests fail immediately
        instead of being completed first."""
        if self._drain_thread is not None and self._drain_thread.is_alive():
            return
        self._drain_result = None

        def _drain():
            self._drain_result = self._engine.drain(
                timeout=timeout, reject_queued=reject_queued)

        self._drain_thread = threading.Thread(
            target=_drain, name="server-drain", daemon=True)
        self._drain_thread.start()

    def wait_drained(self, timeout: Optional[float] = None) -> bool:
        """Block until a begin_drain() started earlier finishes;
        True if it completed within ``timeout``."""
        t = self._drain_thread
        if t is None:
            eng = self._engine
            with eng._cond:
                return not (eng._active or len(eng._sched)
                            or eng._prefilling or eng._preempted)
        t.join(timeout)
        return not t.is_alive()

    def attach_preemption(self, handler,
                          drain_timeout: Optional[float] = None) -> None:
        """Wire a distributed.fault_tolerance.PreemptionHandler: on
        SIGTERM (the TPU pod preemption notice) the server begins a
        graceful drain — the resilience contract's 'finish what you
        admitted, reject what you have not' shutdown.  With
        ``snapshot_path`` configured the drain is bracketed by
        snapshots (ISSUE 8): one taken IMMEDIATELY (the crash floor —
        if the grace period ends mid-drain, everything in flight is
        journaled) and one refreshed when the drain settles, so
        requests the drain DID finish are dropped from the journal and
        never re-executed by the relaunched process; whatever the
        drain window was too short to finish resumes exactly."""
        def drain_on_preemption():
            # stop admissions SYNCHRONOUSLY first: begin_drain only
            # spawns the drain thread, and a request admitted before
            # that thread flips the flag would be journal-invisible
            # and lost if the grace period ends mid-drain
            self._engine.stop_admissions()
            self.begin_drain(timeout=drain_timeout)
            if self._journal is not None:
                # ISSUE 13: the WAL already holds every in-flight
                # request — the SIGTERM "snapshot" collapses to one
                # durable flush (the crash floor) plus a final
                # compaction once the drain truly completed, so a
                # relaunch resumes exactly what the grace period was
                # too short to finish and nothing more
                try:
                    self._journal.flush(sync=True, timeout=30.0)
                except Exception as e:  # noqa: BLE001 — drain anyway
                    warnings.warn(f"pre-drain journal flush failed: "
                                  f"{e!r}")

                def _refresh_journal():
                    if self.wait_drained(None) and self._drain_result:
                        try:
                            self._journal.compact(wait=True,
                                                  timeout=30.0)
                        except Exception as e:  # noqa: BLE001 — keep
                            # the crash-floor journal rather than none
                            warnings.warn(
                                f"post-drain journal compaction "
                                f"failed: {e!r}")
                threading.Thread(target=_refresh_journal, daemon=True,
                                 name="journal-refresh").start()
            elif self._snapshot_path:
                try:
                    self.save_snapshot()
                except Exception as e:   # noqa: BLE001 — the drain
                    # must still happen even if the journal write fails
                    warnings.warn(f"pre-drain snapshot failed: {e!r}")
                def _refresh():
                    # shrink the journal ONLY after a drain that
                    # actually COMPLETED its requests — a timed-out
                    # drain or a hard stop() (which ERRORS the
                    # remainder) must keep the crash-floor journal, or
                    # the relaunch would resume nothing.  The wait is
                    # unbounded: the drain thread itself terminates at
                    # ITS deadline, and racing it with the same
                    # timeout would skip the refresh for a drain that
                    # finished right at the wire
                    if self.wait_drained(None) and self._drain_result:
                        try:
                            self.save_snapshot()
                        except Exception as e:  # noqa: BLE001 — keep
                            # the crash-floor journal rather than none
                            warnings.warn(
                                f"post-drain snapshot refresh failed: "
                                f"{e!r}")
                threading.Thread(target=_refresh, daemon=True,
                                 name="snapshot-refresh").start()
        handler.on_preemption(drain_on_preemption)

    def stop(self):
        super().stop()
        self._engine.stop()
        if self._drain_thread is not None:
            self._drain_thread.join(timeout=5)
            self._drain_thread = None
        if self._journal is not None:
            # closing flushes + final-fsyncs but deliberately does NOT
            # retire live entries: a stop without retirement is the
            # crash floor a relaunched server resumes from
            self._journal.close()


def serve(model_prefix: str, host: str = "127.0.0.1", port: int = 8000,
          pool_size: int = 1):
    """Blocking CLI-style entry: serve the model until interrupted."""
    server = InferenceServer(model_prefix, host, port, pool_size)
    print(f"serving {model_prefix} at http://{server.host}:{server.port}")
    try:
        server._httpd.serve_forever()
    except KeyboardInterrupt:
        server.stop()
