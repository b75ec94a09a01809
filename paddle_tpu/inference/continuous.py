"""Continuous batching over the paged-KV pool.

Reference capability: the block-multi-head serving path
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu) —
sequences share a page pool and join/leave the running decode batch per
step.  The round-4 GenerationServer serialized whole requests behind a
lock; this engine admits each sequence independently:

  * requests enqueue; a scheduler thread admits them whenever a running
    slot and enough pool pages are free (admission RESERVES the
    sequence's worst-case pages so mid-decode allocation can never fail
    and wedge the batch);
  * every decode step runs ALL active sequences as one batch — each at
    its own length/position (per-row rope positions, per-row page
    tables), so a long generation no longer blocks short ones behind it;
  * finished sequences retire per step (pages freed, waiter woken) and
    their slots are immediately re-admissible.

Batch shapes are bucketed to powers of two (padding rows ride on a
scratch sequence that is truncated every step) so the decode step
compiles once per bucket, not once per active-count.

Resilience layer (ISSUE 4):

  * request lifecycle — per-request deadlines (queue-wait +
    total TTL), cooperative ``cancel()`` honored at admission and
    between decode steps, and a bounded admission queue whose overflow
    raises :class:`EngineSaturated` (HTTP 429 at the server);
  * graceful drain — ``drain()`` stops new submissions, finishes
    everything already submitted, then reclaims the pool and stops the
    scheduler (``stop()`` stays the hard kill);
  * failure isolation — a failing step (chunk rows, decode rows and
    verify rows are one step) is retried whole once and then BISECTED
    by its rows (solo replay at size 1) to eject exactly the poisoned
    sequence(s) while the rest of the batch keeps going
    (:meth:`ContinuousBatchingEngine._isolate_unified`); a failing
    whole-prompt prefill of an unchunked engine errors only its
    request;
  * stall detection — an engine heartbeat registered with the comm
    watchdog (``step_timeout_s``) fires the same timeout machinery as
    a hung collective when a device step wedges;
  * deterministic fault injection — the ``paddle_tpu.testing.faults``
    sites ``prefill`` / ``prefill_chunk`` / ``decode_step`` /
    ``engine_wedge`` / ``page_alloc`` are consulted at near-zero cost
    when no plan is installed.

Speculative decoding (ISSUE 6):

  * pass ``draft_model`` and the engine decodes speculatively: the
    draft proposes ``spec_tokens`` greedy tokens per active sequence in
    ONE compiled scan over its OWN PagedKVCache (pages allocated/freed
    in lockstep with the target's), then the target scores each row's
    ``k+1``-token block as a verify row of the step's ONE ragged
    dispatch — accept lengths
    and the bonus token are computed on device, so the host boundary
    stays ``(batch,)`` ids + ``(batch,)`` accept counts;
  * greedy speculative decoding is EXACT (bit-identical tokens to
    target-only greedy, whatever the draft proposes); sampled requests
    ride along unaccelerated (their draft slots never match, so they
    advance exactly one fused-sampled token per step);
  * rejected suffixes roll back via page-granular length truncation on
    BOTH caches (pages stay mapped inside the admission reservation);
    draft-side failures DOWNGRADE the affected requests to plain decode
    instead of quarantining them — speculation is an optimization, so
    a broken draft must never fail a request.

Heterogeneous workloads (ISSUE 7):

  * admission and step composition are delegated to a
    :class:`~paddle_tpu.inference.scheduler.WorkloadScheduler` —
    ``submit(priority=..., tenant=...)`` routes into per-class,
    per-tenant bounded queues served by weighted deficit-round-robin
    (see scheduler.py for the policy contract);
  * **chunked prefill** — with ``prefill_chunk_tokens`` set, each
    engine iteration runs at most ~one chunk budget of prefill before
    the decode step, so a long prompt can no longer stall every
    interactive sequence's next token behind a monolithic prefill;
    chunk boundaries are position-derived (never timing-derived), KV
    pages fill incrementally through the SAME compiled context-prefill
    program the prefix cache uses, and greedy output is bit-identical
    to unchunked prefill (prefix-cache acquire still happens once, at
    admission);
  * **preemption** — a preemptible class's mid-prefill request can be
    PAUSED (slot handed to more urgent traffic) and later resumed: it
    keeps its seq id, its written pages and its reservation, and
    continues from the next chunk — it never re-prefills;
  * per-class SLO series (queue-wait / TTFT / TPOT histograms,
    admission / preemption / chunk counters) land in ``monitor``
    labeled ``cls=<class>``; ``/health`` reports queue depths and the
    active policy knobs.

Crash-consistent serving (ISSUE 8):

  * ONE replay primitive — a sequence's KV state is reconstructed by
    re-prefilling ``prompt + generated-so-far`` through the existing
    (chunked) context-prefill program.  Bit-exact for greedy AND
    sampled rows: the fused sampler's counter is ``(seed, absolute
    position)``, so a replayed draw is the original draw — and the
    already-transferred ``next_token`` is host state that survives any
    device-side loss, so the continuation is token-for-token what the
    uninterrupted run would have produced;
  * **device-failure recovery** — after a REAL donated-buffer loss the
    decoder rebuilds the pools zeroed (``PagedKVCache.generation``
    bumps); the engine detects the bump across any failed step/chunk,
    replays EVERY survivor (active, mid-prefill and preempted; draft
    pool in lockstep; prefix-cache entries re-registered with page
    refcounts restored) and only then retries/bisects — so quarantine
    ejects exactly the poisoned row for device-side failures too, not
    just host-side ones;
  * **watchdog-driven restart** — when the ``step_timeout_s``
    heartbeat fires, the watchdog's ``on_timeout`` callback flags the
    in-flight step as wedged; the engine then performs a BOUNDED
    rebuild (reset pools + survivor replay + one retry, after which
    the normal retry/bisect ladder bounds further attempts) instead of
    only incrementing ``comm_timeouts_total``;
  * **snapshot/restore** — ``snapshot()`` quiesces at a step boundary
    and serializes every in-flight request (prompt, generated ids,
    pending next token, seed, class/tenant, draft opt-in, remaining
    TTL) to a JSON-able journal; ``restore()`` resubmits each entry
    through the replay primitive (admission-path mode: the chunked
    prefill ingests ``prompt + generated`` instead of the prompt), so
    a restarted process resumes mid-stream requests exactly;
  * telemetry: ``survivor_replays_total`` / ``engine_rebuilds_total``
    counters, the ``engine_recovery_seconds`` histogram (serving MTTR)
    and ``snapshot_requests_total``.
"""
from __future__ import annotations

import math
import threading
import time
import uuid
from collections import OrderedDict, namedtuple
from typing import List, Optional

import jax
import numpy as np
from .. import monitor
from ..monitor.gc_hooks import pause_ns as gc_pause_ns
from ..monitor.trace import get_tracer as _get_tracer
from ..ops.pallas.paged_attention import PagedKVCache, paged_layout
from ..testing import faults as _faults
from .scheduler import (DEFAULT_CLASS, PriorityClass, QueueFull,
                        WorkloadScheduler)

__all__ = [
    "ContinuousBatchingEngine", "EngineSaturated", "EngineDraining",
    "DeadlineExceeded", "RequestCancelled", "retry_after_seconds",
    "PriorityClass", "WorkloadScheduler",
]

_PAD_SEQ = "__pad__"


def _null_sampling(n: int = 1):
    """Fused-sampling args whose rows draw nothing (flags all False):
    the argmax-only program tail for dispatches whose sampled value is
    discarded — intermediate prefill chunks, draft prompt ingestion,
    and KV replay."""
    return (np.zeros(n, np.uint32), np.zeros(n, np.int32),
            np.ones(n, np.float32), np.zeros(n, bool))


class EngineSaturated(RuntimeError):
    """The bounded admission queue is full — retryable later (the
    GenerationServer maps this to HTTP 429 + Retry-After)."""


class EngineDraining(RuntimeError):
    """The engine is draining for graceful shutdown and accepts no new
    submissions (HTTP 503; in-flight requests still complete)."""


class DeadlineExceeded(RuntimeError):
    """The request's queue-wait or total TTL expired before completion
    (HTTP 504); its pages/reservation were reclaimed."""


class RequestCancelled(RuntimeError):
    """The request was cooperatively cancelled via ``cancel()``."""


class _EngineWedged(RuntimeError):
    """Internal (ISSUE 8): the comm watchdog flagged the in-flight
    compiled step as wedged (heartbeat age exceeded
    ``step_timeout_s``).  The engine treats the step's results as
    suspect: pools are rebuilt, survivors replayed, and the step
    retried — the normal retry/bisect ladder bounds a persistent
    wedge."""


# engine telemetry (ISSUE 1): the serving-side numbers the ROADMAP's
# "serve heavy traffic" goal is judged by
_queue_depth = monitor.gauge(
    "inference_queue_depth", "sequences waiting for admission")
_active_seqs = monitor.gauge(
    "inference_active_sequences", "sequences in the running decode batch")
_batch_occupancy = monitor.histogram(
    "inference_batch_occupancy", "active/max_batch fraction per decode "
    "step", buckets=tuple(i / 8 for i in range(1, 9)))
_decode_step_s = monitor.histogram(
    "decode_step_seconds", "one continuous-batching decode step")
_prefill_s = monitor.histogram(
    "prefill_seconds", "one sequence's prefill")
_tokens_total = monitor.counter(
    "generated_tokens_total", "tokens produced by the decode loop")
_ttft_s = monitor.histogram(
    "time_to_first_token_seconds", "submit -> first sampled token")
_gen_latency_s = monitor.histogram(
    "generate_latency_seconds", "submit -> sequence retirement")
# serving hot-path telemetry (ISSUE 2): prefix-cache effectiveness and
# the on-device-sampling mode flag
_prefix_lookups = monitor.counter(
    "prefix_cache_lookups_total", "admissions that consulted the prefix "
    "cache")
_prefix_hits = monitor.counter(
    "prefix_cache_hits_total", "admissions whose prompt shared a cached "
    "page-aligned prefix")
_prefix_hit_tokens = monitor.counter(
    "prefix_cache_hit_tokens_total", "prompt tokens served from cached "
    "prefix pages instead of being re-prefilled")
_sampling_on_device_g = monitor.gauge(
    "sampling_on_device", "1 when the engine samples inside the compiled "
    "step (host transfer is (batch,) ids), 0 on the host-logits path")
# resilience telemetry (ISSUE 4): failure isolation + lifecycle + the
# serving heartbeat the watchdog scans
_decode_retries = monitor.counter(
    "decode_retries_total", "decode-step re-executions after a failure "
    "(one whole-batch retry, then one per bisection probe)")
_quarantined = monitor.counter(
    "quarantined_requests_total", "requests ejected by failure "
    "isolation: failed prefill, or poisoned sequence identified by "
    "decode-step bisection")
_expired_total = monitor.counter(
    "requests_expired_total", "requests retired by deadline expiry "
    "(queue-wait or total TTL)")
_cancelled_total = monitor.counter(
    "requests_cancelled_total", "requests retired by cooperative "
    "cancel()")
_saturated_total = monitor.counter(
    "engine_saturated_total", "submissions rejected because the bounded "
    "admission queue was full")
_last_step_ts = monitor.gauge(
    "engine_last_step_timestamp_seconds", "unix time the engine last "
    "completed a prefill or decode step — the serving heartbeat")
_draining_g = monitor.gauge(
    "engine_draining", "1 while the engine is draining for graceful "
    "shutdown, else 0")
_drain_rejected = monitor.counter(
    "drain_rejected_requests_total", "queued-but-unadmitted requests "
    "failed fast by drain(reject_queued=True)")
# speculative-decoding telemetry (ISSUE 6): acceptance economics and the
# draft cache's capacity footprint
_spec_proposed = monitor.counter(
    "spec_proposed_tokens_total", "draft tokens proposed to the "
    "compiled verify step")
_spec_accepted = monitor.counter(
    "spec_accepted_tokens_total", "proposed draft tokens the target "
    "verified and accepted")
_spec_accept_len = monitor.histogram(
    "spec_accept_len", "accepted draft tokens per sequence per verify "
    "step", buckets=tuple(float(i) for i in range(9)) + (12.0, 16.0,
                                                        24.0, 32.0))
_spec_rollback = monitor.counter(
    "spec_rollback_total", "per-sequence verify outcomes that rejected "
    "a draft suffix (partial multi-token rollback on both caches)")
_spec_draft_pages = monitor.gauge(
    "spec_draft_pages", "pages pinned in the draft model's KV pool — "
    "the speculative mode's capacity cost")
_spec_draft_failures = monitor.counter(
    "spec_draft_failures_total", "draft-side prefill/propose failures "
    "that downgraded requests to plain decode")
# crash-consistency telemetry (ISSUE 8): the recovery machinery's
# footprint — replays per survivor, rebuild events, and the MTTR
# histogram the serve_bench recovery lane quotes
_survivor_replays = monitor.counter(
    "survivor_replays_total", "sequences whose KV was reconstructed by "
    "replay (re-prefill of prompt + generated-so-far) after a "
    "donated-buffer loss or watchdog-driven rebuild")
_rebuilds_total = monitor.counter(
    "engine_rebuilds_total", "pool-rebuild recovery events the engine "
    "absorbed: device-side donated-buffer losses plus watchdog-flagged "
    "wedged steps")
_recovery_s = monitor.histogram(
    "engine_recovery_seconds", "one recovery event end to end: pool "
    "rebuild + every survivor's KV replay (the serving MTTR)")
_snapshot_reqs = monitor.counter(
    "snapshot_requests_total", "in-flight requests serialized by "
    "engine.snapshot()")
# quantized-serving telemetry (ISSUE 9): the capacity lever's footprint
_quant_enabled_g = monitor.gauge(
    "quant_enabled", "1 when the engine's compiled programs run "
    "quantized weights (w8/w8a8), else 0")
_kv_quant_enabled_g = monitor.gauge(
    "kv_quant_enabled", "1 when the PagedKVCache stores int8 pages "
    "with per-slot scale pools, else 0")
_kv_quant_pool_bytes_g = monitor.gauge(
    "kv_quant_pool_bytes", "resident bytes of the KV data pages "
    "(int8 mode stores a quarter of f32 / half of bf16)")
_kv_quant_scale_bytes_g = monitor.gauge(
    "kv_quant_scale_bytes", "resident bytes of the int8 mode's "
    "per-slot scale pools (0 at full precision)")
# expert layers, sliding-attention layers and the paged kernel's query
# tiles and page copies in the unified step: the sums of the ``dispatch`` records' fields
# of the same names, for a model that has such layers (none is touched
# for one that has not)
_STEP_SUMS = {
    name: monitor.counter(f"serve_{name}_total", text) for name, text in (
        ("moe_slots", "(real token, chosen expert) pairs of the unified "
         "steps: tokens x experts a token x expert layers"),
        ("moe_rows_computed", "rows the grouped expert product computed "
         "for them: the pairs in whole blocks, an expert at a time"),
        ("moe_experts_touched", "distinct experts a real token chose, "
         "summed over the expert layers: whose weights a step reads"),
        ("moe_expert_layers", "experts a step could have touched: experts "
         "x expert layers"),
        ("moe_max_expert_pairs", "pairs a layer's FULLEST expert got, "
         "summed over the expert layers: over moe_slots it is the share of "
         "a layer's tokens the expert most chosen holds"),
        ("ctx_tokens_window", "KV positions a sliding-attention layer's "
         "queries attend"),
        ("kv_tokens_walked_window", "KV positions a sliding-attention "
         "layer's paged kernel walks, from the first visible page"),
        ("kv_tokens_walked_nowindow", "what that walk would be from page "
         "0"),
        ("q_positions_computed", "query positions the paged kernel "
         "computed for the unified steps' padded rows: a row's own queries "
         "in whole tiles, a layer's worth"),
        ("page_copies", "page-copy descriptors the paged kernel's walks "
         "issued for the unified steps' padded rows: one a page for each "
         "group of kv heads a grid step owns and each pool, a layer's "
         "worth"),
        ("head_page_reads", "(page, kv head, pool) reads those descriptors "
         "served: over page_copies it is the heads a copy carries"),
        ("state_bytes", "bytes of recurrent state the unified steps' rows "
         "read and wrote, as the equations count a state (a row that "
         "carries a token: a layer's state once in, once out)"),
        ("kv_tokens_walked_shared", "KV positions the paged kernel walked "
         "for layers that read ANOTHER layer's pages (they append nothing "
         "and hold no pool): their part of the steps' walk, a paged "
         "call's worth"),
        ("kv_pinned_bytes", "bytes the pages the unified steps' real rows "
         "map hold in every page pool, each pool at its own page shape"),
        ("kv_dead_bytes", "the part of kv_pinned_bytes that lies in the "
         "sliding layers' own pools wholly behind the rows' next query's "
         "window: what a page table a pool kind would free"))}
_kv_bytes_copied = monitor.counter(
    "serve_kv_bytes_copied_total", "bytes of K and V pages the paged "
    "kernels' walks copied for the unified steps' padded rows, summed "
    "over all the calls of a kind (full, sliding) and every kv head, each "
    "pool at its own page shape", label_names=("kind",))
# recurrent slots (a model whose layers carry a state of fixed size a
# sequence): the cache's slot pool beside the page pools
_slots_taken = monitor.counter(
    "recurrent_slots_taken_total", "recurrent slots handed to a sequence "
    "(at admission, and again when a preempted sequence resumes)")
_slots_zeroed = monitor.counter(
    "recurrent_slots_zeroed_total", "rows that entered their slot with an "
    "empty context: the step starts them from zero whatever the slot held")
_slots_in_use_g = monitor.gauge(
    "recurrent_slots_in_use", "recurrent slots held by live sequences")
_kv_window_dead_pages_g = monitor.gauge(
    "kv_window_dead_pages", "pages the step's rows hold wholly behind "
    "their next query's sliding window: kept for the full-attention "
    "layers that share the table, dead to the sliding layers")
# batched survivor replay (ISSUE 9 satellite): dispatch economics —
# fewer compiled dispatches per recovery event is the MTTR lever
_replay_dispatches = monitor.counter(
    "replay_dispatches_total", "compiled dispatches issued by survivor-"
    "KV replay (batched replay amortizes many survivors per dispatch)")
# ragged unified step (ISSUE 17): dispatch economics.  An iteration's
# chunk, decode and verify rows are ONE "ragged" dispatch, so a mixed
# iteration's serving cost is quoted straight off this counter's mode
# split (serve_bench's mixed-batch lane gates on it)
_dispatches_total = monitor.counter(
    "engine_dispatches_total", "compiled program dispatches issued by "
    "the serving loop, per program mode — 'ragged' is the unified "
    "single-dispatch step; 'prefill'/'chunk' are the whole-prompt "
    "prefill of an engine without prefill_chunk_tokens; 'draft' is the "
    "draft model's own propose/ingest dispatches (a second model: never "
    "foldable)", ("mode",))
_unified_fallbacks = monitor.counter(
    "engine_unified_fallbacks_total", "unified steps that failed and "
    "went down the isolation ladder: rolled back, retried whole, then "
    "by halves of their rows")
# one step in flight (ISSUE 38): how often the loop dispatches a step
# over an uncommitted one, and why it does not
_steps_overlapped = monitor.counter(
    "serve_steps_overlapped_total", "unified steps dispatched while the "
    "step before them was still uncommitted (its decode rows' tokens fed "
    "on the device)")
_overlap_drains = monitor.counter(
    "serve_overlap_drains_total", "unified steps committed BEFORE the "
    "next one was dispatched, by what the engine saw: spec, "
    "host_sampling, unchunked, fault_plan, replaced, snapshot, "
    "drain, cancel, deadline, preempt, idle (nothing to dispatch), "
    "launch_failed", ("reason",))
_overlap_dropped_rows = monitor.counter(
    "serve_overlap_dropped_rows_total", "decode rows that rode in a step "
    "whose request had met its EOS in the step before: computed, dropped "
    "at the commit, their pages and slot returned there")
_steps_overlapped.inc(0)
_overlap_dropped_rows.inc(0)
# the host's step, timed where the work happens (ISSUE 39): the loop's
# phases are ``monitor.span``s that add their seconds to one dict of the
# scheduler thread's (``into=``), and the counters move ONCE an iteration
# (:meth:`ContinuousBatchingEngine._flush_host_seconds`), with the
# profiler off as well as on.  Work and waiting have a counter each, so
# a sum over a counter's series is one or the other
_host_work_s = monitor.counter(
    "engine_host_work_seconds_total", "seconds the scheduler thread "
    "WORKED on the unified step's iterations, by phase: schedule (reap, "
    "brownout, preempt, admit, plan), build (row lists, page reservation, "
    "packing), dispatch (uploads and the program call), commit (what "
    "depends on a token's value, and the journal flush); over "
    "serve_steps_launched_total it is what the host costs a step",
    ("phase",))
_host_wait_s = monitor.counter(
    "engine_host_wait_seconds_total", "seconds the scheduler thread "
    "WAITED, by phase: fetch (for a step's outputs: the device is the "
    "slower side while this grows) and wait (idle, nothing to run)",
    ("phase",))
_host_part_s = monitor.counter(
    "engine_host_part_seconds_total", "seconds inside named parts of the "
    "commit and build phases (parts OF engine_host_work_seconds_total, "
    "not further terms of it): finish_prefill, prefix_register, retire, "
    "journal; rows, reserve (page reservation, prefix evictions "
    "included), pack", ("part",))
_steps_launched = monitor.counter(
    "serve_steps_launched_total", "unified steps dispatched (a retried "
    "or probed dispatch that succeeds counts; one that fails does not)")
_steps_late = monitor.counter(
    "serve_steps_launched_late_total", "unified steps dispatched when "
    "the device had nothing left to run: no step was in flight, or the "
    "one in flight had already finished (its output asked, without "
    "waiting, just before the program call): the device's idle time as "
    "the program sees it with no profiler")
_steps_launched.inc(0)
_steps_late.inc(0)
#: span name -> (counter, its label): what an iteration's seconds move
_HOST_SECONDS = {
    **{f"engine/{ph}": (_host_work_s, {"phase": ph})
       for ph in ("schedule", "build", "dispatch", "commit")},
    **{f"engine/{ph}": (_host_wait_s, {"phase": ph})
       for ph in ("fetch", "wait")},
    **{f"engine/{part}": (_host_part_s, {"part": part.split("/")[1]})
       for part in ("commit/finish_prefill", "commit/prefix_register",
                    "commit/retire", "commit/journal", "build/rows",
                    "build/reserve", "build/pack")},
}
for _counter, _label in _HOST_SECONDS.values():
    _counter.inc(0, **_label)

# closed-loop overload protection (ISSUE 19): the controller's own
# series — materialized at import so existence gates (chaos_smoke) see
# them before the first overload
_decode_preempt_total = monitor.counter(
    "decode_preemptions_total", "decoding rows paused mid-decode "
    "(pages kept, next token still pending host-side) so an urgent "
    "waiter could take the slot or an interactive row could get back "
    "inside its TPOT budget; the row resumes bit-exactly through the "
    "preempt/resume path")
_brownout_level_g = monitor.gauge(
    "engine_brownout_level", "degradation ladder rung: 0 normal, "
    "1 shed least-urgent class, 2 shed two least-urgent classes, "
    "3 interactive-only (tightened deadline checks), 4 journal "
    "fsync flipped to 'os'")
_brownout_transitions = monitor.counter(
    "engine_brownout_transitions_total", "brownout ladder rung "
    "changes (escalations are immediate, de-escalations are damped "
    "by the hysteresis patience)")
_decode_preempt_total.inc(0)
_brownout_level_g.set(0)
_brownout_transitions.inc(0)

# request-level tracing (ISSUE 10): the process-wide trace buffer —
# OFF outside a monitor.start_capture() window, when every probe below
# is a single attribute read (the decode hot path must not notice it)
_tracer = _get_tracer()


def _note_quarantine(req) -> None:
    """Count a quarantine AND stamp it on the request's trace timeline
    (the chaos gate asserts a quarantined request's timeline carries
    the event) — one helper so the counter and the trace can't drift
    across the many ejection sites."""
    _quarantined.inc()
    _tracer.request_event(
        getattr(req, "request_id", None), "quarantine",
        error=(type(req.error).__name__ if req.error is not None
               else None))

#: one request's share of a speculative verify step: the bonus token
#: (ids or the logits-row escape hatch), the device-computed accept
#: length, and the draft tokens the host already knows (so accepted
#: token VALUES never cross the host boundary a second time)
_SpecRow = namedtuple("_SpecRow", ("out", "accept", "drafts"))


def _decode_p50_seconds() -> Optional[float]:
    """p50 of the process-wide ``decode_step_seconds`` histogram
    (prometheus-style upper bucket bound), or None before the engine
    has decoded anything."""
    counts = _decode_step_s.cumulative_counts()
    total = counts[-1]
    if total <= 0:
        return None
    rank = 0.5 * total
    for bound, cum in zip(_decode_step_s.buckets, counts):
        if cum >= rank:
            return bound
    return _decode_step_s.buckets[-1]


def retry_after_seconds(queue_depth: int,
                        decode_p50_s: Optional[float]) -> int:
    """Retry-After for a saturated engine: the backlog's estimated
    service time — queue depth x measured decode-step p50 — clamped to
    [1, 30] seconds (ROADMAP PR 4 follow-up c: replaces the constant
    1s).  Falls back to 1s before any step has been measured."""
    if not queue_depth or not decode_p50_s or decode_p50_s <= 0:
        return 1
    return int(min(30.0, max(1.0, math.ceil(queue_depth * decode_p50_s))))


class _LandFirst(Exception):
    """Internal (ISSUE 38): the schedule pass is about to move a request
    that has a row in the step in flight.  Raised BEFORE anything is
    changed; the loop commits that step (counted by ``reason``) and
    makes the pass again."""

    def __init__(self, reason: str):
        super().__init__(reason)
        self.reason = reason


class _Step:
    """One unified step between its dispatch and its commit
    (``_launch_step`` / ``_commit_step``): the composition a failure
    unwinds and the ladder re-runs, the decoder's flight, and what the
    launch already told the scheduler."""

    __slots__ = ("chunks", "active", "retried", "spec",
                 "k_spec", "drafts", "lens_before", "sampled", "flight",
                 "result", "record", "t_ns", "traced", "t0", "index",
                 "overlapped", "launched", "moved", "chunk_no", "riders",
                 "deferred", "leaving", "out_index", "dropped")

    def __init__(self, chunks, active, retried, spec, k_spec):
        self.chunks, self.active = chunks, active
        self.retried = retried
        self.spec, self.k_spec, self.drafts = spec, k_spec, None
        self.lens_before, self.sampled = {}, False
        self.flight = self.result = self.record = None
        self.t_ns, self.traced, self.t0, self.index = 0, False, 0.0, 0
        self.overlapped = self.launched = False
        self.moved = []         # prefills its launch moved to _active
        self.chunk_no = []      # each chunk row's ordinal in its request
        self.riders = {}        # id -> request with a row in it
        self.deferred = set()   # rows whose token was fed on the device
        self.leaving = set()    # rows fed their last token by count/EOS
        self.out_index = {}     # id -> the row of ``out`` it continues from
        self.dropped = {}       # id -> request retired (EOS) a step ago


class _Request:
    """One sequence's life in the engine."""

    def __init__(self, prompt, max_new_tokens, eos_token_id, do_sample,
                 temperature, seed, ttl_s=None, queue_timeout_s=None,
                 priority=None, tenant="default", request_id=None):
        # request-id continuity (ISSUE 10 satellite + ROADMAP crash
        # follow-up (a)): a stable, client-visible id — caller-supplied
        # or server-assigned — that survives snapshot/restore, keys the
        # bounded result cache (GET /result/<id> re-attach after a
        # restart) and names this request's trace timeline
        self.request_id = (str(request_id) if request_id
                           else f"req-{uuid.uuid4().hex[:16]}")
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.do_sample = bool(do_sample)
        self.temperature = float(temperature)
        self.seed = int(seed) & 0xFFFFFFFF   # on-device threefry seed
        self.rng = np.random.default_rng(seed)
        self.prefix_tokens = 0               # prompt tokens shared at admit
        # heterogeneous-workload scheduling (ISSUE 7): the class/tenant
        # the scheduler queues this request under, and the chunked
        # prefill cursor (prompt tokens already resident in the cache —
        # a preempted request resumes from here, never re-prefills)
        self.priority = priority             # normalized by the scheduler
        self.tenant = str(tenant)
        self.prefill_pos = 0
        self.chunks_done = 0
        self.admitted_at: Optional[float] = None
        self._admit_plan = None          # (need, shared_tok) fit-check stash
        # crash consistency (ISSUE 8): a restored request carries the
        # full prompt + generated token sequence its prefill must make
        # KV-resident (the replay primitive's admission-path mode);
        # preempted_at/paused_total bound a paused prefill's page
        # reservation (paused_total accumulates across preempt/resume
        # cycles so re-preemption cannot reset the aging clock)
        self.replay_tokens: Optional[np.ndarray] = None
        self.preempted_at: Optional[float] = None
        self.paused_total = 0.0
        # speculative decoding (ISSUE 6): set by the engine at submit;
        # _draft_reserved tracks whether draft-pool reservation is held
        self.use_draft = False
        self._draft_reserved = False
        self.generated: List[int] = []
        self.next_token: Optional[int] = None   # sampled, not yet decoded
        self.seq_id: Optional[int] = None
        self.done = threading.Event()
        self.error: Optional[BaseException] = None
        self.submitted_at = time.perf_counter()
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # lifecycle (ISSUE 4): deadlines are absolute perf_counter
        # instants; the scheduler reaps at admission and between steps
        self.ttl_s = ttl_s
        self.queue_timeout_s = queue_timeout_s
        self.deadline = (None if ttl_s is None
                         else self.submitted_at + float(ttl_s))
        self.queue_deadline = (
            None if queue_timeout_s is None
            else self.submitted_at + float(queue_timeout_s))
        self._cancel = threading.Event()

    @property
    def output_ids(self) -> np.ndarray:
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int32)])

    @property
    def prefill_target(self) -> np.ndarray:
        """The tokens that must be KV-resident before this request can
        decode: the prompt — or, for a restored request, prompt +
        generated-so-far (the replay primitive's admission-path mode:
        the SAME chunked context-prefill program ingests the longer
        sequence, ISSUE 8)."""
        return (self.prompt if self.replay_tokens is None
                else self.replay_tokens)

    def cancel(self) -> bool:
        """Cooperative cancel: honored before admission and between
        decode steps (an in-flight compiled step finishes first).  The
        request's pages and reservation are reclaimed when the
        scheduler reaps it; waiters get :class:`RequestCancelled`.
        Returns False if the request had already finished."""
        already_done = self.done.is_set()
        self._cancel.set()
        return not already_done

    @property
    def cancelled(self) -> bool:
        return self._cancel.is_set()

    def _lifecycle_error(self, now: float,
                         queued: bool) -> Optional[BaseException]:
        """The error this request should retire with right now, or
        None while it is still live."""
        if self._cancel.is_set():
            return RequestCancelled("request cancelled")
        if self.deadline is not None and now > self.deadline:
            return DeadlineExceeded(
                f"request exceeded its {float(self.ttl_s):.3f}s TTL")
        if queued and self.queue_deadline is not None \
                and now > self.queue_deadline:
            return DeadlineExceeded(
                f"request waited past its {float(self.queue_timeout_s):.3f}s "
                "queue-wait deadline without being admitted")
        return None

    def result(self, timeout=None, cancel_on_timeout: bool = True
               ) -> np.ndarray:
        """Wait for the generation.  On timeout the request is
        CANCELLED by default (``cancel_on_timeout=False`` keeps it
        running) so an abandoned wait does not leave the sequence
        decoding — and holding pool pages — forever."""
        if not self.done.wait(timeout):
            if cancel_on_timeout:
                self.cancel()
                raise TimeoutError(
                    "generation still running; request cancelled "
                    "(pass cancel_on_timeout=False to keep it)")
            raise TimeoutError("generation still running")
        if self.error is not None:
            raise self.error
        return self.output_ids


class ContinuousBatchingEngine:
    """Scheduler + decode loop over one shared PagedKVCache.

    ``submit`` is thread-safe and non-blocking; ``generate`` is the
    blocking batch facade with PagedGenerator's signature.

    Hot-path defaults (ISSUE 2): ``sample_on_device`` fuses greedy
    argmax + temperature sampling into the compiled step, so each
    decode step transfers (batch,) int32 ids instead of the full
    (batch, vocab) logits; ``prefix_cache`` keeps retired prompts'
    page-aligned prefix KV resident (refcounted, LRU-evicted under
    pool pressure) so a request sharing a cached prefix maps those
    pages read-only and prefills only its suffix.

    Resilience knobs (ISSUE 4): ``max_queue`` bounds EACH scheduling
    class's admission queue (overflow raises :class:`EngineSaturated`
    naming the class; per-class overrides via
    ``PriorityClass.max_queue``);
    ``default_ttl_s`` / ``default_queue_timeout_s`` set engine-wide
    deadlines each ``submit`` may override; ``step_timeout_s``
    registers a heartbeat with the comm watchdog so a wedged device
    step fires ``comm_timeouts_total`` like a hung collective.

    Speculative decoding (ISSUE 6): ``draft_model`` enables it —
    ``spec_tokens`` draft proposals per sequence per step are verified
    by ONE compiled multi-token target dispatch (exact for greedy).
    Requests opt out per-call (``submit(draft=False)``); the draft
    holds its own page pool (``draft_total_pages``, default the
    target's size) whose pages move in lockstep with the target's.

    Workload scheduling (ISSUE 7): ``prefill_chunk_tokens`` caps
    per-iteration prefill so long prompts interleave with decode;
    ``scheduler_classes`` / ``default_class`` configure the priority
    taxonomy (``submit(priority=..., tenant=...)``);
    ``min_table_pages`` pins compiled page-table widths so
    mixed-length serving stays recompile-free.

    Crash consistency (ISSUE 8): a REAL donated-buffer loss or a
    watchdog-flagged wedged step triggers a pool rebuild + bit-exact
    survivor KV replay (see the module docstring);
    :meth:`snapshot` / :meth:`restore` journal and resume in-flight
    requests across a process restart; ``preempt_resume_ttl_s`` bounds
    how long a preempted prefill may hold its page reservation (aging
    boost at half the TTL, reaped with pages reclaimed past it).

    Durability (ISSUE 13): pass ``journal`` (a
    :class:`~paddle_tpu.inference.journal.RequestJournal`) and every
    request state transition — admission, one coalesced token-emission
    record per engine step, retirement — is appended to the
    write-ahead journal by its dedicated writer thread, so a restarted
    process reconstructs the live set after a SIGKILL/OOM-kill and
    resumes every admitted request bit-exactly through the replay
    admission path (the journal generalizes :meth:`snapshot` from a
    cooperative cut to an always-current one).

    Observability (ISSUE 10): every request carries a stable
    ``request_id`` (``submit(request_id=...)`` or server-assigned,
    preserved across snapshot/restore) keying a bounded result cache
    (:meth:`result_for` — the ``GET /result/<id>`` re-attach surface)
    and, inside a ``monitor.start_capture()`` window, a per-request
    event timeline + per-engine-step records exported as chrome-trace
    JSON by ``monitor.export_chrome_trace()``.  Outside a window every
    trace probe is one attribute read — the decode hot path does not
    notice it.
    """

    def __init__(self, model, total_pages: int = 512, page_size: int = 16,
                 max_batch: int = 8, sample_on_device: bool = True,
                 prefix_cache: bool = True, max_queue: int = 256,
                 default_ttl_s: Optional[float] = None,
                 default_queue_timeout_s: Optional[float] = None,
                 step_timeout_s: Optional[float] = None,
                 draft_model=None, spec_tokens: int = 4,
                 draft_total_pages: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 scheduler_classes=None,
                 default_class: str = DEFAULT_CLASS,
                 min_table_pages: int = 1,
                 preempt_resume_ttl_s: Optional[float] = None,
                 quantize: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 replay_batch: Optional[bool] = None,
                 result_cache_size: int = 256,
                 journal=None,
                 brownout_thresholds=None,
                 brownout_patience: int = 3,
                 decode_preempt: bool = True,
                 tpot_preempt_cooldown_s: float = 0.25,
                 tp: int = 1,
                 tp_quant_collectives: bool = False):
        self.model = model
        self.max_batch = int(max_batch)
        self.max_position = int(model.config.max_position_embeddings)
        self.sample_on_device = bool(sample_on_device)
        self.prefix_cache = bool(prefix_cache)
        # what the model is, read from the model: layers that carry a
        # recurrent state a sequence (``recurrent_state``) are served by
        # the ragged unified step alone, a slot a sequence beside the
        # pages; what cannot hold for such a state refuses here
        self.cache_layout = paged_layout(model)
        self._recurrent = self.cache_layout["state"] is not None
        if self._recurrent:
            self._refuse_for_recurrent(
                draft_model=draft_model, kv_quant=kv_quant, tp=tp,
                prefill_chunk_tokens=prefill_chunk_tokens)
            # a page-aligned prefix has no state to share (a state is of
            # the whole sequence up to a token, not of a page): the
            # default is turned off rather than refused, and says so
            self.prefix_cache = False
            replay_batch = False    # replay is chunk rows of the ragged step
        if self.cache_layout["kv_heads"] is None:
            # pools of unequal shape (KV heads a pool, K wider than V): the
            # ragged unified step's kernels alone take them; the prefix
            # cache shares PAGES, which are pages in every pool alike
            self._refuse_for_unlike_pools(
                draft_model=draft_model, kv_quant=kv_quant, tp=tp,
                prefill_chunk_tokens=prefill_chunk_tokens)
            replay_batch = False    # as above: chunk rows of the ragged step
        # survivors of a pool loss are replayed through the ragged program
        # where no other program can write the model's state or pages
        self._replay_ragged = (self._recurrent
                               or self.cache_layout["kv_heads"] is None)
        self.max_queue = int(max_queue)
        self.default_ttl_s = default_ttl_s
        self.default_queue_timeout_s = default_queue_timeout_s
        self.step_timeout_s = step_timeout_s
        # heterogeneous-workload knobs (ISSUE 7): the per-step prefill
        # token budget (None = monolithic prefill, the historical
        # behavior) and the class taxonomy admission is scheduled under
        if prefill_chunk_tokens is not None \
                and int(prefill_chunk_tokens) < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1 or None")
        self.prefill_chunk_tokens = (None if prefill_chunk_tokens is None
                                     else int(prefill_chunk_tokens))
        # resume-TTL for preempted prefills (ISSUE 8 satellite): a
        # paused request holds its page reservation at most this long —
        # past HALF the TTL an aging boost forces its resume ahead of
        # any queued class; past the full TTL it is reaped with pages
        # reclaimed (None keeps the historical unbounded behavior)
        self.preempt_resume_ttl_s = (
            None if preempt_resume_ttl_s is None
            else float(preempt_resume_ttl_s))
        _sampling_on_device_g.set(int(self.sample_on_device))
        # runtime mirror of the analysis auditor's recompile rules:
        # every XLA compile the decode loop triggers shows up in
        # jit_recompile_count (steady-state serving should sit at zero)
        monitor.install_compile_hooks()
        # and of what the collector costs: a full collection stops the
        # loop for as long as it takes (``host_gc_*``, span ``host/gc``)
        monitor.install_gc_hooks()
        # quantized serving (ISSUE 9): ``quantize`` runs the compiled
        # programs' Linears int8 (w8 weight-only / w8a8 dynamic);
        # ``kv_quant="int8"`` stores KV pages int8 with per-slot scale
        # pools — at equal pool bytes that roughly 4x's (f32) or 2x's
        # (bf16) the pages, i.e. the concurrent sequences one chip
        # admits.  Both knobs apply to the TARGET model; a draft model
        # stays full-precision (its pool is small and its accuracy
        # directly sets the acceptance rate).
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {kv_quant!r}")
        self.quantize = quantize
        self.kv_quant = kv_quant
        # batched survivor replay (ISSUE 9 satellite) is verified
        # bit-exact on CPU; on TPU its k == 0 round runs a different
        # attention kernel than the original prefill and the
        # accumulation order has NOT been verified there (PR 24 ran the
        # engine on the chip but not a recovery; still open), so the
        # unset default keeps the ISSUE 8 bit-exact recovery contract:
        # batched everywhere but TPU.  Explicit True/False overrides
        # either way.
        if replay_batch is None:
            replay_batch = jax.default_backend() != "tpu"
        self.replay_batch = bool(replay_batch)
        # tensor-parallel serving (ISSUE 20): one engine = one TP
        # replica.  ``tp > 1`` builds a 1-D ('tensor',) mesh, commits
        # the model weights to Megatron-style column/row shardings and
        # shards every KV pool on the kv-head axis, so per-chip HBM for
        # weights and pages drops by the TP degree while the engine's
        # batching/scheduling surface is unchanged — supervisors and
        # routers treat it exactly like a 1-chip replica.
        self.tp = int(tp)
        self.tp_quant_collectives = bool(tp_quant_collectives)
        if self.tp > 1:
            from ..framework.jax_compat import make_tp_mesh
            self.mesh = make_tp_mesh(self.tp)
        else:
            self.mesh = None
        self.cache = PagedKVCache.from_model(
            model, total_pages=total_pages, page_size=page_size,
            kv_dtype=kv_quant, mesh=self.mesh,
            # a slot a row of the widest step: admitted sequences are at
            # most ``max_batch``, a preempted one gives its slot back
            state_slots=self.max_batch)
        _slots_in_use_g.set(0)
        self.draft_model = draft_model
        self.spec_k = int(spec_tokens)
        from .paged import JittedPagedDecoder
        self._decoder = JittedPagedDecoder(
            model, min_table_pages=min_table_pages, quantize=quantize,
            mesh=self.mesh, tp_quant_collectives=self.tp_quant_collectives,
            step_tokens=self._step_token_bound())
        _quant_enabled_g.set(int(quantize is not None))
        _kv_quant_enabled_g.set(int(kv_quant is not None))
        _kv_quant_pool_bytes_g.set(self.cache.kv_pool_bytes)
        _kv_quant_scale_bytes_g.set(self.cache.kv_scale_bytes)
        _replay_dispatches.inc(0)       # materialize the series
        # speculative decoding (ISSUE 6): the draft gets its own
        # decoder + page pool; proposals/verification share the target's
        # bucketing so steady-state serving stays compile-free
        if draft_model is not None:
            if self.spec_k < 1:
                raise ValueError("spec_tokens must be >= 1")
            if (int(draft_model.config.vocab_size)
                    != int(model.config.vocab_size)):
                raise ValueError(
                    "draft and target models must share a vocabulary "
                    f"({draft_model.config.vocab_size} vs "
                    f"{model.config.vocab_size})")
            self._draft_decoder = JittedPagedDecoder(
                draft_model, min_table_pages=min_table_pages)
            self.draft_cache = PagedKVCache.from_model(
                draft_model,
                total_pages=(total_pages if draft_total_pages is None
                             else draft_total_pages),
                page_size=page_size)
            self._draft_max_position = int(
                draft_model.config.max_position_embeddings)
        else:
            self._draft_decoder = None
            self.draft_cache = None
            self._draft_max_position = 0
        # one scratch sequence backs every padding row of the draft's
        # propose scan (the ragged step's pad rows write to a dropped
        # page and hold nothing); its page(s) stay allocated WHILE
        # sequences are active (an allocate/truncate/free per padded
        # step would churn the free list under the pool lock) and are
        # released whenever the engine goes idle, so an idle engine
        # still reports a fully reclaimed pool; admission arithmetic
        # always reserves the pad headroom, on both pools.  A
        # speculative pad row rewrites spec_tokens + 1 slots per step,
        # so its headroom grows with k.
        pad_tokens = (self.spec_k + 1) if draft_model is not None else 1
        self._pad_pages = max(1, -(-pad_tokens // int(page_size)))
        self._reserved_pages = self._pad_pages
        self._reserved_draft_pages = self._pad_pages
        # admission queues live in the workload scheduler (per-class,
        # per-tenant DRR); the engine owns two mid-prefill lists the
        # drain/reap/fail paths must see: _prefilling (admitted, chunk
        # cursor advancing) and _preempted (paused mid-prefill, pages
        # kept, waiting for a slot to resume)
        self._sched = WorkloadScheduler(
            classes=scheduler_classes, max_queue=self.max_queue,
            default_class=default_class)
        self._active: List[_Request] = []
        self._prefilling: List[_Request] = []
        self._preempted: List[_Request] = []
        # request-id continuity (ISSUE 10 satellite): finished requests'
        # outputs/errors, keyed by request_id, bounded FIFO — a client
        # that lost its HTTP stream (timeout, server restart) re-attaches
        # via result_for() / GET /result/<id>
        self.result_cache_size = max(0, int(result_cache_size))
        self._results: "OrderedDict[str, dict]" = OrderedDict()
        # write-ahead request journal (ISSUE 13): every probe below is
        # one None check when no journal is attached.  Producers only
        # ENQUEUE (the journal's writer thread owns all I/O), so the
        # _cond hot path never waits on a disk.  _jadm/_jrows
        # accumulate the scheduler thread's per-iteration coalesced
        # step record (admitted ids + per-row token emissions); admit
        # and retire records are appended at their own sites.  The
        # engine's hard stop() path deliberately journals NOTHING —
        # "engine stopped" is process-death-adjacent, and the journal's
        # whole point is that a relaunch resumes exactly that state.
        self.journal = journal
        self._jadm: List[str] = []
        self._jrows: List[tuple] = []
        # ragged unified step (ISSUE 17): each iteration's chunk, decode
        # and verify rows are ONE compiled dispatch.  `_disp_n` /
        # `_disp_ragged` (this iteration's dispatch count and mode for
        # the journal's step record) are scheduler-thread only, like
        # _jadm/_jrows.
        self._disp_n = 0
        self._disp_ragged = False
        # closed-loop overload protection (ISSUE 19).  The brownout
        # ladder is OFF by default (None): rung thresholds are
        # queue-pressure ratios (depth / max_queue) for rungs 1..4,
        # ascending.  Escalation is immediate (overload is now);
        # de-escalation needs `brownout_patience` consecutive calm
        # iterations below the hysteresis band, and an engine going
        # idle drops straight to rung 0 (brownout is a property of
        # load, not a latch).  `decode_preempt` lets the admission loop
        # pause preemptible DECODING rows when no mid-prefill victim
        # exists; the TPOT trigger additionally preempts at full
        # occupancy when the measured step time breaches a running
        # row's `tpot_budget_s`, rate-limited by the cooldown so a
        # marginal budget cannot thrash pause/resume every iteration.
        if brownout_thresholds is not None:
            brownout_thresholds = tuple(
                float(t) for t in brownout_thresholds)
            if len(brownout_thresholds) != 4 \
                    or list(brownout_thresholds) \
                    != sorted(brownout_thresholds):
                raise ValueError(
                    "brownout_thresholds must be 4 ascending "
                    f"queue-pressure ratios, got {brownout_thresholds!r}")
        self.brownout_thresholds = brownout_thresholds
        self.brownout_patience = max(1, int(brownout_patience))
        self.decode_preempt = bool(decode_preempt)
        self.tpot_preempt_cooldown_s = float(tpot_preempt_cooldown_s)
        self._brownout = 0
        self._brownout_calm = 0         # scheduler-thread only
        self._step_ewma: Optional[float] = None   # scheduler-thread only
        self._tpot_last_preempt = 0.0   # scheduler-thread only
        self._cond = threading.Condition()
        self._stop = False
        self._draining = False
        self._next_seq = 0
        self.steps = 0                          # decode steps executed
        # crash consistency (ISSUE 8): the summed pool generation the
        # engine last reconciled (a mismatch after a failed step means
        # a donated-buffer loss zeroed survivor KV — replay required);
        # _wedged is set from the WATCHDOG thread when the heartbeat
        # fires, consumed at the next step boundary; _stepping/_
        # snap_waiters implement the snapshot() quiesce barrier
        self._pool_gen = self.cache.generation + (
            self.draft_cache.generation if self._spec else 0)
        # trace support (ISSUE 10): the last executed step's speculative
        # economics, read by the step-ring record (scheduler-thread only)
        self._last_spec = (0, 0)
        self._wedged = threading.Event()
        # the ONE dispatched and uncommitted unified step, if any, and
        # the moment the last step's results reached the host (the ring
        # records' intervals start no earlier): scheduler-thread only
        self._flight: Optional[_Step] = None
        self._fetched_ns = 0
        # this iteration's seconds by span name (the phases' and their
        # parts' ``into=``; the decoder's spans add to the same dict) and
        # the ``dispatch`` records of the steps it committed, which are
        # written when it ends (:meth:`_flush_host_seconds`)
        self._host_s = self._decoder.host_seconds
        self._ring_pending: List[tuple] = []
        self._stepping = False
        self._snap_waiters = 0
        # stall detection (ISSUE 4): while a compiled step is in flight
        # this holds its start instant; the watchdog heartbeat reports
        # its age so a wedged step trips the comm timeout machinery
        self._step_started_at: Optional[float] = None
        self._hb_id: Optional[int] = None
        if step_timeout_s is not None:
            from ..distributed.watchdog import CommTaskManager
            mgr = CommTaskManager.instance()
            self._hb_id = mgr.register_heartbeat(
                "engine/decode_step", self._step_age,
                float(step_timeout_s), on_timeout=self._wedged.set)
            mgr.start()
        # journal co-location (ISSUE 19 satellite): every live engine
        # registers with the journal module so each journal's writer
        # scales its flush cadence by the number of engines sharing the
        # GIL on this host — N colocated writers each waking at the
        # configured interval steal N x the GIL share one does
        from . import journal as _journal_mod
        _journal_mod.engine_started()
        self._coloc_registered = True
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _refuse_for_recurrent(self, draft_model, kv_quant, tp,
                              prefill_chunk_tokens) -> None:
        """What cannot hold for a model whose layers carry a recurrent
        state, each with its reason."""
        name = type(self.model).__name__
        if draft_model is not None:
            raise ValueError(
                f"draft_model: {name} carries a recurrent state, and a "
                "rejected draft block cannot be rolled out of it (a KV "
                "page is truncated; S has summed the rejected tokens in)")
        if kv_quant is not None:
            raise ValueError(
                f"kv_quant={kv_quant!r}: {name} carries a recurrent state "
                "in float32 slots, which no mode quantises, "
                + ("and has no K/V page to quantise"
                   if not self.cache_layout["pools"] else
                   "and its K/V pages have not been held to a reference "
                   "in int8 beside them"))
        if int(tp) > 1:
            from ..framework.jax_compat import make_tp_mesh
            from .paged import _tp_plan
            _tp_plan(self.model, make_tp_mesh(int(tp)))  # names what it lacks
            raise ValueError(
                f"tp={tp}: the slot pools of {name} have no placement over "
                "a tensor mesh")
        if prefill_chunk_tokens is None:
            raise ValueError(
                f"prefill_chunk_tokens=None: {name} carries a recurrent "
                "state, which only the ragged unified step updates (a slot "
                "a row); the whole-prompt prefill programs carry no slot "
                "pools.  Pass prefill_chunk_tokens")

    def _refuse_for_unlike_pools(self, draft_model, kv_quant, tp,
                                 prefill_chunk_tokens) -> None:
        """What cannot hold for a model whose page pools differ in shape
        (KV heads a pool, K heads wider than V's), each with its reason."""
        name = type(self.model).__name__
        shapes = sorted(set(self.cache_layout["pool_shapes"]))
        what = (f"{name}'s page pools differ in shape ({shapes} as (kv "
                "heads, K width, V width))")
        if draft_model is not None:
            raise ValueError(
                f"draft_model: {what}, and the draft-and-verify path has "
                "not been held to a reference over such pools (a draft "
                "would need them too)")
        if kv_quant is not None:
            raise ValueError(
                f"kv_quant={kv_quant!r}: {what}, and the int8 scale pools "
                "have not been held to a reference beside pools of unequal "
                "heads or a K wider than its V")
        if int(tp) > 1:
            from ..framework.jax_compat import make_tp_mesh
            from .paged import _tp_plan
            _tp_plan(self.model, make_tp_mesh(int(tp)))  # names what it lacks
            raise ValueError(
                f"tp={tp}: {what}, and the head-axis sharding of the pools "
                "has no plan for pools of unequal heads")
        if prefill_chunk_tokens is None:
            raise ValueError(
                f"prefill_chunk_tokens=None: {what}; only the ragged "
                "unified step's paged kernels take K and V of unequal "
                "width (the whole-prompt prefill programs' dense attention "
                "takes one width and no sink).  Pass prefill_chunk_tokens")

    # ------------------------------------------------------------- public
    @property
    def _spec(self) -> bool:
        return self.draft_model is not None

    def submit(self, prompt, max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None, do_sample: bool = False,
               temperature: float = 1.0, seed: int = 0,
               ttl_s: Optional[float] = None,
               queue_timeout_s: Optional[float] = None,
               draft: Optional[bool] = None,
               priority: Optional[str] = None,
               tenant: str = "default",
               request_id: Optional[str] = None,
               _restore: Optional[dict] = None) -> _Request:
        """``draft``: speculative-decoding opt-in for this request.
        ``None`` (default) speculates whenever the engine has a draft
        model and the request is greedy; ``False`` opts out; ``True``
        demands it (ValueError if the engine has no draft model or the
        request cannot speculate).

        ``priority`` names a scheduling class (``None`` -> the engine's
        default class; unknown names raise ValueError — a client
        mistake, not a capacity problem); ``tenant`` is a free-form
        tenant id fair-queued within the class.

        ``request_id`` (ISSUE 10): a stable client-visible id — the
        handle for ``result_for()`` re-attach and the request's trace
        timeline; auto-assigned (``req-<hex>``) when omitted, carried
        verbatim across snapshot/restore."""
        # validate the class BEFORE any capacity checks: an unknown
        # class must 400, never 429/503
        pclass = self._sched.resolve(priority)
        req = _Request(prompt, max_new_tokens, eos_token_id, do_sample,
                       temperature, seed,
                       ttl_s=self.default_ttl_s if ttl_s is None else ttl_s,
                       queue_timeout_s=(self.default_queue_timeout_s
                                        if queue_timeout_s is None
                                        else queue_timeout_s),
                       priority=pclass.name, tenant=tenant,
                       request_id=request_id)
        if _restore is not None:
            # snapshot restore (ISSUE 8): preload the journaled
            # generation state BEFORE the request becomes visible to
            # the scheduler thread — admission then prefills
            # prompt + generated through the replay primitive and the
            # journaled next token continues the stream exactly
            gen = [int(t) for t in _restore.get("generated", ())]
            if gen:
                req.generated = gen
                req.replay_tokens = np.concatenate(
                    [req.prompt, np.asarray(gen, np.int32)])
            # the journaled pending token is kept even with NO
            # generated tokens yet (snapshot cut between prefill
            # completion and the first decode step) — on the
            # host-logits path re-deriving it would draw from a fresh
            # RNG and break the journaled-next-token exactness
            nt = _restore.get("next_token")
            req.next_token = None if nt is None else int(nt)
            # deadlines come from the JOURNAL verbatim: a journaled
            # None means the original request had no (remaining)
            # deadline — it must NOT pick up this engine's defaults,
            # or a restore storm would reap the very streams the
            # journal exists to save
            ttl = _restore.get("ttl_remaining_s")
            req.ttl_s = ttl
            req.deadline = (None if ttl is None
                            else req.submitted_at + float(ttl))
            qt = _restore.get("queue_timeout_remaining_s")
            req.queue_timeout_s = qt
            req.queue_deadline = (None if qt is None
                                  else req.submitted_at + float(qt))
        total = len(req.prompt) + req.max_new_tokens
        # a verify step writes spec_k + 1 positions before rolling back,
        # so the rope table must cover the overhang for EVERY request a
        # speculative engine serves (opt-out rows ride in the same block)
        overhang = self.spec_k if self._spec else 0
        if total + overhang > self.max_position:
            # past the rope table the gather would silently clamp and
            # reuse the last angles (the scalar path raises; so do we)
            raise ValueError(
                f"prompt + max_new_tokens = {total} "
                + (f"+ speculative overhang {overhang} " if overhang
                   else "")
                + f"exceeds the model's max_position_embeddings "
                f"({self.max_position})")
        if draft and not self._spec:
            raise ValueError(
                "draft=True but the engine was built without a "
                "draft_model")
        use = self._spec and (draft is None or bool(draft))
        if use and req.do_sample:
            # acceptance-by-argmax is only exact for greedy rows;
            # sampled rows ride along unaccelerated instead of drawing
            # from the wrong distribution
            if draft:
                raise ValueError(
                    "speculative decoding is greedy-exact only; "
                    "draft=True cannot be combined with do_sample")
            use = False
        if use and total + self.spec_k > self._draft_max_position:
            if draft:
                raise ValueError(
                    f"prompt + max_new_tokens + speculative overhang = "
                    f"{total + self.spec_k} exceeds the DRAFT model's "
                    f"max_position_embeddings "
                    f"({self._draft_max_position})")
            use = False
        req.use_draft = use
        need = self._pages_for(req)
        if need > self.cache.total_pages - self._pad_pages:
            raise RuntimeError(
                f"request needs {need} pages but the pool holds "
                f"{self.cache.total_pages} total; grow total_pages")
        if req.use_draft and need > self.draft_cache.total_pages \
                - self._pad_pages:
            raise RuntimeError(
                f"request needs {need} draft-cache pages but the draft "
                f"pool holds {self.draft_cache.total_pages} total; grow "
                "draft_total_pages")
        with self._cond:
            if self._draining:
                raise EngineDraining(
                    "engine is draining or drained; not accepting new "
                    "requests")
            if self._stop:
                raise RuntimeError("engine stopped")
            if request_id is not None:
                # a pinned id may be REUSED after the original request
                # finished (deliberate resubmit overwrites the result
                # cache) but never while it is live: admitting a second
                # stream under the same id would interleave two
                # lifecycles in one trace timeline and make
                # /result/<id> race whichever finished last
                live = (self._active + self._prefilling
                        + self._preempted + self._sched.pending())
                if any(r.request_id == req.request_id for r in live):
                    raise ValueError(
                        f"request_id {req.request_id!r} is already "
                        "live; poll GET /result/<id> or pick a new id")
            # SLO-aware admission (ISSUE 19): shed a doomed arrival in
            # microseconds — BEFORE it enters the queue, holds a trace
            # timeline slot, or journals an admit record — when its
            # class's deadline budget is already blown by the projected
            # queue wait, or the brownout ladder sheds the class
            shed_after = self._shed_decision_locked(pclass)
            if shed_after is not None:
                self._sched.note_shed(pclass.name)
                _saturated_total.inc()
                _tracer.request_event(
                    req.request_id, "shed", cls=pclass.name,
                    retry_after_s=shed_after, brownout=self._brownout)
                err = EngineSaturated(
                    f"admission shed for class {pclass.name!r}: "
                    "projected queue wait exceeds its SLO budget "
                    f"(brownout level {self._brownout}); retry in "
                    f"~{shed_after}s")
                err.priority_class = pclass.name
                err.retry_after_s = shed_after
                raise err
            try:
                self._sched.push(req)
            except QueueFull as e:
                _saturated_total.inc()
                err = EngineSaturated(str(e))
                err.priority_class = e.priority_class
                raise err from None
            if self.journal is not None:
                # journal the admission BEFORE the request is visible
                # to the scheduler thread, so its step/retire records
                # can never precede the admit record in the log
                self.journal.append_admit(self._journal_entry(req))
            _queue_depth.set(len(self._sched))
            _tracer.request_event(
                req.request_id, "enqueue", cls=req.priority,
                tenant=req.tenant, prompt_tokens=len(req.prompt),
                restored=bool(_restore is not None))
            self._cond.notify_all()
        return req

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 seed: int = 0, ttl_s: Optional[float] = None,
                 draft: Optional[bool] = None,
                 priority: Optional[str] = None,
                 tenant: str = "default",
                 request_id: Optional[str] = None):
        """Blocking batch API (PagedGenerator-compatible): submits each
        row as its own sequence and eos-pads rows to a common length.
        If any row fails to submit or errors, the other rows are
        CANCELLED so a rejected batch never leaves orphan sequences
        decoding against the pool."""
        out, _reqs = self.generate_with_requests(
            input_ids, max_new_tokens=max_new_tokens,
            eos_token_id=eos_token_id, do_sample=do_sample,
            temperature=temperature, seed=seed, ttl_s=ttl_s, draft=draft,
            priority=priority, tenant=tenant, request_id=request_id)
        return out

    def generate_with_requests(self, input_ids, max_new_tokens: int = 32,
                               eos_token_id: Optional[int] = None,
                               do_sample: bool = False,
                               temperature: float = 1.0,
                               seed: int = 0,
                               ttl_s: Optional[float] = None,
                               draft: Optional[bool] = None,
                               priority: Optional[str] = None,
                               tenant: str = "default",
                               request_id: Optional[str] = None):
        """:meth:`generate` returning ``(output_ids, requests)`` so the
        HTTP server can hand the per-row ``request_id``s back to the
        client (ISSUE 10: a multi-row body's id seeds per-row ids as
        ``<id>/<row>``)."""
        ids = np.asarray(input_ids, np.int32)

        def rid(i: int) -> Optional[str]:
            if request_id is None:
                return None
            return request_id if len(ids) == 1 else f"{request_id}/{i}"

        reqs: List[_Request] = []
        try:
            for i, row in enumerate(ids):
                reqs.append(self.submit(row, max_new_tokens, eos_token_id,
                                        do_sample, temperature, seed + i,
                                        ttl_s=ttl_s, draft=draft,
                                        priority=priority, tenant=tenant,
                                        request_id=rid(i)))
            rows = [r.result() for r in reqs]
        except BaseException:
            for r in reqs:
                r.cancel()
            raise
        width = max(len(r) for r in rows)
        pad = 0 if eos_token_id is None else eos_token_id
        out = np.full((len(rows), width), pad, np.int32)
        for i, r in enumerate(rows):
            out[i, :len(r)] = r
        return out, reqs

    @property
    def draining(self) -> bool:
        return self._draining

    def retry_after_hint(self, priority: Optional[str] = None) -> int:
        """Seconds a 429'd client should wait before retrying: the
        backlog x the measured decode-step p50 from the monitor,
        clamped to [1, 30].  With ``priority`` the backlog is the
        REQUESTING CLASS's queue depth (an interactive client behind an
        empty interactive queue is told 1s even while the batch queue
        is deep), otherwise the global depth.

        ISSUE 19 satellite: when the class carries a deadline budget
        the hint folds in the admission controller's projected-wait
        estimate — the time for the backlog to drain back UNDER the
        budget, not the time to drain it entirely — so the fleet
        router's min-Retry-After aggregation propagates truthful
        backpressure instead of a depth-only guess."""
        with self._cond:
            cls = None
            if priority is not None \
                    and priority in {c.name for c in self._sched.classes}:
                cls = self._sched.resolve(priority)
                depth = self._sched.depth(priority)
            else:
                depth = len(self._sched)
            level = self._brownout
        p50 = _decode_p50_seconds()
        hint = retry_after_seconds(depth, p50)
        if cls is not None and cls.deadline_s is not None \
                and p50 and p50 > 0:
            budget = cls.deadline_s * (0.5 if level >= 3 else 1.0)
            projected = depth * p50
            if projected > budget:
                hint = int(min(30.0, max(1.0,
                                         math.ceil(projected - budget))))
        return hint

    # ----------------------- closed-loop overload protection (ISSUE 19)
    def _shed_decision_locked(self, pclass) -> Optional[int]:
        """Why this arrival must shed, as a truthful Retry-After in
        seconds — or None to admit.  Two independent controllers:

        * the brownout ladder sheds whole classes: rung L sheds the L
          least-urgent rank bands (rung >= 3 sheds every non-top rank
          and HALVES the surviving class's deadline budget, so the
          interactive-only mode also tightens its own admission);
        * the class's ``deadline_s`` budget sheds individually doomed
          requests: projected queue wait (class depth x measured
          decode-step p50) already past the budget means the request
          would time out after holding pages — reject it now instead.
        """
        level = self._brownout
        p50 = _decode_p50_seconds()
        if level >= 1:
            ranks = sorted({c.rank for c in self._sched.classes})
            if pclass.rank > ranks[0]:
                bands = ranks[1:]
                shed = bands[len(bands) - min(level, len(bands)):]
                if level >= 3 or pclass.rank in shed:
                    depth = self._sched.depth(pclass.name)
                    return retry_after_seconds(max(1, depth), p50)
        budget = pclass.deadline_s
        if budget is None or not p50 or p50 <= 0:
            return None
        if level >= 3:
            budget *= 0.5
        projected = self._sched.depth(pclass.name) * p50
        if projected <= budget:
            return None
        return int(min(30.0, max(1.0, math.ceil(projected - budget))))

    def _set_brownout_locked(self, level: int, pressure: float) -> None:
        if level == self._brownout:
            return
        prev, self._brownout = self._brownout, level
        _brownout_level_g.set(level)
        _brownout_transitions.inc()
        _tracer.request_event(None, "brownout", level=level, prev=prev,
                              pressure=round(pressure, 4))
        if self.journal is not None:
            # the last rung trades the journal's configured durability
            # for throughput: fsync policy flips to "os" (explicit,
            # reversible — unlike the watchdog's sticky degrade())
            if level >= 4:
                self.journal.set_policy("os")
            elif prev >= 4:
                self.journal.set_policy(self.journal.fsync_policy)

    def _update_brownout_locked(self) -> None:
        """One control-loop evaluation, each scheduler iteration.
        Pressure is the max of queue-depth ratio and the urgent class's
        SLO-attainment deficit; rungs escalate immediately and
        de-escalate only after `brownout_patience` calm iterations
        below HALF the rung's threshold (hysteresis, so a workload
        hovering at a threshold cannot thrash the ladder)."""
        th = self.brownout_thresholds
        if th is None:
            return
        ratio = len(self._sched) / float(max(1, self.max_queue))
        att = self._sched.urgent_attainment()
        pressure = ratio if att is None else max(ratio, 1.0 - att)
        level = self._brownout
        if level < 4 and pressure >= th[level]:
            self._brownout_calm = 0
            self._set_brownout_locked(level + 1, pressure)
            return
        if level > 0 and pressure < 0.5 * th[level - 1]:
            self._brownout_calm += 1
            if self._brownout_calm >= self.brownout_patience:
                self._brownout_calm = 0
                self._set_brownout_locked(level - 1, pressure)
        else:
            self._brownout_calm = 0

    # ------------------------------------- write-ahead journal (ISSUE 13)
    @staticmethod
    def _entry_fields(r) -> dict:
        """The request fields BOTH persistence formats — the
        cooperative snapshot entry and the write-ahead journal's admit
        record — serialize identically.  One builder, so a field added
        to the request can never restore on one recovery path and be
        silently dropped on the other (the formats differ only in how
        they carry generation state and deadlines)."""
        return {
            # the stable client-visible id survives the restart — a
            # client holding it re-attaches via GET /result/<id> on
            # the restored process (ISSUE 10)
            "request_id": r.request_id,
            "max_new_tokens": r.max_new_tokens,
            "eos_token_id": (None if r.eos_token_id is None
                             else int(r.eos_token_id)),
            "do_sample": r.do_sample,
            "temperature": r.temperature,
            "seed": r.seed,
            "priority": r.priority,
            "tenant": r.tenant,
            "draft": bool(r.use_draft),
        }

    def _journal_entry(self, req) -> dict:
        """The admit record's payload: the FULL request state in the
        snapshot-entry shape (a restored request carries its generated
        tokens + pending next token, making journal replay idempotent
        by request_id), with deadlines converted to absolute WALL-CLOCK
        instants — a perf_counter deadline is meaningless in the next
        process, and the recovery scan converts back to the
        remaining-seconds fields restore() takes verbatim."""
        now_p = time.perf_counter()
        now_w = time.time()

        def wall(d):
            return None if d is None else now_w + (d - now_p)

        return {
            **self._entry_fields(req),
            "prompt": req.prompt,            # np array; writer encodes
            "generated": list(req.generated),
            "next_token": (None if req.next_token is None
                           else int(req.next_token)),
            "deadline_unix": wall(req.deadline),
            "queue_deadline_unix": wall(req.queue_deadline),
        }

    def _journal_retire(self, req) -> None:
        if self.journal is None:
            return
        why = ("done" if req.error is None
               else type(req.error).__name__)
        self.journal.append_retire(req.request_id, why=why)

    def _journal_pages(self, req, event: str, n_tokens: int) -> None:
        """Page-provenance record (ISSUE 14 satellite): the page-
        aligned prefix ``req`` shares with the prefix cache — its
        replica-local page indices plus the stable content key.
        Failover groups the migrating live set by that key (sharers
        land together, the destination's prefix index warms once); a
        disaggregated decode tier re-attaches transported pages by it
        (the ROADMAP slice this record type exists for)."""
        if self.journal is None:
            return
        ps = self.cache.page_size
        n = (int(n_tokens) // ps) * ps
        if n <= 0:
            return
        pages = self.cache._seq_pages.get(req.seq_id, [])[:n // ps]
        self.journal.append_pages(
            req.request_id, event, n, pages,
            self.cache.prefix_key_hex(req.prompt, n))

    def _journal_flush_step(self) -> None:
        """Scheduler thread, end of one loop iteration: ONE coalesced
        step record — the ids admitted to a slot plus every surviving
        row's (tokens appended, new pending next_token) — written off
        the hot path by the journal's writer thread."""
        if self.journal is not None and (self._jadm or self._jrows):
            self.journal.append_step(
                self._jadm, self._jrows, dispatches=self._disp_n,
                mode=(("ragged" if self._disp_ragged else "prefill")
                      if self._disp_n else None))
        self._jadm = []
        self._jrows = []
        self._disp_n = 0
        self._disp_ragged = False

    def _count_dispatch(self, mode: str) -> None:
        """Scheduler thread: one compiled serving dispatch ATTEMPT —
        the per-mode fleet counter plus this iteration's accumulator
        for the journal's step record (retry/bisect probes count again:
        dispatches issued IS the cost being quoted)."""
        _dispatches_total.inc(mode=mode)
        self._disp_n += 1
        if mode == "ragged":
            self._disp_ragged = True

    # ---------------------------------------- request-id surface (ISSUE 10)
    def _cache_result_locked(self, req) -> None:
        """Caller holds ``self._cond``.  Record a finished request's
        outcome in the bounded result cache so a detached client can
        re-attach by id (``GET /result/<id>``) — including after a
        snapshot/restore, where the journaled id is carried verbatim."""
        if not self.result_cache_size:
            return
        if req.error is None:
            entry = {"request_id": req.request_id, "status": "done",
                     "output_ids": [int(t) for t in req.output_ids],
                     "new_tokens": len(req.generated)}
        else:
            entry = {"request_id": req.request_id, "status": "error",
                     "error": str(req.error),
                     "error_type": type(req.error).__name__}
        self._results[req.request_id] = entry
        self._results.move_to_end(req.request_id)
        while len(self._results) > self.result_cache_size:
            self._results.popitem(last=False)

    def result_for(self, request_id: str) -> Optional[dict]:
        """The cached outcome for ``request_id`` — ``status`` is
        ``done`` (with ``output_ids``) or ``error`` once finished,
        ``pending`` while queued/decoding, None for an id this engine
        has never seen (or one evicted from the bounded cache)."""
        with self._cond:
            hit = self._results.get(request_id)
            if hit is not None:
                return dict(hit)
            live = (self._active + self._prefilling + self._preempted
                    + self._sched.pending())
            for r in live:
                if r.request_id == request_id:
                    return {"request_id": request_id, "status": "pending",
                            "generated_tokens": len(r.generated)}
        return None

    def scheduler_info(self) -> dict:
        """JSON-able scheduling state for ``/health``: the active
        policy knobs and per-class/per-tenant queue depths."""
        with self._cond:
            return {
                "prefill_chunk_tokens": self.prefill_chunk_tokens,
                "default_class": self._sched.default_class,
                "classes": self._sched.policy(),
                "tenants_queued": self._sched.tenant_depths(),
                "prefilling": len(self._prefilling),
                "preempted": len(self._preempted),
                # closed-loop overload state (ISSUE 19): the ladder
                # rung and whether the controllers are armed — the
                # fleet autoscaler reads these off /health
                "brownout_level": self._brownout,
                "brownout_enabled": self.brownout_thresholds is not None,
                "decode_preempt": self.decode_preempt,
            }

    # ------------------------------------------------- snapshot/restore
    def snapshot(self) -> dict:
        """Serialize every in-flight request to a JSON-able journal
        (ISSUE 8 tentpole, consumer 3).  Quiesces first: waits for the
        in-flight chunk/decode batch to finish so (generated,
        next_token) is a consistent between-steps cut — the journal's
        ``next_token`` is the already-transferred host-side sample, so
        a restore continues each stream token-for-token.  Safe to call
        while draining (SIGTERM snapshot-then-drain) or on an idle
        engine (empty journal)."""
        with self._cond:
            self._snap_waiters += 1
            try:
                while self._stepping and not self._stop:
                    self._cond.wait(0.1)
                # under the lock: only shallow snapshots of the mutable
                # state (generated grows once the loop resumes; prompt
                # is written once at submit).  The O(total tokens) JSON
                # conversion below runs with the lock RELEASED so a
                # deep journal never stalls submission or the loop
                now = time.perf_counter()
                # in-flight streams FIRST: restore() resubmits in
                # journal order, so if the journal saturates the
                # restoring engine's bounded queues it is never-started
                # queued work that gets dropped — not the mid-stream
                # generations the journal exists to save
                cuts = [(r, r.prompt, list(r.generated), r.next_token)
                        for r in (list(self._active)
                                  + list(self._prefilling)
                                  + list(self._preempted)
                                  + self._sched.pending())
                        if not r.done.is_set() and not r.cancelled]
            finally:
                self._snap_waiters -= 1
                self._cond.notify_all()
        entries = []
        for r, prompt, generated, next_token in cuts:
            entries.append({
                **self._entry_fields(r),
                "prompt": [int(t) for t in prompt],
                "generated": [int(t) for t in generated],
                "next_token": (None if next_token is None
                               else int(next_token)),
                "ttl_remaining_s": (
                    None if r.deadline is None
                    else max(1e-3, r.deadline - now)),
                # a request that was ALREADY admitted satisfied its
                # queue-wait contract — re-imposing the (likely spent)
                # deadline on the restore queue would reap exactly the
                # long-running streams the journal exists to save
                "queue_timeout_remaining_s": (
                    None if r.queue_deadline is None
                    or r.admitted_at is not None
                    else max(1e-3, r.queue_deadline - now)),
            })
        _snapshot_reqs.inc(len(entries))
        return {"version": 1, "requests": entries}

    def restore(self, snapshot: dict, strict: bool = True
                ) -> List[_Request]:
        """Resubmit a :meth:`snapshot` journal onto THIS engine.  Each
        entry flows through normal admission; entries with generated
        tokens carry them as ``replay_tokens`` so the chunked
        context-prefill program reconstructs their KV bit-exactly and
        the journaled next token continues the stream (ISSUE 8).
        ``strict=False`` skips entries the engine rejects (unknown
        class, full queue) with a warning instead of raising — the
        restarted-server path, where one unplaceable request must not
        abort the whole resume.  Returns the new request handles.

        Exactness caveat: sampled (``do_sample``) rows resume
        bit-exactly on the default on-device sampler, whose draws are
        keyed by (seed, absolute position).  On the
        ``sample_on_device=False`` host-logits escape hatch a sampled
        row's host RNG stream position is not journaled — its already-
        generated tokens and journaled next token are exact, but
        draws after that come from a freshly seeded RNG (greedy rows
        are exact on both paths)."""
        import warnings
        out: List[_Request] = []
        for e in snapshot.get("requests", ()):
            try:
                out.append(self.submit(
                    np.asarray(e["prompt"], np.int32),
                    max_new_tokens=int(e.get("max_new_tokens", 32)),
                    eos_token_id=e.get("eos_token_id"),
                    do_sample=bool(e.get("do_sample", False)),
                    temperature=float(e.get("temperature", 1.0)),
                    seed=int(e.get("seed", 0)),
                    # deadlines are taken verbatim from the journal by
                    # the _restore branch (incl. "no deadline"), never
                    # from this engine's defaults
                    # None lets the restored engine speculate when IT
                    # can (a journal from a drafted engine restores
                    # cleanly onto a draft-free one); False preserves
                    # an explicit opt-out
                    draft=None if e.get("draft") else False,
                    priority=e.get("priority"),
                    tenant=e.get("tenant", "default"),
                    request_id=e.get("request_id"),
                    _restore=e))
            except BaseException as exc:  # noqa: BLE001 — per-entry
                if strict:
                    raise
                warnings.warn(
                    f"snapshot restore skipped one request: {exc!r}")
        return out

    def stop_admissions(self) -> None:
        """Synchronously flip the draining flag (``drain()`` sets it
        again, idempotently).  The server's SIGTERM path calls this
        BEFORE taking the crash-floor snapshot: ``begin_drain`` only
        spawns the drain thread, so without this a request admitted in
        the spawn-to-flag window would be journal-invisible (ISSUE 8)."""
        with self._cond:
            self._draining = True
            _draining_g.set(1)
            self._cond.notify_all()

    def drain(self, timeout: Optional[float] = None,
              reject_queued: bool = False) -> bool:
        """Graceful shutdown: stop accepting NEW submissions, let every
        already-submitted request (queued and active) run to
        completion, then stop the scheduler thread — the pool reclaims
        to idle as the last sequence retires.  Returns True when fully
        drained; False if ``timeout`` elapsed first (the engine keeps
        draining — call again, or escalate to ``stop()``).

        ``reject_queued=True`` is the hard-preemption fast path
        (ROADMAP PR 4 follow-up b): queued-but-unadmitted requests fail
        fast with :class:`EngineDraining` — they hold no pages, so
        rejection is free — while admitted work still runs to
        completion within the (shorter) deadline."""
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        rejected: List[_Request] = []
        with self._cond:
            self._draining = True
            _draining_g.set(1)
            if reject_queued and len(self._sched):
                rejected = self._sched.pop_all()
                for r in rejected:
                    r.error = EngineDraining(
                        "engine draining: request rejected before "
                        "admission (reject_queued fast path)")
                    self._cache_result_locked(r)
                    self._journal_retire(r)
                _queue_depth.set(0)
                _drain_rejected.inc(len(rejected))
            self._cond.notify_all()
        for r in rejected:
            r.done.set()
        with self._cond:
            while len(self._sched) or self._active or self._prefilling \
                    or self._preempted:
                if self._stop:
                    # a concurrent hard stop() preempted the drain: the
                    # remaining requests were ERRORED, not completed —
                    # never report that as a successful drain
                    return False
                wait = 0.5
                if deadline is not None:
                    wait = min(wait, deadline - time.monotonic())
                    if wait <= 0:
                        return False
                self._cond.wait(wait)
        self.stop()
        _draining_g.set(0)
        return True

    def stop(self):
        """Hard stop: errors whatever is still queued/active.  Use
        :meth:`drain` for the graceful path."""
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout=10)
        if getattr(self, "_coloc_registered", False):
            self._coloc_registered = False
            from . import journal as _journal_mod
            _journal_mod.engine_stopped()
        if self._hb_id is not None:
            from ..distributed.watchdog import CommTaskManager
            CommTaskManager.instance().unregister_heartbeat(self._hb_id)
            self._hb_id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # ---------------------------------------------------------- scheduler
    def _step_age(self) -> Optional[float]:
        """Watchdog heartbeat probe: seconds the current compiled step
        has been in flight, or None while idle (never flagged)."""
        t0 = self._step_started_at
        return None if t0 is None else time.monotonic() - t0

    def _pages_for(self, req) -> int:
        ps = self.cache.page_size
        total = len(req.prompt) + req.max_new_tokens
        if self._spec:
            # a verify step writes spec_k + 1 tokens from length
            # <= prompt + max_new - 1 before rolling back, so the
            # worst-case footprint carries a spec_k-token overhang (the
            # draft pool's propose scan peaks at the same bound)
            total += self.spec_k
        return -(-total // ps)

    def _free_pads_locked(self) -> None:
        """Caller holds ``self._cond`` (or the engine is single-threaded
        at the call site).  Release the draft pool's pad scratch page(s)
        so an idle engine reports fully reclaimed capacity."""
        if self._spec:
            self.draft_cache.free(_PAD_SEQ)
            _spec_draft_pages.set(self.draft_cache.pinned_pages)

    def _reap_locked(self) -> List[_Request]:
        """Caller holds ``self._cond``.  Retire queued and active
        requests that were cancelled or whose deadline passed — their
        pages and reservations are reclaimed here, so an abandoned
        request can never hold pool capacity past its TTL.  Returns the
        reaped requests; the caller sets their ``done`` events outside
        the lock."""
        now = time.perf_counter()
        out: List[_Request] = []
        for r in self._sched.reap(now):
            r.error = r._lifecycle_error(now, queued=True)
            self._count_lifecycle(r)
            self._cache_result_locked(r)
            self._journal_retire(r)
            _tracer.request_event(r.request_id, "retire", ok=False)
            out.append(r)
        if out:
            _queue_depth.set(len(self._sched))
        # mid-prefill requests (chunking spans iterations) and paused
        # preempted requests hold pages: reap them too, so a cancelled
        # or expired request never parks capacity in either list
        for lst_name in ("_prefilling", "_preempted"):
            lst = getattr(self, lst_name)
            if not lst:
                continue
            keep: List[_Request] = []
            for r in lst:
                # (one with a row in the step in flight waits for that
                # step's commit: ``_riders_hold`` lands it first)
                err = (None if self._rides(r)
                       else r._lifecycle_error(now, queued=False))
                if err is None and lst_name == "_preempted":
                    # resume-TTL (ISSUE 8 satellite): a paused prefill
                    # may hold its page reservation at most
                    # preempt_resume_ttl_s — past that it is reaped
                    # with pages reclaimed, never parked forever
                    err = self._preempt_expired_error(r, now)
                if err is None:
                    keep.append(r)
                else:
                    r.error = err
                    self._count_lifecycle(r)
                    self._retire_locked(r)
                    out.append(r)
            setattr(self, lst_name, keep)
        if self._active:
            still: List[_Request] = []
            for r in self._active:
                err = (None if self._rides(r)
                       else r._lifecycle_error(now, queued=False))
                if err is None:
                    still.append(r)
                else:
                    r.error = err
                    self._count_lifecycle(r)
                    self._retire_locked(r)
                    out.append(r)
            self._active = still
            if not still:
                # everything reaped: the pad scratch page goes back too
                self._free_pads_locked()
        if out:
            self._cond.notify_all()
        return out

    @staticmethod
    def _count_lifecycle(req) -> None:
        if isinstance(req.error, RequestCancelled):
            _cancelled_total.inc()
            _tracer.request_event(req.request_id, "cancel")
        else:
            _expired_total.inc()
            _tracer.request_event(req.request_id, "expire")

    @staticmethod
    def _pause_age(r, now: Optional[float] = None) -> float:
        """Total time this request has spent preempted — the CURRENT
        pause plus every earlier preempt/resume cycle, so thrashing
        re-preemption can never reset the aging/reap clock."""
        age = r.paused_total
        if r.preempted_at is not None:
            age += (time.perf_counter() if now is None else now) \
                - r.preempted_at
        return age

    def _preempt_expired_error(self, r,
                               now: float) -> Optional[BaseException]:
        """Caller holds ``self._cond``.  The reap error for a preempted
        prefill that exhausted its resume TTL, or None while it may
        still be resumed (or no TTL is configured)."""
        ttl = self.preempt_resume_ttl_s
        if ttl is None or self._pause_age(r, now) <= ttl:
            return None
        self._sched.note_preempt_expired(r)
        return DeadlineExceeded(
            f"preempted prefill spent more than its {ttl:.3f}s resume "
            "TTL paused without a slot freeing up")

    def _preempt_rank_locked(self, r) -> int:
        """Caller holds ``self._cond``.  A request's EFFECTIVE rank
        for preemption decisions: its class rank — or, once it has
        spent half the resume TTL paused, an aging boost (rank -1)
        that outranks every queued class, so a slot that frees is
        forced to the aged request (and, symmetrically, an aged
        resumed prefill can no longer be picked as a preemption
        victim) instead of fresh urgent traffic starving it all the
        way to the reap bound."""
        ttl = self.preempt_resume_ttl_s
        if ttl is not None and self._pause_age(r) >= 0.5 * ttl:
            return -1
        return self._sched.class_of(r).rank

    def _admission_cost_locked(self, req, slots_freed: int = 0
                               ) -> Optional[int]:
        """Caller holds ``self._cond``.  PURE fit check: the pages this
        request's admission would newly reserve (its DRR cost), or None
        when it does not fit right now.  A prompt whose prefix is
        already cached reserves only what the pool must newly provide:
        the un-shared pages plus whichever shared pages were not
        already pinned by another live sharer — shared pages are
        counted once across the engine, not once per sharer."""
        shared_tok, newly_pinned = (
            self.cache.probe_prefix(req.prompt) if self.prefix_cache
            else (0, 0))
        need = (self._pages_for(req)
                - shared_tok // self.cache.page_size + newly_pinned)
        if self._reserved_pages + need > self.cache.total_pages:
            return None
        if self._recurrent and self.cache.free_slots + slots_freed < 1:
            return None                 # a slot a sequence, as pages are
        # the draft pool reserves the full worst case too (no prefix
        # sharing there — the draft always prefills whole prompts);
        # both pools must fit or neither is reserved
        dneed = self._pages_for(req) if req.use_draft else 0
        if dneed and self._reserved_draft_pages + dneed \
                > self.draft_cache.total_pages:
            return None
        # stash the plan for _finalize_admission_locked: nothing can
        # mutate pool state between this check and the commit (same
        # lock hold), so the winner's prefix hash walk is not repeated
        req._admit_plan = (need, shared_tok)
        return max(1, need)

    def _finalize_admission_locked(self, req) -> None:
        """Caller holds ``self._cond``.  Commit an admission the cost
        check just approved: RESERVE worst-case pages (prompt + full
        max_new_tokens) so decode-time allocate() can never exhaust the
        pool, assign the seq id, and ACQUIRE any cached prefix (pinning
        the shared pages against eviction).  Prefill itself runs
        outside the lock — submit() must never wait on device work."""
        need, shared_tok = req._admit_plan
        req._admit_plan = None
        self._reserved_pages += need
        if req.use_draft:
            self._reserved_draft_pages += self._pages_for(req)
            req._draft_reserved = True
        req.seq_id = self._next_seq
        self._next_seq += 1
        if self._recurrent:
            self._take_slot_locked(req)
        if shared_tok:
            got = self.cache.acquire_prefix(req.seq_id, req.prompt)
            assert got == shared_tok   # nothing ran between probe/acquire
            req.prefix_tokens = got
        req.prefill_pos = req.prefix_tokens
        req.admitted_at = time.perf_counter()
        self._sched.note_admitted(req, req.admitted_at)
        if self.journal is not None:
            # the admitted marker drops the (satisfied) queue-wait
            # deadline on recovery — the PR 8 snapshot convention
            self._jadm.append(req.request_id)
            if req.prefix_tokens:
                # page provenance (ISSUE 14 satellite): which cached
                # prefix pages this admission mapped read-only — the
                # content key is what survives a replica boundary
                self._journal_pages(req, "acquired", req.prefix_tokens)
        _tracer.request_event(
            req.request_id, "admitted", cls=req.priority,
            seq_id=req.seq_id, prefix_tokens=req.prefix_tokens,
            queue_wait_s=round(req.admitted_at - req.submitted_at, 6))

    def _take_slot_locked(self, req) -> None:
        """Caller holds ``self._cond``.  The sequence's recurrent slot,
        a slot of every layer's pool; the row that enters it with an
        empty context starts it from zero."""
        self.cache.take_slot(req.seq_id)
        _slots_taken.inc()
        _slots_in_use_g.set(self.cache.slots_in_use)

    def _tpot_parked_locked(self, r) -> bool:
        """Caller holds ``self._cond``.  True while a row parked by the
        TPOT trigger must STAY parked: some active row's TPOT budget is
        still breached by the measured step time.  The aging boost
        (half the resume TTL) overrides, so TPOT parking can never
        starve a row past the reservation-bound contract; once no
        active row is breaching (the interactive burst retired, or the
        smaller batch brought the step time back under budget) the row
        resumes through the ordinary path."""
        if not getattr(r, "_tpot_parked", False):
            return False
        if self._preempt_rank_locked(r) < self._sched.class_of(r).rank:
            return False                       # aging boost won
        ewma = self._step_ewma
        if ewma is None:
            return False
        for a in self._active:
            budget = self._sched.class_of(a).tpot_budget_s
            if budget is not None and ewma > budget:
                return True
        return False

    def _best_preempted_locked(self) -> Optional[_Request]:
        """Caller holds ``self._cond``.  The paused request that should
        resume first: most urgent EFFECTIVE class (aging boost
        included), then preemption order.  Rows the TPOT trigger parked
        stay invisible while the budget breach that parked them
        persists — resuming one into the still-too-slow batch would
        undo the preemption the very next iteration."""
        cands = [r for r in self._preempted
                 if not self._tpot_parked_locked(r)]
        if not cands:
            return None
        return min(cands,
                   key=lambda r: (self._preempt_rank_locked(r),
                                  self._preempted.index(r)))

    def _preemption_victim_locked(self, rank: int) -> Optional[_Request]:
        """Caller holds ``self._cond``.  The request to pause so a
        rank-``rank`` request can take its slot: the LEAST urgent
        preemptible prefilling request strictly outranked by the
        waiter, preferring the least prefill progress (cheapest pause).
        EFFECTIVE rank, so an aging-boosted resumed prefill is immune
        to re-preemption — a forced resume must stick.

        With ``decode_preempt`` (ISSUE 19) and no mid-prefill victim,
        the search extends to DECODING rows: the least urgent
        preemptible active row is paused mid-decode — pages kept, its
        pending ``next_token`` still host-side — and re-enters through
        the same resume path, so batch-class rows squatting decode
        slots can no longer wall off urgent admissions."""
        victims = [r for r in self._prefilling
                   if self._sched.class_of(r).preemptible
                   and self._preempt_rank_locked(r) > rank]
        if victims:
            return max(victims,
                       key=lambda r: (self._sched.class_of(r).rank,
                                      -r.prefill_pos))
        if not self.decode_preempt:
            return None
        victims = [r for r in self._active
                   if self._sched.class_of(r).preemptible
                   and self._preempt_rank_locked(r) > rank]
        if not victims:
            return None
        return max(victims,
                   key=lambda r: (self._sched.class_of(r).rank,
                                  -len(r.generated)))

    def _pause_locked(self, victim, for_rank: int) -> None:
        """Caller holds ``self._cond``.  Move a preemption victim —
        mid-prefill or mid-decode — onto the paused list (seq id,
        pages and reservation all kept).  One with a row in the step in
        flight is paused only after that step's commit."""
        if self._rides(victim):
            raise _LandFirst("preempt")
        if victim in self._prefilling:
            self._prefilling.remove(victim)
        else:
            self._active.remove(victim)
            _decode_preempt_total.inc()
        if self._recurrent:
            # a paused sequence gives its slot back (slots are a row
            # each; there is nothing to re-map as pages are): it resumes
            # by running its tokens so far through chunk rows again into
            # a zeroed slot, the pending token kept (as a restored
            # request does)
            self.cache.release_slot(victim.seq_id)
            self.cache.truncate(victim.seq_id, 0)
            _slots_in_use_g.set(self.cache.slots_in_use)
            if victim.generated:
                victim.replay_tokens = victim.output_ids
            victim.prefill_pos = 0
        victim.preempted_at = time.perf_counter()
        self._preempted.append(victim)
        self._sched.note_preempted(victim)
        _tracer.request_event(
            victim.request_id, "preempt", for_rank=for_rank,
            prefill_pos=victim.prefill_pos,
            decoded=len(victim.generated))

    def _resume_locked(self, pre) -> None:
        """Caller holds ``self._cond``.  Un-pause a preempted request:
        its pause time banks into ``paused_total`` (the aging/reap
        clock survives the resume) and chunking continues from
        ``prefill_pos`` — it never re-prefills.  A row preempted
        MID-DECODE (prefill complete, next token pending host-side)
        rejoins the decode batch directly: its first token was already
        emitted, so routing it through _prefilling would strand it —
        the chunk planner has no work for a finished prefill."""
        self._preempted.remove(pre)
        if pre.preempted_at is not None:
            pre.paused_total += time.perf_counter() - pre.preempted_at
            pre.preempted_at = None
        pre._tpot_parked = False
        if self._recurrent:
            self._take_slot_locked(pre)
        if pre.first_token_at is not None \
                and pre.prefill_pos >= len(pre.prefill_target):
            self._active.append(pre)
        else:
            self._prefilling.append(pre)
        self._sched.note_resumed(pre)
        _tracer.request_event(pre.request_id, "resume",
                              prefill_pos=pre.prefill_pos,
                              decoded=len(pre.generated),
                              paused_s=round(pre.paused_total, 6))

    def _tpot_preempt_locked(self) -> None:
        """Caller holds ``self._cond``.  The TPOT feedback loop
        (ISSUE 19): at full occupancy, when the engine's measured
        iteration time (EWMA over decode-bearing steps — for an active
        row, one iteration IS one output token) breaches a running
        row's ``tpot_budget_s``, pause the least-urgent preemptible
        DECODING row so the smaller batch steps faster.  Rate-limited
        by ``tpot_preempt_cooldown_s``; the parked row stays invisible
        to resume while the breach persists (see _tpot_parked_locked)
        and its pause time still accrues toward the aging/reap
        clocks."""
        if not self.decode_preempt or not self._active:
            return
        if len(self._active) + len(self._prefilling) < self.max_batch:
            return
        ewma = self._step_ewma
        if ewma is None:
            return
        now = time.perf_counter()
        if now - self._tpot_last_preempt < self.tpot_preempt_cooldown_s:
            return
        breached = [r for r in self._active
                    if self._sched.class_of(r).tpot_budget_s is not None
                    and ewma > self._sched.class_of(r).tpot_budget_s]
        if not breached:
            return
        urgent = min(self._sched.class_of(r).rank for r in breached)
        victims = [r for r in self._active
                   if self._sched.class_of(r).preemptible
                   and self._preempt_rank_locked(r) > urgent]
        if not victims:
            return
        victim = max(victims,
                     key=lambda r: (self._sched.class_of(r).rank,
                                    -len(r.generated)))
        self._pause_locked(victim, urgent)
        victim._tpot_parked = True
        self._tpot_last_preempt = now

    def _admit_locked(self) -> None:
        """Caller holds ``self._cond``.  Fill free slots from (a) paused
        preempted requests — they resume for free, their pages are
        already reserved — and (b) the workload scheduler's queues in
        weighted-DRR order; when every slot is held and a MORE URGENT
        class is waiting, pause a preemptible mid-prefill request and
        hand its slot over (the tentpole preemption path: the victim
        keeps seq id, pages and reservation, and resumes later).
        Under SUSTAINED higher-priority load a preemptible request
        stays paused (that is the priority contract) while holding its
        reservation — bound the pause with a request TTL if that
        matters; the ROADMAP carries resume-aging as a follow-up."""
        pending_rank = None     # rank a preemption just freed a slot for
        while True:
            slots = (self.max_batch - len(self._active)
                     - len(self._prefilling))
            qrank = self._sched.min_waiting_rank()
            pre = self._best_preempted_locked()
            if slots <= 0:
                if qrank is None:
                    break
                victim = self._preemption_victim_locked(qrank)
                head = self._sched.peek_urgent()
                # (a paused victim keeps its pages, and gives back the
                # slot of its recurrent state where it has one)
                if victim is None or head is None \
                        or self._admission_cost_locked(
                            head, slots_freed=1) is None:
                    break
                self._pause_locked(victim, qrank)
                pending_rank = qrank
                continue
            if pending_rank is None and pre is not None and (
                    qrank is None
                    or self._preempt_rank_locked(pre) <= qrank):
                self._resume_locked(pre)
                continue
            # a slot bought with a preemption belongs to the rank it
            # was preempted for: a less urgent class's banked DRR
            # deficit must not snatch it (that would pause one batch
            # prefill just to start another)
            req = self._sched.pop_next(self._admission_cost_locked,
                                       max_rank=pending_rank)
            pending_rank = None
            if req is None:
                if pre is not None:
                    self._resume_locked(pre)
                    continue
                break
            self._finalize_admission_locked(req)
            self._prefilling.append(req)
        _queue_depth.set(len(self._sched))

    def _step_token_bound(self) -> Optional[int]:
        """The most tokens one unified step can carry, which the ragged
        programs' dense layers are packed to (None: no bound, monolithic
        prefill hands a step whole prompts).  A step's rows are at most
        ``max_batch``, padded by the decoder to a power of two with
        one-token rows.  ``_plan_chunks_locked`` never splits a chunk
        to fit the leftover budget, so prompt tails summing to under
        one chunk and then a full chunk can share a step:
        ``2 * chunk - 1`` prefill tokens, in at least one row; every
        other row decodes one token or, beside a draft model, verifies
        ``spec_k + 1``."""
        chunk = self.prefill_chunk_tokens
        if chunk is None:
            return None
        from .paged import next_pow2
        per_row = self.spec_k + 1 if self._spec else 1
        return (max(2 * chunk - 1, per_row)
                + (next_pow2(self.max_batch) - 1) * per_row)

    def _plan_chunks_locked(self) -> List:
        """Caller holds ``self._cond``.  (request, n_tokens) prefill
        work for THIS iteration: most urgent classes first, bounded by
        the per-step chunk budget.  A request's chunk is never split to
        fit leftover budget — chunk sizes are position-derived (full
        ``prefill_chunk_tokens`` or the prompt's tail), so the compiled
        bucket shapes a workload needs are deterministic, never
        timing-dependent.  Requests whose chunk the budget gave to a
        MORE URGENT class are counted as deferred (the soft half of
        preemption; the slot pause above is the hard half — same-class
        queueing is not a deferral)."""
        if not self._prefilling:
            return []
        order = sorted(
            range(len(self._prefilling)),
            key=lambda i: (self._sched.class_of(
                self._prefilling[i]).rank, i))
        chunk = self.prefill_chunk_tokens
        plan: List = []
        budget = chunk if chunk is not None else None
        best_served_rank: Optional[int] = None
        for i in order:
            req = self._prefilling[i]
            remaining = len(req.prefill_target) - req.prefill_pos
            if remaining <= 0:     # defensive: completion moves it out
                continue
            if budget is None:
                plan.append((req, remaining))
                continue
            if budget <= 0:
                # the deferral metric means PRIORITY pressure: count it
                # only when the budget actually went to a more urgent
                # class, not when same-class peers simply queued up
                rank = self._sched.class_of(req).rank
                if best_served_rank is not None \
                        and rank > best_served_rank:
                    self._sched.note_chunk_deferred(req)
                continue
            n = min(remaining, chunk)
            plan.append((req, n))
            rank = self._sched.class_of(req).rank
            if best_served_rank is None or rank < best_served_rank:
                best_served_rank = rank
            budget -= n
        return plan

    def _sampling_for(self, reqs, ctrs):
        """(seeds, ctrs, temps, flags) arrays for the fused on-device
        sampler, padded to ``len(ctrs)`` rows (pad rows draw nothing:
        flags False).  ``ctrs`` is each row's absolute token position —
        the replay-stable per-draw counter."""
        n = len(ctrs)
        seeds = np.zeros(n, np.uint32)
        temps = np.ones(n, np.float32)
        flags = np.zeros(n, bool)
        for i, r in enumerate(reqs):
            seeds[i] = r.seed
            temps[i] = max(r.temperature, 1e-6)
            flags[i] = r.do_sample
        return seeds, np.asarray(ctrs, np.int32), temps, flags

    def _ingest(self, decoder, cache, sid, tokens, k: int, n: int,
                sampling):
        """ONE bucketed prompt-ingest dispatch — tokens[k:k+n] into
        ``sid``'s pages, via fresh prefill at k == 0 or the traced
        context-prefill continuation otherwise.  THE single dispatch
        choice both the serving prefill path (:meth:`_prefill_chunk`)
        and the replay primitive (:meth:`_replay_kv`) ride, so the
        replay's bit-exactness contract can never drift from the path
        it replays."""
        ids = tokens[None, k:k + n]
        if k:
            return decoder.chunk_prefill(cache, [sid], ids,
                                         context_tokens=k, bucket=True,
                                         sampling=sampling)
        return decoder.prefill(cache, [sid], ids, bucket=True,
                               sampling=sampling)

    def _prefill_chunk(self, req, n: int) -> bool:
        """Ingest the next ``n`` tokens of ``req``'s prefill target in
        ONE compiled dispatch (bucketed: one compile per power-of-two
        chunk length, not one per distinct length).  The target is the
        prompt — or, for a restored request, prompt + generated-so-far:
        the replay primitive's admission-path mode (ISSUE 8) rides the
        SAME program.  Returns True when the target is fully resident —
        only then is the next token sampled (with the SAME (seed,
        absolute position) counter as a monolithic prefill, so chunked,
        preempted and replayed prefill are greedy- and sample-replay-
        identical to the unchunked path).

        Intermediate chunks run the fused-sampling program in its
        argmax-only tail — the per-chunk host transfer stays (1,) ids
        whose value is discarded."""
        target = req.prefill_target
        k = req.prefill_pos
        total = len(target)
        n = min(n, total - k)
        last = (k + n == total)
        if not self.sample_on_device:
            sampling = None
        elif last:
            sampling = self._sampling_for([req], [total])
        else:
            sampling = _null_sampling()
        self._wedged.clear()      # only THIS dispatch may flag itself
        t0 = self._step_started_at = time.monotonic()
        t_tr = _tracer.now_ns() if _tracer.enabled else 0
        try:
            if req.chunks_done == 0:
                # per-sequence site, once — chunking must not change
                # existing fault plans' semantics
                _faults.maybe_fire("prefill", seq_ids=[req.seq_id])
            _faults.maybe_fire("prefill_chunk", seq_ids=[req.seq_id])
            self._count_dispatch("chunk" if k else "prefill")
            with monitor.span("engine/prefill", histogram=_prefill_s):
                out = self._ingest(self._decoder, self.cache, req.seq_id,
                                   target, k, n, sampling)
        finally:
            self._step_started_at = None
        _last_step_ts.set(time.time())
        try:
            self._check_wedged(t0)      # same stale-fire guard as decode
        except _EngineWedged:
            # the watchdog flagged this dispatch as wedged: its writes
            # are suspect — roll the cache back to the chunk's start
            # so the caller's rebuild + replay + retry is exact
            self.cache.truncate(req.seq_id, k)
            raise
        req.prefill_pos = k + n
        req.chunks_done += 1
        self._sched.note_chunk(req)
        if _tracer.enabled and t_tr:
            # one step-track entry per chunk dispatch + the request's
            # own timeline entry — flow-linked in the chrome export
            # (t_tr == 0 means the window opened MID-dispatch: skip the
            # slice rather than emit one starting at clock zero)
            _tracer.step_record(
                "prefill_chunk", self.steps, t_tr, _tracer.now_ns(),
                request=req.request_id, tokens=n, pos=k,
                cls=req.priority)
            _tracer.request_event(req.request_id, "prefill_chunk",
                                  tokens=n, pos=k,
                                  chunk=req.chunks_done)
        if not last:
            return False
        self._finish_prefill(req, out[0], sampling is not None)
        return True

    def _finish_prefill(self, req, out_row, sampled: bool) -> None:
        """Prefill-completion side effects, shared by whole-prompt
        prefill and the unified ragged step: the target is fully resident
        — register its prefix, ingest the draft's copy, latch the first
        sampled token, stamp TTFT, journal the pending sample."""
        with monitor.span("engine/commit/finish_prefill",
                          into=self._host_s):
            # ---- target fully resident: finish what monolithic prefill did
            if self.prefix_cache:
                _prefix_lookups.inc()
                if req.prefix_tokens:
                    _prefix_hits.inc()
                    _prefix_hit_tokens.inc(req.prefix_tokens)
                # retain this prompt's page-aligned prefixes for later
                # sharers (idempotent for the pages it itself shared);
                # chunk-written pages carry identical KV, so chunked
                # prompts seed the prefix cache exactly like monolithic ones
                with monitor.span("engine/commit/prefix_register",
                                  into=self._host_s):
                    self.cache.register_prefix(req.seq_id, req.prompt)
                    self._journal_pages(req, "registered", len(req.prompt))
            if req.use_draft:
                # the draft ingests the WHOLE target (no prefix sharing in
                # its pool) so its cache sits at the same length as the
                # target's — the lockstep invariant every propose/verify
                # round preserves.  Deferred to prefill COMPLETION under
                # chunking: a preempted target resumes without ever having
                # touched the draft pool.  The greedy-tail sampling keeps
                # the transfer at (1,) ids; the value is discarded.
                try:
                    self._count_dispatch("draft")
                    self._draft_decoder.prefill(
                        self.draft_cache, [req.seq_id],
                        req.prefill_target[None],
                        bucket=True, sampling=_null_sampling())
                except BaseException:  # noqa: BLE001 — degrade, don't fail
                    self._downgrade_draft([req])
            if req.next_token is None:
                # a restored request keeps its journaled next token (the
                # replayed final draw equals it by the counter contract);
                # sampled rows on the host-logits path must ALSO keep it —
                # re-picking would burn a host RNG draw
                req.next_token = (int(out_row) if sampled
                                  else self._pick(req, out_row))
            if req.first_token_at is None:  # not one resumed into a new slot
                req.first_token_at = time.perf_counter()
                ttft = req.first_token_at - req.submitted_at
                _ttft_s.observe(ttft)
                self._sched.note_first_token(req, ttft)
                _tracer.request_event(req.request_id, "first_token",
                                      ttft_s=round(ttft, 6))
            if self.journal is not None:
                # prefill completion: no tokens appended yet, but the first
                # pending sample is host state a SIGKILL must not lose
                self._jrows.append((req.request_id, (), req.next_token))

    def _run_chunks(self, plan) -> None:
        """Execute one iteration's prefill chunk plan (device work —
        called WITHOUT the lock).  A failing chunk quarantines exactly
        its request: the decoder already rolled the failed dispatch
        back, retirement reclaims the pages every EARLIER chunk wrote,
        and batchmates/other tenants are untouched (host-side faults
        leave the donated pools valid — see _recover_pools)."""
        completed: List[_Request] = []
        failed: List[_Request] = []
        for req, n in plan:
            if req.cancelled or req.done.is_set():
                # cancelled: the next reap retires it; done: a replay
                # failure during an earlier chunk's recovery already
                # quarantined it
                continue
            try:
                if self._prefill_chunk(req, n):
                    completed.append(req)
            except _EngineWedged as e:
                # watchdog-flagged wedge mid-prefill: bounded rebuild
                # (pools reset, every survivor's KV replayed — this
                # request's earlier chunks included) then ONE retry of
                # the same chunk; a second failure quarantines as usual
                self._after_step_failure(e)
                if req.done.is_set():
                    # its OWN replay failed during the rebuild: already
                    # quarantined and retired — retrying would write
                    # into pages nothing will ever free
                    continue
                try:
                    if self._prefill_chunk(req, n):
                        completed.append(req)
                except BaseException as e2:  # noqa: BLE001
                    req.error = e2
                    failed.append(req)
                    self._after_step_failure(e2, exclude=(req,))
            except BaseException as e:  # noqa: BLE001 — quarantine one
                req.error = e
                failed.append(req)
                # a REAL donated-buffer loss in this chunk zeroed every
                # OTHER sequence's KV too: detect the pool rebuild and
                # replay the survivors before the next dispatch runs
                # over zeroed pools (no-op for host-side faults)
                self._after_step_failure(e, exclude=(req,))
        if not completed and not failed:
            return
        with self._cond:
            for r in failed:
                if r in self._prefilling:
                    self._prefilling.remove(r)
                # quarantine BEFORE retire so the timeline's terminal
                # event matches the decode-path ejection sites
                # (consumers classify an ended request by last event)
                _note_quarantine(r)
                self._retire_locked(r)
            for r in completed:
                if r in self._prefilling:
                    self._prefilling.remove(r)
                    self._active.append(r)
            self._cond.notify_all()
        for r in failed:
            r.done.set()

    # ------------------------------------------- unified ragged step
    def _propose_drafts(self, reqs):
        """The draft model proposes ``spec_k`` greedy tokens for each
        opted-in row in ONE compiled scan dispatch (plus one write-only
        step, so its cache covers the last proposal).  Greedy, so a
        rolled-back step proposes the same drafts again.  A draft
        failure never fails the step: those rows are downgraded to
        plain decode (their drafts stay ``-1``, which never matches:
        they ride the verify rows with unmatched slots and advance
        exactly one token)."""
        k = self.spec_k
        drafts = np.full((len(reqs), k), -1, np.int32)
        d_idx = [i for i, r in enumerate(reqs) if r.use_draft]
        if not d_idx:
            return drafts
        Bd = self._bucket(len(d_idx))
        d_seqs = [reqs[i].seq_id for i in d_idx]
        d_tok = np.array([reqs[i].generated[-1] for i in d_idx],
                         np.int32)
        d_pos = np.array([self.draft_cache.length(s) for s in d_seqs],
                         np.int32)
        if Bd > len(d_idx):
            self.draft_cache.truncate(_PAD_SEQ, 0)
            pad_n = Bd - len(d_idx)
            d_seqs += [_PAD_SEQ] * pad_n
            d_tok = np.concatenate([d_tok, np.zeros(pad_n, np.int32)])
            d_pos = np.concatenate([d_pos, np.zeros(pad_n, np.int32)])
        try:
            self._count_dispatch("draft")
            prop = self._draft_decoder.multi_step(
                self.draft_cache, d_seqs, d_tok, d_pos, k + 1)
        except BaseException:  # noqa: BLE001 — degrade, don't fail
            self._downgrade_draft([reqs[i] for i in d_idx])
        else:
            for j, i in enumerate(d_idx):
                drafts[i] = prop[j, :k]
        return drafts

    def _unified_rollback(self, step) -> None:
        """Undo a unified step that failed (or wedged), or that was
        dispatched over one that did, so that the SAME step can run
        again: decode tokens appended at its launch pop, every row's
        cache length returns to its value from before the step (the
        decoder rolled its own advance back on a host/device error; a
        wedge's advance stands until this truncate), speculative rows
        unwind the draft cache the propose scan advanced, and what its
        launch told the scheduler is taken back: chunk cursors, a
        finished prefill's place among the decoding rows."""
        with self._cond:
            for req, _target, k, _n, _last in step.chunks:
                self.cache.truncate(req.seq_id, k)
                if step.launched:
                    req.prefill_pos = k
                    req.chunks_done -= 1
            for req in step.moved:
                if req in self._active:
                    self._active.remove(req)
                    self._prefilling.append(req)
            for r in step.active:
                if id(r) in step.dropped:
                    # retired at its EOS a step ago: only its pages wait
                    self._release_pages_locked(r)
                    continue
                if id(r) not in step.deferred:
                    r.generated.pop()
                tgt, dft = step.lens_before[r.seq_id]
                self.cache.truncate(r.seq_id, tgt)
                if dft is not None and self._spec:
                    self.draft_cache.truncate(r.seq_id, dft)
            step.dropped = {}

    def _step_replaced(self) -> bool:
        """True when something stands in for the decoder's
        ``ragged_step`` (a test's wrapper, a subclass): the engine then
        calls it whole, so whoever wrapped it sees every step, and
        leaves no step in flight."""
        from .paged import JittedPagedDecoder
        dec = self._decoder
        return ("ragged_step" in vars(dec) or type(dec).ragged_step
                is not JittedPagedDecoder._RAGGED_STEP)

    def _overlap_hold(self) -> Optional[str]:
        """THE predicate of the one-step-deep pipeline: why no step may
        be dispatched while another is uncommitted right now (the reason
        ``serve_overlap_drains_total`` counts it by), or None.  Each is
        something the engine sees in itself, never a model's name or a
        caller's choice: a draft model (accept lengths decide the next
        tokens and truncate the cache), sampling on the host (it picks
        from logits), whole-prompt prefill (its own programs run before
        the step), an installed fault plan (a failed step is isolated
        with every token on the host), a stand-in for the decoder's
        step, a snapshot that waits for a cut between steps, stop and
        drain.  With a reason, an iteration runs schedule, dispatch,
        fetch, commit, and leaves nothing in flight."""
        if self._spec:
            return "spec"
        if not self.sample_on_device:
            return "host_sampling"
        if self.prefill_chunk_tokens is None:
            return "unchunked"
        if _faults.active() is not None:
            return "fault_plan"
        if self._step_replaced():
            return "replaced"
        if self._snap_waiters:
            return "snapshot"
        if self._stop or self._draining:
            return "drain"
        return None

    def _riders_hold(self) -> Optional[str]:
        """Why the step in flight must be committed before the schedule
        pass runs: a request with a row in it was cancelled or is past
        its deadline, and the pass is about to reap it."""
        now = time.perf_counter()
        for r in self._flight.riders.values():
            err = r._lifecycle_error(now, queued=False)
            if err is not None:
                return ("cancel" if isinstance(err, RequestCancelled)
                        else "deadline")
        return None

    def _rides(self, req) -> bool:
        """True while ``req`` has a row in the step in flight: nothing
        but that step's commit may retire, pause or replay it."""
        f = self._flight
        return f is not None and id(req) in f.riders

    def _land(self, reason: str) -> None:
        """Commit the step in flight BEFORE anything else is dispatched
        or any of its requests is moved, counted by ``reason``; the
        loop is in today's order from here until a step is left in
        flight again."""
        step, self._flight = self._flight, None
        _overlap_drains.inc(reason=reason)
        self._commit_step(step)
        with self._cond:
            self._stepping = False      # a snapshot may take its cut
            self._cond.notify_all()

    def _unified_step(self, plan, active=None, retried=False) -> None:
        """ONE ragged dispatch for the whole iteration (ISSUE 17): the
        scheduler's rank-ordered chunk plan feeds prefill/chunk row
        spans directly and every active row contributes its decode
        token — or, under speculation, a (k+1)-token verify row of
        freshly proposed drafts — to a single compiled ``ragged_step``
        call.

        The step is two halves, :meth:`_launch_step` and
        :meth:`_commit_step`; the loop (:meth:`_pipeline`) dispatches
        the next step between them where :meth:`_overlap_hold` lets it.
        This method runs them back to back, with nothing in flight: the
        probes of :meth:`_isolate_unified`.

        On ANY failure the step unwinds (:meth:`_unified_rollback`),
        the pools are rebuilt and the survivors replayed if a
        device-side loss zeroed them, and the step goes down the ladder
        (:meth:`_step_failed`, :meth:`_isolate_unified`), which calls
        this method again with the rows to probe (``active``: the decode
        rows, all of ``self._active`` if None; ``retried``: the whole
        step has had its second try)."""
        step = self._launch_step(plan, active, retried)
        if step is not None:
            self._commit_step(step)

    def _pipeline(self, plan) -> None:
        """One iteration's device work, at most ONE step deep: dispatch
        the step the schedule pass planned (its decode rows fed the
        tokens of the step in flight on the device), THEN fetch and
        commit the step in flight, and leave the new one in flight for
        the next iteration — unless :meth:`_overlap_hold` has a reason,
        and it is committed at once as it always was."""
        step = self._launch_step(plan)
        prev = self._flight     # read after: a failed launch landed it
        self._flight = step
        if prev is not None:
            if step is None:
                _overlap_drains.inc(reason="idle")  # nothing to dispatch
            self._commit_step(prev)     # a failure unwinds ``step`` too
        step = self._flight
        if step is not None:
            why = self._overlap_hold()
            if why is not None:
                self._land(why)

    def _launch_step(self, plan, active=None, retried=False):
        """The first half of a unified step: compose the rows, dispatch
        them, and tell the scheduler what does not depend on a token's
        value — chunk cursors, a finished prefill's move to the decoding
        rows, who will not continue because the token it is fed is its
        last by count.  Returns the :class:`_Step` to commit, or None
        when there was nothing to dispatch or the dispatch failed (and
        was dealt with: :meth:`_step_failed`, or — over a step in
        flight — unwound, to be planned again once that one landed).

        A decode row of a request that has a row in the step in flight
        takes its token from that step's output on the device
        (``ragged_launch``'s ``feed``); the host learns it at that
        step's commit and appends it to ``generated`` at THIS step's, so
        ``generated`` only ever holds ints the host has."""
        prev = self._flight
        chunks = []
        for req, n in plan:
            if req.cancelled or req.done.is_set():
                continue
            target = req.prefill_target
            k = req.prefill_pos
            n = min(n, len(target) - k)
            chunks.append((req, target, k, n, k + n == len(target)))
        # a probe's rows: one ejected since (its replay failed during a
        # sibling's recovery) is never stepped again
        if active is not None:
            active = [r for r in active if r in self._active]
        else:
            active = [r for r in self._active
                      if prev is None or id(r) not in prev.leaving]
        if not chunks and not active:
            return None
        spec = self._spec and any(r.use_draft for r in active)
        step = _Step(chunks, active, retried, spec,
                     self.spec_k if spec else 0)
        step.overlapped = prev is not None
        step.index = self.steps + (1 if prev is not None and prev.active
                                   else 0)
        step.lens_before = {
            r.seq_id: (self.cache.length(r.seq_id),
                       (self.draft_cache.length(r.seq_id)
                        if self._spec and r.use_draft else None))
            for r in active}
        nchunks = len(chunks)
        src = [-1] * (nchunks + len(active))
        for i, r in enumerate(active):
            at = prev.out_index.get(id(r)) if prev is not None else None
            if at is None:
                r.generated.append(r.next_token)
                fed = len(r.generated)
                eos = (r.eos_token_id is not None
                       and r.next_token == r.eos_token_id)
            else:               # its token is still on the device
                step.deferred.add(id(r))
                src[nchunks + i] = at
                fed = len(r.generated) + (id(r) in prev.deferred) + 1
                eos = False
            if eos or fed >= r.max_new_tokens:
                step.leaving.add(id(r))     # this token is its last
        if active:
            _active_seqs.set(len(active))
            _batch_occupancy.observe(len(active) / self.max_batch)
            _sampling_on_device_g.set(int(self.sample_on_device))
        step.t_ns = _tracer.now_ns()
        step.traced = _tracer.enabled
        try:
            if spec:
                step.drafts = self._propose_drafts(active)
            k_spec, drafts = step.k_spec, step.drafts
            with monitor.span("engine/build", into=self._host_s), \
                    monitor.span("engine/build/rows", into=self._host_s):
                seq_ids, rows, ctxs, nds = [], [], [], []
                for req, target, k, n, _last in chunks:
                    seq_ids.append(req.seq_id)
                    rows.append(np.asarray(target[k:k + n], np.int32))
                    ctxs.append(k)
                    nds.append(0)
                for i, r in enumerate(active):
                    seq_ids.append(r.seq_id)
                    if spec:
                        row = np.empty(k_spec + 1, np.int32)
                        row[0] = r.generated[-1]
                        row[1:] = drafts[i]
                        nds.append(k_spec)
                    else:
                        row = np.asarray(
                            [0 if id(r) in step.deferred
                             else r.generated[-1]], np.int32)
                        nds.append(0)
                    rows.append(row)
                    ctxs.append(self.cache.length(r.seq_id))
                if self.sample_on_device:
                    b = len(seq_ids)
                    seeds = np.zeros(b, np.uint32)
                    temps = np.ones(b, np.float32)
                    flags = np.zeros(b, bool)
                    # the draw counter is computed IN-PROGRAM per row
                    # (ctx + span - drafts + accept), so chunk-final,
                    # decode and verify draws all land on the row's
                    # absolute token position — the replay-stable counter
                    # contract.  Intermediate chunk rows draw nothing.
                    live = [req if last else None
                            for req, _t, _k, _n, last in chunks] + active
                    for i, r in enumerate(live):
                        if r is None:
                            continue
                        seeds[i] = r.seed
                        temps[i] = max(r.temperature, 1e-6)
                        flags[i] = r.do_sample
                    sampling = (seeds, temps, flags)
                else:
                    sampling = None
                step.sampled = sampling is not None
            step.t0 = time.monotonic()
            if prev is None:
                # only THIS dispatch may flag itself; over a step in
                # flight the heartbeat keeps that step's start
                self._wedged.clear()
                self._step_started_at = step.t0
            # the engine's fault sites, each with the seq_ids of the
            # rows it speaks of: a rule of any kind fires here, for
            # every model, and an error goes down the ladder
            # (:meth:`_step_failed`) like a failed program call
            for req, _t, k, _n, _l in chunks:
                if not k:
                    _faults.maybe_fire("prefill",
                                       seq_ids=[req.seq_id])
                _faults.maybe_fire("prefill_chunk",
                                   seq_ids=[req.seq_id])
            if active:
                decoding = [r.seq_id for r in active]
                _faults.maybe_fire("decode_step", seq_ids=decoding)
                _faults.maybe_fire("engine_wedge", seq_ids=decoding)
            self._count_dispatch("ragged")
            step_args = dict(n_drafts=(nds if spec else None),
                             sampling=sampling)
            if prev is None and self._step_replaced():
                step.result = self._decoder.ragged_step(
                    self.cache, seq_ids, rows, ctxs, **step_args)
                step.record = self._decoder.last_dispatch
            else:
                step.flight = self._decoder.ragged_launch(
                    self.cache, seq_ids, rows, ctxs, **step_args,
                    feed=((prev.flight, src) if step.deferred else None),
                    after=None if prev is None else prev.flight)
                step.record = step.flight.record
        except BaseException as e:  # noqa: BLE001 — the ladder isolates
            if prev is None:
                self._step_started_at = None
                self._step_failed(step, e)
                return None
            # over a step in flight nothing can be isolated yet (the
            # ladder needs every token on the host): take this step
            # back, land that one, repair what this failure cost the
            # pools, and let the next iteration plan the step again
            self._unified_rollback(step)
            self._land("launch_failed")
            self._after_step_failure(e)
            return None
        # ---- what the next schedule pass must see of this step
        for req, _target, k, n, _last in chunks:
            req.prefill_pos = k + n
            req.chunks_done += 1
            step.chunk_no.append(req.chunks_done)
        step.launched = True
        with self._cond:
            for i, (req, _t, _k, _n, last) in enumerate(chunks):
                if not last:
                    continue
                if req in self._prefilling:
                    self._prefilling.remove(req)
                    self._active.append(req)
                    step.moved.append(req)
                if req.next_token is None:  # else: restored, it has one
                    step.out_index[id(req)] = i
        for i, r in enumerate(active):
            if id(r) not in step.leaving:
                step.out_index[id(r)] = nchunks + i
        step.riders = {id(r): r for r in
                       [c[0] for c in chunks] + active}
        if step.overlapped:
            _steps_overlapped.inc()
        # ``late``: the device had run dry before this launch (the
        # decoder asked the step in flight just before the program call;
        # a stand-in's record may not say: nothing was in flight then)
        _steps_launched.inc()
        if step.record.get("late", 1):
            _steps_late.inc()
        return step

    def _step_failed(self, step, error) -> None:
        """A unified step failed with nothing else in flight: unwind
        it, repair the pools, and go down the ladder with ITS rows."""
        self._unified_rollback(step)
        _unified_fallbacks.inc()
        # a device-side loss zeroed every survivor's KV: rebuild +
        # replay BEFORE the retry decodes over zeroed pages
        # (replay-dead requests are quarantined/ejected in here)
        self._after_step_failure(error)
        self._isolate_unified(step.chunks, step.active, error,
                              step.retried)

    def _commit_step(self, step) -> bool:
        """The second half of a unified step: fetch its outputs and do
        everything that depends on a token's value — ``generated``,
        ``next_token``, ``first_token_at``, the journal's rows,
        retirement, ``done``.  ``self._flight`` is the step dispatched
        over this one, if any: a request that retires here at its EOS
        with a row in that step keeps its pages until that step lands
        (:meth:`_retire_locked`), and a failure here unwinds that step
        first.  Returns False when the step failed (and the ladder has
        run)."""
        newer = self._flight
        try:
            out, accept = (step.result if step.flight is None else
                           self._decoder.ragged_fetch(step.flight))
            fetched_ns = _tracer.now_ns()
            self._step_started_at = None if newer is None else newer.t0
            self._check_wedged(step.t0)
        except BaseException as e:  # noqa: BLE001 — the ladder isolates
            self._step_started_at = None
            if newer is not None:
                self._flight = None
                self._decoder.ragged_discard(newer.flight)
                self._unified_rollback(newer)
            self._step_failed(step, e)
            return False
        _last_step_ts.set(time.time())
        chunks, active = step.chunks, step.active
        spec, k_spec, drafts = step.spec, step.k_spec, step.drafts
        nchunks = len(chunks)
        # the step's ONE interval, which no neighbour's overlaps: from
        # the later of its dispatch and the moment the step before it
        # reached the host, to the moment it did
        start_ns = max(step.t_ns, self._fetched_ns)
        self._fetched_ns = fetched_ns
        (_decode_step_s if active else _prefill_s).observe(
            (fetched_ns - start_ns) / 1e9)
        with monitor.span("engine/commit", into=self._host_s):
            traced = _tracer.enabled and step.traced
            if traced:
                # what the decoder says it dispatched: the (rows, span,
                # table) bucket against the real tokens and contexts.
                # Written when this iteration ends, with what the host
                # spent in it (:meth:`_flush_host_seconds`)
                self._ring_pending.append(
                    (step.index, start_ns, fetched_ns,
                     dict(step.record, overlapped=int(step.overlapped))))
            for name, value in step.record.items():
                if name in _STEP_SUMS:
                    _STEP_SUMS[name].inc(value)
                elif name.startswith("kv_bytes_copied_"):
                    _kv_bytes_copied.inc(
                        value, kind=name[len("kv_bytes_copied_"):])
                elif name == "kv_window_dead_pages":
                    _kv_window_dead_pages_g.set(value)
                elif name == "slots_zeroed":
                    _slots_zeroed.inc(value)
            # ---- chunk rows: the scheduler's count, the timeline, and
            # what a finished prefill owes (:meth:`_finish_prefill`)
            for i, (req, _target, k, n, last) in enumerate(chunks):
                self._sched.note_chunk(req)
                if traced:
                    _tracer.step_record(
                        "prefill_chunk", step.index, start_ns, fetched_ns,
                        request=req.request_id, tokens=n, pos=k,
                        cls=req.priority)
                    _tracer.request_event(req.request_id, "prefill_chunk",
                                          tokens=n, pos=k,
                                          chunk=step.chunk_no[i])
                if last:
                    self._finish_prefill(req, out[i], step.sampled)
            # ---- decode/verify rows: tokens, retirement, the journal's
            # rows (a row whose request met its EOS a step ago is dropped)
            dropped = step.dropped
            rows_at = [(nchunks + i, r) for i, r in enumerate(active)
                       if id(r) not in dropped]
            live = [r for _, r in rows_at]
            retired = []
            accepted_emitted = 0
            if live:
                jlens = {}
                for r in live:
                    jlens[id(r)] = len(r.generated) - (
                        id(r) not in step.deferred)
                    if id(r) in step.deferred:
                        # the token this step fed it: the host has had
                        # it since the step before was committed
                        r.generated.append(r.next_token)
                srows = []
                d_idx = ([i for i, r in enumerate(live) if r.use_draft]
                         if spec else [])
                for i, (at, r) in enumerate(rows_at):
                    if spec:
                        a = int(accept[at])
                        # page-granular partial rollback: the rejected
                        # positions' lengths unwind on BOTH caches; their
                        # pages stay mapped (inside the admission
                        # reservation) and later steps rewrite the slots
                        new_len = step.lens_before[r.seq_id][0] + a + 1
                        self.cache.truncate(r.seq_id, new_len)
                        if r.use_draft:
                            self.draft_cache.truncate(r.seq_id, new_len)
                        srows.append(_SpecRow(out[at], a,
                                              drafts[at - nchunks]))
                    else:
                        srows.append(out[at])
                if spec:
                    acc = [int(accept[rows_at[i][0]]) for i in d_idx]
                    self._last_spec = (k_spec * len(d_idx), sum(acc))
                    if d_idx:
                        _spec_proposed.inc(k_spec * len(d_idx))
                        _spec_accepted.inc(self._last_spec[1])
                        for a in acc:
                            _spec_accept_len.observe(a)
                        rejected = sum(a < k_spec for a in acc)
                        if rejected:
                            _spec_rollback.inc(rejected)
                    _spec_draft_pages.set(self.draft_cache.pinned_pages)
                else:
                    self._last_spec = (0, 0)
                if traced:
                    comp: dict = {}
                    for r in live:
                        comp[r.priority] = comp.get(r.priority, 0) + 1
                    prop, acc = self._last_spec
                    _tracer.step_record(
                        "decode", step.index, start_ns, fetched_ns,
                        batch=len(live), classes=comp,
                        spec_proposed=prop, spec_accepted=acc, poisoned=0,
                        requests=[r.request_id for r in live])
                _tokens_total.inc(len(live))
                on_device = self.sample_on_device
                still = []
                for r, row in zip(live, srows):
                    if _tracer.enabled:
                        if isinstance(row, _SpecRow):
                            _tracer.request_event(
                                r.request_id, "verify_step",
                                step=step.index, accept=int(row.accept))
                        else:
                            _tracer.request_event(r.request_id,
                                                  "decode_step",
                                                  step=step.index)
                    eos_hit = (r.eos_token_id is not None
                               and r.generated[-1] == r.eos_token_id)
                    if eos_hit or len(r.generated) >= r.max_new_tokens:
                        retired.append(r)
                        continue
                    if isinstance(row, _SpecRow):
                        done = False
                        for t in row.drafts[:row.accept]:
                            r.generated.append(int(t))
                            accepted_emitted += 1
                            if (r.eos_token_id is not None
                                    and int(t) == r.eos_token_id) \
                                    or len(r.generated) >= r.max_new_tokens:
                                done = True
                                break
                        if done:
                            retired.append(r)
                            continue
                        out_row = row.out
                    else:
                        out_row = row
                    r.next_token = (int(out_row) if on_device
                                    else self._pick(r, out_row))
                    still.append(r)
                if accepted_emitted:
                    _tokens_total.inc(accepted_emitted)
                if self.journal is not None:
                    for r in still:
                        self._jrows.append(
                            (r.request_id,
                             list(r.generated[jlens[id(r)]:]),
                             r.next_token))
            with monitor.span("engine/commit/retire", into=self._host_s), \
                    self._cond:
                if active:
                    self.steps += 1
                for r in retired:
                    self._retire_locked(r)
                for r in dropped.values():
                    self._release_pages_locked(r)
                if retired:
                    # the rows this step did not carry stay: a probe's
                    # siblings, the prefills finished by the steps since
                    gone = {id(r) for r in retired}
                    self._active = [r for r in self._active
                                    if id(r) not in gone]
                if active and not self._active:
                    self._free_pads_locked()
                self._cond.notify_all()
            if dropped:
                _overlap_dropped_rows.inc(len(dropped))
                step.dropped = {}
            if active:
                _active_seqs.set(len(self._active))
            for r in retired:
                r.done.set()
        return True

    def _isolate_unified(self, chunks, active, error, retried) -> None:
        """THE failure ladder, for every model: the failed step (rolled
        back, survivors replayed) runs whole once more — a transient
        fault, the common case after a preemption blip — and then by
        halves of its rows, chunk rows and decode rows alike, so healthy
        halves advance normally and only a row that fails alone is
        quarantined with the error that killed it.  O(k log n) extra
        dispatches for k poisoned rows in a step of n.  A probe is a
        step like any other (:meth:`_unified_step`): derived from
        request and cache state, so it draws the same samples and
        proposes the same drafts as the step it replays."""
        plan = [(c[0], c[3]) for c in chunks]
        n = len(plan) + len(active)
        if not retried:
            cuts = [(0, n)]
        elif n > 1:
            cuts = [(0, (n + 1) // 2), ((n + 1) // 2, n)]
        else:
            r = plan[0][0] if plan else active[0]
            with self._cond:
                if not r.done.is_set():
                    r.error = error
                    for lst in (self._active, self._prefilling):
                        if r in lst:
                            lst.remove(r)
                    _note_quarantine(r)
                    self._retire_locked(r)
                if not self._active:
                    self._free_pads_locked()
                self._cond.notify_all()
            r.done.set()
            return
        for a, b in cuts:       # the chunk rows first, as the step has them
            _decode_retries.inc()
            self._unified_step(
                plan[a:b], active[max(a - len(plan), 0):max(b - len(plan), 0)],
                retried=True)

    def _pick(self, req, logits_row) -> int:
        from .paged import sample_token
        return sample_token(logits_row, req.do_sample, req.temperature,
                            req.rng)

    def _release_draft_locked(self, req) -> None:
        """Caller holds ``self._cond``.  Free the request's draft-cache
        pages and return exactly the reservation they covered (the
        draft pool has no prefix index, so every freed page is truly
        free).  Idempotent via the per-request flag — downgrade and
        retirement may both reach here."""
        if not req._draft_reserved:
            return
        slack = (self._pages_for(req)
                 - len(self.draft_cache._seq_pages.get(req.seq_id, ())))
        released = self.draft_cache.free(req.seq_id)
        self._reserved_draft_pages -= slack + released
        req._draft_reserved = False

    def _downgrade_draft(self, reqs) -> None:
        """Speculation is an optimization: after a draft-side failure
        the affected requests keep decoding on the plain path instead
        of being quarantined.  Sticky for the request's lifetime (a
        desynced draft cache cannot rejoin lockstep mid-stream)."""
        _spec_draft_failures.inc(len(list(reqs)))
        with self._cond:
            for r in reqs:
                r.use_draft = False
                self._release_draft_locked(r)

    def _retire_locked(self, req):
        """Caller holds ``self._cond``.  Release the request's pages and
        exactly the reservation its retirement uncovers: the worst-case
        pages it never allocated, plus each held page that stopped being
        pinned (a shared page another live sharer still maps keeps its
        reservation — it transfers to that sharer's accounting)."""
        if self._rides(req):
            # it met its EOS with its next row already on the device:
            # that step writes into these pages and this slot, so they
            # go back when it lands (``_commit_step`` drops the row)
            self._flight.dropped[id(req)] = req
        else:
            self._release_pages_locked(req)
        req.finished_at = time.perf_counter()
        if req.error is None:
            _gen_latency_s.observe(req.finished_at - req.submitted_at)
        self._sched.note_retired(req)   # per-class TPOT (no-op on error)
        self._cache_result_locked(req)
        self._journal_retire(req)
        _tracer.request_event(
            req.request_id, "retire", ok=req.error is None,
            generated=len(req.generated),
            latency_s=round(req.finished_at - req.submitted_at, 6))

    def _release_pages_locked(self, req) -> None:
        """Caller holds ``self._cond``.  The capacity half of a
        retirement (see :meth:`_retire_locked`)."""
        slack = (self._pages_for(req)
                 - len(self.cache._seq_pages.get(req.seq_id, ())))
        released = self.cache.free(req.seq_id)
        self._reserved_pages -= slack + released
        if self._recurrent:
            _slots_in_use_g.set(self.cache.slots_in_use)
        self._release_draft_locked(req)

    def _bucket(self, n: int) -> int:
        from .paged import next_pow2
        return min(next_pow2(n), self.max_batch)

    # ------------------------------------------- crash recovery (ISSUE 8)
    def _pools_rebuilt(self) -> bool:
        """True exactly once per pool-rebuild event: compares the
        caches' ``generation`` counters (bumped by ``reset_pools``
        after a consumed donated buffer) against the last value the
        engine reconciled.  Scheduler-thread only."""
        g = self.cache.generation + (
            self.draft_cache.generation if self._spec else 0)
        if g == self._pool_gen:
            return False
        self._pool_gen = g
        return True

    def _replay_kv(self, req, upto=None, dlen=None) -> None:
        """THE replay primitive (ISSUE 8 tentpole): reconstruct one
        sequence's KV state by re-prefilling its token sequence —
        ``prompt + generated-so-far``, up to the CURRENT logical cache
        length — through the existing (chunked) context-prefill
        program, into the pages the sequence already maps (same
        (page, slot) plan, so shared prefix pages are rewritten with
        identical content whichever sharer replays first).

        Bit-exact by construction: prompt/generated are host state, the
        weights are unchanged, and the fused sampler draws by (seed,
        absolute position) — so the KV a replayed chunk writes is the
        KV the original prefill/decode wrote.  The pending
        ``next_token`` is host state too and is NOT resampled; replay
        outputs are discarded (argmax-only tail).  The draft cache is
        re-prefilled to its own length so the lockstep invariant
        survives the rebuild.

        ``upto``/``dlen`` override the replay targets — the batched
        path records them before truncating anything, so its per-row
        fallback can still replay a row a failed batched attempt left
        at a partial length."""
        sid = req.seq_id
        if upto is None:
            upto = self.cache.length(sid)
        if dlen is None:
            dlen = (self.draft_cache.length(sid)
                    if self._spec and req.use_draft else 0)
        if upto <= 0 and dlen <= 0:
            return                     # nothing resident yet
        sampling = _null_sampling() if self.sample_on_device else None
        if upto > 0 and self._replay_ragged:
            # a slot is zeroed by the chunk row that enters it at
            # context 0; the rest follow through the ragged program the
            # serving path runs (there is nothing to re-map)
            tokens = req.output_ids[:upto]
            self.cache.truncate(sid, 0)
            seeds, _, temps, flags = _null_sampling()
            for k in range(0, upto, self.prefill_chunk_tokens):
                self._step_started_at = time.monotonic()
                try:
                    _replay_dispatches.inc()
                    self._decoder.ragged_step(
                        self.cache, [sid],
                        [tokens[k:k + self.prefill_chunk_tokens]], [k],
                        sampling=((seeds, temps, flags)
                                  if self.sample_on_device else None))
                finally:
                    self._step_started_at = None
        elif upto > 0:
            tokens = req.output_ids[:upto]
            self.cache.truncate(sid, 0)
            chunk = self.prefill_chunk_tokens or upto
            k = 0
            while k < upto:
                n = min(chunk, upto - k)
                # the heartbeat must age during replay dispatches too:
                # a recovery that wedges on the still-sick device has
                # to be as visible to the watchdog as the step that
                # triggered it (the stale flag is cleared at the next
                # step's start, so a slow replay never condemns it)
                self._step_started_at = time.monotonic()
                try:
                    _replay_dispatches.inc()
                    self._ingest(self._decoder, self.cache, sid, tokens,
                                 k, n, sampling)
                finally:
                    self._step_started_at = None
                k += n
            if self.prefix_cache and upto >= len(req.prompt):
                # re-seed the prefix index the pool rebuild dropped:
                # the entry's page refcounts come back with it
                self.cache.register_prefix(sid, req.prompt)
        if dlen > 0:
            # the draft pool rides in lockstep — rebuild its KV to its
            # own pre-loss length from the same host-side tokens
            self.draft_cache.truncate(sid, 0)
            self._step_started_at = time.monotonic()
            try:
                _replay_dispatches.inc()
                self._draft_decoder.prefill(
                    self.draft_cache, [sid], req.output_ids[None, :dlen],
                    bucket=True, sampling=sampling)
            finally:
                self._step_started_at = None
        _survivor_replays.inc()
        _tracer.request_event(req.request_id, "replay",
                              tokens=int(upto), draft_tokens=int(dlen))

    def _replay_kv_batch(self, rows, targets) -> None:
        """Batched survivor replay (ISSUE 9 satellite, ROADMAP crash-
        consistency follow-up (c)): reconstruct MANY survivors' KV in
        lockstep chunk rounds — each round ingests up to a chunk budget
        per row for up to ``max_batch`` rows in ONE compiled dispatch
        through the decoder's batched context-prefill program (per-row
        context lengths are traced, so mixed-progress rows share the
        dispatch).  For continuation chunks (k > 0) this is the SAME
        traced "prefix" program the per-row path compiles — only the
        dispatch count changes, which is the MTTR lever on
        many-survivor pools.  Caveat carried with the TPU capture
        window: a row's FIRST chunk originally ingested through the
        "prefill" program (flash attention), while the batched k == 0
        round runs the prefix program's dense masked attention — on
        CPU both lower to identical XLA math (tier-1 locks the
        bit-exactness), on real TPU the two kernels' accumulation
        orders may differ in ulps, so hardware replay exactness must
        be re-verified there (``replay_batch=False`` restores the
        per-row path, whose k == 0 chunk uses the original prefill
        program).

        ``targets`` maps ``id(req)`` to the (upto, dlen) lengths
        recorded BEFORE any truncation; any failure propagates to the
        caller, which falls back to per-row replay for exact
        quarantine isolation."""
        def collect(cache, which):
            out = []
            for r in rows:
                upto = targets[id(r)][which]
                if upto > 0:
                    out.append((r, r.output_ids[:upto], upto))
                    cache.truncate(r.seq_id, 0)
            return out

        def rounds(decoder, cache, work, chunk):
            """ONE lockstep-round loop for both pools: up to max_batch
            rows per batched dispatch, each ingesting up to a chunk
            budget, dropping out as it reaches its target length."""
            cursor = {id(r): 0 for r, _, _ in work}
            pending = list(work)
            while pending:
                batch = pending[:self.max_batch]
                sids = [r.seq_id for r, _, _ in batch]
                ks = [cursor[id(r)] for r, _, _ in batch]
                slices = [toks[k:k + min(chunk or upto, upto - k)]
                          for (r, toks, upto), k in zip(batch, ks)]
                self._step_started_at = time.monotonic()
                try:
                    _replay_dispatches.inc()
                    decoder.batch_context_prefill(
                        cache, sids, slices, ks,
                        sampling=(_null_sampling(len(sids))
                                  if self.sample_on_device else None))
                finally:
                    self._step_started_at = None
                for (r, toks, upto), sl in zip(batch, slices):
                    cursor[id(r)] += len(sl)
                pending = [(r, toks, upto) for r, toks, upto in pending
                           if cursor[id(r)] < upto]

        chunk = self.prefill_chunk_tokens
        work = collect(self.cache, 0)
        rounds(self._decoder, self.cache, work, chunk)
        for r, toks, upto in work:
            if self.prefix_cache and upto >= len(r.prompt):
                self.cache.register_prefix(r.seq_id, r.prompt)
        # draft pools ride in lockstep: batched rounds over the draft
        # decoder's batched program (context starts at 0 — the draft
        # always holds whole prompts)
        dwork = collect(self.draft_cache, 1) if self._spec else []
        if dwork:
            rounds(self._draft_decoder, self.draft_cache, dwork, chunk)
        done = {id(r) for r, _, _ in work} | {id(r) for r, _, _ in dwork}
        _survivor_replays.inc(len(done))
        if _tracer.enabled:
            seen = set()
            for r, _, _ in work + dwork:
                if id(r) in seen:
                    continue
                seen.add(id(r))
                _tracer.request_event(
                    r.request_id, "replay", batched=True,
                    tokens=int(targets[id(r)][0]),
                    draft_tokens=int(targets[id(r)][1]))

    def _replay_survivors(self, exclude=()) -> List[_Request]:
        """Device-failure recovery (ISSUE 8 consumer 1): replay every
        live sequence — active, mid-prefill and preempted — to its
        current logical length after a pool rebuild zeroed the device
        KV.  ``exclude`` names requests about to be quarantined (their
        replay would be wasted work).  Scheduler-thread only: the three
        lists are stable while the loop thread is here.

        A replay that ITSELF fails (the device fault is pinned to that
        sequence) marks the request with the error and returns it for
        quarantine — one unreconstructible row must never fail the
        engine; if the failed replay consumed the pools again, the
        whole pass restarts so earlier survivors are re-replayed over
        the fresh pools (bounded: every restart removes a row).

        With ``replay_batch`` (the default everywhere but TPU, where
        the batched round's kernel swap is not yet hardware-verified
        bit-exact) survivors replay in
        BATCHED lockstep rounds — many rows per compiled dispatch
        (ISSUE 9 satellite; the MTTR lever).  A failed batched dispatch
        cannot name the poisoned row, so it falls back to the per-row
        pass, which preserves exact quarantine isolation."""
        skip = {id(r) for r in exclude}
        failed: List[_Request] = []

        def eligible():
            return [r for r in (self._active + self._prefilling
                                + self._preempted)
                    # r.error covers rows an EARLIER recovery in this
                    # same step already condemned (their done event is
                    # only set at step end) — never re-replay one
                    if id(r) not in skip and r.seq_id is not None
                    and not r.done.is_set() and r.error is None]

        # replay targets recorded BEFORE any truncation: the batched
        # path's per-row fallback must know the full lengths even after
        # a mid-round failure left a row partially re-ingested
        targets = {id(r): (self.cache.length(r.seq_id),
                           (self.draft_cache.length(r.seq_id)
                            if self._spec and r.use_draft else 0))
                   for r in eligible()}
        batched = self.replay_batch
        while True:
            restart = False
            rows = eligible()
            if batched and len(rows) > 1:
                try:
                    self._replay_kv_batch(rows, targets)
                    break
                except BaseException:  # noqa: BLE001 — isolate per row
                    batched = False
                    self._pools_rebuilt()   # reconcile a mid-batch loss
                    continue
            for r in rows:
                try:
                    self._replay_kv(r, *targets[id(r)])
                except BaseException as e:  # noqa: BLE001 — per-row
                    r.error = e
                    skip.add(id(r))
                    failed.append(r)
                    if self._pools_rebuilt():
                        restart = True
                        break
            if not restart:
                break
        return failed

    def _after_step_failure(self, error=None, exclude=()) -> None:
        """Recovery hook run after ANY failed (or wedged) step/chunk
        was rolled back: a wedge rebuilds the pools outright
        (consumer 2 — the watchdog-driven restart); then, if the pools
        were rebuilt by anyone (here, or the decoder after a REAL
        donated-buffer loss), every survivor's KV is replayed before
        the caller retries — so a retry/bisect never decodes over
        zeroed pages and quarantine stays per-request for device-side
        failures too.

        Requests whose own replay failed are quarantined and retired
        here (the failed step was rolled back first, so none of them
        carries an un-executed token)."""
        if isinstance(error, _EngineWedged):
            self.cache.reset_pools()
            if self._spec:
                self.draft_cache.reset_pools()
        if not self._pools_rebuilt():
            return
        _rebuilds_total.inc()
        t_tr = _tracer.now_ns() if _tracer.enabled else 0
        with monitor.span("engine/recovery", histogram=_recovery_s):
            failed = self._replay_survivors(exclude=exclude)
        if _tracer.enabled and t_tr:
            _tracer.step_record(
                "recovery", self.steps, t_tr, _tracer.now_ns(),
                wedged=isinstance(error, _EngineWedged),
                replay_failed=len(failed))
        if not failed:
            return
        with self._cond:
            for r in failed:
                for lst_name in ("_active", "_prefilling", "_preempted"):
                    lst = getattr(self, lst_name)
                    if r in lst:
                        lst.remove(r)
                # quarantine BEFORE retire: terminal timeline event
                # stays 'retire' at every ejection site
                _note_quarantine(r)
                self._retire_locked(r)
            self._cond.notify_all()
        for r in failed:
            r.done.set()

    def _check_wedged(self, started_at: Optional[float] = None) -> None:
        """Consume the watchdog's wedge flag: raised as a step failure
        so the retry/bisect ladder (plus ``_after_step_failure``'s
        rebuild) handles it like any other suspect step.

        ``started_at`` guards against a STALE fire: the watchdog reads
        the heartbeat age and invokes ``on_timeout`` as two separate
        actions, so a fire aimed at a slow dispatch (e.g. a recovery
        replay compiling a program) can be delivered AFTER the next
        dispatch already cleared the flag — and without this guard
        that fresh dispatch would be condemned, quarantining a healthy
        single-row batch on its second "failure".  A dispatch that ran
        for less than ``step_timeout_s`` provably did not wedge."""
        if not self._wedged.is_set():
            return
        self._wedged.clear()
        if started_at is not None and self.step_timeout_s is not None \
                and time.monotonic() - started_at \
                <= float(self.step_timeout_s):
            return                   # stale fire: not this dispatch
        raise _EngineWedged(
            "decode step exceeded the watchdog heartbeat timeout; "
            "treating its results as suspect")

    def _fail_all(self, exc):
        """LAST-RESORT scheduler-fault handler (isolation failed or the
        fault was outside any step): error out every in-flight request
        WITHOUT leaking pool capacity — sequences that already own
        pages are freed and their reservations rolled back, so the
        engine stays usable."""
        with self._cond:
            queued = self._sched.pop_all()
            holders = self._active + self._prefilling + self._preempted
            # a step in flight is abandoned with its rows (they are among
            # the holders); one it dropped is retired and holds pages only
            zombies = self._abandon_flight_locked()
            for r in holders + queued:
                if r.done.is_set():
                    continue
                if r.finished_at is not None:
                    # retired successfully earlier THIS step (its
                    # done.set() is deferred to the end of the commit):
                    # deliver the completed generation, don't error it
                    r.done.set()
                    continue
                r.error = exc
                self._cache_result_locked(r)
                # the error IS delivered to the waiter — terminal, so
                # the journal must not resurrect it after a restart
                self._journal_retire(r)
                r.done.set()
            for r in holders + zombies:
                if r.seq_id is not None:
                    self.cache.free(r.seq_id)
                    if self._spec:
                        self.draft_cache.free(r.seq_id)
                    r._draft_reserved = False
            self._free_pads_locked()
            self._reserved_pages = self._pad_pages   # only pad headroom
            self._reserved_draft_pages = self._pad_pages
            self._active = []
            self._prefilling = []
            self._preempted = []
            _active_seqs.set(0)
            _queue_depth.set(0)
            self._cond.notify_all()

    def _abandon_flight_locked(self) -> List[_Request]:
        """Caller holds ``self._cond``.  Forget the step in flight
        without committing it (hard stop, last-resort failure): the
        device finishes it on its own and nobody reads it.  Returns the
        requests it had dropped, which are retired and only wait for
        their pages to go back."""
        step, self._flight = self._flight, None
        self._step_started_at = None
        if step is None:
            return []
        zombies, step.dropped = list(step.dropped.values()), {}
        return zombies

    def _flush_host_seconds(self, gc_ns: int) -> None:
        """Scheduler thread, end of one iteration: move the phase
        counters by what the iteration's spans added to ``_host_s`` (the
        one place they move: a dozen labelled increments an iteration
        and none a span), and write the ``dispatch`` records of the
        steps it committed, each with ``host_work_ns`` — the work phases
        of THIS iteration, the one whose span is ``engine/step <index>``
        of the record's index; no waiting — and ``gc_ns``, the collector
        pauses inside it on any thread.  (``late`` and
        ``prefix_evicted`` on the record are of the step's own
        launch, an iteration earlier when it was overlapped.)"""
        work = 0.0
        for name, seconds in self._host_s.items():
            counted, label = _HOST_SECONDS[name]
            counted.inc(seconds, **label)
            if counted is _host_work_s:
                work += seconds
        self._host_s.clear()
        for index, start_ns, end_ns, record in self._ring_pending:
            _tracer.step_record("dispatch", index, start_ns, end_ns,
                                host_work_ns=int(work * 1e9), gc_ns=gc_ns,
                                **record)
        self._ring_pending.clear()

    def _loop(self):
        while True:
            with self._cond:
                while not self._stop and not len(self._sched) \
                        and not self._active and not self._prefilling \
                        and not self._preempted and self._flight is None:
                    # brownout is a property of LOAD: an engine with
                    # nothing queued and nothing running is not
                    # browned out, whatever the ladder last latched —
                    # without this, a drained engine would keep
                    # shedding the first arrivals of the next burst
                    if self._brownout:
                        self._set_brownout_locked(0, 0.0)
                    with monitor.span("engine/wait", into=self._host_s):
                        self._cond.wait(timeout=0.5)
                if self._stop:
                    for r in self._abandon_flight_locked():
                        self._release_pages_locked(r)
                    self._free_pads_locked()
                    stopped = (self._sched.pop_all() + self._prefilling
                               + self._preempted + self._active)
                    self._prefilling = []
                    self._preempted = []
                    self._active = []
                    for r in stopped:
                        r.error = RuntimeError("engine stopped")
                        self._cache_result_locked(r)
                        r.done.set()
                    return
            # one iteration is one ``engine/step <index>`` span on the
            # profiler's clock, <index> being the ``index`` of the
            # step-ring records of the step it COMMITS (with a step in
            # flight it dispatches the one after that)
            with monitor.span(f"engine/step {self.steps}"):
                self._iteration()

    def _iteration(self) -> None:
        """One pass of the scheduler thread: the scheduling pass under
        the lock (``engine/schedule``), the device work outside it, the
        journal flush (``engine/commit``).  With a step in flight
        (:meth:`_pipeline`) the pass plans the step AFTER it, from what
        its launch told the scheduler; it is committed first instead
        where :meth:`_overlap_hold` says so, where one of its requests
        is about to be reaped (:meth:`_riders_hold`) or paused
        (:class:`_LandFirst`)."""
        reaped: List[_Request] = []
        gc_ns = gc_pause_ns()
        try:
            if self._flight is not None:
                why = self._overlap_hold() or self._riders_hold()
                if why is not None:
                    self._land(why)
            while True:
                try:
                    with monitor.span("engine/schedule",
                                      into=self._host_s), self._cond:
                        reaped += self._reap_locked()
                        # closed-loop overload protection (ISSUE 19): one
                        # controller evaluation per iteration — the ladder
                        # first (its level gates this iteration's sheds),
                        # then the TPOT trigger (its freed slot is visible
                        # to the admission pass below)
                        self._update_brownout_locked()
                        self._tpot_preempt_locked()
                        self._admit_locked()
                        plan = self._plan_chunks_locked()
                        # snapshot barrier (ISSUE 8): a waiting snapshot()
                        # reads its consistent between-steps cut before the
                        # next device batch opens (the wait releases the
                        # lock; nothing below mutates what was planned).
                        # With a step in flight there is no such cut yet:
                        # the next pass lands it first (``snapshot`` hold)
                        while self._snap_waiters and not self._stop \
                                and self._flight is None:
                            self._cond.wait(0.1)
                        self._stepping = (bool(plan) or bool(self._active)
                                          or self._flight is not None)
                    break
                except _LandFirst as first:
                    self._land(first.reason)
        except BaseException as e:  # noqa: BLE001 — scheduler fault
            # a bug in admission/reaping must fail the in-flight
            # requests LOUDLY, never kill this thread silently and
            # leave every waiter blocked on a dead engine
            self._fail_all(e)
            return
        finally:
            for r in reaped:
                r.done.set()
        # TPOT signal (ISSUE 19): for an active row one iteration
        # is one output token, so the whole iteration's wall time —
        # chunks included — is the per-token latency the budget is
        # judged against.  Scheduler-thread only, like _disp_n.
        had_active = bool(self._active)
        t_iter = time.perf_counter()
        try:
            # unified ragged step (ISSUE 17): the chunk plan's
            # spans + every active row in ONE compiled dispatch
            if self.prefill_chunk_tokens is None and plan:
                # unchunked: full-prompt spans would give the
                # ragged program an unbounded (rows, max-span)
                # bucket space — every novel prompt length a
                # recompile.  Whole-prompt prefill stays on the
                # length-bucketed programs and only the active
                # rows (span 1 or k+1: bounded) go into the
                # ragged dispatch.
                self._run_chunks(plan)     # device work: outside lock
                plan = ()
            self._pipeline(plan)
        except BaseException as e:  # noqa: BLE001 — fail loudly, not hang
            self._fail_all(e)
        finally:
            if had_active:
                dt = time.perf_counter() - t_iter
                self._step_ewma = (dt if self._step_ewma is None
                                   else 0.7 * self._step_ewma
                                   + 0.3 * dt)
            # ISSUE 13: the iteration's coalesced journal record —
            # admitted ids + per-row emissions — enqueued ONCE per
            # loop pass (rows for requests _fail_all just retired
            # are ignored at replay: their retire precedes them)
            with monitor.span("engine/commit", into=self._host_s), \
                    monitor.span("engine/commit/journal",
                                 into=self._host_s):
                self._journal_flush_step()
            self._flush_host_seconds(gc_pause_ns() - gc_ns)
            # the snapshot barrier's flag stays up while a step is in
            # flight: there is no between-steps cut to read
            stepping = self._flight is not None
            if self._stepping != stepping:
                with self._cond:
                    self._stepping = stepping
                    self._cond.notify_all()
