"""Serving hot-path benchmark (ISSUE 2 CI satellite).

Drives a ContinuousBatchingEngine with a mixed shared-prefix workload —
one warm-up request seeds the prefix cache, then a wave of requests
that share its system prefix interleaved with fully-unique prompts —
and prints ONE JSON line with tokens/sec, TTFT p50/p99, decode-step
p50, and the prefix-cache hit rate, every number read from
``monitor.snapshot()`` deltas (the monitor registry is the single
source of serving truth; no ad-hoc timers).

``--baseline`` runs the same workload with ``sample_on_device=False,
prefix_cache=False`` — diffing the two JSON lines is the before/after
evidence for the hot-path PR.  Exit 0 = ran and (non-baseline) saw a
nonzero prefix hit rate; 1 = broken.  tests/test_tools.py runs main()
as a tier-1 gate, `python tools/serve_bench.py` is the standalone lane.

Speculative lane (ISSUE 6): ``--draft`` serves the same workload
through the engine's speculative path — the draft is a CLONE of the
target degraded by ``--draft-noise=<sigma>`` weight noise, so the
acceptance rate is a turnable knob (0.0 = perfect draft, accept ~1.0).
``--sweep`` emits one JSON line per noise level plus a no-draft
baseline, turning accept-rate vs tokens/sec vs TTFT into a curve; all
numbers are monitor.snapshot() deltas (``spec_*`` counters + the
``spec_accept_len`` histogram) and the measured window still gates
``jit_recompiles == 0``.

Recovery lane (ISSUE 8): a ``--fault-plan`` containing ``buffer_loss``
or ``engine_wedge`` rules exercises crash-consistent recovery — the
JSON line carries ``survivor_replays`` / ``engine_rebuilds`` and the
MTTR (``engine_recovery_seconds`` p50/mean), and the gate requires the
recovery machinery to have engaged with every survivor completing
(failed requests within the injected-error budget; recompiles inside
the declared rebuild window are exempt from the steady-state gate).

Journal overhead lane (ISSUE 13): ``--journal`` runs the workload
with the write-ahead request journal off then on (``interval_ms``
fsync policy, tempdir segments) and quotes decode p50 with journaling
beside without — the WAL is enqueue-only on the engine threads, so the
hot path should not notice it — gating records and bytes written, the
same tokens generated and ``jit_recompiles == 0`` in both measured
windows, with ``journal_bytes`` / ``journal_records`` /
``journal_fsync_p50`` in the JSON line.

Scenario-matrix lane (ISSUE 7): ``--scenario-matrix`` serves the
three-way mixed workload — chat (short, latency-bound, interactive
class), RAG (long shared-prefix prompt, standard class) and
offline-batch (8x-chunk long prompts, preemptible batch class) —
through the heterogeneous-workload scheduler, emitting one JSON line
per class (TTFT p50/p99, TPOT, queue wait, preemptions — all labeled
monitor deltas) plus a summary line gating: under the long-prompt
flood every chat request has its first token before the first flood
request is done, and in the unchunked FIFO run none has (the stall
chunking removes; chat TTFT by lane is printed alongside),
``jit_recompiles == 0`` in every measured window, the chunked-prefill
program audited transfer-free, and batch-class preemption exercised.

Overload lane (ISSUE 19): ``--overload`` drives a 3x interactive burst
into a batch-saturated engine with the closed-loop controllers on
(SLO-aware admission + brownout ladder + decode-time preemption) and
off, one JSON line per class — gating controlled interactive SLO
attainment >= 0.95 while batch arrivals shed with truthful 429s, the
no-controller baseline breaching the same SLO, and both measured
windows compile-free.  ``--overload-fleet`` runs sustained overload
against a 1-replica fleet: the autoscaler spawns a replica under
pressure, the scaled fleet serves a compile-free window, and calm
drains it back to the floor with zero failed requests.

Mixed-batch dispatch lane (ISSUE 17): the scenario matrix prints a
``mixed-batch-unified`` JSON line for the flood workload quoting
tokens/s, per-class TTFT/TPOT and the ``engine_dispatches_total`` mode
split, gating that the window is single-program (ragged-mode dispatches
only, zero fallbacks).
"""
from __future__ import annotations

import contextlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _find_series(snap: dict, name: str, labels):
    m = snap.get(name)
    if not m:
        return None
    for s in m["series"]:
        if labels is None or s.get("labels", {}) == labels:
            return s
    return None


def _hist_delta(before: dict, after: dict, name: str, labels=None):
    """(bucket_delta {le: count}, sum_delta, count_delta) for a
    histogram between two monitor.snapshot() dicts.  ``labels`` picks
    one labeled series (e.g. ``{"cls": "interactive"}`` for the
    per-class SLO histograms); None takes the first/only series."""
    def series(snap):
        s = _find_series(snap, name, labels)
        if s is None:
            return {}, 0.0, 0
        return s["buckets"], s["sum"], s["count"]

    b0, s0, c0 = series(before)
    b1, s1, c1 = series(after)
    buckets = {le: c - b0.get(le, 0) for le, c in b1.items()}
    return buckets, s1 - s0, c1 - c0


def _counter_delta(before: dict, after: dict, name: str,
                   labels=None) -> float:
    def val(snap):
        if labels is None:      # the metric's total, whatever its series
            return sum(s["value"] for s in
                       (snap.get(name) or {}).get("series", ()))
        s = _find_series(snap, name, labels)
        return s["value"] if s else 0.0
    return val(after) - val(before)


def hist_quantile(buckets: dict, q: float):
    """Prometheus-style histogram_quantile over CUMULATIVE {le: count}
    deltas: the upper bound of the first bucket at or past the
    quantile rank (None if the histogram saw nothing)."""
    total = buckets.get("+Inf", 0)
    if total <= 0:
        return None
    finite = sorted(((float(le), c) for le, c in buckets.items()
                     if le != "+Inf"))
    rank = q * total
    for bound, cum in finite:
        if cum >= rank:
            return bound
    return finite[-1][0] if finite else None


# the mixed workload's fixed prompt lengths (suffix bucket 8,
# cold-prompt bucket 32) and the bench page size — module-level because
# run_quant_lane's capacity arithmetic must reuse the EXACT values
# run_bench builds the workload from, or the gated capacity ratio is
# computed for a different workload than the one actually run
PAGE_SIZE = 8
SUF_TOKENS, UNIQ_TOKENS = 5, 20


def run_bench(model=None, sharers: int = 6, uniques: int = 3,
              max_new_tokens: int = 8, system_tokens: int = 16,
              vocab: int = 64, hidden: int = 32, do_sample: bool = False,
              sample_on_device: bool = True,
              prefix_cache: bool = True, seed: int = 0,
              fault_plan=None, draft: bool = False, spec_k: int = 3,
              draft_noise: float = 0.0, draft_model=None,
              quantize=None, kv_quant=None, total_pages: int = 128,
              replay_batch=None, journal_dir=None,
              journal_fsync: str = "interval_ms",
              tp: int = 1, tp_quant_collectives: bool = False) -> dict:
    """Run the mixed shared-prefix workload; return the metrics dict
    (everything monitor-sourced).  The tiny default model keeps the CI
    gate fast; ``--vocab``/``--hidden`` grow it so the host-boundary
    cost the fused sampler removes is actually visible.

    ``fault_plan`` (ISSUE 4): a ``paddle_tpu.testing.faults`` plan
    (dict/JSON/FaultPlan) installed for the MEASURED wave only — the
    chaos lane proving throughput recovers after injected failures,
    with the quarantine/retry counters quoted from the same
    ``monitor.snapshot()`` deltas as everything else.

    ``draft`` (ISSUE 6): speculative lane — the draft model is a clone
    of the target with ``draft_noise``-sigma Gaussian weight noise, so
    acceptance degrades continuously from ~1.0 at noise 0 (callers may
    pass an explicit ``draft_model`` instead).

    ``journal_dir`` (ISSUE 13): attach a write-ahead request journal
    (``journal_fsync`` policy) to the engine for the whole run — the
    overhead lane (``--journal``) compares decode p50 with it on vs
    off and quotes ``journal_bytes``/``journal_fsync_p50``."""
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.testing import faults

    # compile telemetry (ISSUE 3): the measured window of a warm serving
    # loop should show ZERO recompiles — a nonzero delta here means a
    # bucket/shape leak the program auditor should be pointed at
    monitor.install_compile_hooks()

    # a plan with engine_wedge rules needs the watchdog ARMED: the
    # wedge path only exists through the step_timeout_s heartbeat (use
    # delay_s comfortably above the 0.25s threshold in such plans)
    if fault_plan is not None and not isinstance(fault_plan,
                                                 faults.FaultPlan):
        fault_plan = faults.FaultPlan.from_json(fault_plan)
    wedge_plan = fault_plan is not None and any(
        r.site == "engine_wedge" for r in fault_plan.rules)
    step_timeout_s = 0.25 if wedge_plan else None

    @contextlib.contextmanager
    def _fast_watchdog_scan():
        """Temporarily speed the (process-wide) watchdog scan so the
        wedge lane's heartbeat fires within the bench's time scale —
        restored on every exit path, since test_tools runs this lane
        in-process alongside timing-sensitive suites."""
        if not wedge_plan:
            yield
            return
        from paddle_tpu.distributed.watchdog import CommTaskManager
        mgr = CommTaskManager.instance()
        prev = mgr._scan_interval
        mgr._scan_interval = 0.05
        try:
            yield
        finally:
            mgr._scan_interval = prev

    draft_built = False
    if model is None:
        import paddle_tpu as paddle
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

        def build():
            paddle.seed(0)
            cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                              intermediate_size=2 * hidden,
                              num_hidden_layers=2,
                              num_attention_heads=4, num_key_value_heads=2,
                              max_position_embeddings=128)
            return LlamaForCausalLM(cfg)

        model = build()
        if draft and draft_model is None:
            draft_model = build()        # same seed -> identical weights
            draft_built = True
            if draft_noise:
                # degrade ONLY the bench-built clone — a caller-supplied
                # draft_model is never mutated
                import jax.numpy as jnp
                nrng = np.random.default_rng(1234)
                for p in draft_model.parameters():
                    a = p._data
                    p._data = a + jnp.asarray(
                        nrng.normal(0.0, draft_noise, a.shape), a.dtype)
    if draft and draft_model is None:
        raise ValueError("--draft with an explicit model needs an "
                         "explicit draft_model too")
    if draft and draft_noise and not draft_built:
        raise ValueError("draft_noise only degrades the bench-built "
                         "clone; pre-degrade an explicit draft_model "
                         "yourself")

    rng = np.random.default_rng(seed)
    # the shared system prompt must cover full pages (PAGE_SIZE below)
    system = rng.integers(0, 64, (system_tokens,)).astype("int32")
    # fixed lengths so the warm-up wave compiles the EXACT bucket shapes
    # the measured wave runs: the measured window then holds
    # steady-state serving, not compiles

    def shared_prompt():
        return np.concatenate(
            [system,
             rng.integers(0, 64, (SUF_TOKENS,))]).astype("int32")

    def unique_prompt():
        return rng.integers(0, 64, (UNIQ_TOKENS,)).astype("int32")

    n_sub = [0]

    def submit(eng, prompt):
        n_sub[0] += 1
        return eng.submit(prompt, max_new_tokens=max_new_tokens,
                          do_sample=do_sample, temperature=0.8,
                          seed=n_sub[0])

    MAX_BATCH = 4
    failed = 0
    journal = None
    j_before = None
    # the journal closes when this stack unwinds — AFTER the engine
    # stops (outermost context), and on error paths too, so a failing
    # bench never leaks the writer thread into later in-process lanes
    jstack = contextlib.ExitStack()
    if journal_dir is not None:
        from paddle_tpu.inference.journal import RequestJournal
        j_before = monitor.snapshot()    # journal-lifetime fsync stats
        journal = jstack.enter_context(
            RequestJournal(journal_dir, fsync=journal_fsync))
    with jstack, _fast_watchdog_scan(), ContinuousBatchingEngine(
            model, total_pages=total_pages, page_size=PAGE_SIZE,
            max_batch=MAX_BATCH,
            sample_on_device=sample_on_device,
            prefix_cache=prefix_cache,
            draft_model=draft_model if draft else None,
            spec_tokens=spec_k, step_timeout_s=step_timeout_s,
            quantize=quantize, kv_quant=kv_quant,
            replay_batch=replay_batch, journal=journal,
            tp=tp, tp_quant_collectives=tp_quant_collectives) as eng:
        # None inherits the engine's backend-aware default (batched
        # everywhere but TPU); report what actually ran
        replay_batch = eng.replay_batch
        # unmeasured warm-up: compiles the cold-prefill and suffix
        # (prefix-hit) prefill and seeds the prefix cache with the
        # system prompt (sequenced: the second sharer must be admitted
        # AFTER the first's prefill registered the system prefix, or it
        # misses and the suffix-prefill program stays uncompiled)
        submit(eng, shared_prompt()).result(timeout=600)
        warm = [submit(eng, p)
                for p in (shared_prompt(), unique_prompt())]
        for r in warm:
            r.result(timeout=600)
        # ... then a full-batch wave so EVERY decode-batch bucket
        # (1, 2, ..., max_batch) is compiled before the window opens:
        # the waves above covered buckets 1-2, this one reaches
        # max_batch while its stragglers retire through the lower
        # buckets again — the measured window must show ZERO compiles
        # (the ROADMAP telemetry finding this closes)
        wave = [submit(eng, shared_prompt() if i % 2 == 0
                       else unique_prompt()) for i in range(MAX_BATCH)]
        for r in wave:
            r.result(timeout=600)

        before = monitor.snapshot()
        if fault_plan is not None:
            fault_plan = faults.install(fault_plan)
        try:
            reqs = []
            for i in range(max(sharers, uniques)):
                if i < sharers:
                    reqs.append(submit(eng, shared_prompt()))
                if i < uniques:
                    reqs.append(submit(eng, unique_prompt()))
            for r in reqs:
                try:
                    r.result(timeout=600)
                except Exception:   # noqa: BLE001 — poisoned by the plan
                    if fault_plan is None:
                        raise       # no plan: a failure is a real bug
                    failed += 1
        finally:
            if fault_plan is not None:
                faults.clear()
        after = monitor.snapshot()
        # cost/MFU accounting (ISSUE 10): price the decode program the
        # window actually dispatched — a jaxpr trace, no compile, run
        # AFTER the measured window closes so the recompile gate is
        # untouched.  flops / max_batch is the per-token cost; the
        # window's achieved FLOP/s over the configured peak is the MFU
        # every future BENCH round quotes for free.
        from paddle_tpu.analysis import cost as _cost
        # distributed audit (ISSUE 11): static peak HBM + priced
        # collectives of the SAME decode program, published as
        # program_peak_hbm_bytes / collective_bytes_total /
        # ici_time_seconds (jaxpr tier; the CPU lane's mesh-of-1
        # prices to zero ICI, which is the correct verdict).  One
        # trace serves both tiers: the audit carries its CostEstimate.
        from paddle_tpu.analysis import spmd as _spmd
        spmd_audit = _spmd.audit_spmd_engine(eng, mode="decode",
                                             compiled=False)
        cost_est = spmd_audit.cost
        cost_est.publish()
        kv_pool_bytes = eng.cache.kv_pool_bytes
        kv_pool_bytes_per_chip = eng.cache.kv_pool_bytes_per_chip

    # the with-exit above closed the journal (final flush + fsync)
    dec_b, dec_sum, dec_n = _hist_delta(before, after,
                                        "decode_step_seconds")
    ttft_b, ttft_sum, ttft_n = _hist_delta(before, after,
                                           "time_to_first_token_seconds")
    pre_b, pre_sum, pre_n = _hist_delta(before, after, "prefill_seconds")
    tokens = _counter_delta(before, after, "generated_tokens_total")
    _, compile_sum, compile_n = _hist_delta(before, after,
                                            "jit_compile_seconds")
    lookups = _counter_delta(before, after, "prefix_cache_lookups_total")
    hits = _counter_delta(before, after, "prefix_cache_hits_total")
    hit_tokens = _counter_delta(before, after,
                                "prefix_cache_hit_tokens_total")
    sp = _counter_delta(before, after, "spec_proposed_tokens_total")
    sa = _counter_delta(before, after, "spec_accepted_tokens_total")
    sr = _counter_delta(before, after, "spec_rollback_total")
    _, al_sum, al_n = _hist_delta(before, after, "spec_accept_len")
    # recovery lane (ISSUE 8): the crash-consistency machinery's
    # footprint in the measured window — replay/rebuild counts and the
    # MTTR (engine_recovery_seconds p50, one observation per recovery
    # event covering pool rebuild + every survivor's replay)
    rec_b, rec_sum, rec_n = _hist_delta(before, after,
                                        "engine_recovery_seconds")
    # journal overhead lane (ISSUE 13): bytes/records are the
    # measured-window footprint (the hot-path overhead evidence); the
    # fsync histogram spans the journal's whole lifetime including the
    # close-time final fsync — the tiny CI wave can finish inside one
    # interval_ms period, and the durability COST is per-fsync, not
    # per-window
    jb = _counter_delta(before, after, "journal_bytes")
    jr = _counter_delta(before, after, "journal_records_total")
    jf_b, _, jf_n = _hist_delta(
        j_before if j_before is not None else before,
        monitor.snapshot() if journal is not None else after,
        "journal_fsync_seconds")
    flops_per_token = cost_est.flops / MAX_BATCH
    peak = _cost.peak_flops()
    mfu = (_cost.record_mfu(tokens * flops_per_token, dec_sum, peak=peak)
           if dec_sum > 0 else None)
    return {
        # speculative lane (ISSUE 6): acceptance economics of the
        # measured window; tokens_per_step is the structural win — a
        # plain engine cannot exceed max_batch (one token per row per
        # compiled step), speculation can
        "max_batch": MAX_BATCH,
        # quantized-serving lane (ISSUE 9): the active modes + the
        # batched-replay dispatch economics
        "quantize": quantize,
        "kv_quant": kv_quant,
        "replay_batch": bool(replay_batch),
        "replay_dispatches": int(_counter_delta(
            before, after, "replay_dispatches_total")),
        "speculative": bool(draft),
        "spec_k": int(spec_k) if draft else None,
        "draft_noise": float(draft_noise) if draft else None,
        "spec_proposed_tokens": int(sp),
        "spec_accepted_tokens": int(sa),
        "spec_accept_rate": (sa / sp) if sp else None,
        "spec_accept_len_mean": (al_sum / al_n) if al_n else None,
        "spec_rollbacks": int(sr),
        "tokens_per_step": (tokens / dec_n) if dec_n else None,
        "requests": len(reqs),
        "failed_requests": failed,
        "sample_on_device": bool(sample_on_device),
        "prefix_cache": bool(prefix_cache),
        # resilience lane (ISSUE 4): zero on a clean run; under a fault
        # plan the quarantine/retry machinery's footprint
        "fault_plan": (None if fault_plan is None
                       else fault_plan.snapshot()),
        "decode_retries": int(_counter_delta(
            before, after, "decode_retries_total")),
        "quarantined_requests": int(_counter_delta(
            before, after, "quarantined_requests_total")),
        "survivor_replays": int(_counter_delta(
            before, after, "survivor_replays_total")),
        "engine_rebuilds": int(_counter_delta(
            before, after, "engine_rebuilds_total")),
        "recovery_events": rec_n,
        "mttr_p50_s": hist_quantile(rec_b, 0.50),
        "mttr_mean_s": (rec_sum / rec_n) if rec_n else None,
        # write-ahead journal (ISSUE 13): the durability lane's fields
        "journal": journal_dir is not None,
        "journal_fsync": journal_fsync if journal_dir else None,
        "journal_bytes": int(jb),
        "journal_records": int(jr),
        "journal_fsync_p50": hist_quantile(jf_b, 0.50),
        "journal_fsyncs": jf_n,
        "tokens_per_sec": (tokens / dec_sum) if dec_sum > 0 else 0.0,
        "generated_tokens": int(tokens),
        "decode_steps": dec_n,
        "decode_step_p50_s": hist_quantile(dec_b, 0.50),
        "decode_step_mean_s": (dec_sum / dec_n) if dec_n else None,
        "ttft_p50_s": hist_quantile(ttft_b, 0.50),
        "ttft_p99_s": hist_quantile(ttft_b, 0.99),
        "ttft_mean_s": (ttft_sum / ttft_n) if ttft_n else None,
        # prefill alone (no queue wait): with prefix_cache on, a hit
        # runs only its suffix — THE TTFT win, isolated
        "prefill_p50_s": hist_quantile(pre_b, 0.50),
        "prefill_mean_s": (pre_sum / pre_n) if pre_n else None,
        "prefix_hit_rate": (hits / lookups) if lookups else 0.0,
        "prefix_hit_tokens": int(hit_tokens),
        # steady-state contract: the warm-up wave compiled every bucket,
        # so the measured window should recompile nothing
        "jit_recompiles": int(compile_n),
        "jit_compile_seconds": compile_sum,
        # cost/MFU accounting (ISSUE 10): analytical decode-program
        # cost (jaxpr walk; int8 ops at their width) + the window's MFU
        # — the automated source of the ROADMAP's MFU ladder
        "program_flops": cost_est.flops,
        "program_hbm_bytes": cost_est.hbm_bytes,
        "flops_per_token": flops_per_token,
        "peak_flops": peak,
        "peak_source": _cost.peak_source(),
        "mfu": mfu,
        # SPMD/memory audit (ISSUE 11): the tier-3 field group — the
        # static HBM verdict and the compute-vs-communication roofline
        # of the decode program the window dispatched
        "spmd": {
            "peak_hbm_bytes": spmd_audit.peak_hbm_bytes,
            "collective_bytes_total": spmd_audit.collective_bytes_total,
            "collective_bytes_f32_equiv":
                spmd_audit.collective_bytes_f32_equiv,
            "ici_time_seconds": spmd_audit.ici_time_seconds,
            "comm_compute_ratio": spmd_audit.comm_compute_ratio,
            "comm_bound": spmd_audit.comm_bound,
            "mesh_axes": spmd_audit.mesh_axes,
            "collectives": len(spmd_audit.collectives),
            "findings": len(spmd_audit.findings),
        },
        # tensor-parallel lane (ISSUE 20): the mesh degree the window
        # ran at + PER-CHIP resident-KV bytes (global / tp — the HBM
        # win TP buys on the pool side)
        "tp": int(tp),
        "tp_quant_collectives": bool(tp_quant_collectives),
        "kv_pool_bytes": int(kv_pool_bytes),
        "kv_pool_bytes_per_chip": int(kv_pool_bytes_per_chip),
    }


# --------------------------------------------------------------------
# scenario-matrix lane (ISSUE 7): chat + RAG + offline-batch mixed
# workload through the heterogeneous-workload scheduler
# --------------------------------------------------------------------

SCENARIO_CLASSES = ("interactive", "standard", "batch")


def _p50(vals):
    vals = sorted(vals)
    return vals[len(vals) // 2] if vals else None


def _build_tiny_model(vocab=64, hidden=32):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=2 * hidden, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def run_scenario_lane(model=None, chunk_tokens=16, use_classes=True,
                      flood_n=4, rag_n=2, chat_n=6, seed=0) -> dict:
    """One scenario-matrix serving run: ``flood_n`` long-prompt
    (96-token, 8x chunk) offline-batch requests, ``rag_n`` shared-
    system-prefix RAG requests, and ``chat_n`` short interactive
    requests submitted BEHIND the flood — the exact pattern that
    stalls a FIFO engine.  A flood of ``max_batch`` (4) requests
    saturates every slot, so interactive admission must exercise SLOT
    PREEMPTION, not just the chunk budget.  ``chunk_tokens=None`` disables chunking and
    ``use_classes=False`` submits everything default-class: together
    they are the unchunked-FIFO baseline the ROADMAP item measures
    against.

    Chat-class TTFT is taken per request (submit -> first token, the
    same instants the monitor histograms observe) so the three lanes
    compare exactly; per-class SLO series come from labeled
    ``monitor.snapshot()`` deltas.  The measured window must be
    compile-free: the warm pass covers every decode bucket and every
    chunk/prefix program shape the (position-derived, never
    timing-derived) chunk plan can produce.

    Every lane quotes the ``engine_dispatches_total`` mode split,
    steps, tokens/s and wall time over the measured window."""
    import time

    import numpy as np
    from paddle_tpu import analysis, monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    monitor.install_compile_hooks()
    if model is None:
        model = _build_tiny_model()
    rng = np.random.default_rng(seed)
    system = rng.integers(0, 64, (32,)).astype("int32")

    def cls(name):
        return name if use_classes else None

    with ContinuousBatchingEngine(
            model, total_pages=192, page_size=8, max_batch=4,
            prefill_chunk_tokens=chunk_tokens,
            min_table_pages=16, max_queue=64) as eng:
        n_sub = [0]

        def submit(prompt, max_new, priority, tenant):
            n_sub[0] += 1
            # the FIFO baseline collapses tenants too: one class + one
            # tenant = strict submission order, the stall scenario
            return eng.submit(prompt, max_new_tokens=max_new,
                              priority=cls(priority),
                              tenant=tenant if use_classes else "default",
                              seed=n_sub[0])

        def chat_req(i):
            return submit(rng.integers(0, 64, (6,)).astype("int32"), 8,
                          "interactive", f"chat{i % 2}")

        def rag_req():
            p = np.concatenate(
                [system, rng.integers(0, 64, (5,))]).astype("int32")
            return submit(p, 6, "standard", "rag")

        def flood_req():
            return submit(rng.integers(0, 64, (96,)).astype("int32"), 6,
                          "batch", "offline")

        def wave():
            import time as _time
            batch_reqs = [flood_req() for _ in range(flood_n)]
            # the flood must be ADMITTED (slots held, prefill running)
            # before interactive traffic arrives — that is the stall
            # scenario, and what forces the chunked lane through slot
            # preemption rather than mere admission ordering
            deadline = _time.monotonic() + 120
            while _time.monotonic() < deadline and not all(
                    r.seq_id is not None for r in batch_reqs):
                _time.sleep(0.002)
            reqs = {
                "batch": batch_reqs,
                "standard": [rag_req() for _ in range(rag_n)],
                "interactive": [chat_req(i) for i in range(chat_n)],
            }
            for rs in reqs.values():
                for r in rs:
                    r.result(timeout=600)
            return reqs

        # warm pass: decode buckets 1/2/4 explicitly, then a SEQUENCED
        # rag request (its prefill must register the system prefix
        # before any other rag admits, or the prefix-HIT suffix
        # program stays uncompiled until the measured window), then
        # the full mix (cold + prefix-hit chunk shapes)
        chat_req(0).result(timeout=600)
        for r in [chat_req(i) for i in range(2)]:
            r.result(timeout=600)
        for r in [chat_req(i) for i in range(4)]:
            r.result(timeout=600)
        if rag_n:
            rag_req().result(timeout=600)
        wave()
        # the unified step buckets (rows, max span) JOINTLY, so
        # admission timing can realize a bucket combo the first
        # warm wave missed; a second pass keeps the measured
        # window compile-free
        wave()

        before = monitor.snapshot()
        steps0 = eng.steps
        t0 = time.monotonic()
        reqs = wave()
        wall_s = time.monotonic() - t0
        steps = eng.steps - steps0
        after = monitor.snapshot()
        audit_errors = None
        if chunk_tokens:
            # audit the program that served the window
            audit = analysis.audit_engine(eng, mode="ragged",
                                          publish=False)
            audit_errors = sum(1 for f in audit.findings
                               if f.severity == "error")

    chat_ttfts = [r.first_token_at - r.submitted_at
                  for r in reqs["interactive"]
                  if r.first_token_at is not None]
    # the ORDER the scheduler exists to change, read off the engine's
    # own event instants: how many chat requests had their first token
    # before the first flood request was done (a stalled engine: none —
    # every slot is a flood request's until one retires)
    first_flood_done = min((r.finished_at for r in reqs["batch"]),
                           default=None)
    chat_ahead = (None if first_flood_done is None else
                  sum(1 for r in reqs["interactive"]
                      if r.first_token_at is not None
                      and r.first_token_at < first_flood_done))
    _, compile_sum, compile_n = _hist_delta(before, after,
                                            "jit_compile_seconds")
    tokens = _counter_delta(before, after, "generated_tokens_total")
    # target-model program dispatches issued in the measured window,
    # per mode — 'draft' is a second model's own dispatches and never
    # folds into the unified step, so it is quoted but kept out of
    # the collapse arithmetic
    dispatches = {
        m: int(_counter_delta(before, after, "engine_dispatches_total",
                              {"mode": m}))
        for m in ("ragged", "prefill", "chunk", "draft")}
    dispatches_target = sum(v for m, v in dispatches.items()
                            if m != "draft")
    per_class = {}
    if use_classes:
        for c in SCENARIO_CLASSES:
            lb = {"cls": c}
            tb, ts, tn = _hist_delta(before, after,
                                     "sched_ttft_seconds", lb)
            qb, qs, qn = _hist_delta(before, after,
                                     "sched_queue_wait_seconds", lb)
            pb, ps, pn = _hist_delta(before, after,
                                     "sched_tpot_seconds", lb)
            per_class[c] = {
                "lane": "scenario-matrix", "class": c,
                "requests": len(reqs.get(c, ())),
                "ttft_p50_s": hist_quantile(tb, 0.50),
                "ttft_p99_s": hist_quantile(tb, 0.99),
                "ttft_mean_s": (ts / tn) if tn else None,
                "queue_wait_p50_s": hist_quantile(qb, 0.50),
                "queue_wait_mean_s": (qs / qn) if qn else None,
                "tpot_mean_s": (ps / pn) if pn else None,
                "admitted": int(_counter_delta(
                    before, after, "sched_admitted_total", lb)),
                "preemptions": int(_counter_delta(
                    before, after, "sched_preemptions_total", lb)),
                "chunk_deferrals": int(_counter_delta(
                    before, after, "sched_chunk_deferrals_total", lb)),
                "prefill_chunks": int(_counter_delta(
                    before, after, "sched_prefill_chunks_total", lb)),
            }
    return {
        "lane": "scenario-matrix",
        "chunk_tokens": chunk_tokens,
        "classes": bool(use_classes),
        "flood": flood_n, "rag": rag_n, "chat": chat_n,
        "chat_ttft_p50_s": _p50(chat_ttfts),
        "chat_ttft_mean_s": (sum(chat_ttfts) / len(chat_ttfts)
                             if chat_ttfts else None),
        "chat_first_token_before_flood_done": chat_ahead,
        "wall_s": wall_s,
        "generated_tokens": int(tokens),
        "tokens_per_s": (tokens / wall_s) if wall_s > 0 else None,
        "steps": int(steps),
        "dispatches": dispatches,
        "dispatches_target_model": int(dispatches_target),
        "dispatches_per_step": ((dispatches_target / steps)
                                if steps else None),
        "unified_fallbacks": int(_counter_delta(
            before, after, "engine_unified_fallbacks_total")),
        "jit_recompiles": int(compile_n),
        "jit_compile_seconds": compile_sum,
        "audit_error_findings": audit_errors,
        "per_class": per_class,
    }


def run_scenario_matrix(argv) -> int:
    """The ``--scenario-matrix`` lane: three runs of the same mixed
    workload — (1) chunked+classes without the flood (the chat-class
    no-flood TTFT baseline), (2) chunked+classes with the flood (one
    JSON line per class), (3) unchunked FIFO with the flood (the stall
    the scheduler exists to prevent).  Gates: under the flood every
    chat request has its first token before the first flood request is
    done; in the FIFO baseline none has (it demonstrably stalled); zero
    recompiles in every measured window; the serving program audited
    transfer-free; batch-class preemption actually exercised; and the
    chunked window is single-program — ONLY ragged-mode dispatches
    (zero prefill/chunk programs) and zero steps down the failure
    ladder.  Every timing (chat TTFT by lane, tokens/s) is quoted in
    the summary JSON and decides nothing: a CPU that other processes
    share measures the neighbours."""
    chunk = _int_arg(argv, "chunk-tokens", 16)
    flood_n = _int_arg(argv, "flood", 4)
    rag_n = _int_arg(argv, "rag", 2)
    chat_n = _int_arg(argv, "chat", 6)
    model = _build_tiny_model(vocab=_int_arg(argv, "vocab", 64),
                              hidden=_int_arg(argv, "hidden", 32))
    alone = run_scenario_lane(model, chunk_tokens=chunk, flood_n=0,
                              rag_n=rag_n, chat_n=chat_n)
    mixed = run_scenario_lane(model, chunk_tokens=chunk, flood_n=flood_n,
                              rag_n=rag_n, chat_n=chat_n)
    # the FIFO stall baseline: no scheduler classes, no chunking
    # (whole-prompt prefill on its own programs, decode rows ragged)
    fifo = run_scenario_lane(model, chunk_tokens=None, use_classes=False,
                             flood_n=flood_n, rag_n=rag_n, chat_n=chat_n)
    for c in SCENARIO_CLASSES:
        if c in mixed["per_class"]:
            print(json.dumps(mixed["per_class"][c], sort_keys=True))
    print(json.dumps({
        "lane": "mixed-batch-unified",
        "tokens_per_s": mixed["tokens_per_s"],
        "generated_tokens": mixed["generated_tokens"],
        "wall_s": mixed["wall_s"],
        "steps": mixed["steps"],
        "dispatches": mixed["dispatches"],
        "dispatches_target_model": mixed["dispatches_target_model"],
        "dispatches_per_step": mixed["dispatches_per_step"],
        "unified_fallbacks": mixed["unified_fallbacks"],
        "chat_ttft_p50_s": mixed["chat_ttft_p50_s"],
        "chat_ttft_mean_s": mixed["chat_ttft_mean_s"],
        "chat_tpot_mean_s": (mixed["per_class"]
                             .get("interactive", {})
                             .get("tpot_mean_s")),
        "jit_recompiles": mixed["jit_recompiles"],
        "audit_error_findings": mixed["audit_error_findings"],
    }, sort_keys=True))
    preemptions = (mixed["per_class"]["batch"]["preemptions"]
                   + mixed["per_class"]["batch"]["chunk_deferrals"])
    summary = {
        "lane": "scenario-matrix-summary",
        "chunk_tokens": chunk,
        "chat_ttft_p50_no_flood_s": alone["chat_ttft_p50_s"],
        "chat_ttft_p50_flood_chunked_s": mixed["chat_ttft_p50_s"],
        "chat_ttft_p50_flood_fifo_s": fifo["chat_ttft_p50_s"],
        "chat_ttft_mean_no_flood_s": alone["chat_ttft_mean_s"],
        "chat_ttft_mean_flood_chunked_s": mixed["chat_ttft_mean_s"],
        "chat_ttft_mean_flood_fifo_s": fifo["chat_ttft_mean_s"],
        "chat_requests": chat_n,
        "chat_ahead_of_flood_chunked":
            mixed["chat_first_token_before_flood_done"],
        "chat_ahead_of_flood_fifo":
            fifo["chat_first_token_before_flood_done"],
        "batch_preemptions": preemptions,
        "audit_error_findings": mixed["audit_error_findings"],
        "jit_recompiles": (alone["jit_recompiles"]
                           + mixed["jit_recompiles"]
                           + fifo["jit_recompiles"]),
        "tokens_per_s_unified": mixed["tokens_per_s"],
        "dispatches_unified": mixed["dispatches_target_model"],
        "unified_fallbacks": mixed["unified_fallbacks"],
    }
    print(json.dumps(summary, sort_keys=True))
    if not all((alone["chat_ttft_p50_s"], mixed["chat_ttft_p50_s"],
                fifo["chat_ttft_p50_s"])):
        print("FAIL: a lane produced no chat TTFT samples — the "
              "scenario matrix needs --chat >= 1", file=sys.stderr)
        return 1
    ok = True
    if summary["chat_ahead_of_flood_chunked"] != chat_n:
        print("FAIL: under the flood only "
              f"{summary['chat_ahead_of_flood_chunked']} of {chat_n} "
              "chat requests had a first token before the first flood "
              "request was done — chat waited for the flood",
              file=sys.stderr)
        ok = False
    # the stall comparison holds the LOAD fixed (same flood) and flips
    # the scheduler: in unchunked FIFO no chat request gets a token
    # until a flood request has retired and freed its slot
    if summary["chat_ahead_of_flood_fifo"] != 0:
        print("FAIL: the unchunked FIFO baseline did not stall "
              f"({summary['chat_ahead_of_flood_fifo']} chat requests "
              "were served ahead of the flood) — the scenario is not "
              "exercising the problem", file=sys.stderr)
        ok = False
    if summary["jit_recompiles"] != 0:
        print(f"FAIL: {summary['jit_recompiles']} recompile(s) inside "
              "measured windows; a warm-up pass missed a program shape",
              file=sys.stderr)
        ok = False
    if mixed["audit_error_findings"] != 0:
        print(f"FAIL: chunked-prefill program audit found "
              f"{mixed['audit_error_findings']} error finding(s)",
              file=sys.stderr)
        ok = False
    if preemptions <= 0:
        print("FAIL: the flood never preempted/deferred batch-class "
              "prefill — the priority machinery did not engage",
              file=sys.stderr)
        ok = False
    # single-program gates (ISSUE 17): structural, not wall-clock —
    # CPU CI cannot gate tokens/s, but it CAN prove the chunked window
    # served every phase through the one ragged program
    md = mixed["dispatches"]
    other_modes = {m: md[m] for m in ("prefill", "chunk") if md[m]}
    if other_modes or md["ragged"] <= 0:
        print("FAIL: the unified window was not single-program — "
              f"ragged={md['ragged']}, other dispatches="
              f"{other_modes}", file=sys.stderr)
        ok = False
    if mixed["unified_fallbacks"] != 0:
        print(f"FAIL: {mixed['unified_fallbacks']} unified step(s) "
              "failed and went down the isolation ladder inside the "
              "measured window", file=sys.stderr)
        ok = False
    return 0 if ok else 1


# --------------------------------------------------------------------
# quantized-serving lane (ISSUE 9): int8 KV + w8/w8a8 weights — the
# users-per-chip capacity lever, A/B'd exactly via the logits escape
# hatch
# --------------------------------------------------------------------

def _quant_parity(model, mode, vocab=64, seed=0) -> dict:
    """Greedy A/B on the ``sampling=None`` logits escape hatch: the
    SAME prompt set through a full-precision and a quantized engine,
    both on the host-logits path (host argmax over f32 logits), so the
    comparison is exact and deterministic — plus the raw decoders'
    prefill logits max-abs-diff as the numeric-error quote."""
    import numpy as np
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.inference.paged import JittedPagedDecoder
    from paddle_tpu.ops.pallas.paged_attention import PagedKVCache

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (n,)).astype("int32")
               for n in (5, 9, 13, 20, 7, 16)]
    outs = []
    for kw in (dict(), dict(quantize=mode, kv_quant="int8")):
        with ContinuousBatchingEngine(
                model, total_pages=128, page_size=8, max_batch=4,
                sample_on_device=False, **kw) as eng:
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            outs.append([r.result(timeout=600) for r in reqs])
    matches = [bool(np.array_equal(a, b)) for a, b in zip(*outs)]
    cache_b = PagedKVCache.from_model(model, total_pages=16, page_size=8)
    cache_q = PagedKVCache.from_model(model, total_pages=16, page_size=8,
                                      kv_dtype="int8")
    lb = JittedPagedDecoder(model).prefill(cache_b, [0], prompts[3][None])
    lq = JittedPagedDecoder(model, quantize=mode).prefill(
        cache_q, [0], prompts[3][None])
    return {
        "parity_requests": len(matches),
        "parity_matches": sum(matches),
        "greedy_exact": all(matches),
        "logits_max_abs_diff": float(np.max(np.abs(lb - lq))),
    }


def run_quant_lane(argv) -> int:
    """The ``--quant`` lane: the mixed shared-prefix workload through
    (1) a full-precision baseline engine and (2) an int8-KV + w8/w8a8
    engine whose page pool holds EQUAL BYTES — so the quant lane's
    extra pages are exactly what int8 storage buys.  One JSON line
    quoting pool capacity (max concurrent sequences at the workload's
    worst-case footprint), resident KV bytes, tokens/sec, TTFT, the
    logits-escape-hatch greedy parity, and ``jit_recompiles``.

    Gates: capacity ratio >= 1.8 (the ISSUE 9 acceptance bound),
    greedy outputs EXACT on the logits-parity path (w8a8 instead gets
    the documented near-tie tolerance: at most one flipped request and
    logits within the error bound), zero recompiles in both measured
    windows, and tokens/sec >= ``--tps-floor`` x baseline.  The floor
    defaults to 1.0 on TPU (int8 halves the HBM-bandwidth-bound
    decode's weight/KV traffic — quantization must not lose) and is
    OFF on CPU, where XLA EMULATES int8 and pays the quant/dequant
    compute with no bandwidth win to harvest — the documented lose
    case, and on the tiny CI model the wall-clock ratio is noise-
    dominated, so it is quoted in the JSON but never gated (pass
    ``--tps-floor=`` to force a bound)."""
    import jax
    from paddle_tpu.ops.pallas.paged_attention import PagedKVCache

    mode = next((a.split("=", 1)[1] for a in argv
                 if a.startswith("--quant-mode=")), "w8")
    vocab = _int_arg(argv, "vocab", 64)
    hidden = _int_arg(argv, "hidden", 64)
    base_pages = _int_arg(argv, "total-pages", 128)
    model = _build_tiny_model(vocab=vocab, hidden=hidden)
    on_tpu = jax.default_backend() == "tpu"
    # the tokens/sec gate is TPU-only by default: there int8 halves the
    # bandwidth-bound decode's traffic and quantization must not lose
    # (floor 1.0).  On CPU XLA emulates int8 — the ratio is both a
    # documented lose case AND noise-dominated on the tiny CI model —
    # so the number is quoted ungated unless --tps-floor forces a bound
    # (the same no-timing-gates-on-shared-CI discipline as the replay
    # lane's MTTR quote)
    tps_floor = _float_arg(argv, "tps-floor",
                           1.0 if on_tpu else None)

    # equal page-pool BYTES: size the quant pool so data + scale pools
    # together occupy what the baseline's pages do
    probe_b = PagedKVCache.from_model(model, total_pages=1,
                                      page_size=PAGE_SIZE)
    probe_q = PagedKVCache.from_model(model, total_pages=1,
                                      page_size=PAGE_SIZE,
                                      kv_dtype="int8")
    bytes_b = probe_b.kv_pool_bytes
    bytes_q = probe_q.kv_pool_bytes + probe_q.kv_scale_bytes
    quant_pages = (base_pages * bytes_b) // bytes_q

    kw = dict(sharers=_int_arg(argv, "sharers", 6),
              uniques=_int_arg(argv, "uniques", 3),
              system_tokens=_int_arg(argv, "system-tokens", 16),
              max_new_tokens=_int_arg(argv, "max-new-tokens", 8),
              vocab=vocab, hidden=hidden)
    base = run_bench(model=model, total_pages=base_pages, **kw)
    quant = run_bench(model=model, total_pages=quant_pages,
                      quantize=mode, kv_quant="int8", **kw)
    parity = _quant_parity(model, mode, vocab=vocab)

    # the workload's worst-case request footprint (prompt + max_new),
    # in pages — the same arithmetic the engine's admission reserves
    worst_tokens = (kw["system_tokens"] + SUF_TOKENS
                    + kw["max_new_tokens"])
    worst_tokens = max(worst_tokens, UNIQ_TOKENS + kw["max_new_tokens"])
    pages_per_req = -(-worst_tokens // PAGE_SIZE)
    cap_base = (base_pages - 1) // pages_per_req       # -1: pad page
    cap_quant = (quant_pages - 1) // pages_per_req
    out = {
        "lane": "quant",
        "quant_mode": mode,
        "kv_quant": "int8",
        "backend_tpu": on_tpu,
        "base_total_pages": base_pages,
        "quant_total_pages": quant_pages,
        "pool_bytes_base": base_pages * bytes_b,
        "pool_bytes_quant": quant_pages * bytes_q,
        "pages_per_request": pages_per_req,
        "pool_capacity_base": cap_base,
        "pool_capacity_quant": cap_quant,
        "capacity_ratio": (cap_quant / cap_base) if cap_base else None,
        "tokens_per_sec_base": base["tokens_per_sec"],
        "tokens_per_sec_quant": quant["tokens_per_sec"],
        "tps_ratio": (quant["tokens_per_sec"] / base["tokens_per_sec"]
                      if base["tokens_per_sec"] else None),
        "tps_floor": tps_floor,
        "ttft_p50_base_s": base["ttft_p50_s"],
        "ttft_p50_quant_s": quant["ttft_p50_s"],
        "jit_recompiles": (base["jit_recompiles"]
                           + quant["jit_recompiles"]),
        **parity,
    }
    print(json.dumps(out, sort_keys=True))
    ok = True
    if out["capacity_ratio"] is None or out["capacity_ratio"] < 1.8:
        print(f"FAIL: int8 KV pool admits only "
              f"{out['capacity_ratio']}x the baseline's concurrent "
              "sequences at equal pool bytes (acceptance bound: 1.8x)",
              file=sys.stderr)
        ok = False
    # weight-only (and the int8 KV cache alone) is greedy-EXACT by
    # contract; w8a8's dynamic activation noise MAY flip near-tie
    # argmaxes — the documented accuracy caveat (README "when w8a8
    # loses") — so its gate is the test suite's tolerance: at most one
    # flipped request plus the logits error bound
    if mode == "w8a8":
        parity_ok = (out["parity_matches"]
                     >= out["parity_requests"] - 1
                     and out["logits_max_abs_diff"] < 0.05)
    else:
        parity_ok = out["greedy_exact"]
    if not parity_ok:
        print(f"FAIL: greedy outputs diverged on the logits-parity "
              f"path ({out['parity_matches']}/{out['parity_requests']} "
              f"requests exact, logits max|diff| "
              f"{out['logits_max_abs_diff']:.4g})", file=sys.stderr)
        ok = False
    if out["jit_recompiles"] != 0:
        print(f"FAIL: {out['jit_recompiles']} recompile(s) inside "
              "measured windows", file=sys.stderr)
        ok = False
    if tps_floor is not None and (out["tps_ratio"] is None
                                  or out["tps_ratio"] < tps_floor):
        print(f"FAIL: quantized tokens/sec is {out['tps_ratio']}x "
              f"baseline (floor {tps_floor}; on CPU int8 is emulated — "
              "the bandwidth win only exists on TPU)", file=sys.stderr)
        ok = False
    return 0 if ok else 1


# --------------------------------------------------------------------
# tensor-parallel lane (ISSUE 20): the unified serving step compiled
# TP-sharded over a ('tensor',) mesh — per-chip HBM divided by the TP
# degree, every collective named+priced before dispatch, greedy
# outputs bit-exact against the 1-chip engine
# --------------------------------------------------------------------

def _tp_parity(tp, vocab=64, hidden=32, seed=0) -> dict:
    """Greedy A/B on the logits escape hatch: the SAME prompt set
    through a 1-chip engine and a TP-sharded engine, both on the
    host-logits path, so the comparison is exact token equality.  TWO
    same-seed models — the TP decoder COMMITS its model's params to
    the mesh, so the engines must not share one instance."""
    import numpy as np
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, (n,)).astype("int32")
               for n in (5, 9, 13, 20, 7, 16)]
    outs = []
    for kw in (dict(), dict(tp=tp)):
        with ContinuousBatchingEngine(
                _build_tiny_model(vocab=vocab, hidden=hidden),
                total_pages=128, page_size=8, max_batch=4,
                sample_on_device=False, **kw) as eng:
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            outs.append([r.result(timeout=600) for r in reqs])
    matches = [bool(np.array_equal(a, b)) for a, b in zip(*outs)]
    return {
        "parity_requests": len(matches),
        "parity_matches": sum(matches),
        "greedy_exact": all(matches),
    }


def run_tp_lane(argv) -> int:
    """The ``--tp`` lane: the mixed shared-prefix workload through a
    1-chip baseline engine and a TP-sharded engine at EQUAL GLOBAL
    BATCH (same max_batch, same workload), one JSON line quoting
    tokens/sec/chip vs the baseline, the priced collective bytes and
    analytic ICI seconds of the sharded decode program, its
    comm_bound roofline verdict, per-chip kv_pool_bytes, and the int8
    collective pricing of the same program's quantized-collective
    twin (static audit — EQuARX's win, priced before it's built).

    Gates: zero recompiles in both measured windows, greedy outputs
    bit-exact against the 1-chip engine on the logits-parity path,
    every collective in the sharded program named+priced (nonzero
    bytes, 'tensor' axes), and at tp=2 the int8-collective variant
    pricing >= 3x fewer bytes than f32 (ring math: the width-4 win
    minus the all_gather-vs-all_reduce algorithm change; the ratio is
    8/n, so the bound is only asserted at n=2).  tokens/sec/chip is
    QUOTED, never gated: on CPU the mesh is virtual devices on one
    host (TP=2 runs ~half speed per chip, the documented lose case —
    TP pays for itself only when the model doesn't fit one chip or
    ICI is real)."""
    from paddle_tpu.analysis import spmd as _spmd
    from paddle_tpu.framework import jax_compat as _jc
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    tp = _int_arg(argv, "tp", 2)
    # CLI path: the model builds (first jax op) BEFORE the TP engine,
    # so the virtual CPU devices must be provisioned now, while the
    # backend is still un-initialized (no-op on real multi-chip hosts
    # and under the test suite's pre-split conftest)
    if tp > 1 and not _jc.backend_initialized():
        _jc.pin_cpu_devices(max(tp, 2))
    vocab = _int_arg(argv, "vocab", 64)
    hidden = _int_arg(argv, "hidden", 32)
    total_pages = _int_arg(argv, "total-pages", 128)
    kw = dict(sharers=_int_arg(argv, "sharers", 6),
              uniques=_int_arg(argv, "uniques", 3),
              system_tokens=_int_arg(argv, "system-tokens", 16),
              max_new_tokens=_int_arg(argv, "max-new-tokens", 8),
              vocab=vocab, hidden=hidden, total_pages=total_pages)
    base = run_bench(model=_build_tiny_model(vocab=vocab, hidden=hidden),
                     **kw)
    shard = run_bench(model=_build_tiny_model(vocab=vocab, hidden=hidden),
                      tp=tp, **kw)
    parity = _tp_parity(tp, vocab=vocab, hidden=hidden)

    # static int8-collective pricing: the SAME sharded decode program
    # with quantized all-reduces, audited (never dispatched) — the
    # f32-equivalent ratio is the EQuARX bandwidth win
    with ContinuousBatchingEngine(
            _build_tiny_model(vocab=vocab, hidden=hidden),
            total_pages=32, page_size=PAGE_SIZE, max_batch=4,
            sample_on_device=False, tp=tp,
            tp_quant_collectives=True) as eng_q:
        audit_q = _spmd.audit_spmd_engine(eng_q, mode="decode",
                                          compiled=False, publish=False)
    int8_ratio = (audit_q.collective_bytes_f32_equiv
                  / audit_q.collective_bytes_total
                  if audit_q.collective_bytes_total else None)

    out = {
        "lane": "tp",
        "tp": tp,
        "max_batch": base["max_batch"],
        "tokens_per_sec_base": base["tokens_per_sec"],
        "tokens_per_sec_tp": shard["tokens_per_sec"],
        "tokens_per_sec_per_chip": shard["tokens_per_sec"] / tp,
        "tps_per_chip_ratio": (shard["tokens_per_sec"] / tp
                               / base["tokens_per_sec"]
                               if base["tokens_per_sec"] else None),
        "collective_bytes": shard["spmd"]["collective_bytes_total"],
        "ici_time_seconds": shard["spmd"]["ici_time_seconds"],
        "comm_bound": shard["spmd"]["comm_bound"],
        "collectives": shard["spmd"]["collectives"],
        "mesh_axes": shard["spmd"]["mesh_axes"],
        "kv_pool_bytes": shard["kv_pool_bytes"],
        "kv_pool_bytes_per_chip": shard["kv_pool_bytes_per_chip"],
        "peak_hbm_bytes_base": base["spmd"]["peak_hbm_bytes"],
        "peak_hbm_bytes_per_chip": shard["spmd"]["peak_hbm_bytes"],
        "int8_collective_bytes": audit_q.collective_bytes_total,
        "int8_collective_f32_equiv": audit_q.collective_bytes_f32_equiv,
        "int8_collective_ratio": int8_ratio,
        "jit_recompiles": (base["jit_recompiles"]
                           + shard["jit_recompiles"]),
        **parity,
    }
    print(json.dumps(out, sort_keys=True))
    ok = True
    if not out["greedy_exact"]:
        print(f"FAIL: greedy outputs diverged between the 1-chip and "
              f"tp={tp} engines ({out['parity_matches']}/"
              f"{out['parity_requests']} requests exact) — the sharded "
              "step is not bit-exact", file=sys.stderr)
        ok = False
    if out["jit_recompiles"] != 0:
        print(f"FAIL: {out['jit_recompiles']} recompile(s) inside "
              "measured windows", file=sys.stderr)
        ok = False
    if out["collectives"] == 0 or out["collective_bytes"] <= 0:
        print("FAIL: the sharded decode program priced no collectives "
              "— the audit lost sight of the mesh", file=sys.stderr)
        ok = False
    if out["kv_pool_bytes_per_chip"] * tp != out["kv_pool_bytes"]:
        print(f"FAIL: per-chip pool bytes "
              f"{out['kv_pool_bytes_per_chip']} x {tp} != global "
              f"{out['kv_pool_bytes']} — the pools are not sharded by "
              "the TP degree", file=sys.stderr)
        ok = False
    if tp == 2 and (int8_ratio is None or int8_ratio < 3.0):
        print(f"FAIL: int8 collectives price only {int8_ratio}x fewer "
              "bytes than f32 (bound: 3x at tp=2)", file=sys.stderr)
        ok = False
    return 0 if ok else 1


# --------------------------------------------------------------------
# journal overhead lane (ISSUE 13): the write-ahead request journal
# must be invisible to the decode hot path — records are enqueued and
# a dedicated writer thread does the I/O.  The lane quotes decode p50
# with journaling on (interval_ms policy) beside journaling off, and
# gates what it can count: records and bytes written, the same tokens
# generated, both measured windows compile-free
# --------------------------------------------------------------------

def run_journal_lane(argv) -> int:
    import tempfile
    kw = dict(sharers=_int_arg(argv, "sharers", 6),
              uniques=_int_arg(argv, "uniques", 3),
              system_tokens=_int_arg(argv, "system-tokens", 16),
              max_new_tokens=_int_arg(argv, "max-new-tokens", 8),
              vocab=_int_arg(argv, "vocab", 64),
              hidden=_int_arg(argv, "hidden", 32))
    off = run_bench(**kw)
    print(json.dumps(off, sort_keys=True))
    with tempfile.TemporaryDirectory() as d:
        on = run_bench(journal_dir=os.path.join(d, "journal"),
                       journal_fsync="interval_ms", **kw)
    # quoted, never a verdict: on a CPU that other processes share the
    # two p50s differ by what the neighbours were doing
    on["baseline_decode_step_p50_s"] = off["decode_step_p50_s"]
    print(json.dumps(on, sort_keys=True))
    checks = [
        ("journaled run produced throughput",
         on["generated_tokens"] > 0),
        ("journal actually wrote records in the measured window",
         on["journal_bytes"] > 0 and on["journal_records"] > 0),
        ("interval_ms policy fsynced (journal_fsync_p50 quoted)",
         on["journal_fsync_p50"] is not None),
        ("baseline wrote nothing", off["journal_bytes"] == 0),
        ("journaling changed no token count",
         on["generated_tokens"] == off["generated_tokens"]),
        ("measured windows compile-free",
         off["jit_recompiles"] == 0 and on["jit_recompiles"] == 0),
        ("no failed requests",
         off["failed_requests"] == 0 and on["failed_requests"] == 0),
    ]
    bad = [name for name, ok in checks if not ok]
    if bad:
        print(f"FAIL (journal lane): {bad}", file=sys.stderr)
        return 1
    return 0


# --------------------------------------------------------------------
# fleet lane (ISSUE 14): N supervised replicas behind the router; one
# JSON line with fleet tokens/sec + TTFT p50/p99 during a replica
# failure window + failovers/migrated counts.  Gates: jit_recompiles
# == 0 in every measured window, the same tokens generated behind the
# router as without it, a failover observed, zero failed requests.
# Per-replica decode p50 is QUOTED beside the router-free baseline's
# (and the fleet=1 run's beside one engine's), never gated.
# --------------------------------------------------------------------

#: decode delay under which the fleet lanes' warm waves run (see
#: ``run_fleet_lane.warm``)
WARM_DECODE_DELAY_S = 0.05


def run_fleet_lane(argv) -> int:
    import tempfile
    import threading
    import time as _time
    import urllib.request
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.inference.server import GenerationServer
    from paddle_tpu.inference.fleet import FleetRouter, ReplicaSupervisor
    from paddle_tpu.testing import faults

    monitor.install_compile_hooks()
    n = max(1, _int_arg(argv, "fleet", 2))
    n_requests = _int_arg(argv, "requests", 12)
    max_new = _int_arg(argv, "max-new-tokens", 8)
    vocab = _int_arg(argv, "vocab", 64)
    hidden = _int_arg(argv, "hidden", 32)
    PROMPT_TOKENS = 8
    MAX_BATCH = 4

    def build():
        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                          intermediate_size=2 * hidden,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2,
                          max_position_embeddings=128)
        return LlamaForCausalLM(cfg)

    rng = np.random.default_rng(3)

    def prompt():
        return rng.integers(0, vocab, (PROMPT_TOKENS,)).astype("int32")

    def window(fn):
        """Run ``fn`` between snapshots; return monitor deltas."""
        before = monitor.snapshot()
        t0 = _time.perf_counter()
        fn()
        wall = _time.perf_counter() - t0
        after = monitor.snapshot()
        dec_b, dec_sum, dec_n = _hist_delta(before, after,
                                            "decode_step_seconds")
        ttft_b, _, _ = _hist_delta(before, after,
                                   "time_to_first_token_seconds")
        _, _, compile_n = _hist_delta(before, after,
                                      "jit_compile_seconds")
        return {
            "wall_s": wall,
            "generated_tokens": int(_counter_delta(
                before, after, "generated_tokens_total")),
            "decode_step_p50_s": hist_quantile(dec_b, 0.50),
            "ttft_p50_s": hist_quantile(ttft_b, 0.50),
            "ttft_p99_s": hist_quantile(ttft_b, 0.99),
            "jit_recompiles": int(compile_n),
            "failovers": int(_counter_delta(
                before, after, "fleet_failovers_total")),
            "migrated_requests": int(_counter_delta(
                before, after, "fleet_migrated_requests_total")),
            "router_retries": int(_counter_delta(
                before, after, "router_retries_total")),
        }

    counter = [0]
    failed = [0]

    def post_wave(urls, k, rid_prefix="b", join=True):
        """POST ``k`` single-row bodies round-robin across ``urls``
        from one thread each; returns (outs, threads)."""
        outs, threads = {}, []
        for j in range(k):
            counter[0] += 1
            body = {"input_ids": [prompt().tolist()],
                    "max_new_tokens": max_new, "seed": counter[0],
                    "request_id": f"{rid_prefix}-{counter[0]}"}
            url = urls[j % len(urls)]

            def go(b=body, u=url):
                try:
                    req = urllib.request.Request(
                        u + "/generate", data=json.dumps(b).encode(),
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=600) as r:
                        outs[b["request_id"]] = json.loads(r.read())
                except Exception:   # noqa: BLE001
                    failed[0] += 1
            t = threading.Thread(target=go, daemon=True)
            t.start()
            threads.append(t)
        if join:
            for t in threads:
                t.join(timeout=600)
        return outs, threads

    def warm(urls):
        """Compile decode buckets 1/2/4 on every server DETERMINISTIC-
        ALLY: per-bucket waves sized to the bucket, run under a decode
        delay so admission backs up and the batch actually REACHES the
        wave size (an undelayed warm wave retires faster than it
        admits on a fast CPU, leaving max_batch to compile inside the
        measured window).  0.05 s a step keeps a request alive for a
        quarter of a second: at 0.01 s a loaded host posted the wave's
        requests further apart than one lived, the wave never batched,
        and the bucket compiled inside the measured window."""
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": WARM_DECODE_DELAY_S}]))
        try:
            for b in (1, 2, MAX_BATCH):
                post_wave(urls, b * len(urls), rid_prefix="warm")
        finally:
            faults.clear()

    # ---- router-free baseline: ``size`` GenerationServers in the
    # EXACT replica configuration (journal included — at 2+ co-located
    # engines the journal writers cost a measurable GIL share, and
    # that cost belongs to the durability knob, not the router) driven
    # over HTTP.  The fleet-vs-baseline diff isolates what the ROUTER
    # and the supervisor's probes add to the hot path.
    def run_direct(size=1):
        import tempfile
        servers = [GenerationServer(
            build(), total_pages=128, page_size=PAGE_SIZE,
            max_batch=MAX_BATCH,
            journal_dir=tempfile.mkdtemp(prefix="fleet-bench-base-"),
            journal_fsync="os").start() for _ in range(size)]
        try:
            urls = [f"http://{s.host}:{s.port}" for s in servers]
            warm(urls)
            return window(lambda: post_wave(urls, n_requests))
        finally:
            for s in servers:
                s.stop()

    # ---- a supervised fleet serving the same workload over HTTP
    def run_fleet(size, kill):
        root = tempfile.mkdtemp(prefix="fleet-bench-")

        def factory(name, jdir):
            return GenerationServer(
                build(), total_pages=128, page_size=PAGE_SIZE,
                max_batch=MAX_BATCH, journal_dir=jdir,
                journal_fsync="os")

        # the replicas share this process's GIL with their own
        # warm-up compiles: a /health probe can wait seconds behind a
        # trace, and a 1 s probe timeout then declared a LIVE replica
        # dead before the kill (no failover left to observe in the
        # failure window).  A killed replica refuses the connection at
        # once, so the long timeout costs the real detection nothing.
        sup = ReplicaSupervisor(
            factory=factory, replicas=size, journal_root=root,
            probe_interval_s=0.05, probe_failure_threshold=2,
            probe_timeout_s=10.0, heartbeat_timeout_s=60.0)
        router = FleetRouter(sup)
        sup.start()
        router.start()
        try:
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < 60 \
                    and len(sup.routable_replicas()) < size:
                _time.sleep(0.02)
            url = f"http://{router.host}:{router.port}"
            # warm-up: the router's round-robin spreads each wave
            # evenly, so every replica compiles its prefill bucket and
            # decode buckets 1..max_batch (multiplying the per-bucket
            # wave by the fleet size keeps per-replica sizing right)
            warm([url] * size)
            if kill:
                # warm the journal-replay admission path on every
                # replica (a migrated entry with generated tokens
                # ingests prompt+generated through the next pow2
                # prefill bucket): the failure window must stay
                # compile-free
                for rep in sup.all_replicas():
                    eng = rep.server._engine
                    entry = {"request_id": f"warm-replay-{rep.name}",
                             "prompt": prompt().tolist(),
                             "generated": [1], "next_token": 2,
                             "max_new_tokens": max_new, "seed": 0}
                    for r in eng.restore({"version": 1,
                                          "requests": [entry]},
                                         strict=False):
                        r.result(timeout=600)

            f0 = failed[0]
            healthy = window(lambda: post_wave([url], n_requests))
            failure = None
            if kill and size > 1:
                def failure_wave():
                    # widen the mid-decode window so the kill lands on
                    # in-flight streams (the delay is confined to THIS
                    # window; the healthy window above carries the p50
                    # gate)
                    faults.install(faults.FaultPlan(
                        [{"site": "decode_step", "kind": "delay",
                          "delay_s": 0.02}]))
                    try:
                        outs, threads = post_wave([url], n_requests,
                                                  rid_prefix="fw",
                                                  join=False)
                        _time.sleep(0.05)   # let admissions spread
                        victim = sup.routable_replicas()[0].name
                        sup.kill(victim)
                        for t in threads:
                            t.join(timeout=600)
                        # the wave can finish on the survivor before
                        # the probe cadence even notices the corpse —
                        # hold the window open until the failover
                        # lands so its counters are inside the deltas
                        t0 = _time.monotonic()
                        while _time.monotonic() - t0 < 30 and \
                                sup.replica(victim).state != "dead":
                            _time.sleep(0.02)
                    finally:
                        faults.clear()
                failure = window(failure_wave)
            return healthy, failure, failed[0] - f0
        finally:
            try:
                router.stop()
                sup.stop()
            except Exception:   # noqa: BLE001 — teardown best-effort
                pass

    direct1 = run_direct(1)
    direct_n = direct1 if n == 1 else run_direct(n)
    fleet1_healthy, _, fleet1_failed = run_fleet(1, kill=False)
    if n == 1:
        healthy, failure, fleet_failed = (fleet1_healthy, None, 0)
    else:
        healthy, failure, fleet_failed = run_fleet(n, kill=True)
    # the four decode p50s are quoted side by side and decide nothing
    p_dir = direct1["decode_step_p50_s"]
    p_dir_n = direct_n["decode_step_p50_s"]
    p_one = fleet1_healthy["decode_step_p50_s"]
    p_n = healthy["decode_step_p50_s"]
    line = {
        "fleet": n,
        "max_batch": MAX_BATCH,
        "requests_per_window": n_requests,
        "fleet_tokens_per_sec": (
            healthy["generated_tokens"] / healthy["wall_s"]
            if healthy["wall_s"] > 0 else 0.0),
        "decode_step_p50_s": p_n,
        "fleet1_decode_step_p50_s": p_one,
        "baseline_decode_step_p50_s": p_dir,
        "baseline_n_decode_step_p50_s": p_dir_n,
        "ttft_p50_s": healthy["ttft_p50_s"],
        "ttft_p99_s": healthy["ttft_p99_s"],
        "jit_recompiles": (direct1["jit_recompiles"]
                           + direct_n["jit_recompiles"]
                           + fleet1_healthy["jit_recompiles"]
                           + healthy["jit_recompiles"]
                           + (failure["jit_recompiles"]
                              if failure else 0)),
        "jit_recompiles_windows": {
            "direct": direct1["jit_recompiles"],
            "direct_n": direct_n["jit_recompiles"],
            "fleet1": fleet1_healthy["jit_recompiles"],
            "healthy": healthy["jit_recompiles"],
            "failure": failure["jit_recompiles"] if failure else 0,
        },
        "failed_requests": fleet_failed + fleet1_failed,
        "failovers": failure["failovers"] if failure else 0,
        "migrated_requests": (failure["migrated_requests"]
                              if failure else 0),
        "router_retries": (failure["router_retries"]
                           if failure else 0),
        # the failure window's own latency picture (decode was
        # delay-widened there, so these are failover numbers, not
        # hot-path numbers)
        "failure_window": None if failure is None else {
            "ttft_p50_s": failure["ttft_p50_s"],
            "ttft_p99_s": failure["ttft_p99_s"],
            "tokens_per_sec": (
                failure["generated_tokens"] / failure["wall_s"]
                if failure["wall_s"] > 0 else 0.0),
        },
    }
    print(json.dumps(line, sort_keys=True))
    checks = [
        ("fleet produced throughput",
         healthy["generated_tokens"] > 0),
        ("every measured window compile-free",
         line["jit_recompiles"] == 0),
        ("the routed windows generated what the router-free ones did",
         healthy["generated_tokens"] == direct_n["generated_tokens"]
         and fleet1_healthy["generated_tokens"]
         == direct1["generated_tokens"]),
        ("no failed requests", line["failed_requests"] == 0),
    ]
    if n > 1:
        checks += [
            ("no replica was declared dead before the kill",
             healthy["failovers"] == 0),
            ("replica kill triggered a failover",
             line["failovers"] >= 1),
            ("failure-window requests all completed",
             failure is not None
             and failure["generated_tokens"] > 0),
        ]
    bad = [name for name, ok in checks if not ok]
    if bad:
        print(f"FAIL (fleet lane): {bad}", file=sys.stderr)
        return 1
    return 0


# --------------------------------------------------------------------
# overload lane (ISSUE 19): 3x sustained overload against one engine,
# controllers on vs off.  The controlled run must hold interactive SLO
# attainment >= 0.95 while batch arrivals shed with truthful 429s and
# decode-time preemption frees slots; the no-controller baseline serves
# the same arrival sequence and BREACHES the interactive SLO — the
# evidence that shedding beats queueing once the queue wait passes the
# deadline.  One JSON line per class + a baseline/summary pair; gates:
# attainment, sheds on both sides, >=1 decode preemption, >=1 brownout
# transition, and jit_recompiles == 0 in both measured windows.
# --------------------------------------------------------------------

#: the overload lane's class taxonomy: deadline budgets arm SLO-aware
#: admission (ISSUE 19) — batch's tiny budget makes it the load shed
#: first, interactive's must survive the 3x burst on a loaded CI box
OVERLOAD_SLO = {"interactive": 0.5, "standard": 0.3, "batch": 0.05}


def run_overload_lane(argv) -> int:
    import time as _time
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import (ContinuousBatchingEngine,
                                                 EngineSaturated)
    from paddle_tpu.inference.scheduler import PriorityClass
    from paddle_tpu.testing import faults

    monitor.install_compile_hooks()
    MAX_BATCH = 4
    MAX_QUEUE = 32
    interactive_n = _int_arg(argv, "interactive", 16)
    batch_tail_n = _int_arg(argv, "batch-tail", 8)
    model = _build_tiny_model()

    def overload_classes():
        return tuple(
            PriorityClass(name, rank=rank, weight=weight,
                          preemptible=(name == "batch"),
                          deadline_s=OVERLOAD_SLO[name])
            for name, rank, weight in (("interactive", 0, 8),
                                       ("standard", 1, 4),
                                       ("batch", 2, 1)))

    def run(controlled):
        """One overload run; same arrival sequence either way."""
        kw = (dict(scheduler_classes=overload_classes(),
                   brownout_thresholds=(0.25, 0.6, 0.85, 1.0),
                   brownout_patience=3, decode_preempt=True)
              if controlled else dict(decode_preempt=False))
        rng = np.random.default_rng(5)
        nsub = [0]
        with ContinuousBatchingEngine(
                model, total_pages=192, page_size=PAGE_SIZE,
                max_batch=MAX_BATCH, max_queue=MAX_QUEUE,
                min_table_pages=16, **kw) as eng:

            def submit(max_new, priority):
                nsub[0] += 1
                return eng.submit(
                    rng.integers(0, 64, (6,)).astype("int32"),
                    max_new_tokens=max_new, priority=priority,
                    seed=nsub[0])

            # the decode delay runs through warm-up AND the measured
            # window: the admission controller projects queue wait from
            # the PROCESS-GLOBAL decode p50, so the warm decodes must
            # land in the same histogram bucket the overloaded decodes
            # will
            faults.install(faults.FaultPlan(
                [{"site": "decode_step", "kind": "delay",
                  "delay_s": 0.008}]))
            try:
                # warm: decode buckets 1/2/4 + the 8-token prefill
                # bucket, so the measured window is compile-free.
                # Warm under the STANDARD class: compile-time TTFTs
                # would otherwise land in the interactive attainment
                # window and pre-escalate the brownout ladder the
                # measured window is supposed to drive
                for b in (1, 2, MAX_BATCH):
                    for r in [submit(4, "standard") for _ in range(b)]:
                        r.result(timeout=600)
                deadline = _time.monotonic() + 30
                while _time.monotonic() < deadline and \
                        eng.scheduler_info()["brownout_level"] > 0:
                    _time.sleep(0.002)     # idle engine resets the ladder
                # saturate: a batch flood takes every slot into decode —
                # the squatters the interactive burst must displace.
                # Admit one at a time: a queued batch flood would trip
                # batch's own (deliberately tiny) deadline budget
                sat = []
                for _ in range(MAX_BATCH):
                    r = submit(64, "batch")
                    deadline = _time.monotonic() + 120
                    while _time.monotonic() < deadline \
                            and r.seq_id is None:
                        _time.sleep(0.002)
                    sat.append(r)
                deadline = _time.monotonic() + 120
                while _time.monotonic() < deadline and not all(
                        len(r.generated) >= 1 for r in sat):
                    _time.sleep(0.002)

                before = monitor.snapshot()
                t0 = _time.perf_counter()
                inter = []
                inter_shed = [0]
                for _ in range(interactive_n):     # the 3x burst
                    try:
                        inter.append((_time.perf_counter(),
                                      submit(4, "interactive")))
                    except EngineSaturated:
                        # only a pathologically slow box sheds the top
                        # class; count it as a missed SLO, not a crash
                        inter_shed[0] += 1
                if controlled:
                    # the ladder reacts within an iteration or two;
                    # gate the batch tail on it so the band shed is
                    # deterministic, not a race with the control loop
                    deadline = _time.monotonic() + 30
                    while _time.monotonic() < deadline and \
                            eng.scheduler_info()["brownout_level"] < 1:
                        _time.sleep(0.001)
                shed = 0
                retry_hints = []
                for _ in range(batch_tail_n):      # arrivals to shed
                    try:
                        sat.append(submit(8, "batch"))
                    except EngineSaturated as e:
                        shed += 1
                        retry_hints.append(
                            getattr(e, "retry_after_s", None))
                ttfts = []
                for t_sub, r in inter:
                    r.result(timeout=600)
                    ttfts.append(r.first_token_at - t_sub)
                # a shed interactive is a missed SLO (999s sentinel
                # keeps the JSON line standard)
                ttfts += [999.0] * inter_shed[0]
                wall = _time.perf_counter() - t0
                after = monitor.snapshot()
                for r in sat:                      # admitted batch work
                    r.result(timeout=600)          # all still completes
            finally:
                faults.clear()
            info = eng.scheduler_info()

        slo = OVERLOAD_SLO["interactive"]
        att = (sum(1 for t in ttfts if t <= slo) / len(ttfts))
        _, _, compile_n = _hist_delta(before, after,
                                      "jit_compile_seconds")
        return {
            "attainment": att,
            "ttfts": ttfts,
            "shed_submits": shed,
            "retry_hints": [h for h in retry_hints if h],
            "wall_s": wall,
            "jit_recompiles": int(compile_n),
            "decode_preemptions": int(_counter_delta(
                before, after, "decode_preemptions_total")),
            "brownout_transitions": int(_counter_delta(
                before, after, "engine_brownout_transitions_total")),
            "sheds_by_class": {
                cls: int(_counter_delta(
                    before, after, "sched_shed_on_arrival_total",
                    labels={"cls": cls}))
                for cls in ("interactive", "standard", "batch")},
            "scheduler": info,
        }

    # p50-bucket straddles and CPU contention both move TTFTs on a CI
    # box; one retry absorbs a noisy run, a real controller regression
    # fails twice (the same contract the journal/fleet lanes use)
    attempts = 0
    while True:
        attempts += 1
        ctl = run(controlled=True)
        base = run(controlled=False)
        good = (ctl["attainment"] >= 0.95 and base["attainment"] < 0.95
                and ctl["jit_recompiles"] == 0
                and base["jit_recompiles"] == 0)
        if good or attempts >= 2:
            break
    for cls in ("interactive", "standard", "batch"):
        cinfo = ctl["scheduler"]["classes"][cls]
        print(json.dumps({
            "lane": "overload", "class": cls,
            "deadline_s": OVERLOAD_SLO[cls],
            "slo_attainment": (ctl["attainment"]
                               if cls == "interactive"
                               else cinfo["slo_attainment"]),
            "sheds": ctl["sheds_by_class"][cls],
            "queue_depth_end": cinfo["queued"],
        }, sort_keys=True))
    print(json.dumps({
        "lane": "overload", "class": None,
        "interactive_burst": interactive_n,
        "batch_tail": batch_tail_n,
        "controlled_attainment": ctl["attainment"],
        "controlled_ttft_p50_s": _p50(ctl["ttfts"]),
        "controlled_ttft_max_s": max(ctl["ttfts"]),
        "baseline_attainment": base["attainment"],
        "baseline_ttft_p50_s": _p50(base["ttfts"]),
        "baseline_ttft_max_s": max(base["ttfts"]),
        "decode_preemptions": ctl["decode_preemptions"],
        "brownout_transitions": ctl["brownout_transitions"],
        "brownout_level_end": ctl["scheduler"]["brownout_level"],
        "retry_after_hints": ctl["retry_hints"],
        "jit_recompiles": (ctl["jit_recompiles"]
                           + base["jit_recompiles"]),
    }, sort_keys=True))
    checks = [
        ("controlled interactive attainment >= 0.95 under 3x overload "
         f"({ctl['attainment']:.3f})", ctl["attainment"] >= 0.95),
        ("controlled run shed batch arrivals "
         f"({ctl['shed_submits']})", ctl["shed_submits"] >= 1),
        ("shed counter tracked the sheds per class",
         ctl["sheds_by_class"]["batch"] >= ctl["shed_submits"]
         and ctl["sheds_by_class"]["batch"] >= 1),
        ("every shed carried a truthful Retry-After",
         len(ctl["retry_hints"]) == ctl["shed_submits"]
         and all(1 <= h <= 30 for h in ctl["retry_hints"])),
        ("decode-time preemption freed slots for the burst "
         f"({ctl['decode_preemptions']})",
         ctl["decode_preemptions"] >= 1),
        ("brownout ladder engaged "
         f"({ctl['brownout_transitions']} transitions)",
         ctl["brownout_transitions"] >= 1),
        ("no-controller baseline breached the interactive SLO "
         f"({base['attainment']:.3f})", base["attainment"] < 0.95
         and base["attainment"] < ctl["attainment"]),
        ("no-controller baseline shed nothing",
         base["shed_submits"] == 0
         and base["sheds_by_class"]["batch"] == 0),
        ("baseline never decode-preempted",
         base["decode_preemptions"] == 0),
        ("both measured windows compile-free",
         ctl["jit_recompiles"] == 0 and base["jit_recompiles"] == 0),
    ]
    bad = [name for name, ok in checks if not ok]
    if bad:
        print(f"FAIL (overload lane): {bad}", file=sys.stderr)
        return 1
    return 0


# --------------------------------------------------------------------
# fleet overload lane (ISSUE 19 tentpole d): sustained overload against
# a 1-replica fleet drives the autoscaler's control law — >=1 scale-up
# under pressure, the new replica warms and serves a compile-free
# measured window, then calm drains-and-retires it back to the floor —
# with zero failed requests end to end.  evaluate() is driven
# deterministically (it is public exactly for this); the supervisor's
# probe thread supplies the fresh health the control law reads.
# --------------------------------------------------------------------

def run_overload_fleet_lane(argv) -> int:
    import tempfile
    import threading
    import time as _time
    import urllib.request
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.inference.server import GenerationServer
    from paddle_tpu.inference.fleet import (FleetAutoscaler, FleetRouter,
                                            ReplicaSupervisor)
    from paddle_tpu.testing import faults

    monitor.install_compile_hooks()
    MAX_BATCH = 4
    root = tempfile.mkdtemp(prefix="overload-fleet-")
    rng = np.random.default_rng(7)

    def factory(name, jdir):
        return GenerationServer(
            _build_tiny_model(), total_pages=128, page_size=PAGE_SIZE,
            max_batch=MAX_BATCH, max_queue=64, journal_dir=jdir,
            journal_fsync="os",
            brownout_thresholds=(0.25, 0.6, 0.85, 1.0))

    counter = [0]
    failed = [0]

    def post_wave(urls, k, max_new=4, join=True):
        outs, threads = {}, []
        for j in range(k):
            counter[0] += 1
            body = {"input_ids":
                    [rng.integers(0, 64, (6,)).tolist()],
                    "max_new_tokens": max_new, "seed": counter[0],
                    "priority": "interactive",
                    "request_id": f"ov-{counter[0]}"}
            url = urls[j % len(urls)]

            def go(b=body, u=url):
                try:
                    req = urllib.request.Request(
                        u + "/generate", data=json.dumps(b).encode(),
                        headers={"Content-Type": "application/json"})
                    with urllib.request.urlopen(req, timeout=600) as r:
                        outs[b["request_id"]] = json.loads(r.read())
                except Exception:   # noqa: BLE001
                    failed[0] += 1
            t = threading.Thread(target=go, daemon=True)
            t.start()
            threads.append(t)
        if join:
            for t in threads:
                t.join(timeout=600)
        return outs, threads

    def warm(urls):
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": WARM_DECODE_DELAY_S}]))
        try:
            for b in (1, 2, MAX_BATCH):
                post_wave(urls, b * len(urls))
        finally:
            faults.clear()

    sup = ReplicaSupervisor(
        factory=factory, replicas=1, journal_root=root,
        probe_interval_s=0.05, probe_failure_threshold=3,
        probe_timeout_s=2.0, heartbeat_timeout_s=10.0)
    router = FleetRouter(sup)
    scaler = FleetAutoscaler(sup, min_replicas=1, max_replicas=2,
                             scale_up_depth=4.0, scale_down_depth=0.5,
                             up_patience=2, down_patience=5,
                             cooldown_s=0.5, drain_timeout_s=60.0)
    before_all = monitor.snapshot()
    sup.start()
    router.start()
    try:
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 60 \
                and len(sup.routable_replicas()) < 1:
            _time.sleep(0.02)
        url = f"http://{router.host}:{router.port}"
        warm([url])

        # ---- overload: a delayed flood piles queue depth onto the
        # single replica; the control law must answer with ONE spawn
        faults.install(faults.FaultPlan(
            [{"site": "decode_step", "kind": "delay",
              "delay_s": 0.02}]))
        scaled_up = False
        try:
            _, threads = post_wave([url], 16, max_new=8, join=False)
            t0 = _time.monotonic()
            while _time.monotonic() - t0 < 120 and not scaled_up:
                scaled_up = scaler.evaluate() == "up"
                _time.sleep(0.05)
            for t in threads:
                t.join(timeout=600)
        finally:
            faults.clear()
        routable_peak = len(sup.routable_replicas())

        # ---- the NEW replica compiles outside the measured window
        new_urls = [f"http://{r.server.host}:{r.server.port}"
                    for r in sup.routable_replicas()]
        warm(new_urls)
        before = monitor.snapshot()
        post_wave([url], 8)
        after = monitor.snapshot()
        _, _, compile_n = _hist_delta(before, after,
                                      "jit_compile_seconds")

        # ---- calm: depth 0, ladders at rung 0 -> drain-then-retire
        # the newest replica back down to the floor
        scaled_down = False
        t0 = _time.monotonic()
        while _time.monotonic() - t0 < 180 and not scaled_down:
            scaled_down = scaler.evaluate() == "down"
            _time.sleep(0.05)
        routable_end = len(sup.routable_replicas())
    finally:
        try:
            router.stop()
            sup.stop()
        except Exception:   # noqa: BLE001 — teardown best-effort
            pass
    after_all = monitor.snapshot()

    line = {
        "lane": "overload_fleet",
        "scale_ups": scaler.scale_ups,
        "scale_downs": scaler.scale_downs,
        "routable_peak": routable_peak,
        "routable_end": routable_end,
        "failed_requests": failed[0],
        "jit_recompiles": int(compile_n),
        "scale_events_up": int(_counter_delta(
            before_all, after_all, "fleet_scale_events_total",
            labels={"direction": "up"})),
        "scale_events_down": int(_counter_delta(
            before_all, after_all, "fleet_scale_events_total",
            labels={"direction": "down"})),
        "autoscaler": scaler.info(),
    }
    print(json.dumps(line, sort_keys=True))
    checks = [
        ("overload scaled the fleet up", scaler.scale_ups >= 1
         and line["scale_events_up"] >= 1),
        ("the spawned replica became routable", routable_peak == 2),
        ("measured window on the scaled fleet compile-free",
         line["jit_recompiles"] == 0),
        ("calm drained-and-retired back to the floor",
         scaler.scale_downs >= 1 and line["scale_events_down"] >= 1
         and routable_end == 1),
        ("zero failed requests across the whole lane",
         failed[0] == 0),
    ]
    bad = [name for name, ok in checks if not ok]
    if bad:
        print(f"FAIL (overload fleet lane): {bad}", file=sys.stderr)
        return 1
    return 0


def _int_arg(argv, name, default):
    return next((int(a.split("=", 1)[1]) for a in argv
                 if a.startswith(f"--{name}=")), default)


def _float_arg(argv, name, default):
    return next((float(a.split("=", 1)[1]) for a in argv
                 if a.startswith(f"--{name}=")), default)


def _fault_plan_arg(argv):
    """--fault-plan=<inline JSON or @path> -> FaultPlan or None."""
    spec = next((a.split("=", 1)[1] for a in argv
                 if a.startswith("--fault-plan=")), None)
    if spec is None:
        return None
    from paddle_tpu.testing.faults import FaultPlan
    if spec.startswith("@"):
        with open(spec[1:]) as f:
            spec = f.read()
    return FaultPlan.from_json(spec)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from paddle_tpu.framework.compile_cache import configure_compile_cache
    configure_compile_cache()
    if "--scenario-matrix" in argv:
        # heterogeneous-workload lane (ISSUE 7): chat + RAG + offline
        # batch through the scheduler, one JSON line per class plus a
        # summary gating chat TTFT under a long-prompt flood
        return run_scenario_matrix(argv)
    if "--quant" in argv:
        # quantized-serving lane (ISSUE 9): equal-byte pools, capacity
        # ratio + logits-escape-hatch greedy parity + recompile gates
        return run_quant_lane(argv)
    if "--journal" in argv:
        # write-ahead-journal overhead lane (ISSUE 13): decode p50
        # with journaling on quoted beside off, compile-free, with
        # journal_bytes/journal_fsync_p50 in the JSON line
        return run_journal_lane(argv)
    if any(a == "--tp" or a.startswith("--tp=") for a in argv):
        # tensor-parallel lane (ISSUE 20): 1-chip vs TP-sharded engine
        # at equal global batch — tokens/sec/chip, priced collectives,
        # per-chip pool bytes, bit-exact greedy parity.  Exact-match on
        # the flag: --tps-floor belongs to the quant lane.
        return run_tp_lane(argv)
    if "--overload-fleet" in argv:
        # fleet overload lane (ISSUE 19): sustained overload scales a
        # 1-replica fleet up, the new replica serves a compile-free
        # window, calm drains it back down — zero failed requests
        return run_overload_fleet_lane(argv)
    if "--overload" in argv:
        # overload lane (ISSUE 19): 3x interactive burst against a
        # batch-saturated engine, controllers on vs off — attainment,
        # shed counts, brownout transitions, preemptions per class
        return run_overload_lane(argv)
    if any(a.startswith("--fleet") for a in argv):
        # fleet lane (ISSUE 14): N supervised replicas behind the
        # router, a replica kill mid-window, failover/migration counts
        # + TTFT during the failure window, gated recompile-free with
        # the router adding no hot-path cost
        return run_fleet_lane(argv)
    baseline = "--baseline" in argv
    plan = _fault_plan_arg(argv)
    kw = dict(sharers=_int_arg(argv, "sharers", 6),
              uniques=_int_arg(argv, "uniques", 3),
              system_tokens=_int_arg(argv, "system-tokens", 16),
              max_new_tokens=_int_arg(argv, "max-new-tokens", 8),
              vocab=_int_arg(argv, "vocab", 64),
              hidden=_int_arg(argv, "hidden", 32),
              do_sample="--sample" in argv,
              sample_on_device=not baseline,
              prefix_cache=not baseline,
              fault_plan=plan,
              replay_batch=(False if "--no-replay-batch" in argv
                            else True if "--replay-batch" in argv
                            else None))
    spec_k = _int_arg(argv, "spec-k", 3)
    if "--sweep" in argv:
        # acceptance-rate sweep: a no-draft baseline line, then the
        # speculative lane at increasing draft degradation — the
        # accept-rate/tokens-per-sec/TTFT curve in raw JSON lines.
        # An explicit --draft-noise joins the ladder rather than being
        # silently ignored.
        base = run_bench(**kw)
        print(json.dumps(base, sort_keys=True))
        ok = base["generated_tokens"] > 0
        levels = sorted({0.0, 0.03, 0.1, 0.5,
                         _float_arg(argv, "draft-noise", 0.0)})
        for noise in levels:
            out = run_bench(draft=True, spec_k=spec_k,
                            draft_noise=noise, **kw)
            out["baseline_tokens_per_sec"] = base["tokens_per_sec"]
            out["baseline_ttft_p50_s"] = base["ttft_p50_s"]
            print(json.dumps(out, sort_keys=True))
            ok = ok and out["generated_tokens"] > 0 \
                and out["jit_recompiles"] == 0
            if noise == 0.0:
                # a perfect draft must accept ~everything and beat the
                # plain engine's hard ceiling of max_batch tokens per
                # compiled decode step
                ok = ok and out["spec_accept_rate"] is not None \
                    and out["spec_accept_rate"] >= 0.7 \
                    and out["tokens_per_step"] > out["max_batch"]
        return 0 if ok else 1
    out = run_bench(draft="--draft" in argv, spec_k=spec_k,
                    draft_noise=_float_arg(argv, "draft-noise", 0.0),
                    **kw)
    print(json.dumps(out, sort_keys=True))
    if "--draft" in argv and plan is None:
        if not out["spec_proposed_tokens"]:
            print("FAIL: speculative lane proposed nothing",
                  file=sys.stderr)
            return 1
        if _float_arg(argv, "draft-noise", 0.0) == 0.0 \
                and (out["spec_accept_rate"] < 0.7
                     or out["tokens_per_step"] <= out["max_batch"]):
            print(f"FAIL: clone draft accept rate "
                  f"{out['spec_accept_rate']:.3f} / "
                  f"{out['tokens_per_step']:.2f} tokens per step — the "
                  "verify step is not converting acceptance into "
                  "multi-token advances", file=sys.stderr)
            return 1
    if out["generated_tokens"] <= 0 or out["decode_steps"] <= 0:
        print("FAIL: bench decoded nothing", file=sys.stderr)
        return 1
    if plan is None and out["failed_requests"] != 0:
        print(f"FAIL: {out['failed_requests']} request(s) failed with no "
              "fault plan installed", file=sys.stderr)
        return 1
    if plan is not None:
        # chaos lane: the blast radius must stay inside the plan — at
        # most one failed request per injected error rule, and the
        # workload still produced throughput after the failures
        budget = plan.error_rule_count()
        if out["failed_requests"] > budget:
            print(f"FAIL: {out['failed_requests']} failed requests for "
                  f"{budget} injected error fault(s) — isolation leaked",
                  file=sys.stderr)
            return 1
        if out["tokens_per_sec"] <= 0:
            print("FAIL: no surviving throughput after injected faults",
                  file=sys.stderr)
            return 1
        # recovery lane (ISSUE 8): a device-fault plan (buffer_loss /
        # engine_wedge rules) must show the recovery machinery ENGAGED
        # — survivors replayed, a rebuild counted, and an MTTR sample
        # in engine_recovery_seconds — with EVERY survivor completing
        # (failed_requests stays within the error budget above; a
        # transient buffer loss costs zero failures)
        device_rules = [r for r in plan.rules
                        if r.site in ("buffer_loss", "engine_wedge")]
        if device_rules:
            if all(r._fires == 0 for r in device_rules):
                print("FAIL: the plan's device-fault rules never fired "
                      "— the recovery lane measured nothing (lower nth "
                      "or grow the workload)", file=sys.stderr)
                return 1
            if out["survivor_replays"] <= 0 \
                    or out["engine_rebuilds"] <= 0:
                print("FAIL: device-fault plan fired but no survivor "
                      "replay/rebuild was counted — recovery did not "
                      "engage", file=sys.stderr)
                return 1
            if out["mttr_p50_s"] is None:
                print("FAIL: recovery ran but engine_recovery_seconds "
                      "saw no sample — MTTR unmeasured", file=sys.stderr)
                return 1
        return 0
    if not baseline and out["prefix_hit_rate"] <= 0:
        print("FAIL: shared-prefix workload saw no prefix-cache hits",
              file=sys.stderr)
        return 1
    if out["program_flops"] <= 0 or out["mfu"] is None:
        # ISSUE 10 acceptance: every serve_bench line must carry the
        # cost-analyzer numbers so BENCH rounds get the MFU ladder free
        print("FAIL: cost analyzer produced no program FLOPs / MFU for "
              "the measured window", file=sys.stderr)
        return 1
    if out["spmd"]["peak_hbm_bytes"] <= 0:
        # ISSUE 11 acceptance: the tier-3 field group must carry a
        # real static HBM verdict for the dispatched decode program
        print("FAIL: spmd auditor produced no peak-HBM estimate",
              file=sys.stderr)
        return 1
    if out["jit_recompiles"] != 0:
        # ROADMAP telemetry finding (ISSUE 4 satellite): warm-up covers
        # every decode-batch bucket, so the measured window of a warm
        # serving loop must be compile-free
        print(f"FAIL: measured window compiled "
              f"{out['jit_recompiles']} program(s); warm-up missed a "
              "bucket", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
