"""Unified ragged serving step (ISSUE 17): the engine's whole
iteration — decode rows, chunked-prefill spans, prefix-hit suffixes
and speculative verify blocks — runs as ONE compiled dispatch of the
ragged program.  The correctness anchor is parity: token-for-token
identical output to the legacy multi-dispatch composition
(``unified_step=False``) on every serving mode, individually and
composed in the same step.  The structural anchor is the dispatch
counter: a unified window issues ragged-mode dispatches ONLY, and a
dispatch failure falls back to the legacy composition without
changing a single token."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def tiny_model(seed=0, layers=2):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=layers, num_attention_heads=4,
                      num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def target():
    return tiny_model(0)


@pytest.fixture(scope="module")
def bad_draft():
    """Different seed -> proposals rarely match: partial-acceptance
    verify rows, the adversarial exactness case."""
    return tiny_model(7)


def _prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, (n,)).astype(np.int32) for n in sizes]


def _counter(snap, name, mode=None):
    total = 0.0
    for s in snap.get(name, {}).get("series", ()):
        if mode is None or s.get("labels", {}).get("mode") == mode:
            total += s["value"]
    return total


def _dispatch_deltas(before, after):
    """engine_dispatches_total per-mode delta between two
    monitor.snapshot() dicts."""
    return {mode: int(_counter(after, "engine_dispatches_total", mode)
                      - _counter(before, "engine_dispatches_total", mode))
            for mode in ("ragged", "prefill", "chunk", "decode",
                         "verify", "draft")}


def _run(model, prompts, budgets, unified, submit_kw=None, timeout=300,
         **kw):
    """Serve the prompt set; returns (outputs, steps, dispatch deltas).
    ``submit_kw`` is one dict per request (sampling etc.)."""
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    submit_kw = submit_kw or [{}] * len(prompts)
    with ContinuousBatchingEngine(model, total_pages=128, page_size=8,
                                  max_batch=4, unified_step=unified,
                                  **kw) as eng:
        before = monitor.snapshot()
        reqs = [eng.submit(p, max_new_tokens=m, **skw)
                for p, m, skw in zip(prompts, budgets, submit_kw)]
        outs = [r.result(timeout=timeout) for r in reqs]
        steps = eng.steps
        after = monitor.snapshot()
    return outs, steps, _dispatch_deltas(before, after)


def _assert_rows_equal(got, want):
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


class TestUnifiedParity:
    """unified_step=True vs the legacy composition on the SAME
    workload: identical tokens, identical step counts."""

    def test_decode_parity(self, target):
        prompts, budgets = _prompts([3, 5, 9]), [6, 8, 4]
        ref, ref_steps, _ = _run(target, prompts, budgets, unified=False)
        got, steps, disp = _run(target, prompts, budgets, unified=True)
        _assert_rows_equal(got, ref)
        # iteration counts depend on admission timing (the loop thread
        # races submit()), so bound rather than pin them
        assert steps > 0 and ref_steps > 0
        assert disp["ragged"] > 0

    def test_chunked_prefill_parity(self, target):
        """Chunk spans (including the sampled final chunk) ride the
        ragged program; the chunk plan itself is unchanged."""
        prompts, budgets = _prompts([40, 24, 6], seed=1), [6, 6, 6]
        ref, ref_steps, _ = _run(target, prompts, budgets, unified=False,
                                 prefill_chunk_tokens=16)
        got, steps, disp = _run(target, prompts, budgets, unified=True,
                                prefill_chunk_tokens=16)
        _assert_rows_equal(got, ref)
        assert steps == ref_steps
        assert disp["chunk"] == disp["prefill"] == 0

    def test_sampled_parity(self, target):
        """On-device sampling (seeds + temperatures) reproduces
        bit-identically through the unified program."""
        prompts, budgets = _prompts([4, 7, 11], seed=2), [8, 8, 8]
        skw = [dict(do_sample=True, temperature=t, seed=s)
               for t, s in ((0.7, 11), (1.3, 12), (1.0, 13))]
        ref, _, _ = _run(target, prompts, budgets, unified=False,
                         submit_kw=skw)
        got, _, _ = _run(target, prompts, budgets, unified=True,
                         submit_kw=skw)
        _assert_rows_equal(got, ref)

    def test_spec_and_chunk_composed_step_parity(self, target,
                                                 bad_draft):
        """The COMPOSED mixed step: a long chunking prompt admitted
        alongside speculating decode rows, so one dispatch carries
        chunk spans AND verify blocks.  Output must equal both the
        legacy spec composition and plain target-only greedy (the
        spec exactness anchor), with zero verify-mode dispatches."""
        prompts = _prompts([40, 5, 9], seed=3)
        budgets = [6, 10, 8]
        plain, _, _ = _run(target, prompts, budgets, unified=False)
        ref, ref_steps, _ = _run(target, prompts, budgets, unified=False,
                                 draft_model=bad_draft, spec_tokens=3,
                                 prefill_chunk_tokens=16)
        got, steps, disp = _run(target, prompts, budgets, unified=True,
                                draft_model=bad_draft, spec_tokens=3,
                                prefill_chunk_tokens=16)
        _assert_rows_equal(got, ref)
        _assert_rows_equal(got, plain)
        assert steps == ref_steps
        assert disp["verify"] == disp["chunk"] == disp["decode"] == 0
        # the draft model is a SECOND model: its propose/ingest
        # dispatches never fold into the target's unified program
        assert disp["draft"] > 0

    def test_int8_kv_parity(self, target):
        """int8 KV rows dequantize inside the ragged kernel exactly as
        in the legacy per-mode programs."""
        prompts, budgets = _prompts([24, 6, 9], seed=4), [6, 6, 6]
        ref, _, _ = _run(target, prompts, budgets, unified=False,
                         kv_quant="int8", prefill_chunk_tokens=16)
        got, _, disp = _run(target, prompts, budgets, unified=True,
                            kv_quant="int8", prefill_chunk_tokens=16)
        _assert_rows_equal(got, ref)
        assert disp["ragged"] > 0 and disp["decode"] == 0

    def test_prefix_hit_parity(self, target):
        """Prefix-cache hits shorten a row's span (suffix-only
        prefill); hit rows must produce identical tokens through the
        unified program."""
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        rng = np.random.default_rng(5)
        system = rng.integers(0, 64, (16,)).astype(np.int32)
        prompts = [np.concatenate([system,
                                   rng.integers(0, 64, (n,))
                                   ]).astype(np.int32)
                   for n in (5, 7)]
        outs = {}
        for unified in (False, True):
            with ContinuousBatchingEngine(
                    target, total_pages=128, page_size=8, max_batch=4,
                    prefill_chunk_tokens=16,
                    unified_step=unified) as eng:
                before = monitor.snapshot()
                # sequenced: the first request must REGISTER the
                # prefix before the second can hit it
                a = eng.submit(prompts[0],
                               max_new_tokens=6).result(timeout=300)
                b = eng.submit(prompts[1],
                               max_new_tokens=6).result(timeout=300)
                after = monitor.snapshot()
                outs[unified] = (a, b)

            assert (_counter(after, "prefix_cache_hits_total")
                    - _counter(before, "prefix_cache_hits_total")) >= 1
        _assert_rows_equal(outs[True], outs[False])


class TestUnifiedStructure:
    def test_unified_window_is_single_program(self, target):
        """Every serving phase in a unified window dispatches the
        ragged program — zero prefill/chunk/decode/verify programs;
        the legacy engine on the same workload shows the
        multi-dispatch composition the unified step collapses."""
        prompts, budgets = _prompts([40, 6, 9], seed=6), [6, 6, 6]
        _, _, uni = _run(target, prompts, budgets, unified=True,
                         prefill_chunk_tokens=16)
        _, _, leg = _run(target, prompts, budgets, unified=False,
                         prefill_chunk_tokens=16)
        assert uni["ragged"] > 0
        assert all(uni[m] == 0 for m in ("prefill", "chunk", "decode",
                                         "verify"))
        assert leg["ragged"] == 0
        assert leg["decode"] > 0 and leg["chunk"] > 0
        total = lambda d: sum(v for m, v in d.items() if m != "draft")
        assert total(uni) < total(leg)

    def test_live_engine_journal_witnesses_one_dispatch(self, target,
                                                        tmp_path):
        """Every step record the unified engine journals carries
        ``n == 1, mode == "ragged"`` — the 5->1 collapse witnessed
        per iteration in the WAL, not just in aggregate counters."""
        import os

        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        from paddle_tpu.inference.journal import (RequestJournal,
                                                  _read_frames)

        d = str(tmp_path / "j")
        j = RequestJournal(d, fsync="always")
        try:
            with ContinuousBatchingEngine(target, total_pages=128,
                                          page_size=8, max_batch=4,
                                          prefill_chunk_tokens=16,
                                          unified_step=True,
                                          journal=j) as eng:
                reqs = [eng.submit(p, max_new_tokens=6)
                        for p in _prompts([24, 5], seed=9)]
                for r in reqs:
                    r.result(timeout=300)
                j.flush(sync=True, timeout=30)
        finally:
            j.close()
        raw = b"".join(
            open(os.path.join(d, f), "rb").read()
            for f in sorted(os.listdir(d))
            if f.endswith((".seg", ".seg.consumed")))
        steps = [r for r in _read_frames(raw) if r["t"] == "step"]
        assert steps
        assert all(r.get("n") == 1 and r.get("mode") == "ragged"
                   for r in steps)

    def test_dispatch_failure_falls_back_to_legacy_exactly(self, target):
        """A ragged dispatch failure rolls the composition back and
        re-runs the SAME iteration through the legacy programs: tokens
        identical, fallbacks counted, and repeated failure latches
        ``unified_step`` off for the engine's lifetime."""
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        prompts, budgets = _prompts([5, 9], seed=7), [8, 6]
        ref, _, _ = _run(target, prompts, budgets, unified=False)

        with ContinuousBatchingEngine(target, total_pages=128,
                                      page_size=8, max_batch=4,
                                      unified_step=True) as eng:
            before = monitor.snapshot()

            def broken(*a, **kw):
                raise RuntimeError("injected ragged dispatch failure")

            eng._decoder.ragged_step = broken
            reqs = [eng.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, budgets)]
            outs = [r.result(timeout=300) for r in reqs]
            after = monitor.snapshot()
            assert eng._unified_off   # >= 3 consecutive failures latch
        _assert_rows_equal(outs, ref)
        assert (_counter(after, "engine_unified_fallbacks_total")
                - _counter(before, "engine_unified_fallbacks_total")) >= 3

    def test_delay_pacing_plan_stays_unified(self, target):
        """A delay-kind rule on a dispatch site is pacing, not failure
        injection: the unified step fires prefill/prefill_chunk/
        decode_step itself, so throttling plans (bench backpressure,
        trace timing probes) slow the ragged program instead of
        diverting the window to legacy — warm-up and measurement keep
        compiling the SAME programs."""
        from paddle_tpu.testing import faults

        prompts, budgets = _prompts([5, 9], seed=10), [5, 5]
        ref, _, _ = _run(target, prompts, budgets, unified=False)
        plan = faults.FaultPlan([{"site": "decode_step", "kind": "delay",
                                  "delay_s": 0.002}])
        with faults.installed(plan):
            got, _, disp = _run(target, prompts, budgets, unified=True)
        _assert_rows_equal(got, ref)
        assert disp["ragged"] > 0 and disp["decode"] == 0

    def test_fault_plan_iterations_divert_to_legacy(self, target):
        """Chaos quarantine semantics are defined per legacy dispatch,
        so an iteration under an engine-site fault plan runs the
        legacy composition — the injected fault fires at its
        documented site and the output still matches."""
        from paddle_tpu.testing import faults

        prompts, budgets = _prompts([5, 9], seed=8), [6, 6]
        ref, _, _ = _run(target, prompts, budgets, unified=False)
        plan = faults.FaultPlan([{"site": "decode_step", "nth": 2}])
        with faults.installed(plan):
            got, _, disp = _run(target, prompts, budgets, unified=True)
        _assert_rows_equal(got, ref)
        assert disp["ragged"] == 0 and disp["decode"] > 0


def _pow2s(upto):
    out, v = [], 1
    while v <= upto:
        out.append(v)
        v *= 2
    return out


class TestPackedTokenBound:
    """ISSUE 32: the engine promises its decoder a bound on a step's
    tokens and the ragged programs' dense layers are packed to it.  A
    wrong bound would not raise to the caller — the step would fall
    back to the legacy composition and, after three, latch the unified
    step off — so the tests count fallbacks and read every ``dispatch``
    record."""

    CHUNK, BATCH = 16, 4
    #: lengths that leave tails of 5, 11, 2, 13, 7 and 9 tokens, so a
    #: prompt's tail and the next prompt's full chunk share steps
    SIZES = (21, 43, 50, 29, 39, 25)

    def _serve(self, target, draft=None):
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        kw = {} if draft is None else dict(draft_model=draft, spec_tokens=2)
        monitor.start_capture(host_events=False)
        try:
            with ContinuousBatchingEngine(
                    target, total_pages=256, page_size=8,
                    max_batch=self.BATCH, min_table_pages=16,
                    prefill_chunk_tokens=self.CHUNK, **kw) as eng:
                before = monitor.snapshot()
                bound = eng._decoder.step_tokens
                reqs = [eng.submit(p, max_new_tokens=6)
                        for p in _prompts(self.SIZES, seed=32)]
                outs = [r.result(timeout=300) for r in reqs]
                latched = eng._unified_off
                after = monitor.snapshot()
        finally:
            monitor.stop_capture()
        fallbacks = (_counter(after, "engine_unified_fallbacks_total")
                     - _counter(before, "engine_unified_fallbacks_total"))
        return outs, bound, fallbacks, latched, \
            monitor.get_tracer().step_records()

    @pytest.mark.parametrize("spec", [False, True], ids=["plain", "draft"])
    def test_every_step_is_inside_the_bound(self, target, bad_draft, spec):
        outs, bound, fallbacks, latched, records = self._serve(
            target, bad_draft if spec else None)
        per_row = 3 if spec else 1
        assert bound == (2 * self.CHUNK - 1) + (self.BATCH - 1) * per_row
        assert fallbacks == 0 and not latched
        assert [len(o) for o in outs] == [n + 6 for n in self.SIZES]
        disp = [r for r in records if r["kind"] == "dispatch"]
        assert disp
        for r in disp:
            assert r["tokens"] <= r["tokens_padded"] \
                <= r["rows_padded"] * r["span_padded"], r
            assert r["tokens_padded"] <= -(-bound // 16) * 16, r
        # the traffic does what the bound is derived from: some step
        # carries more prefill tokens than one chunk
        prefill = {}
        for r in records:
            if r["kind"] == "prefill_chunk":
                prefill[r["index"]] = prefill.get(r["index"], 0) + r["tokens"]
        assert max(prefill.values()) > self.CHUNK
        # and the pack bites: some step computes fewer positions than
        # its rectangle holds
        assert any(r["tokens_padded"] < r["rows_padded"] * r["span_padded"]
                   for r in disp)

    def test_no_program_is_built_after_a_warm_up_by_bucket(self, target):
        """The benchmark's warm-up sends one step per (rows bucket, span
        bucket): ``b - 1`` decoders and one ``s``-token prompt.  The
        packed width is a function of that key, so a mixed run after it
        — tails beside chunks, any number of rows — compiles nothing."""
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        reg = monitor.get_registry()
        rng = np.random.default_rng(5)
        with ContinuousBatchingEngine(
                target, total_pages=256, page_size=8, max_batch=self.BATCH,
                min_table_pages=16,
                prefill_chunk_tokens=self.CHUNK) as eng:
            back = []
            for b in _pow2s(self.BATCH):
                back += [eng.submit(rng.integers(0, 64, (1,)),
                                    max_new_tokens=100)
                         for _ in range(b - 1 - len(back))]
                for r in back:
                    while r.next_token is None and not r.done.is_set():
                        r.done.wait(0.002)
                for s in _pow2s(self.CHUNK):
                    eng.submit(rng.integers(0, 64, (s,)),
                               max_new_tokens=1).result(timeout=300)
            prog = eng._decoder._programs[("ragged", "greedy")]
            built = prog._cache_size()
            assert built == len(_pow2s(self.BATCH)) * len(_pow2s(self.CHUNK))
            compiles = reg.get("jit_recompile_count").value()
            for r in back:
                r.cancel()
            reqs = [eng.submit(p, max_new_tokens=6)
                    for p in _prompts(self.SIZES, seed=33)]
            for r in reqs:
                r.result(timeout=300)
            assert not eng._unified_off
            assert prog._cache_size() == built
            assert reg.get("jit_recompile_count").value() == compiles
