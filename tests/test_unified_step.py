"""Unified ragged serving step (ISSUE 17): the engine's whole
iteration — decode rows, chunked-prefill spans, prefix-hit suffixes
and speculative verify blocks — runs as ONE compiled dispatch of the
ragged program, for every model, and nothing else carries such a row.
The correctness anchor is a reference that shares no code with the
ragged step: eager ``model.generate`` for greedy rows (speculation is
exact greedy), ``fused_sample`` over the eager logits at (seed,
absolute position) for sampled rows, ``PagedGenerator`` over an int8
cache for int8 pages.  The structural anchor is the dispatch counter: a
window issues ragged-mode dispatches ONLY, with or without a fault
plan, and a failed dispatch is retried on the ragged step without
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


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    from paddle_tpu.testing import faults
    yield
    faults.clear()


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
    monitor.snapshot() dicts (every label the counter has ever had: a
    mode that is gone must stay at zero)."""
    return {mode: int(_counter(after, "engine_dispatches_total", mode)
                      - _counter(before, "engine_dispatches_total", mode))
            for mode in ("ragged", "prefill", "chunk", "decode",
                         "verify", "draft")}


def _eager(model, prompts, budgets):
    """Greedy tokens of the eager model, a prompt at a time: no page, no
    compiled step, no engine."""
    outs = []
    for p, m in zip(prompts, budgets):
        out = model.generate(paddle.to_tensor(np.asarray(p)[None]),
                             max_new_tokens=m)
        outs.append(np.asarray(out.numpy() if hasattr(out, "numpy")
                               else out)[0])
    return outs


def _eager_sampled(model, prompt, budget, temperature, seed):
    """The sampler's contract from the outside: the token at absolute
    position ``n`` is ``fused_sample`` of the eager logits after ``n``
    tokens, keyed by (seed, n)."""
    from paddle_tpu.inference.paged import fused_sample
    ids = [int(t) for t in prompt]
    for _ in range(budget):
        logits = model(paddle.to_tensor(np.asarray(ids, np.int32)[None]))
        row = np.asarray(logits.numpy(), np.float32)[0, -1]
        tok = fused_sample(row[None], np.array([seed], np.uint32),
                           np.array([len(ids)], np.int32),
                           np.array([temperature], np.float32),
                           np.array([True]))
        ids.append(int(np.asarray(tok)[0]))
    return np.asarray(ids, np.int32)


def _run(model, prompts, budgets, submit_kw=None, timeout=300, **kw):
    """Serve the prompt set; returns (outputs, steps, dispatch deltas).
    ``submit_kw`` is one dict per request (sampling etc.)."""
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    submit_kw = submit_kw or [{}] * len(prompts)
    with ContinuousBatchingEngine(model, total_pages=128, page_size=8,
                                  max_batch=4, **kw) as eng:
        before = monitor.snapshot()
        reqs = [eng.submit(p, max_new_tokens=m, **skw)
                for p, m, skw in zip(prompts, budgets, submit_kw)]
        outs = [r.result(timeout=timeout) for r in reqs]
        steps = eng.steps
        after = monitor.snapshot()
    return outs, steps, _dispatch_deltas(before, after)


def _assert_rows_equal(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _ragged_only(disp, also=()):
    """Every dispatch of the window was the ragged program's (and the
    modes in ``also``)."""
    assert disp["ragged"] > 0, disp
    assert all(v == 0 for m, v in disp.items()
               if m != "ragged" and m not in also), disp


class TestUnifiedParity:
    """The ragged step against references that share no code with it,
    on every serving mode, individually and composed in one step."""

    def test_decode_parity(self, target):
        prompts, budgets = _prompts([3, 5, 9]), [6, 8, 4]
        got, steps, disp = _run(target, prompts, budgets)
        _assert_rows_equal(got, _eager(target, prompts, budgets))
        assert steps > 0
        # an unchunked engine prefills a whole prompt on its own program
        _ragged_only(disp, also=("prefill",))

    def test_chunked_prefill_parity(self, target):
        """Chunk spans (including the sampled final chunk) ride the
        ragged program."""
        prompts, budgets = _prompts([40, 24, 6], seed=1), [6, 6, 6]
        got, steps, disp = _run(target, prompts, budgets,
                                prefill_chunk_tokens=16)
        _assert_rows_equal(got, _eager(target, prompts, budgets))
        _ragged_only(disp)

    def test_sampled_parity(self, target):
        """On-device sampling (seeds + temperatures) draws, through the
        ragged program, what the sampler draws from the eager logits at
        the same (seed, absolute position)."""
        prompts, budgets = _prompts([4, 7, 11], seed=2), [8, 8, 8]
        skw = [dict(do_sample=True, temperature=t, seed=s)
               for t, s in ((0.7, 11), (1.3, 12), (1.0, 13))]
        got, _, _ = _run(target, prompts, budgets, submit_kw=skw)
        want = [_eager_sampled(target, p, m, kw["temperature"], kw["seed"])
                for p, m, kw in zip(prompts, budgets, skw)]
        _assert_rows_equal(got, want)

    def test_spec_and_chunk_composed_step_parity(self, target,
                                                 bad_draft):
        """The COMPOSED mixed step: a long chunking prompt admitted
        alongside speculating decode rows, so one dispatch carries
        chunk spans AND verify blocks.  Speculation is exact greedy:
        the output is the eager model's, whatever the draft proposes."""
        prompts = _prompts([40, 5, 9], seed=3)
        budgets = [6, 10, 8]
        got, steps, disp = _run(target, prompts, budgets,
                                draft_model=bad_draft, spec_tokens=3,
                                prefill_chunk_tokens=16)
        _assert_rows_equal(got, _eager(target, prompts, budgets))
        # the draft model is a SECOND model: its propose/ingest
        # dispatches never fold into the target's unified program
        _ragged_only(disp, also=("draft",))
        assert disp["draft"] > 0

    def test_int8_kv_parity(self, target):
        """int8 KV rows dequantize inside the ragged kernel to what the
        decode program of ``PagedGenerator`` reads from an int8 cache,
        token for token."""
        from paddle_tpu.inference.paged import PagedGenerator
        prompts, budgets = _prompts([24, 6, 9], seed=4), [6, 6, 6]
        got, _, disp = _run(target, prompts, budgets,
                            kv_quant="int8", prefill_chunk_tokens=16)
        gen = PagedGenerator(target, total_pages=128, page_size=8,
                             kv_dtype="int8")
        want = [np.asarray(gen.generate(p[None], max_new_tokens=m))[0]
                for p, m in zip(prompts, budgets)]
        _assert_rows_equal(got, want)
        _ragged_only(disp)

    def test_prefix_hit_parity(self, target):
        """Prefix-cache hits shorten a row's span (suffix-only
        prefill); hit rows must produce the eager model's tokens."""
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        rng = np.random.default_rng(5)
        system = rng.integers(0, 64, (16,)).astype(np.int32)
        prompts = [np.concatenate([system,
                                   rng.integers(0, 64, (n,))
                                   ]).astype(np.int32)
                   for n in (5, 7)]
        with ContinuousBatchingEngine(
                target, total_pages=128, page_size=8, max_batch=4,
                prefill_chunk_tokens=16) as eng:
            before = monitor.snapshot()
            # sequenced: the first request must REGISTER the
            # prefix before the second can hit it
            got = [eng.submit(p, max_new_tokens=6).result(timeout=300)
                   for p in prompts]
            after = monitor.snapshot()
        assert (_counter(after, "prefix_cache_hits_total")
                - _counter(before, "prefix_cache_hits_total")) >= 1
        _assert_rows_equal(got, _eager(target, prompts, [6, 6]))


def _flaky(real, fail_calls):
    """Stand-in for ``ragged_step``: raises on the numbered calls (1 is
    the first) BEFORE the program runs, and is the real step otherwise."""
    calls = [0]

    def step(*a, **kw):
        calls[0] += 1
        if calls[0] in fail_calls:
            raise RuntimeError(f"injected ragged dispatch failure "
                               f"(call {calls[0]})")
        return real(*a, **kw)
    return step, calls


class TestUnifiedStructure:
    def test_unified_window_is_single_program(self, target):
        """Every serving phase of a chunked window dispatches the
        ragged program — zero prefill/chunk programs, and the modes the
        counter no longer has stay at zero."""
        prompts, budgets = _prompts([40, 6, 9], seed=6), [6, 6, 6]
        _, _, uni = _run(target, prompts, budgets,
                         prefill_chunk_tokens=16)
        _ragged_only(uni)

    def test_live_engine_journal_witnesses_one_dispatch(self, target,
                                                        tmp_path):
        """Every step record the engine journals carries
        ``n == 1, mode == "ragged"`` — one dispatch an iteration
        witnessed in the WAL, not just in aggregate counters."""
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

    def test_failed_dispatch_is_retried_on_the_ragged_step(self, target):
        """A ragged dispatch failure rolls the step back and runs the
        SAME step again through the SAME program: tokens exact, every
        failed step counted, no request quarantined, nothing else
        dispatched."""
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        prompts, budgets = _prompts([5, 9], seed=7), [8, 6]
        with ContinuousBatchingEngine(target, total_pages=128,
                                      page_size=8, max_batch=4,
                                      prefill_chunk_tokens=16) as eng:
            before = monitor.snapshot()
            eng._decoder.ragged_step, calls = _flaky(
                eng._decoder.ragged_step, {1, 4})
            reqs = [eng.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, budgets)]
            outs = [r.result(timeout=300) for r in reqs]
            after = monitor.snapshot()
            assert eng.cache.free_pages == eng.cache.total_pages
        _assert_rows_equal(outs, _eager(target, prompts, budgets))
        assert calls[0] > 4
        assert (_counter(after, "engine_unified_fallbacks_total")
                - _counter(before, "engine_unified_fallbacks_total")) == 2
        assert (_counter(after, "decode_retries_total")
                - _counter(before, "decode_retries_total")) == 2
        assert (_counter(after, "quarantined_requests_total")
                == _counter(before, "quarantined_requests_total"))
        _ragged_only(_dispatch_deltas(before, after))

    def test_three_failed_steps_in_a_row_trip_no_latch(self, target):
        """Three consecutive steps each fail their first dispatch: the
        engine has no other composition to fall to and no count of
        failures to latch on, so the fourth step, and every step after
        it, is still the ragged program's."""
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        prompts, budgets = _prompts([5, 9, 3], seed=11), [10, 10, 10]
        with ContinuousBatchingEngine(target, total_pages=128,
                                      page_size=8, max_batch=4,
                                      prefill_chunk_tokens=16) as eng:
            before = monitor.snapshot()
            # calls 1, 3, 5: each step's first try fails, its retry runs
            eng._decoder.ragged_step, calls = _flaky(
                eng._decoder.ragged_step, {1, 3, 5})
            reqs = [eng.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, budgets)]
            outs = [r.result(timeout=300) for r in reqs]
            after = monitor.snapshot()
        _assert_rows_equal(outs, _eager(target, prompts, budgets))
        assert (_counter(after, "engine_unified_fallbacks_total")
                - _counter(before, "engine_unified_fallbacks_total")) == 3
        disp = _dispatch_deltas(before, after)
        _ragged_only(disp)
        # the steps after the third failure were dispatched too
        assert disp["ragged"] == calls[0] > 6

    def test_delay_pacing_plan_stays_unified(self, target):
        """A delay-kind rule on a dispatch site is pacing: the unified
        step fires prefill/prefill_chunk/decode_step itself, so
        throttling plans (bench backpressure, trace timing probes) slow
        the ragged program — warm-up and measurement keep compiling the
        SAME programs."""
        from paddle_tpu.testing import faults

        prompts, budgets = _prompts([5, 9], seed=10), [5, 5]
        plan = faults.FaultPlan([{"site": "decode_step", "kind": "delay",
                                  "delay_s": 0.002}])
        with faults.installed(plan):
            got, _, disp = _run(target, prompts, budgets,
                                prefill_chunk_tokens=16)
        _assert_rows_equal(got, _eager(target, prompts, budgets))
        _ragged_only(disp)

    def test_error_plan_iterations_stay_on_the_ragged_step(self, target):
        """An error-kind plan's iterations run the ragged step like any
        other: the injected fault fires at its documented site, the
        failed step is retried there, and the output still matches."""
        from paddle_tpu.testing import faults

        prompts, budgets = _prompts([5, 9], seed=8), [6, 6]
        plan = faults.FaultPlan([{"site": "decode_step", "nth": 2}])
        with faults.installed(plan):
            got, _, disp = _run(target, prompts, budgets,
                                prefill_chunk_tokens=16)
        assert [f[0] for f in plan.fired] == ["decode_step"]
        _assert_rows_equal(got, _eager(target, prompts, budgets))
        _ragged_only(disp)


def _pow2s(upto):
    out, v = [], 1
    while v <= upto:
        out.append(v)
        v *= 2
    return out


class TestPackedTokenBound:
    """ISSUE 32: the engine promises its decoder a bound on a step's
    tokens and the ragged programs' dense layers are packed to it.  A
    wrong bound would not raise to the caller — the step would go down
    the failure ladder and its rows be quarantined — so the tests count
    fallbacks and read every ``dispatch`` record."""

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
                after = monitor.snapshot()
        finally:
            monitor.stop_capture()
        fallbacks = (_counter(after, "engine_unified_fallbacks_total")
                     - _counter(before, "engine_unified_fallbacks_total"))
        return outs, bound, fallbacks, \
            monitor.get_tracer().step_records()

    @pytest.mark.parametrize("spec", [False, True], ids=["plain", "draft"])
    def test_every_step_is_inside_the_bound(self, target, bad_draft, spec):
        outs, bound, fallbacks, records = self._serve(
            target, bad_draft if spec else None)
        per_row = 3 if spec else 1
        assert bound == (2 * self.CHUNK - 1) + (self.BATCH - 1) * per_row
        assert fallbacks == 0
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
            assert prog._cache_size() == built
            assert reg.get("jit_recompile_count").value() == compiles
