"""One unified step in flight (ISSUE 38): the serving loop dispatches
step n + 1 before it has committed step n, the decode rows of n + 1 fed
step n's tokens on the device (``JittedPagedDecoder.ragged_launch`` /
``ragged_fetch``, ``ContinuousBatchingEngine._pipeline``).

The correctness anchor is parity: served tokens identical to the loop in
the old order, which a test reaches by patching the ONE predicate
(``_overlap_hold``) to "never" — there is no argument for it.  The
structural anchors are counts: ``serve_steps_overlapped_total``,
``serve_overlap_drains_total{reason}``, ``serve_overlap_dropped_rows_total``,
the ``overlapped`` field and the intervals of the step ring's records.
No test here asserts a duration: a state that is needed is held (a
``ragged_fetch`` that sleeps keeps a long request running) and counted."""
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.inference import paged  # noqa: E402
from paddle_tpu.inference.continuous import (  # noqa: E402
    ContinuousBatchingEngine, DeadlineExceeded, RequestCancelled)
from paddle_tpu.inference.paged import JittedPagedDecoder  # noqa: E402
from paddle_tpu.inference.scheduler import PriorityClass  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache  # noqa: E402


def tiny_llama(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def llama():
    return tiny_llama(0)


@pytest.fixture(scope="module")
def brumby():
    """``tests/test_brumby.py``'s model: three power-retention layers, a
    recurrent slot a sequence."""
    from test_brumby import SEED, driver, model_cfg
    m = driver.build_model(model_cfg(), SEED)
    for _, p in m.named_parameters():
        p._data = p._data.astype(jnp.float32)
    return m


@pytest.fixture(params=["llama", "brumby"])
def served(request):
    """(model, engine options, vocabulary) of the two kinds of step: K/V
    pages, and a recurrent slot beside them."""
    model = request.getfixturevalue(request.param)
    if request.param == "llama":
        return model, dict(total_pages=128, page_size=8, max_batch=4,
                           prefill_chunk_tokens=8), 64
    return model, dict(total_pages=64, page_size=16, max_batch=4,
                       prefill_chunk_tokens=16), 96


def counters():
    """{name{labels}: value} of this PR's three counters."""
    out = {}
    for name, m in monitor.snapshot().items():
        if m["type"] == "counter" and name.startswith(
                ("serve_steps_overlapped", "serve_overlap_")):
            for s in m["series"]:
                labels = s.get("labels") or {}
                out[name + (":" + labels["reason"] if labels else "")] = \
                    s["value"]
    return out


class counted:
    """``with counted() as d:`` — ``d`` holds the counters' increase."""

    def __enter__(self):
        self.before, self.delta = counters(), {}
        return self.delta

    def __exit__(self, *exc):
        for k, v in counters().items():
            if v - self.before.get(k, 0):
                self.delta[k] = v - self.before.get(k, 0)
        return False


def never(monkeypatch):
    monkeypatch.setattr(ContinuousBatchingEngine, "_overlap_hold",
                        lambda self: "never")


def prompts_of(sizes, vocab, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, (n,)).astype(np.int32) for n in sizes]


def wait_for(cond, what, timeout=120.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if cond():
            return
        time.sleep(0.002)
    raise AssertionError(f"timed out waiting for {what}")


def all_returned(eng):
    """Every page, reservation and slot is back."""
    wait_for(lambda: eng.cache.free_pages == eng.cache.total_pages
             and eng._flight is None, "the pool to reclaim")
    assert eng._reserved_pages == eng._pad_pages
    assert eng.cache.slots_in_use == 0
    assert not eng.cache._seq_pages and not eng.cache._seq_len


def serve(model, opts, prompts, budgets, **submit_kw):
    with ContinuousBatchingEngine(model, **opts) as eng:
        reqs = [eng.submit(p, max_new_tokens=m, **submit_kw)
                for p, m in zip(prompts, budgets)]
        outs = [r.result(timeout=300) for r in reqs]
        all_returned(eng)
        return outs, eng.steps


def slowed(eng, seconds=0.01):
    """Every fetch of ``eng`` takes at least ``seconds``: a request of
    many tokens is then still running when the test acts on it.  (An
    attribute of the decoder INSTANCE; ``ragged_step`` is untouched, so
    the engine still leaves steps in flight.)"""
    real = eng._decoder.ragged_fetch

    def slow(flight):
        time.sleep(seconds)
        return real(flight)
    eng._decoder.ragged_fetch = slow


# ------------------------------------------------------- served tokens
class TestServedTokens:
    SIZES = [5, 19, 9, 30, 3, 12, 25]       # more requests than rows;
    BUDGETS = [8, 6, 12, 5, 9, 7, 10]       # chunks beside decoders

    def test_mixed_greedy_load_is_identical_to_the_old_order(
            self, served, monkeypatch):
        model, opts, vocab = served
        prompts = prompts_of(self.SIZES, vocab, seed=3)
        with counted() as on:
            got, steps = serve(model, opts, prompts, self.BUDGETS)
        with monkeypatch.context() as m, counted() as off:
            never(m)
            want, steps_old = serve(model, opts, prompts, self.BUDGETS)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        # every step but the first had one in flight; the old order never
        assert on["serve_steps_overlapped_total"] >= steps - 2
        assert "serve_steps_overlapped_total" not in off
        assert off["serve_overlap_drains_total:never"] >= steps_old
        # a request is admitted at most one step later than it was
        assert steps_old <= steps <= steps_old + len(prompts)

    def test_sampled_rows_draw_the_same_tokens(self, llama, monkeypatch):
        """The on-device sampler draws by (seed, absolute position): a
        token that stays on the device is the token the host would have
        handed back."""
        opts = dict(total_pages=128, page_size=8, max_batch=4,
                    prefill_chunk_tokens=8)
        prompts = prompts_of([6, 17, 11], 64, seed=5)
        kw = dict(do_sample=True, temperature=0.9, seed=77)
        with counted() as on:
            got, _ = serve(llama, opts, prompts, [9, 7, 8], **kw)
        with monkeypatch.context() as m:
            never(m)
            want, _ = serve(llama, opts, prompts, [9, 7, 8], **kw)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert on["serve_steps_overlapped_total"] > 0

    def test_a_request_ends_at_its_eos_and_its_row_in_flight_is_dropped(
            self, served):
        model, opts, vocab = served
        prompt = prompts_of([11], vocab, seed=9)[0]
        free, _ = serve(model, opts, [prompt], [24])
        tail = free[0][len(prompt):].tolist()
        # a token first met well inside the run: the request rides in the
        # step after the one that feeds it, and that step is in flight
        j = next(i for i in range(2, 20) if tail[i] not in tail[:i])
        with counted() as d, ContinuousBatchingEngine(model, **opts) as eng:
            out = eng.submit(prompt, max_new_tokens=24,
                             eos_token_id=tail[j]).result(timeout=300)
            all_returned(eng)
        assert out[len(prompt):].tolist() == tail[:j + 1]   # AT the EOS
        assert d["serve_overlap_dropped_rows_total"] == 1
        assert d["serve_steps_overlapped_total"] >= j

    def test_an_eos_known_at_launch_rides_no_further(self, llama):
        """A restored request's pending token is on the host: if it is
        the EOS, the step that feeds it is known to be its last."""
        opts = dict(total_pages=128, page_size=8, max_batch=4,
                    prefill_chunk_tokens=8)
        prompt = prompts_of([9], 64, seed=4)[0]
        free, _ = serve(llama, opts, [prompt], [6])
        tail = free[0][len(prompt):].tolist()
        snap = {"version": 1, "requests": [{
            "prompt": prompt.tolist(), "generated": tail[:2],
            "next_token": tail[2], "max_new_tokens": 6,
            "eos_token_id": tail[2], "request_id": "restored-eos"}]}
        with counted() as d, ContinuousBatchingEngine(llama, **opts) as eng:
            out = eng.restore(snap)[0].result(timeout=300)
            all_returned(eng)
        assert out[len(prompt):].tolist() == tail[:3]
        assert "serve_overlap_dropped_rows_total" not in d


# ------------------------------------------------------------ failures
class TestFailureAtFetch:
    @pytest.mark.parametrize("lose", [False, True],
                             ids=["host_error", "buffers_lost"])
    def test_both_steps_unwind_and_the_survivors_tokens_stand(
            self, served, lose):
        """The fetch of step n fails with step n + 1 dispatched: n + 1
        is unwound first, then n; the pools are repaired once; the
        ladder runs with n's rows; every request's tokens equal an
        undisturbed run's."""
        model, opts, vocab = served
        prompts = prompts_of([5, 19, 9, 30], vocab, seed=13)
        budgets = [10, 8, 12, 6]
        want, _ = serve(model, opts, prompts, budgets)
        seen = {}
        with ContinuousBatchingEngine(model, **opts) as eng:
            real = eng._decoder.ragged_fetch
            calls = [0]

            def failing(flight):
                calls[0] += 1
                if calls[0] == 6:
                    seen["newer"] = eng._flight
                    seen["lengths"] = {
                        s: eng.cache.length(s) for s in flight.seq_ids}
                    if lose:
                        for a in eng.cache._device_pools():
                            a.delete()
                    eng._decoder.ragged_discard(flight, failed=True)
                    raise RuntimeError("planted at the fetch")
                return real(flight)
            eng._decoder.ragged_fetch = failing
            before = monitor.snapshot()
            reqs = [eng.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, budgets)]
            outs = [r.result(timeout=300) for r in reqs]
            after = monitor.snapshot()
            all_returned(eng)
        assert seen["newer"] is not None        # a newer step WAS launched
        for g, w in zip(outs, want):
            np.testing.assert_array_equal(g, w)

        def total(snap, name):
            return sum(s["value"] for s in snap[name]["series"])
        assert total(after, "engine_unified_fallbacks_total") \
            - total(before, "engine_unified_fallbacks_total") == 1
        rebuilt = total(after, "engine_rebuilds_total") \
            - total(before, "engine_rebuilds_total")
        # a recurrent slot the failed steps updated cannot be rolled back
        assert rebuilt == (1 if lose or eng._recurrent else 0)

    def test_a_launch_that_fails_over_a_step_in_flight_is_planned_again(
            self, llama):
        opts = dict(total_pages=128, page_size=8, max_batch=4,
                    prefill_chunk_tokens=8)
        prompts = prompts_of([7, 21], 64, seed=17)
        want, _ = serve(llama, opts, prompts, [9, 7])
        with counted() as d, ContinuousBatchingEngine(llama, **opts) as eng:
            real = eng._decoder.ragged_launch
            calls = [0]

            def failing(*a, **kw):
                calls[0] += 1
                if calls[0] == 5:
                    assert eng._flight is not None
                    raise RuntimeError("planted at the launch")
                return real(*a, **kw)
            eng._decoder.ragged_launch = failing
            reqs = [eng.submit(p, max_new_tokens=m)
                    for p, m in zip(prompts, [9, 7])]
            outs = [r.result(timeout=300) for r in reqs]
            all_returned(eng)
        for g, w in zip(outs, want):
            np.testing.assert_array_equal(g, w)
        assert d["serve_overlap_drains_total:launch_failed"] == 1


# ------------------------------------------------- the drains, by reason
class TestDrains:
    OPTS = dict(total_pages=128, page_size=8, max_batch=4,
                prefill_chunk_tokens=8)

    def test_cancel_lands_the_step_in_flight_first(self, llama):
        prompt = prompts_of([9], 64, seed=21)[0]
        with counted() as d, \
                ContinuousBatchingEngine(llama, **self.OPTS) as eng:
            slowed(eng)
            r = eng.submit(prompt, max_new_tokens=100)
            wait_for(lambda: len(r.generated) >= 3, "the victim to decode")
            assert eng._flight is not None or not r.done.is_set()
            r.cancel()
            with pytest.raises(RequestCancelled):
                r.result(timeout=300)
            all_returned(eng)
        assert d["serve_overlap_drains_total:cancel"] == 1
        assert len(r.generated) < 100

    def test_deadline_lands_the_step_in_flight_first(self, llama):
        prompt = prompts_of([9], 64, seed=22)[0]
        with counted() as d, \
                ContinuousBatchingEngine(llama, **self.OPTS) as eng:
            # the engine's programs compiled by a request served first:
            # the deadline then runs against steps, not against a compile
            eng.submit(prompt, max_new_tokens=4).result(timeout=300)
            slowed(eng, 0.02)
            r = eng.submit(prompt, max_new_tokens=100, ttl_s=0.5)
            with pytest.raises(DeadlineExceeded):
                r.result(timeout=300)
            all_returned(eng)
        assert d["serve_overlap_drains_total:deadline"] == 1
        assert 0 < len(r.generated) < 100

    def test_tpot_preemption_lands_the_step_in_flight_first(self, llama):
        classes = (
            PriorityClass("interactive", rank=0, weight=8,
                          tpot_budget_s=1e-4),
            PriorityClass("standard", rank=1, weight=4),
            PriorityClass("batch", rank=2, weight=1, preemptible=True))
        prompt = prompts_of([16], 64, seed=30)[0]
        want, _ = serve(llama, self.OPTS, [prompt], [12])
        preempted = monitor.get_registry().get("decode_preemptions_total")
        before = preempted.value()
        with counted() as d, ContinuousBatchingEngine(
                llama, **dict(self.OPTS, max_batch=2),
                scheduler_classes=classes, default_class="standard",
                tpot_preempt_cooldown_s=0.0) as eng:
            slowed(eng)
            rb = eng.submit(prompt, max_new_tokens=12, priority="batch")
            wait_for(lambda: len(rb.generated) >= 2, "the victim to decode")
            ri = eng.submit(prompts_of([5], 64, seed=31)[0],
                            max_new_tokens=6, priority="interactive")
            ri.result(timeout=300)
            got = rb.result(timeout=300)
            all_returned(eng)
        assert preempted.value() > before
        assert d["serve_overlap_drains_total:preempt"] >= 1
        np.testing.assert_array_equal(got, want[0])   # paused and resumed

    def test_a_snapshot_gets_its_cut_between_steps(self, llama):
        prompt = prompts_of([9], 64, seed=23)[0]
        want, _ = serve(llama, self.OPTS, [prompt], [40])
        with counted() as d, \
                ContinuousBatchingEngine(llama, **self.OPTS) as eng:
            slowed(eng)
            r = eng.submit(prompt, max_new_tokens=40)
            wait_for(lambda: len(r.generated) >= 3, "the request to decode")
            snap = eng.snapshot()
            out = r.result(timeout=300)
            all_returned(eng)
        np.testing.assert_array_equal(out, want[0])
        assert d["serve_overlap_drains_total:snapshot"] >= 1
        # the cut continues the stream: its tokens, then its pending one
        (e,) = snap["requests"]
        tail = want[0][len(prompt):].tolist()
        n = len(e["generated"])
        assert e["generated"] == tail[:n] and e["next_token"] == tail[n]

    @pytest.mark.parametrize("kind", ["spec", "host_sampling", "unchunked",
                                      "replaced", "fault_plan"])
    def test_what_the_engine_sees_in_itself_never_overlaps(self, llama,
                                                           kind):
        from paddle_tpu.testing import faults
        opts = dict(self.OPTS)
        if kind == "spec":
            opts.update(draft_model=tiny_llama(7), spec_tokens=2)
        elif kind == "host_sampling":
            opts.update(sample_on_device=False)
        elif kind == "unchunked":
            opts.update(prefill_chunk_tokens=None)
        prompts = prompts_of([6, 13], 64, seed=25)
        plan = faults.FaultPlan([{"site": "decode_step", "kind": "delay",
                                  "delay_s": 0.0}])
        with counted() as d, ContinuousBatchingEngine(llama, **opts) as eng:
            if kind == "replaced":
                real = eng._decoder.ragged_step
                eng._decoder.ragged_step = lambda *a, **kw: real(*a, **kw)
            try:
                if kind == "fault_plan":
                    faults.install(plan)
                for r in [eng.submit(p, max_new_tokens=6) for p in prompts]:
                    r.result(timeout=300)
            finally:
                faults.clear()
            steps = eng.steps
        assert "serve_steps_overlapped_total" not in d
        assert d[f"serve_overlap_drains_total:{kind}"] >= steps

    def test_the_journal_rows_are_todays(self, llama, monkeypatch):
        """The journal's rows are written at the commit, with the step's
        own tokens: one step in flight or none, the same rows."""
        def rows(hold):
            class Journal:
                def __init__(self):
                    self.rows = {}

                def append_step(self, admitted, rows, **kw):
                    for rid, toks, nxt in rows:
                        self.rows.setdefault(rid, []).append(
                            (list(toks), nxt))

                def append_admit(self, *a, **kw):
                    pass
                append_retire = append_pages = append_admit

            j = Journal()
            with monkeypatch.context() as m:
                if hold:
                    never(m)
                with ContinuousBatchingEngine(llama, journal=j,
                                              **self.OPTS) as eng:
                    for i, p in enumerate(prompts_of([6, 13], 64, seed=26)):
                        eng.submit(p, max_new_tokens=7,
                                   request_id=f"r{i}").result(timeout=300)
            return j.rows
        assert rows(False) == rows(True)


# ---------------------------------------------------------- the records
class TestRecords:
    def test_ring_intervals_do_not_overlap_and_say_what_overlapped(
            self, llama):
        monitor.start_capture(max_steps=4096, host_events=False)
        try:
            serve(llama, TestDrains.OPTS, prompts_of([5, 19, 9], 64, 33),
                  [8, 6, 10])
        finally:
            monitor.stop_capture()
        records = monitor.get_tracer().step_records()
        disp = [r for r in records if r["kind"] == "dispatch"]
        assert len(disp) > 8
        assert all(r["overlapped"] in (0, 1) for r in disp)
        assert disp[0]["overlapped"] == 0       # nothing before the first
        assert sum(r["overlapped"] for r in disp) >= len(disp) - 2
        for a, b in zip(disp, disp[1:]):
            assert a["start_ns"] < a["end_ns"] <= b["start_ns"]
            assert b["index"] >= a["index"]     # given at dispatch, in order
        # a step's records share its ONE interval
        spans = {}
        for r in records:
            if r["kind"] in ("dispatch", "decode", "prefill_chunk"):
                spans.setdefault((r["start_ns"], r["end_ns"]), set()).add(
                    r["kind"])
        assert len(spans) == len(disp)
        assert all("dispatch" in kinds for kinds in spans.values())

    def test_overlap_share_reads_a_share_zero_and_null(self):
        from readers import ring_ratio
        spec = json.loads((ROOT / "benchmark/layer_metrics/"
                           "engine.overlap_share.json").read_text())
        assert spec["reader"] == "ring_ratio"

        def ring(*recs):
            return {"steps": [dict(kind="dispatch", index=i, **r)
                              for i, r in enumerate(recs)]
                    + [{"kind": "decode", "index": 0, "batch": 3}]}
        mixed = ring({"rows": 2, "overlapped": 0}, {"rows": 6, "overlapped": 1},
                     {"rows": 8, "overlapped": 1})
        assert ring_ratio.read(spec["args"], mixed) == pytest.approx(87.5)
        assert ring_ratio.read(spec["args"], ring(
            {"rows": 4, "overlapped": 0}, {"rows": 4, "overlapped": 0})) == 0
        # the parent's records lack the field: nothing to read
        assert ring_ratio.read(spec["args"], ring(
            {"rows": 4}, {"rows": 8})) is None
        manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
        (entry,) = [m for m in manifest["per_layer"]
                    if m["name"] == "engine.overlap_share"]
        # every serving cell reports it: a cell a later PR adds joins the
        # END of the list (membership, not the list's length, is held)
        cells = entry.pop("workloads")
        assert entry == {
            "name": "engine.overlap_share", "unit": "%", "better": "higher",
            "source": "program_span", "layer": "scheduler / engine",
            "moves": "serve.tokens_per_s"}
        assert cells[:4] == ["mistral7b.serve.closed8",
                             "laguna-xs2.serve.agent8",
                             "brumby-14b.serve.reason16",
                             "phi4-flash.serve.reason32"]
        assert set(cells) == {w["name"] for w in manifest["workloads"]
                              if ".serve." in w["name"]}
        # appended: behind every metric the benchmark had before it
        names = [m["name"] for m in manifest["per_layer"]]
        assert names.index(entry["name"]) > names.index(
            "state.serve.slots_used")


# ---------------------------------------------------- the decoder's halves
def decoder_case(model, page_size, seed=41):
    dec = JittedPagedDecoder(model, step_tokens=48)
    rng = np.random.default_rng(seed)
    vocab = int(model.config.vocab_size)
    rows = [rng.integers(0, vocab, n).astype(np.int32) for n in (7, 1, 12)]
    return dec, rows, lambda: PagedKVCache.from_model(
        model, total_pages=32, page_size=page_size, state_slots=4)


def null(n):
    """Greedy rows: the fused tail's argmax, ids on the device."""
    return np.zeros(n, np.uint32), np.ones(n, np.float32), np.zeros(n, bool)


NULL3 = null(3)


class TestDecoderHalves:
    def test_ragged_step_is_launch_then_fetch(self, served):
        model, opts, _ = served
        dec, rows, new_cache = decoder_case(model, opts["page_size"])
        whole, halves = new_cache(), new_cache()
        sids, ctxs = ["a", "b", "c"], [0, 0, 0]
        out_w, acc_w = dec.ragged_step(whole, sids, rows, ctxs,
                                       sampling=NULL3)
        rec_w = dict(dec.last_dispatch)
        dec.last_dispatch = None
        flight = dec.ragged_launch(halves, sids, rows, ctxs, sampling=NULL3)
        assert dec.last_dispatch is None        # the flight's own, so far
        assert [halves.length(s) for s in sids] == [7, 1, 12]  # at once
        out_h, acc_h = dec.ragged_fetch(flight)
        np.testing.assert_array_equal(out_h, out_w)
        np.testing.assert_array_equal(acc_h, acc_w)
        assert [halves.length(s) for s in sids] \
            == [whole.length(s) for s in sids]
        assert dec.last_dispatch == rec_w and dec.last_dispatch is flight.record

    def test_a_fed_row_continues_as_the_host_would_have_fed_it(self, served):
        """Two decode steps after a mixed step: the tokens on the host
        path, and fed on the device from a step that was never fetched
        first."""
        model, opts, _ = served
        dec, rows, new_cache = decoder_case(model, opts["page_size"])
        sids = ["a", "b", "c"]

        def lens(cache):
            return [cache.length(s) for s in sids]
        host, dev = new_cache(), new_cache()
        o1, _ = dec.ragged_step(host, sids, rows, [0, 0, 0], sampling=NULL3)
        o2, _ = dec.ragged_step(host, sids[::-1], [o1[[i]] for i in (2, 1, 0)],
                                lens(host)[::-1], sampling=NULL3)
        o3, _ = dec.ragged_step(host, sids, [o2[[i]] for i in (2, 1, 0)],
                                lens(host), sampling=NULL3)
        f1 = dec.ragged_launch(dev, sids, rows, [0, 0, 0], sampling=NULL3)
        zero = [np.zeros(1, np.int32)] * 3      # what the host packs: nothing
        f2 = dec.ragged_launch(dev, sids[::-1], zero, lens(dev)[::-1],
                               sampling=NULL3, feed=(f1, [2, 1, 0]))
        # one row fed, two from the host: the select mixes them
        f3 = dec.ragged_launch(
            dev, sids, [zero[0], o2[[1]], o2[[0]]], lens(dev),
            sampling=NULL3, feed=(f2, [2, -1, -1]))
        for f, want in ((f1, o1), (f2, o2), (f3, o3)):
            np.testing.assert_array_equal(dec.ragged_fetch(f)[0], want)
        assert lens(dev) == lens(host)

    def test_the_feed_refuses_what_it_cannot_read(self, llama):
        dec, rows, new_cache = decoder_case(llama, 8)
        cache = new_cache()
        f1 = dec.ragged_launch(cache, ["a", "b", "c"], rows, [0, 0, 0],
                               sampling=NULL3)
        one = [np.zeros(1, np.int32)]
        with pytest.raises(ValueError, match="rows"):
            dec.ragged_launch(cache, ["a"], one, [7], sampling=null(1),
                              feed=(f1, [3]))
        assert cache.length("a") == 7           # rolled back, as a step is
        logits = dec.ragged_launch(cache, ["a"], one, [7])   # no sampling
        with pytest.raises(ValueError, match="logits"):
            dec.ragged_launch(cache, ["b"], one, [1], feed=(logits, [0]))
        dec.ragged_fetch(logits)

    def test_the_ragged_program_is_what_it_was_and_the_feed_is_not_fn(
            self, llama):
        """The benchmark finds the serving step's executions by the
        name ``jit_fn(`` and lowers the program again with its
        positional operands: the feed is two programs of its own."""
        dec, rows, new_cache = decoder_case(llama, 8)
        cache = new_cache()
        fn, _ = dec.program_fn("ragged", "greedy")
        assert fn.__name__ == "fn"

        def jaxpr():
            i32 = jnp.int32
            pools = dec._pool_args(cache)
            return str(jax.make_jaxpr(fn)(
                dec._param_arrays(), jnp.zeros((4, 16), i32),
                jnp.zeros(4, i32), jnp.ones(4, i32), jnp.zeros(64, i32),
                jnp.zeros(64, i32), jnp.zeros((4, 4), i32),
                jnp.zeros(4, i32), (), *pools, ()))
        before = jaxpr()
        f1 = dec.ragged_launch(cache, ["a", "b", "c"], rows, [0, 0, 0],
                               sampling=NULL3)
        f2 = dec.ragged_launch(cache, ["a"], [np.zeros(1, np.int32)], [7],
                               sampling=null(1), feed=(f1, [0]))
        dec.ragged_fetch(f1), dec.ragged_fetch(f2)
        assert jaxpr() == before
        assert len(dec._programs) == 1          # and no program beside it
        for prog in (paged._feed_tokens, paged._feed_ids):
            assert prog.__wrapped__.__name__.startswith("_feed_")
        # the feed's programs were compiled with the ragged program's
        # shape, fed or not: (4, 16) and (1, 1) here
        assert dec._feed_warm == {(4, 16), (1, 1)}

    def test_a_failed_fetch_undoes_the_step_and_what_it_left(self, served):
        model, opts, _ = served
        dec, rows, new_cache = decoder_case(model, opts["page_size"])
        cache = new_cache()
        flight = dec.ragged_launch(cache, ["a", "b", "c"], rows, [0, 0, 0],
                                   sampling=NULL3)
        gen = cache.generation

        class Lost:
            ndim = 1

            def __array__(self, *a, **kw):
                raise RuntimeError("the device lost it")
        flight.out = Lost()
        with pytest.raises(RuntimeError, match="lost it"):
            dec.ragged_fetch(flight)
        assert [cache.length(s) for s in "abc"] == [0, 0, 0]
        # K/V pages are rewritten by the retry; a slot updated in place
        # is not, so a recurrent model's pools are rebuilt
        assert cache.generation == gen + (1 if cache.state_pools else 0)
