"""Spans on the profiler's clock (ISSUE 27): ``monitor.span`` is the one
host-span primitive and also writes a ``jax.profiler.TraceAnnotation``,
the serving iteration's phases are such spans under one ``engine/step
<index>``, every ragged dispatch leaves one ``dispatch`` record in the
step ring, the compile hooks keep the seconds of each compile phase, and
``TrainStep`` names its model / loss / optimizer scopes.  No timing
assertion anywhere: only names, nesting and counts."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import RecordEvent

PHASES = ("engine/schedule", "engine/build", "engine/dispatch",
          "engine/fetch", "engine/commit")


def host_events(trace_dir):
    """[(name, start_ns, end_ns)] of every event of the trace's
    ``/host:CPU`` plane."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "the profiler wrote no trace"
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(ev.name, int(ev.start_ns),
                     int(ev.start_ns + ev.duration_ns))
                    for ev in line.events]
    return out


def named(events, name):
    return sorted((s, e) for n, s, e in events if n == name)


def inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


class TestSpanUnderTrace:
    @pytest.fixture(scope="class")
    def events(self, tmp_path_factory):
        d = str(tmp_path_factory.mktemp("span_trace"))
        jax.profiler.start_trace(d)
        try:
            with monitor.span("outer/span"):
                with monitor.span("inner/span"):
                    jnp.ones(8).block_until_ready()
                with RecordEvent("inner/record_event"):
                    pass
            with monitor.span("after/span"):
                pass
        finally:
            jax.profiler.stop_trace()
        return host_events(d)

    @pytest.mark.parametrize("name", ["inner/span", "inner/record_event"])
    def test_nested_on_the_host_plane(self, events, name):
        (outer,), (inner,) = named(events, "outer/span"), named(events, name)
        assert inside(inner, outer)

    def test_sequential_spans_do_not_nest(self, events):
        (outer,), (after,) = (named(events, "outer/span"),
                              named(events, "after/span"))
        assert after[0] >= outer[1]
        (a,), (b,) = (named(events, "inner/span"),
                      named(events, "inner/record_event"))
        assert b[0] >= a[1]

    def test_no_trace_running_costs_nothing_visible(self):
        h = monitor.histogram("span_clock_test_seconds", "test")
        with monitor.span("quiet/span", histogram=h) as sp:
            pass
        assert sp.elapsed is not None and h.sum_count()[1] >= 1

    def test_record_event_reaches_the_recorder_through_span(self):
        from paddle_tpu.profiler.record import get_recorder
        rec = get_recorder()
        rec.collect()
        rec.enable(True)
        try:
            ev = RecordEvent("explicit")
            ev.begin()
            ev.end()
            ev.end()                      # a second end is a no-op
        finally:
            rec.enable(False)
        assert [e.name for e in rec.collect()] == ["explicit"]


def tiny_model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


class TestEngineStepUnderTrace:
    row_lengths = []        # per dispatch, each real row's length after it

    @pytest.fixture(scope="class")
    def run(self, tmp_path_factory):
        """A tiny engine serves three prompts (chunked prefill + decode)
        under a jax.profiler trace and a capture window."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        d = str(tmp_path_factory.mktemp("engine_trace"))
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
                   for n in (5, 19, 9)]
        lens = []
        monitor.start_capture(host_events=False)
        jax.profiler.start_trace(d)
        try:
            # the engine stops inside the trace: the scheduler thread has
            # left its last ``engine/step`` span before the trace ends
            with ContinuousBatchingEngine(
                    tiny_model(), total_pages=128, page_size=8, max_batch=4,
                    prefill_chunk_tokens=8) as eng:
                real = eng._decoder.ragged_step

                def watched(cache, seq_ids, *a, **kw):
                    out = real(cache, seq_ids, *a, **kw)
                    lens.append(sum(cache.length(s) for s in seq_ids))
                    type(self).row_lengths.append(
                        [cache.length(s) for s in seq_ids])
                    return out
                eng._decoder.ragged_step = watched
                for r in [eng.submit(p, max_new_tokens=6) for p in prompts]:
                    r.result(timeout=300)
        finally:
            jax.profiler.stop_trace()
            monitor.stop_capture()
        records = monitor.get_tracer().step_records()
        return host_events(d), records, lens

    def test_one_step_span_and_one_record_per_dispatch(self, run):
        events, records, lens = run
        disp = [r for r in records if r["kind"] == "dispatch"]
        assert disp and len(disp) == len(lens)
        steps = {}
        for n, s, e in events:
            m = re.fullmatch(r"engine/step (\d+)", n)
            if m:
                steps.setdefault(int(m.group(1)), []).append((s, e))
        per_index = {}
        for r in disp:
            per_index[r["index"]] = per_index.get(r["index"], 0) + 1
        # a step span without a dispatch is an iteration that had
        # nothing to run; every dispatch has its span, index for index
        for index, n in per_index.items():
            assert len(steps.get(index, ())) >= n, (index, steps.keys())

    def test_phases_disjoint_ordered_and_inside_their_step(self, run):
        events, records, _ = run
        steps = sorted((s, e) for n, s, e in events
                       if n.startswith("engine/step "))
        phases = sorted((s, e, n) for n, s, e in events if n in PHASES)
        dispatching = 0
        for step in steps:
            mine = [(s, e, n) for s, e, n in phases if inside((s, e), step)]
            names = [n for _s, _e, n in mine]
            for (_s0, e0, _n0), (s1, _e1, _n1) in zip(mine, mine[1:]):
                assert s1 >= e0, names          # disjoint
            if "engine/dispatch" not in names:
                continue
            dispatching += 1
            order = [PHASES.index(n) for n in names]
            assert order == sorted(order), names
            assert set(names) == set(PHASES), names
        assert dispatching == len(
            [r for r in records if r["kind"] == "dispatch"])
        # every phase of a dispatching iteration lies in some step span
        for s, e, n in phases:
            if n in ("engine/build", "engine/dispatch", "engine/fetch"):
                assert any(inside((s, e), st) for st in steps), n

    def test_dispatch_record_fields(self, run):
        _events, records, lens = run
        disp = [r for r in records if r["kind"] == "dispatch"]
        for r, after in zip(disp, lens):
            assert 1 <= r["rows"] <= r["rows_padded"]
            # the tokens asked for, the positions the dense layers
            # computed (pad rows' one each among them), the kernel's
            # rectangle; chunk 8, batch 4: the engine's bound is 18
            assert r["tokens"] + r["rows_padded"] - r["rows"] \
                <= r["tokens_padded"] <= r["rows_padded"] * r["span_padded"]
            assert r["tokens_padded"] == min(
                r["rows_padded"] * r["span_padded"], 32)
            assert r["tokens"] >= r["rows"]
            assert r["ctx_tokens"] == after
            assert r["ctx_tokens"] <= (r["rows_padded"] * r["table_pages"]
                                       * r["page_size"])
            assert r["page_size"] == 8
        assert any(r["span_padded"] > 1 for r in disp)     # a chunk step
        assert any(r["span_padded"] == 1 for r in disp)    # a decode step

    def test_dispatch_record_counts_what_the_kernel_walks(self, run):
        """``kv_tokens_walked`` is the paged kernel's own block rule
        applied to the dispatch's padded rows (a pad row is one token
        long): ``kernel.paged_attn.walk_useful`` divides by it."""
        from paddle_tpu.ops.pallas.paged_attention import (
            kv_tokens_walked, walk_cut)
        _events, records, _ = run
        disp = [r for r in records if r["kind"] == "dispatch"]
        assert len(disp) == len(self.row_lengths)
        for r, rows in zip(disp, self.row_lengths):
            # tiny_model: 4 query heads over 2 KV heads of 8, f32 pages
            block = r["page_size"] * walk_cut(
                2, r["page_size"], 8, r["span_padded"], 2, np.float32,
                np.float32, ragged=r["span_padded"] > 1)[1]
            padded = rows + [1] * (r["rows_padded"] - r["rows"])
            assert r["kv_tokens_walked"] == kv_tokens_walked(padded, block)
            assert r["kv_tokens_walked"] >= r["ctx_tokens"]
            assert r["kv_tokens_walked"] % block == 0

    def test_dispatch_record_counts_the_page_copies(self, run):
        """``page_copies`` and ``head_page_reads`` are the paged kernel's
        own copy rule applied to the dispatch's padded rows: a descriptor
        a page, a pool and a GROUP of kv heads (both of tiny_model's two
        ride together), and ``kernel.paged_attn.copy_share`` reads their
        ratio from the ring."""
        import json
        import sys
        from paddle_tpu.ops.pallas.paged_attention import (
            kv_pages_copied, walk_cut)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "benchmark"))
        try:
            from readers import ring_ratio
        finally:
            sys.path.remove(os.path.join(root, "benchmark"))
        _events, records, _ = run
        disp = [r for r in records if r["kind"] == "dispatch"]
        for r, rows in zip(disp, self.row_lengths):
            assert walk_cut(2, r["page_size"], 8, r["span_padded"], 2,
                            np.float32, np.float32,
                            ragged=r["span_padded"] > 1)[2] == 2
            padded = rows + [1] * (r["rows_padded"] - r["rows"])
            pages = kv_pages_copied(padded, r["page_size"],
                                    r["table_pages"])
            assert pages == sum(-(-n // r["page_size"]) for n in padded)
            assert r["page_copies"] == 2 * pages            # K and V
            assert r["head_page_reads"] == 2 * 2 * pages    # of two heads
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               "kernel.paged_attn.copy_share.json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "ring_ratio"
        assert ring_ratio.read(spec["args"], {"steps": records}) == 50.0
        # a program without the fields (the parent): nothing to read
        bare = [{k: v for k, v in r.items() if k != "page_copies"}
                for r in records]
        assert ring_ratio.read(spec["args"], {"steps": bare}) is None
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            (entry,) = [m for m in json.load(f)["per_layer"]
                        if m["name"] == "kernel.paged_attn.copy_share"]
        # a cell with K/V pages that a later PR adds joins the list's END
        assert entry["better"] == "lower" and entry["workloads"][:3] == [
            "mistral7b.serve.closed8", "laguna-xs2.serve.agent8",
            "phi4-flash.serve.reason32"]
        assert (entry["unit"], entry["layer"], entry["moves"]) == (
            spec["unit"], spec["layer"], spec["moves"])

    def test_dispatch_interval_lies_inside_the_steps_other_records(self, run):
        _events, records, _ = run
        by_index = {}
        for r in records:
            by_index.setdefault(r["index"], []).append(r)
        for rs in by_index.values():
            others = [r for r in rs if r["kind"] != "dispatch"]
            for d in (r for r in rs if r["kind"] == "dispatch"):
                assert any(o["start_ns"] <= d["start_ns"]
                           and d["end_ns"] <= o["end_ns"] for o in others)

    def test_idle_wait_is_a_span(self):
        import time
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        from paddle_tpu.profiler.record import get_recorder
        rec = get_recorder()
        rec.collect()
        rec.enable(True)
        seen = []
        try:
            with ContinuousBatchingEngine(tiny_model(), total_pages=32,
                                          page_size=8, max_batch=2) as eng:
                eng.submit(np.asarray([1, 2, 3], np.int32),
                           max_new_tokens=2).result(timeout=300)
                # with nothing to do the loop sits in the wait, which
                # closes every half second: poll, do not time
                deadline = time.monotonic() + 60
                while (time.monotonic() < deadline
                       and "engine/wait" not in seen):
                    time.sleep(0.05)
                    seen += [e.name for e in rec.collect()]
        finally:
            rec.enable(False)
        assert "engine/wait" in seen


class TestCompilePhaseCounters:
    NAMES = ("jit_trace_seconds_total", "jit_lower_seconds_total",
             "jit_backend_compile_seconds_total")

    @staticmethod
    def totals():
        out = {}
        for name, m in monitor.snapshot().items():
            if m["type"] == "counter":
                out[name] = sum(s["value"] for s in m["series"])
        return out

    def test_series_exist_before_the_first_compile(self):
        monitor.install_compile_hooks()
        now = self.totals()
        for name in self.NAMES + ("jit_recompile_count",):
            assert name in now

    def test_a_fresh_jit_raises_all_three_and_counts_one_program(self):
        monitor.install_compile_hooks()
        x = jnp.arange(7.0)                     # its own programs first
        before = self.totals()

        @jax.jit
        def fresh(a):
            return jnp.tanh(a) * 3.0 + jnp.sum(a)

        fresh(x).block_until_ready()
        after = self.totals()
        for name in self.NAMES:
            assert after[name] > before[name], name
        assert after["jit_recompile_count"] - before[
            "jit_recompile_count"] == 1
        before = after
        fresh(x).block_until_ready()            # served by the jit cache
        after = self.totals()
        for name in self.NAMES + ("jit_recompile_count",):
            assert after[name] == before[name], name

    def test_nested_events_are_not_counted_twice(self):
        import time
        from paddle_tpu.monitor import compile_hooks as ch
        ch._local.done = []
        assert ch._own_seconds(0.001) == pytest.approx(0.001)
        # an event that began before two finished ones contains them
        ch._local.done = [(time.time() - 0.5, 0.2), (time.time() - 0.2, 0.1)]
        assert ch._own_seconds(1.0) == pytest.approx(0.7)
        # a later sibling contains nothing
        assert ch._own_seconds(0.0001) == pytest.approx(0.0001)
        assert len(ch._local.done) == 2


class TestTrainStepScopes:
    @pytest.fixture(scope="class")
    def text(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.optimizer as optim
        from paddle_tpu.jit.train_step import TrainStep

        paddle.seed(0)
        model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        opt = optim.AdamW(learning_rate=1e-2, parameters=model.parameters())
        step = TrainStep(model, lambda out, y: ((out - y) ** 2).mean(), opt)
        x = paddle.to_tensor(np.ones((2, 8), np.float32))
        y = paddle.to_tensor(np.zeros((2, 4), np.float32))
        step([x], [y])
        in_sds, label_sds, treedefs = step._last_sig
        return step._lower(in_sds, label_sds, treedefs,
                           as_avals=True).as_text(debug_info=True)

    @pytest.mark.parametrize("scope", ["train/model", "train/loss",
                                       "train/optimizer"])
    def test_scope_in_the_lowered_text(self, text, scope):
        assert scope in text

    def test_backward_ops_carry_the_forward_scope(self, text):
        assert re.search(r"transpose\(jvp\(train/model\)\)", text)
