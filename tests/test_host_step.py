"""The serving host's step timed inside the program (ISSUE 39): the
phases' seconds as counters that move once an iteration, late launches
counted where the step is dispatched, named parts of ``engine/commit``
and ``engine/build``, collector pauses, and the four fields the ring's
``dispatch`` record gains.  Counts, orders and inequalities between the
program's own sums; no duration is asserted.  A state that is needed is
held: a step "still running" is a stand-in that says so, a commit that
outlasts the step in flight waits until that step has arrived."""
import gc
import json
import threading
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from paddle_tpu import monitor
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.monitor import gc_hooks
from test_span_profiler_clock import host_events, inside, named
from test_step_overlap import prompts_of, tiny_llama, wait_for

OPTS = dict(total_pages=128, page_size=8, max_batch=4,
            prefill_chunk_tokens=8)
WORK = ("schedule", "build", "dispatch", "commit")
NEW_FIELDS = ("host_work_ns", "late", "prefix_evicted", "gc_ns")
PARTS_OF = {"engine/commit": ("finish_prefill", "prefix_register", "retire",
                              "journal"),
            "engine/build": ("rows", "reserve", "pack")}


@pytest.fixture(scope="module")
def llama():
    return tiny_llama(0)


def series():
    """{name{label value}: value} of this PR's counters."""
    out = {}
    for name, m in monitor.snapshot().items():
        if m["type"] == "counter" and name.startswith(
                ("engine_host_", "host_gc_", "serve_steps_launched")):
            for s in m["series"]:
                label = ",".join((s.get("labels") or {}).values())
                out[name + (":" + label if label else "")] = s["value"]
    return out


def moved(before):
    return {k: v - before.get(k, 0) for k, v in series().items()}


def serve(eng, sizes, budget=6, seed=3):
    for r in [eng.submit(p, max_new_tokens=budget)
              for p in prompts_of(sizes, 64, seed)]:
        r.result(timeout=300)


def captured(llama, sizes, hook=None, opts=OPTS, budget=6):
    """The ``dispatch`` records, by index, of an engine that served
    ``sizes`` under a capture window, and what the counters moved by."""
    before = series()
    monitor.start_capture(host_events=False)
    try:
        with ContinuousBatchingEngine(llama, **opts) as eng:
            if hook is not None:
                hook(eng)
            serve(eng, sizes, budget)
            evicted = eng.cache.prefix_evictions
    finally:
        monitor.stop_capture()
    records = monitor.get_tracer().step_records()
    disp = sorted((r for r in records if r["kind"] == "dispatch"),
                  key=lambda r: r["index"])
    return disp, records, moved(before), evicted


# ------------------------------------------------------ phase seconds
class TestPhaseSeconds:
    def test_the_counters_move_and_fit_the_wall(self, llama):
        before, t0 = series(), time.perf_counter()
        with ContinuousBatchingEngine(llama, **OPTS) as eng:
            serve(eng, [5, 19, 9, 30, 3])
            steps = eng.steps
        wall = time.perf_counter() - t0
        d = moved(before)
        work = [d[f"engine_host_work_seconds_total:{ph}"] for ph in WORK]
        wait = [d[f"engine_host_wait_seconds_total:{ph}"]
                for ph in ("fetch", "wait")]
        assert all(s > 0 for s in work) and wait[0] > 0
        # the phases are disjoint spans of one thread
        assert sum(work) + sum(wait) <= wall
        # a part lies inside its phase (``prefix_register`` inside
        # ``finish_prefill``)
        part = {p: d[f"engine_host_part_seconds_total:{p}"]
                for ps in PARTS_OF.values() for p in ps}
        assert all(s > 0 for s in part.values())
        assert part["prefix_register"] <= part["finish_prefill"]
        assert (part["finish_prefill"] + part["retire"] + part["journal"]
                <= d["engine_host_work_seconds_total:commit"])
        assert (part["rows"] + part["reserve"] + part["pack"]
                <= d["engine_host_work_seconds_total:build"])
        assert d["serve_steps_launched_total"] >= steps > 0

    def test_a_span_adds_its_seconds_to_the_callers_dict(self):
        acc = {}
        for _ in range(3):
            with monitor.span("some/phase", into=acc) as sp:
                pass
        with monitor.span("another", into=acc):
            pass
        assert set(acc) == {"some/phase", "another"}
        assert acc["some/phase"] >= sp.elapsed > 0

        @monitor.span("decorated", into=acc)
        def f():
            return 7
        assert f() == 7 and acc["decorated"] > 0

    def test_an_engine_shares_one_dict_with_its_decoder(self, llama):
        with ContinuousBatchingEngine(llama, **OPTS) as eng:
            assert eng._host_s is eng._decoder.host_seconds
            serve(eng, [9])
            wait_for(lambda: eng._flight is None, "the last step to land")
        # cleared once an iteration: nothing piles up
        assert set(eng._host_s) <= {"engine/wait"}


# -------------------------------------------------------- late launches
class _StillRunning:
    """A step in flight whose output has not arrived."""
    class out:
        @staticmethod
        def is_ready():
            return False


def device_still_running(eng):
    real = eng._decoder.ragged_launch

    def launch(*a, after=None, **kw):
        return real(*a, after=None if after is None else _StillRunning,
                    **kw)
    eng._decoder.ragged_launch = launch


def commit_outlasts_the_flight(eng):
    """Every commit ends only once the step dispatched over it has
    arrived: the next launch finds the device dry."""
    real = eng._decoder.ragged_fetch

    def fetch(flight):
        out = real(flight)
        newer = eng._flight
        if newer is not None and newer.flight not in (None, flight):
            wait_for(newer.flight.out.is_ready, "the step in flight")
        return out
    eng._decoder.ragged_fetch = fetch


class TestLateLaunches:
    def test_a_step_launched_with_nothing_in_flight_is_late(self, llama):
        disp, _, d, _ = captured(llama, [9])
        assert disp[0]["overlapped"] == 0 and disp[0]["late"] == 1
        assert all(r["late"] == 1 for r in disp if not r["overlapped"])
        assert d["serve_steps_launched_total"] == len(disp)
        assert d["serve_steps_launched_late_total"] == sum(
            r["late"] for r in disp)

    def test_no_step_is_late_while_the_device_still_runs(self, llama):
        disp, _, d, _ = captured(llama, [9, 21], device_still_running,
                                 budget=12)
        over = [r for r in disp if r["overlapped"]]
        assert len(over) >= 10
        assert all(r["late"] == 0 for r in over)
        assert d["serve_steps_launched_late_total"] == len(disp) - len(over)

    def test_a_commit_that_outlasts_the_step_in_flight_makes_the_next_late(
            self, llama):
        disp, _, d, _ = captured(llama, [9, 21], commit_outlasts_the_flight,
                                 budget=12)
        over = [r for r in disp if r["overlapped"]]
        assert len(over) >= 10
        # the first overlapped launch precedes every such commit
        assert all(r["late"] == 1 for r in over[1:])
        assert d["serve_steps_launched_late_total"] >= len(disp) - 1

    def test_launch_asks_the_flight_it_is_given(self, llama):
        """``ragged_launch(after=)``: a flight that has arrived, one that
        has not, none."""
        with ContinuousBatchingEngine(llama, **OPTS) as eng:
            serve(eng, [9])             # compiled, idle
            wait_for(lambda: eng._flight is None, "the engine to idle")
            dec, cache = eng._decoder, eng.cache
            late = []
            for k, after in enumerate((None, _StillRunning, "arrived")):
                sid = f"probe-{k}"
                if after == "arrived":
                    after = flight
                    jax.block_until_ready(after.out)
                flight = dec.ragged_launch(
                    cache, [sid], [np.asarray([1, 2, 3], np.int32)], [0],
                    sampling=(np.zeros(1, np.uint32), np.ones(1, np.float32),
                              np.zeros(1, bool)), after=after)
                late.append(flight.record["late"])
                dec.ragged_fetch(flight)
            for k in range(3):
                cache.free(f"probe-{k}")
        assert late == [1, 0, 1]


# ----------------------------------------------------------- the ring
class TestTheRing:
    def test_the_dispatch_record_carries_the_four_fields(self, llama):
        disp, records, _, _ = captured(llama, [5, 19, 9])
        assert {r["kind"] for r in records} == {
            "dispatch", "prefill_chunk", "decode"}
        assert disp     # (a prefill-only step shares its index with the next)
        for r in disp:
            assert all(isinstance(r[f], int) for f in NEW_FIELDS), r
            assert r["host_work_ns"] > 0 and r["gc_ns"] >= 0
            assert r["late"] in (0, 1) and r["prefix_evicted"] >= 0
            # the interval is the step's own, as before
            assert r["end_ns"] > r["start_ns"]

    def test_prefix_evictions_are_counted_on_the_step_that_made_them(
            self, llama):
        opts = dict(OPTS, total_pages=16)
        disp, _, _, evicted = captured(llama, [40] * 6, opts=opts, budget=3)
        assert evicted > 0
        assert sum(r["prefix_evicted"] for r in disp) == evicted

    def test_collector_pauses_inside_an_iteration_are_on_its_record(
            self, llama):
        def collect_in_every_commit(eng):
            real = eng._decoder.ragged_fetch

            def fetch(flight):
                gc.collect()
                return real(flight)
            eng._decoder.ragged_fetch = fetch
        disp, _, d, _ = captured(llama, [9], collect_in_every_commit)
        assert all(r["gc_ns"] > 0 for r in disp)
        assert d["host_gc_collections_total:2"] >= len(disp)


# ------------------------------------------------------ collector pauses
class TestCollectorPauses:
    def test_installed_once(self):
        assert monitor.install_gc_hooks() and monitor.install_gc_hooks()
        assert gc.callbacks.count(gc_hooks._on_gc) == 1

    def test_a_collection_moves_the_counters(self):
        monitor.install_gc_hooks()
        gc.collect()        # no collection of its own accord just below
        before, ns = series(), gc_hooks.pause_ns()
        with monitor.span("around/the/collection") as sp:
            gc.collect()
        d = moved(before)
        assert d["host_gc_collections_total:2"] >= 1
        assert 0 < d["host_gc_pause_seconds_total:2"] <= sp.elapsed
        assert 0 < gc_hooks.pause_ns() - ns <= sp.elapsed * 1e9

    def test_the_pause_is_a_span_on_the_profilers_clock(self, tmp_path):
        monitor.install_gc_hooks()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with monitor.span("around/the/collection"):
                gc.collect()
        finally:
            jax.profiler.stop_trace()
        events = host_events(str(tmp_path))
        (outer,) = named(events, "around/the/collection")
        assert any(inside(g, outer) for g in named(events, "host/gc gen2"))

    def test_a_collection_under_a_metric_lock_does_not_deadlock(self):
        """The collector runs wherever it is triggered, also in a thread
        that is reading the collector's own counters, or pushing a span
        to the recorder."""
        from paddle_tpu.profiler.record import get_recorder
        monitor.install_gc_hooks()
        done = []

        def collect_under_the_locks():
            rec = get_recorder()
            with gc_hooks._collections._lock, gc_hooks._pause_s._lock, \
                    gc_hooks._collected._lock, rec._lock:
                gc.collect()
            done.append(True)
        t = threading.Thread(target=collect_under_the_locks, daemon=True)
        t.start()
        t.join(timeout=60)
        assert done == [True]


# ------------------------------------------------- the parts, as spans
class TestPartsUnderTrace:
    @pytest.fixture(scope="class")
    def run(self, llama, tmp_path_factory):
        d = str(tmp_path_factory.mktemp("host_step_trace"))
        sizes = [5, 19, 9]
        monitor.start_capture(host_events=False)
        jax.profiler.start_trace(d)
        try:
            # stopped inside the trace: the scheduler thread has left its
            # last span before the trace ends
            with ContinuousBatchingEngine(llama, **OPTS) as eng:
                serve(eng, sizes)
        finally:
            jax.profiler.stop_trace()
            monitor.stop_capture()
        disp = [r for r in monitor.get_tracer().step_records()
                if r["kind"] == "dispatch"]
        return host_events(d), disp, len(sizes)

    def test_each_part_appears_once_a_step(self, run):
        events, disp, requests = run
        count = {n: len(named(events, f"engine/{n}")) for n in (
            "build/rows", "build/reserve", "build/pack", "commit/retire",
            "commit/journal", "commit/finish_prefill",
            "commit/prefix_register")}
        iterations = sum(n.startswith("engine/step ") for n, _, _ in events)
        steps = len(disp)
        assert steps > 0 and iterations >= steps
        assert count["build/rows"] == count["build/reserve"] \
            == count["build/pack"] == count["commit/retire"] == steps
        assert count["commit/journal"] == iterations
        assert count["commit/finish_prefill"] \
            == count["commit/prefix_register"] == requests

    @pytest.mark.parametrize("phase", sorted(PARTS_OF))
    def test_a_part_lies_inside_its_phase(self, run, phase):
        events = run[0]
        phases = named(events, phase)
        for part in PARTS_OF[phase]:
            for iv in named(events, f"{phase}/{part}"):
                assert any(inside(iv, ph) for ph in phases), (part, iv)

    def test_the_record_joins_the_iteration_that_commits_its_step(self, run):
        events, disp, _ = run
        held = {int(n.split()[1]) for n, _, _ in events
                if n.startswith("engine/step ")}
        assert {r["index"] for r in disp} <= held

    def test_the_span_nothing_read_is_gone(self, run):
        assert not named(run[0], "engine/ragged_step")


# ------------------------------------------- the benchmark's new entries
NINE = ["engine.late_launch_share", "host.step_work_ms",
        "host.gc_pause_ms_per_step", "host.step_work_max_ms",
        "host.profiler_slowdown", "idle.serve.gc",
        "idle.serve.commit.prefix", "idle.serve.commit.retire",
        "idle.serve.build.reserve"]


class TestManifest:
    ROOT = Path(__file__).resolve().parent.parent

    def test_the_nine_metrics_are_appended_each_with_a_file_and_a_reader(
            self):
        manifest = json.loads((self.ROOT / "BENCHMARK.json").read_text())
        names = [m["name"] for m in manifest["per_layer"]]
        at = names.index(NINE[0])       # later PRs append behind them
        last = manifest["per_layer"][at:at + len(NINE)]
        assert [m["name"] for m in last] == NINE
        serving = [w["name"] for w in manifest["workloads"]
                   if ".serve." in w["name"]]
        for m in last:
            spec = json.loads((self.ROOT / "benchmark/layer_metrics"
                               / f"{m['name']}.json").read_text())
            assert (self.ROOT / "benchmark/readers"
                    / f"{spec['reader']}.py").is_file()
            assert (spec["unit"], spec["layer"], spec["moves"]) == (
                m["unit"], m["layer"], m["moves"])
            assert m["moves"] == "serve.tokens_per_s"
            assert set(m["workloads"]) <= set(serving)
            assert m["source"] == ("program_counter" if spec["reader"]
                                   == "counter_delta_ratio"
                                   else "program_span")

    def test_the_counters_the_metrics_name_exist(self):
        have = {n for n, m in monitor.snapshot().items()
                if m["type"] == "counter"}
        monitor.install_gc_hooks()
        have |= {n for n, m in monitor.snapshot().items()
                 if m["type"] == "counter"}
        for name in NINE[:3]:
            args = json.loads((self.ROOT / "benchmark/layer_metrics"
                               / f"{name}.json").read_text())["args"]
            assert set(args["numerator"]) | set(args["denominator"]) <= have
