"""The readers of ISSUE 39 on synthetic counters, rings and planes:
``counter_delta_ratio``, ``ring_profiled``, ``idle_by_span`` over the
parts of a phase; their nine metric files and manifest entries; and that
``step_ring`` reads from one rehearsal's ring what it reads from the same
ring with the new fields taken off."""
import argparse
import importlib
import json
from pathlib import Path

import pytest

import run
from test_tracing_readers import HOST, OPS, PHASES, shares, trace

BENCH = Path(__file__).resolve().parent.parent
SERVE = ["mistral7b.serve.closed8", "laguna-xs2.serve.agent8",
         "brumby-14b.serve.reason16"]
NEW = {  # name -> (unit, source, reader, workloads)
    "engine.late_launch_share": ("%", "program_counter",
                                 "counter_delta_ratio", SERVE),
    "host.step_work_ms": ("ms", "program_counter", "counter_delta_ratio",
                          SERVE),
    "host.gc_pause_ms_per_step": ("ms", "program_counter",
                                  "counter_delta_ratio", SERVE),
    "host.step_work_max_ms": ("ms", "program_span", "ring_profiled", SERVE),
    "host.profiler_slowdown": ("x", "program_span", "ring_profiled", SERVE),
    "idle.serve.gc": ("%", "program_span", "idle_by_span", SERVE),
    "idle.serve.commit.prefix": ("%", "program_span", "idle_by_span",
                                 SERVE[:2]),
    "idle.serve.commit.retire": ("%", "program_span", "idle_by_span", SERVE),
    "idle.serve.build.reserve": ("%", "program_span", "idle_by_span", SERVE),
}
NEW_FIELDS = ("host_work_ns", "late", "prefix_evicted", "gc_ns")


def reader(name):
    return importlib.import_module(f"readers.{name}")


def spec(name):
    return json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())


def read(name, src):
    s = spec(name)
    return reader(s["reader"]).read(s["args"], src)


# ------------------------------------------------- files and manifest
def test_the_nine_metrics_are_the_manifests_last_entries():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    last = manifest["per_layer"][-len(NEW):]
    assert [m["name"] for m in last] == list(NEW)
    for m in last:
        unit, source, rd, cells = NEW[m["name"]]
        s = spec(m["name"])
        assert (m["unit"], m["source"], m["workloads"]) == (unit, source, cells)
        assert m["better"] == "lower" and m["moves"] == "serve.tokens_per_s"
        assert (s["name"], s["unit"], s["layer"], s["moves"], s["reader"]) \
            == (m["name"], unit, m["layer"], m["moves"], rd)
        assert callable(reader(rd).read)
    for name in NEW:
        if NEW[name][2] == "idle_by_span":
            assert spec(name)["args"]["phases"] == PHASES


# ------------------------------------------------- counter_delta_ratio
C0 = {"serve_steps_launched_total": 1000.0,
      "serve_steps_launched_late_total": 10.0,
      "engine_host_work_seconds_total": 3.0,
      "host_gc_pause_seconds_total": 0.5, "jit_recompile_count": 40.0}
C1 = {"serve_steps_launched_total": 3000.0,
      "serve_steps_launched_late_total": 60.0,
      "engine_host_work_seconds_total": 9.0,
      "host_gc_pause_seconds_total": 0.7, "jit_recompile_count": 40.0}


def test_counter_delta_ratio_reads_shares_and_milliseconds_a_step():
    src = {"counters0": C0, "counters1": C1}
    assert read("engine.late_launch_share", src) == pytest.approx(2.5)
    assert read("host.step_work_ms", src) == pytest.approx(3.0)
    assert read("host.gc_pause_ms_per_step", src) == pytest.approx(0.1)
    # a counter born inside the window counts from zero
    born = {k: v for k, v in C0.items() if "late" not in k}
    assert read("engine.late_launch_share",
                {"counters0": born, "counters1": C1}) == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["engine.late_launch_share",
                                  "host.step_work_ms",
                                  "host.gc_pause_ms_per_step"])
def test_counter_delta_ratio_reads_nothing_from_the_parent(name):
    parent = {"jit_recompile_count": 40.0}
    assert read(name, {"counters0": parent, "counters1": parent}) is None
    assert read(name, {}) is None
    # no step launched inside the window
    assert read(name, {"counters0": C1, "counters1": C1}) is None
    # the steps' counter alone: a program between the parent and this
    steps_only = {"serve_steps_launched_total": 5.0}
    assert read(name, {"counters0": {}, "counters1": steps_only}) is None


# ------------------------------------------------------ ring_profiled
S = 10**9


def rec(index, at_s, work_ms, **more):
    return dict(kind="dispatch", index=index, start_ns=int(at_s * S),
                end_ns=int(at_s * S) + 10**7, host_work_ns=int(work_ms * 1e6),
                **more)


# a window of 51 s; the profiler holds steps 100-102 at 23-28 s
RING = ([rec(i, i * 0.2, 2.0 + (i % 3)) for i in range(5, 90)]       # 1-18 s
        + [rec(95, 21.5, 400.0), rec(96, 22.5, 300.0)]    # the profiler starts
        + [rec(100, 23.0, 6.0), rec(101, 25.0, 9.0), rec(102, 28.0, 12.0)]
        + [rec(103, 29.5, 500.0)]                 # ... and writes its file
        + [rec(i, 31 + (i - 110) * 0.2, 3.0) for i in range(110, 150)]
        + [rec(150, 45.0, 40.0)]
        + [{"kind": "decode", "index": 100, "start_ns": 23 * S,
            "end_ns": 23 * S + 5, "batch": 8}])
TRACE = trace(OPS, [("engine/step 100", 0, 5), ("engine/step 101", 10, 5),
                    ("engine/step 102", 20, 5), ("engine/step", 30, 5),
                    ("engine/build", 1, 2)],
              more_host=[("engine/step 101", 10, 5)])


def test_ring_profiled_splits_the_ring_by_the_traces_steps():
    rp = reader("ring_profiled")
    assert rp.traced_indices(TRACE) == {100, 101, 102}
    inside, outside = rp.split(RING, TRACE, "host_work_ns", guard_s=2.0)
    assert [r["index"] for r in inside] == [100, 101, 102]
    held = {r["index"] for r in outside}
    assert held == set(range(5, 90)) | set(range(110, 151))     # the guard
    src = {"steps": RING, "trace": TRACE}
    assert read("host.step_work_max_ms", src) == pytest.approx(40.0)
    assert read("host.profiler_slowdown", src) == pytest.approx(9.0 / 3.0)
    # without a guard the profiler's own start and stop are "untraced"
    assert rp.read({"field": "host_work_ns", "stat": "max_unprofiled"},
                   src) == pytest.approx(500.0)


@pytest.mark.parametrize("name", ["host.step_work_max_ms",
                                  "host.profiler_slowdown"])
def test_ring_profiled_reads_nothing_without_both_sides(name):
    bare = [{k: v for k, v in r.items() if k != "host_work_ns"}
            for r in RING]                          # the parent's records
    no_steps = trace(OPS, [("engine/build", 1, 2)])
    all_traced = [r for r in RING if r["index"] in (100, 101, 102)]
    for src in ({"steps": bare, "trace": TRACE},
                {"steps": RING, "trace": no_steps},
                {"steps": RING, "trace": {"planes": []}},
                {"steps": all_traced, "trace": TRACE},
                {"steps": [], "trace": TRACE}, {"trace": TRACE}):
        assert read(name, src) is None


# ------------------------------------------- the parts under idle_by_span
# HOST's gaps: 100-150 (schedule 95-110, build 110-135, dispatch 135-165)
# and 250-300 (fetch 240-270, commit 270-280 and 281-285)
PARTS = [("engine/build/rows", 110, 20),          # 110-130: 20 of gap one
         ("engine/build/reserve", 130, 2),        # 130-132: 2
         ("engine/build/pack", 132, 3),           # 132-135: 3
         ("engine/commit/finish_prefill", 270, 6),
         ("engine/commit/prefix_register", 271, 4),   # 271-275: 4 of gap two
         ("engine/commit/retire", 276, 3),        # 276-279: 3
         ("engine/commit/journal", 281, 4)]
GC = [("host/gc gen0", 120, 1), ("host/gc gen2", 272, 2)]    # 3 in all


def test_the_parts_leave_the_six_shares_as_they_were():
    assert shares(trace(OPS, HOST + PARTS, more_host=GC)) \
        == pytest.approx(shares(trace(OPS, HOST)))


def test_the_parts_read_their_own_idle_time():
    src = {"trace": trace(OPS, HOST + PARTS, more_host=GC)}
    assert read("idle.serve.build.reserve", src) == pytest.approx(0.5)
    assert read("idle.serve.commit.prefix", src) == pytest.approx(1.0)
    assert read("idle.serve.commit.retire", src) == pytest.approx(0.75)
    assert read("idle.serve.gc", src) == pytest.approx(0.75)
    # a program with the phases and none of the parts: nothing idles
    # under what it does not have
    parent = {"trace": trace(OPS, HOST)}
    assert all(read(n, parent) == 0.0 for n in NEW
               if NEW[n][2] == "idle_by_span")
    assert all(read(n, {"trace": {"planes": []}}) is None for n in NEW
               if NEW[n][2] == "idle_by_span")


# -------------------------------------------- one rehearsal's own ring
@pytest.fixture(scope="module")
def rehearsal():
    """(result line, the window's ring) of the Mistral cell's rehearsal."""
    kept = {}
    real = run.layer_metrics

    def keeping(manifest, cell, result, ctx, trace_, peak):
        kept["steps"] = result["sources"]["steps"]
        kept["max_batch"] = result["sources"]["max_batch"]
        return real(manifest, cell, result, ctx, trace_, peak)
    run.layer_metrics = keeping
    try:
        line, _ = run.run_cell(argparse.Namespace(
            workload=SERVE[0], seed=2**31 + 39, seconds=6.0, trace=1,
            rehearse=True), {})
    finally:
        run.layer_metrics = real
    return line, kept


def test_the_rehearsal_reads_the_counter_and_ring_metrics(rehearsal):
    line, kept = rehearsal
    assert line["correct"] and line["failed"] == 0
    for name in ("engine.late_launch_share", "host.step_work_ms",
                 "host.gc_pause_ms_per_step", "host.step_work_max_ms",
                 "host.profiler_slowdown"):
        assert line["metrics"][name]["value"] >= 0, name
    assert 0 <= line["metrics"]["engine.late_launch_share"]["value"] <= 100
    disp = [r for r in kept["steps"] if r["kind"] == "dispatch"]
    assert disp and all(f in r for r in disp for f in NEW_FIELDS)
    assert {r["kind"] for r in kept["steps"]} == {
        "dispatch", "prefill_chunk", "decode"}


@pytest.mark.parametrize("stat", ["occupancy", "chunk_steps", "step_host_ms"])
def test_step_ring_reads_the_same_without_the_new_fields(rehearsal, stat):
    _, kept = rehearsal
    bare = [{k: v for k, v in r.items() if k not in NEW_FIELDS}
            for r in kept["steps"]]
    assert bare != kept["steps"]
    got = [reader("step_ring").read(
        {"stat": stat}, {"steps": steps, "max_batch": kept["max_batch"]})
        for steps in (kept["steps"], bare)]
    assert got[0] is not None and got[0] == got[1]
