"""The readers of ISSUE 27 on synthetic planes, rings and counters:
``idle_by_span``, ``ring_ratio``, ``counter_at_open``; and that
``step_ring`` reads what it read before beside ``dispatch`` records."""
import importlib
import json
from pathlib import Path

import pytest

import xplane

BENCH = Path(__file__).resolve().parent.parent
PHASES = ["^engine/schedule$", "^engine/build$", "^engine/dispatch$",
          "^engine/fetch$", "^engine/commit$", "^engine/wait$"]
idle_by_span = importlib.import_module("readers.idle_by_span")


def reader(name):
    return importlib.import_module(f"readers.{name}")


def trace(ops, host, more_host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": {xplane.OPS_LINE: list(ops)}},
        {"name": "/host:CPU", "lines": {"scheduler": list(host),
                                       "client": list(more_host)}},
        {"name": "/host:metadata", "lines": {}}]}


# device busy 0-100, 150-250, 300-400: window 400, idle 100 (100-150, 250-300)
OPS = [("fusion", 0, 100), ("fusion", 150, 100), ("copy", 300, 100)]
HOST = [("engine/step 7", 90, 200),           # 90-290: wraps the phases
        ("engine/schedule", 95, 15),          # 95-110: 10 of gap one
        ("engine/build", 110, 20),            # 110-130: 20
        ("engine/ragged_step", 128, 30),      # not a phase of its own
        ("engine/build", 130, 5),             # 130-135: 5
        ("engine/dispatch", 135, 30),         # 135-165: 15
        ("engine/fetch", 240, 30),            # 240-270: 20 of gap two
        ("engine/commit", 270, 10),           # 270-280: 10
        ("engine/commit", 281, 4)]            # 281-285: 4; 16 under no span


def shares(data):
    out = {}
    for ph in ("schedule", "build", "dispatch", "fetch", "commit"):
        out[ph] = idle_by_span.read(
            {"pattern": f"^engine/{ph}$", "phases": PHASES}, {"trace": data})
    out["unattributed"] = idle_by_span.read({"phases": PHASES},
                                            {"trace": data})
    return out


def test_the_six_shares_sum_to_the_idle_share():
    data = trace(OPS, HOST)
    got = shares(data)
    assert got == pytest.approx({
        "schedule": 2.5, "build": 6.25, "dispatch": 3.75, "fetch": 5.0,
        "commit": 3.5, "unattributed": 4.0})
    idle = reader("xplane_busy").read({}, {"trace": data})
    assert sum(got.values()) == pytest.approx(idle) == pytest.approx(25.0)


def test_a_gap_under_no_span_is_unattributed():
    data = trace(OPS, [("engine/schedule", 0, 50)],      # device is busy then
                 [("$python something", 100, 200)])
    got = shares(data)
    assert got["unattributed"] == pytest.approx(25.0)
    assert sum(v for k, v in got.items() if k != "unattributed") == 0


def test_nested_and_repeated_spans_do_not_double_count():
    # engine/step wraps everything and is no phase; a build nested in a
    # build and the same span seen on a second line count once
    host = [("engine/step 3", 0, 400), ("engine/build", 100, 50),
            ("engine/build", 110, 20)]
    data = trace(OPS, host, more_host=[("engine/build", 100, 50)])
    got = shares(data)
    assert got["build"] == pytest.approx(12.5)
    assert got["unattributed"] == pytest.approx(12.5)


def test_no_phase_in_the_trace_reads_nothing():
    for data in (trace(OPS, [("engine/ragged_step", 0, 400)]),   # the parent
                 trace([], HOST), {"planes": []}):
        assert all(v is None for v in shares(data).values())


def test_owner_of_one_gap():
    data = trace(OPS, HOST)
    assert idle_by_span.owner((100, 150), data, PHASES) == "^engine/build$"
    assert idle_by_span.owner((250, 300), data, PHASES) == "^engine/fetch$"
    assert idle_by_span.owner((286, 300), data, PHASES) == "unattributed"


RING = [
    {"kind": "prefill_chunk", "index": 4, "start_ns": 0, "end_ns": 90, "tokens": 128},
    {"kind": "decode", "index": 4, "start_ns": 0, "end_ns": 90, "batch": 7},
    {"kind": "decode", "index": 5, "start_ns": 100, "end_ns": 150, "batch": 8},
]
DISPATCH = [
    {"kind": "dispatch", "index": 4, "start_ns": 10, "end_ns": 90, "rows": 8,
     "rows_padded": 8, "span_padded": 128, "tokens": 135, "ctx_tokens": 2500,
     "table_pages": 256, "page_size": 16},
    {"kind": "dispatch", "index": 5, "start_ns": 105, "end_ns": 150, "rows": 8,
     "rows_padded": 8, "span_padded": 1, "tokens": 8, "ctx_tokens": 2508,
     "table_pages": 256, "page_size": 16},
]


def metric_args(name):
    return json.loads((BENCH / "layer_metrics" / f"{name}.json")
                      .read_text())["args"]


def test_ring_ratio_pad_share_and_useful_context():
    src = {"steps": RING + DISPATCH}
    pad = reader("ring_ratio").read(metric_args("serve.pad_share"), src)
    assert pad == pytest.approx(100 * (1 - 143 / (8 * 128 + 8)))
    ctx = reader("ring_ratio").read(
        metric_args("kernel.paged_attn.ctx_useful"), src)
    assert ctx == pytest.approx(100 * 5008 / (2 * 8 * 256 * 16))
    for steps in (RING, [], None):                 # the parent's ring
        for name in ("serve.pad_share", "kernel.paged_attn.ctx_useful"):
            assert reader("ring_ratio").read(metric_args(name),
                                             {"steps": steps}) is None


@pytest.mark.parametrize("stat", ["occupancy", "chunk_steps", "step_host_ms"])
def test_step_ring_reads_the_same_beside_dispatch_records(stat):
    args = {"stat": stat}
    without = reader("step_ring").read(args, {"steps": RING, "max_batch": 8})
    beside = reader("step_ring").read(
        args, {"steps": RING + DISPATCH, "max_batch": 8})
    assert without is not None and beside == without


def test_counter_at_open():
    c0 = {"jit_trace_seconds_total": 40.5, "jit_lower_seconds_total": 12.0,
          "jit_backend_compile_seconds_total": 9.25, "jit_recompile_count": 40}
    read = reader("counter_at_open").read
    assert read(metric_args("setup.trace_lower_s"),
                {"counters0": c0}) == pytest.approx(52.5)
    assert read(metric_args("setup.compile_s"),
                {"counters0": c0}) == pytest.approx(9.25)
    for src in ({}, {"counters0": {"jit_recompile_count": 40}}):   # the parent
        assert read(metric_args("setup.compile_s"), src) is None


def test_every_new_metric_file_matches_its_manifest_entry():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for m in manifest["per_layer"]:
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json")
                          .read_text())
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) == (
            m["name"], m["unit"], m["layer"], m["moves"])
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
