"""The reduction from traces, rings and counters to numbers, on
synthetic events."""
import importlib
import json
import statistics
from pathlib import Path

import pytest

import flops
import stats
import xplane

BENCH = Path(__file__).resolve().parent.parent


def plane(name, ops=(), modules=()):
    return {"name": name, "lines": {xplane.OPS_LINE: list(ops),
                                    xplane.MODULES_LINE: list(modules)}}


def test_union_merges_overlapping_and_nested_ops():
    iv = [(0, 10), (5, 10), (6, 2), (30, 5)]        # 0-15 and 30-35
    assert xplane.union_ns(iv) == 20
    assert xplane.span_ns(iv) == (0, 35)


def test_busy_and_window_average_two_device_planes_and_skip_hosts():
    s = 1_000_000_000
    data = {"planes": [
        plane("/device:TPU:0", [("a", 0, s), ("b", s // 2, s)]),   # 1.5 of 1.5
        plane("/device:TPU:1", [("a", 0, s // 2), ("b", s, s // 2)]),  # 1.0 of 1.5
        plane("/host:CPU", [("python", 0, 10 * s)]),
        plane("/device:TPU:0 SparseCore 0", [("x", 0, 10 * s)])]}
    busy, window = xplane.busy_and_window(data)
    assert busy == pytest.approx(1.25) and window == pytest.approx(1.5)
    src = {"trace": data}
    idle = importlib.import_module("readers.xplane_busy").read({}, src)
    assert idle == pytest.approx(100 * (1 - 1.25 / 1.5))
    share = importlib.import_module("readers.xplane_op_share").read(
        {"pattern": "^a$"}, src)
    assert share == pytest.approx(100 * 0.75 / 1.25)


def test_an_empty_window_reads_nothing():
    for data in ({"planes": []}, {"planes": [plane("/device:TPU:0")]},
                 {"planes": [plane("/host:CPU", [("x", 0, 5)])]}):
        assert xplane.busy_and_window(data) == (0.0, 0.0)
        for reader, args in (("xplane_busy", {}),
                             ("xplane_op_share", {"pattern": "x"}),
                             ("xplane_module_ms", {"pattern": "x"})):
            mod = importlib.import_module(f"readers.{reader}")
            assert mod.read(args, {"trace": data}) is None
        assert xplane.top_ops(data) == [] and xplane.longest_gaps(data) == []


def test_top_ops_gaps_and_module_median():
    data = {"planes": [plane(
        "/device:TPU:0",
        [("mm", 0, 40), ("attn", 50, 30), ("mm", 100, 40), ("cp", 150, 1)],
        [("jit_ragged(1)", 0, 80), ("jit_ragged(1)", 100, 60),
         ("jit_other", 170, 5)])]}
    assert xplane.top_ops(data, 2) == [["mm", 80 / 1e9], ["attn", 30 / 1e9]]
    gaps = xplane.longest_gaps(data, 2)
    assert [g[1] for g in gaps] == [20 / 1e9, 10 / 1e9]
    assert gaps[0][0].startswith("unattributed:attn>mm")
    med = importlib.import_module("readers.xplane_module_ms").read(
        {"pattern": "ragged"}, {"trace": data})
    assert med == pytest.approx(70 / 1e6)


def test_percentile_says_how_many_samples_it_had():
    assert stats.percentile([], 90) == (None, 0)
    v, n = stats.percentile(range(1, 101), 90)
    assert n == 100 and v == pytest.approx(90.1)
    xs = [10.0, 10.1, 9.9, 10.2, 9.8, 10.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.quartile_spread(xs) == pytest.approx((q3 - q1) / 10.0)


def test_step_ring_stats():
    steps = [
        {"kind": "prefill_chunk", "index": 0, "start_ns": 0, "end_ns": 4_000_000, "tokens": 128},
        {"kind": "decode", "index": 0, "start_ns": 0, "end_ns": 4_000_000, "batch": 6},
        {"kind": "decode", "index": 1, "start_ns": 5_000_000, "end_ns": 7_000_000, "batch": 8},
        {"kind": "decode", "index": 2, "start_ns": 8_000_000, "end_ns": 9_000_000, "batch": 7}]
    rd = importlib.import_module("readers.step_ring").read
    src = {"steps": steps, "max_batch": 8}
    assert rd({"stat": "occupancy"}, src) == pytest.approx(100 * 7 / 8)
    assert rd({"stat": "chunk_steps"}, src) == pytest.approx(100 / 3)
    assert rd({"stat": "step_host_ms"}, src) == pytest.approx(2.0)
    assert rd({"stat": "occupancy"}, {"steps": []}) is None
    cd = importlib.import_module("readers.counter_delta").read
    assert cd({"counter": "c"}, {"counters0": {"c": 3}, "counters1": {"c": 5}}) == 2.0
    assert cd({"counter": "c"}, {}) is None


def train_cfg():
    return json.loads((BENCH / "configs" / "mistral-7b-v0.3.train-d2.json")
                      .read_text())


def test_model_flops_against_a_hand_count():
    cfg = train_cfg()
    layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.layer_params(cfg) == layer == 218_103_808
    matmul = 2 * layer + 4096 * 32768
    # attention, one layer, one 4096-token sequence, causal: forward
    # 4 * heads * s^2 * d / 2, backward 2.5 x that
    attn = 2 * 3.5 * (4 * 32 * 4096 * 4096 * 128 / 2) / 4096
    per_token = flops.train_flops_per_token(cfg, 4096)
    assert per_token == pytest.approx(6 * matmul + attn)
    assert per_token == pytest.approx(3.66e9, rel=0.01)   # "3.6 GFLOP a token"
    mfu = importlib.import_module("readers.mfu").read({}, {
        "end_to_end": {"train.tokens_per_s": 20_000.0}, "config": cfg,
        "traffic": {"seq": 4096}, "peak": {"bf16_flops_per_s": 197e12}})
    assert mfu == pytest.approx(100 * per_token * 20_000 / 197e12)


def test_roofline_and_memory_readers():
    cfg = train_cfg()
    s = 1_000_000_000
    # a trace that starts and stops inside a step: the cut steps' module
    # executions are short, and their operations do not count
    ops = [("flash_bwd", 0, s // 50), ("other", s // 4, s // 10)]
    mods = [("jit_pure_step(1)", 0, s // 2), ("jit_convert(2)", 0, 5)]
    for t in (s // 2, s // 2 + s):
        ops += [("flash_fwd", t, s // 100), ("flash_bwd", t + s // 2, s // 50)]
        mods.append(("jit_pure_step(1)", t, s))
    ops.append(("flash_fwd", 2 * s + s // 2, s // 100))
    mods.append(("jit_pure_step(1)", 2 * s + s // 2, s // 100))
    data = {"planes": [plane("/device:TPU:0", ops, mods)]}
    src = {"trace": data, "config": cfg,
           "traffic": {"batch": 1, "seq": 4096},
           "peak": {"bf16_flops_per_s": 197e12}}
    rd = importlib.import_module("readers.roofline").read
    args = {"pattern": "flash", "work": "flash_attention_train",
            "bound": "compute", "step_module": "^jit_pure_step"}
    least = 2 * 2 * flops.attention_flops(cfg, 1, 4096, True) / 197e12
    assert rd(args, src) == pytest.approx(100 * least / 0.06)   # two whole steps
    assert rd(dict(args, pattern="nothing"), src) is None
    mem = importlib.import_module("readers.memory_stats").read
    assert mem({}, {"memory": {"peak_bytes_in_use": 12, "bytes_limit": 16}}) == 75.0
    assert mem({}, {"memory": {"peak_bytes_in_use": 0, "bytes_limit": 0}}) is None


def test_every_metric_of_the_manifest_has_its_file_and_reader():
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert (spec["unit"], spec["layer"], spec["moves"]) == (m["unit"], m["layer"], m["moves"])
        assert m["moves"] in e2e
        importlib.import_module(f"readers.{spec['reader']}")
    for w in manifest["workloads"]:
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        importlib.import_module(f"generators.{t['kind']}")
