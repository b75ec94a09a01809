"""``readers/roofline_kda.py`` on hand-made lists of device operations:
a program without the kernels reads nothing, a call's work is counted
once a call, and a call that takes its floor reads 100 %."""
import json

import pytest

import run
import xplane
from readers import roofline_kda as r

CFG = json.loads((run.ROOT / "benchmark/configs/"
                  "kimi-linear-48b-a3b.train-ep32-d5.json").read_text())
ARGS = json.loads((run.ROOT / "benchmark/layer_metrics/"
                   "kernel.kda.roofline.json").read_text())["args"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
TRAFFIC = {"batch": 1, "seq": 8192}
CALL = 'custom-call(%q), custom_call_target="tpu_custom_call"'
STEP_NS = 1e8


def source(step_ops, steps=2):
    """``steps`` whole executions of ``jit_pure_step``, each running
    ``step_ops`` [(name, start in the step, duration)]."""
    ops = [(n, s + i * STEP_NS, d) for i in range(steps)
           for n, s, d in step_ops]
    return {"trace": {"planes": [{"name": "/device:TPU:0", "lines": {
                xplane.OPS_LINE: ops,
                xplane.MODULES_LINE: [("jit_pure_step(1)", i * STEP_NS,
                                       STEP_NS) for i in range(steps)]}}]},
            "config": CFG, "peak": PEAK, "traffic": TRAFFIC}


def floors():
    return (r.call_floor_s(CFG, TRAFFIC, False, PEAK) * 1e9,
            r.call_floor_s(CFG, TRAFFIC, True, PEAK) * 1e9)


def test_the_floor_of_a_call_is_its_bytes_at_this_shape():
    fwd, bwd = floors()
    stream = 8192 * 32 * 128
    # 47 GFLOP a layer forward is 0.24 ms at the peak; q, k, v, o in
    # bfloat16 and the log-decay in float32 are 0.40 GB: 0.49 ms
    assert fwd == pytest.approx(stream * 12 / 819e9 * 1e9)
    assert 0.45e6 < fwd < 0.53e6
    assert bwd == pytest.approx(stream * 22 / 819e9 * 1e9)


def test_a_program_without_the_kernels_reads_nothing():
    # the parent commit: the scan over segments and its fusions
    parent = source([("%while.3 = (s32[]) while(%t)", 0, 3e7),
                     ("%fusion.1 = f32[32,8,4,16,128] fusion(%a)", 3e7, 1e7)])
    assert r.read(ARGS, parent) is None
    assert r.read(ARGS, dict(parent, peak=None)) is None


def test_a_call_at_its_floor_reads_100_and_each_call_counts_once():
    fwd, bwd = floors()
    at_floor = source([(f"%kda_chunk_fwd.2 = bf16[1] {CALL}", 0, fwd),
                       (f"%kda_chunk_fwd.3 = bf16[1] {CALL}", 2e7, fwd),
                       (f"%kda_chunk_bwd.1 = bf16[1] {CALL}", 4e7, bwd),
                       ("%fusion.1 = f32[2] fusion(%a)", 9e7, 1e6)])
    assert r.read(ARGS, at_floor) == pytest.approx(100.0)
    # the recomputed forward is a call of its own: two forwards at four
    # times their floor and a backward at twice its floor
    slow = source([(f"%kda_chunk_fwd.2 = bf16[1] {CALL}", 0, 4 * fwd),
                   (f"%kda_chunk_fwd.3 = bf16[1] {CALL}", 2e7, 4 * fwd),
                   (f"%kda_chunk_bwd.1 = bf16[1] {CALL}", 4e7, 2 * bwd)])
    want = 100 * (2 * fwd + bwd) / (8 * fwd + 2 * bwd)
    assert r.read(ARGS, slow) == pytest.approx(want)
    assert 0 < want < 100


def test_only_whole_steps_count():
    fwd, _ = floors()
    ops = [(f"%kda_chunk_fwd.2 = bf16[1] {CALL}", 0, 2 * fwd)]
    src = source(ops)
    # a call outside any whole step (the trace stopped inside a step)
    src["trace"]["planes"][0]["lines"][xplane.OPS_LINE].append(
        (f"%kda_chunk_fwd.2 = bf16[1] {CALL}", 2 * STEP_NS + 10, 50 * fwd))
    assert r.read(ARGS, src) == pytest.approx(50.0)
