"""The Kimi-Linear cell by hand on the CPU: its rehearsal end to end,
the manifest's names, the counts of ``flops_kimi_linear.py`` and the
readers this cell brought, each on a source it can read and on one that
lacks what it reads (a program without the scopes or the counters: the
parent commit)."""
import argparse
import json

import pytest

import run

CELL = "kimi-linear.train.seq8k"
M = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def cell(trace=0, seed=2**31 + 77, seconds=1.5):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True)
    return run.run_cell(args, {})


def test_every_workloads_name_resolves():
    cells = {w["name"]: w for w in M["workloads"]}
    configs = {c["name"]: c for c in M["configs"]}
    for m in M["end_to_end"] + M["per_layer"]:
        assert set(m.get("workloads", [])) <= set(cells), m["name"]
    for w in cells.values():
        cfg = json.loads((run.ROOT / configs[w["config"]]["file"]).read_text())
        traffic = json.loads(
            (run.BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (run.BENCH / "drivers" / f"{cfg['driver']}.py").is_file()
        assert (run.BENCH / "generators" / f"{traffic['kind']}.py").is_file()
    for m in M["per_layer"]:
        spec = json.loads(
            (run.BENCH / "layer_metrics" / f"{m['name']}.json").read_text())
        assert spec["name"] == m["name"] and spec["unit"] == m["unit"]
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert (run.BENCH / "readers" / f"{spec['reader']}.py").is_file()
    assert cells[CELL]["chips"] == 1
    assert CELL not in next(m for m in M["per_layer"]
                            if m["name"] == "train.mfu")["workloads"]


def test_the_configuration_keeps_every_published_width():
    from reference import kimi_linear_plain as plain
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "kimi-linear-48b-a3b.train-ep32-d5.json").read_text())
    published = dict(
        hidden_size=2304, intermediate_size=9216, moe_intermediate_size=1024,
        num_attention_heads=32, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, head_dim=72,
        num_experts_per_token=8, num_shared_experts=1,
        routed_scaling_factor=2.446, first_k_dense_replace=1)
    assert {k: cfg[k] for k in published} == published
    assert cfg["linear_attn_config"]["head_dim"] == 128
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size", "model_max_length"]
    assert all("32 chips share each layer" in cfg["reduced_why"][k]
               for k in cfg["reduced"][:3])
    mc = plain.model_cfg(cfg)
    assert mc["num_experts"] == 256 and mc["held_experts"] == (0, 8)
    count = sum(int(__import__("math").prod(s))
                for _, s in plain.param_specs(mc))
    assert 600e6 < count < 605e6           # 602 M, 9.6 GB at 16 B each


def test_flops_of_the_cell_by_hand():
    import flops_kimi_linear as fl
    from reference import kimi_linear_plain as plain
    cfg = plain.model_cfg(json.loads(
        (run.ROOT / "benchmark/configs/"
         "kimi-linear-48b-a3b.train-ep32-d5.json").read_text()))
    assert fl.kda_matrix_params(cfg) == (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
        + 3 * 4096 * 4)
    assert fl.mla_matrix_params(cfg) == (
        2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304)
    held = 8 * 8 / 256                      # even routing: 0.25 slots a token
    per_token = fl.matrix_params_per_token(cfg, held)
    assert 330e6 < per_token < 340e6        # ISSUE 29: 336 M
    mla = fl.mla_attention_flops(cfg, 1, 8192, True) / 8192
    # forward QK^T, PV; backward dV, dP and the scores again, dQ, dK
    assert mla == 2 * 32 * 8192 * 0.5 * ((192 + 128) + (3 * 192 + 2 * 128))
    kda = fl.kda_chunk_flops(cfg, 1, 8192, True) / 8192
    assert kda == 3 * 32 * 2 * (3 * 64 * 128 + 2 * 64 * 128 + 3 * 128 * 128)
    total = fl.train_flops_per_token(cfg, 8192, held)
    assert total == 6 * per_token + mla + 4 * kda
    assert 2.3e9 < total < 2.5e9            # about 2.4 GFLOP a token
    # no held slot: the routed experts' products go, nothing else
    assert fl.matrix_params_per_token(cfg, 0.0) == pytest.approx(
        per_token - 4 * 3 * 2304 * 1024 * held)


def test_scopes_out_of_a_compiled_text():
    from drivers.train_kimi_linear import hlo_scopes, scope_of
    assert scope_of("jit(pure_step)/jvp(train/model)/kda/closed_call/while"
                    "/body/mul") == "train/model/kda/closed_call"
    assert scope_of("jit(pure_step)/transpose(jvp(train/model))/moe/experts"
                    "/dot_general") == "train/model/moe/experts"
    assert scope_of("jit(pure_step)/train/optimizer/add") \
        == "train/optimizer/add"
    assert scope_of("arrays[3]") == ""
    text = '''
  %fusion.7 = bf16[8,4]{1,0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(pure_step)/jvp(train/model)/mla/dot_general" source_line=3}
  ROOT %while.3 = (s32[]) while(%t), body=%b, metadata={op_name="jit(pure_step)/jvp(train/model)/kda/while"}
  %copy.1 = f32[4]{0} copy(%x)
  %add.9 = f32[] add(%a, %b), metadata={op_name="arrays[1]"}'''
    assert hlo_scopes(text) == {"fusion.7": "train/model/mla/dot_general",
                                "while.3": "train/model/kda/while"}


def trace_of(events):
    return {"planes": [{"name": "/device:TPU:0",
                        "lines": {"XLA Ops": events}}]}


def test_scope_share_counts_an_events_own_time():
    from readers import xplane_scope_share as r
    events = [("%while.3 = (s32[]) while(%t)", 0, 100),       # holds the two below
              ("%fusion.1 = f32[2] fusion(%a)", 10, 30),
              ("%fusion.2 = f32[2] fusion(%b)", 50, 40),
              ("%fusion.7 = bf16[8,4] fusion(%p)", 100, 50),
              ("%copy.1 = f32[4] copy(%x)", 150, 50)]
    assert r.own_times(events) == [
        ("%while.3 = (s32[]) while(%t)", 30),
        ("%fusion.1 = f32[2] fusion(%a)", 30),
        ("%fusion.2 = f32[2] fusion(%b)", 40),
        ("%fusion.7 = bf16[8,4] fusion(%p)", 50),
        ("%copy.1 = f32[4] copy(%x)", 50)]
    scopes = {"while.3": "train/model/kda/while", "fusion.1": "train/model/kda/mul",
              "fusion.2": "train/model/kda/exp", "fusion.7": "train/model/mla/dot"}
    src = {"trace": trace_of(events), "hlo_scopes": scopes}
    kda = r.read({"scope": "^train/model/kda(/|$)"}, src)
    mla = r.read({"scope": "^train/model/mla(/|$)"}, src)
    assert kda == pytest.approx(50.0) and mla == pytest.approx(25.0)
    assert r.read({"scope": "^train/(loss|optimizer)"}, src) == 0.0
    # the parent commit's program: no map, nothing to read, no error
    assert r.read({"scope": "^train/model/kda"},
                  {"trace": trace_of(events)}) is None


def test_named_roofline_counts_each_call_at_its_own_work():
    from readers import roofline_named as r
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "kimi-linear-48b-a3b.train-ep32-d5.json").read_text())
    args = json.loads((run.ROOT / "benchmark/layer_metrics/"
                       "kernel.flash_attn_mla.roofline.json").read_text())["args"]
    pairs = 2 * 32 * 8192 * 8192 * 0.5
    fwd_ns = pairs * 320 / 197e12 * 1e9         # the forward at the peak
    call = 'custom-call(%q), custom_call_target="tpu_custom_call"'
    step = [(f"%flash_attention_fwd.2 = bf16[1] {call}", 0, 2 * fwd_ns),
            (f"%flash_attention_fwd.3 = bf16[1] {call}", 3e7, 2 * fwd_ns),
            (f"%flash_attention_bwd_dkv.1 = f32[1] {call}", 6e7,
             pairs * 832 / 197e12 * 1e9),
            (f"%flash_attention_bwd_dq.1 = bf16[1] {call}", 9e7, 0.0),
            ("%fusion.1 = f32[2] fusion(%a)", 9.5e7, 1e6)]
    trace = trace_of(step + [(n, s + 1e8, d) for n, s, d in step])
    trace["planes"][0]["lines"]["XLA Modules"] = [
        ("jit_pure_step(1)", 0, 1e8), ("jit_pure_step(1)", 1e8, 1e8)]
    src = {"trace": trace, "config": cfg, "peak": {"bf16_flops_per_s": 197e12},
           "traffic": {"batch": 1, "seq": 8192}}
    # two forward calls at half the peak, the backward at the peak
    want = 100 * (2 * 320 + 832) / (4 * 320 + 832)
    assert r.read(args, src) == pytest.approx(want)
    # a program that calls none of them: nothing to read, no error
    none = dict(src, trace=trace_of([("%fusion.1 = f32[2] fusion(%a)", 0, 5)]))
    assert r.read(args, none) is None


def test_counter_ratio_and_mfu_read_the_programs_counters():
    from readers import counter_ratio_at_open as ratio
    from readers import mfu_kimi_linear as mfu
    c0 = {"moe_slots_total": 786432.0, "moe_held_slots_total": 24576.0,
          "moe_rows_computed_total": 98304.0}
    share = {"numerator": ["moe_held_slots_total"],
             "denominator": ["moe_slots_total"]}
    pad = {"numerator": ["moe_held_slots_total"],
           "denominator": ["moe_rows_computed_total"], "complement": True}
    assert ratio.read(share, {"counters0": c0}) == pytest.approx(3.125)
    assert ratio.read(pad, {"counters0": c0}) == pytest.approx(75.0)
    assert ratio.read(share, {"counters0": {}}) is None
    assert ratio.read(share, {}) is None
    cfg = json.loads((run.ROOT / "benchmark/configs/"
                      "kimi-linear-48b-a3b.train-ep32-d5.json").read_text())
    src = {"group_step_ms": [270.0, 273.07, 280.0], "counters0": c0,
           "peak": {"bf16_flops_per_s": 197e12}, "config": cfg,
           "traffic": {"batch": 1, "seq": 8192}}
    # 8,192 tokens in 273.07 ms: 30,000 a second x 2.4 GFLOP / 197 T
    assert 34.0 < mfu.read({}, src) < 38.0
    assert mfu.read({}, dict(src, counters0={})) is None
    assert mfu.read({}, dict(src, group_step_ms=[])) is None


def test_rehearsal_end_to_end(capsys):
    line, checks = cell(trace=1)
    assert line["correct"] and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 2 and line["failed"] == 0
    assert {"train.step_ms", "moe.held_slot_share", "moe.pad_share",
            "setup.compile_s", "setup.trace_lower_s"} <= set(line["metrics"])
    # 8 of 32 experts held, 4 of 32 chosen a token: a quarter of the slots
    assert 15 < line["metrics"]["moe.held_slot_share"]["value"] < 40
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(line))
    line0, _ = cell(trace=0)
    assert set(line0["metrics"]) == {"train.tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["beta1", "sign"])
def test_a_planted_optimizer_fault_is_not_correct(fault):
    """A fault in the program's AdamW and none in the reference comes out
    not ``correct`` by ``run.py``'s own comparison."""
    from chip_limits_kimi_linear import run_planted
    line, checks = run_planted(fault, 2**31 + 77, rehearse=True, seconds=1.0)
    over = [n for n, v, lim in checks if not v <= lim]
    assert over and not line["correct"], checks
