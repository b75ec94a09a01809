"""The Laguna serving cell by hand on the CPU: the manifest's names, the
configuration against the published widths, the counts of
``flops_laguna.py`` against hand counts, its rehearsal end to end, and
the two readers this cell brought, each on a source it can read and on
one that lacks what it reads (a program without the scopes or the ring's
fields: the parent commit)."""
import argparse
import json

import pytest

import run

CELL = "laguna-xs2.serve.agent8"
M = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((run.ROOT / "benchmark/configs/"
                  "laguna-xs.2.serve-pp8-d5.json").read_text())


def test_the_cell_s_names_resolve_and_it_is_on_the_serve_lists():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="laguna-xs.2.serve-pp8-d5",
                        traffic="closed8-code-agent", chips=1)
    assert (run.BENCH / "drivers" / f"{CFG['driver']}.py").is_file()
    traffic = json.loads((run.BENCH / "traffic" /
                          "closed8-code-agent.json").read_text())
    assert traffic["kind"] == "closed_loop" and traffic["clients"] == 8
    mistral = "mistral7b.serve.closed8"
    for m in M["end_to_end"] + M["per_layer"]:
        if mistral in m.get("workloads", []):
            assert CELL in m["workloads"], m["name"]
    mine = [m for m in M["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} >= {
        "serve.device.moe", "serve.device.attn_sliding",
        "serve.device.attn_full", "moe.serve.touched_share",
        "moe.serve.pad_share", "moe.experts.hbm_roofline",
        "kernel.paged_attn.window_walk_share"}
    for m in mine:
        spec = json.loads((run.BENCH / "layer_metrics" /
                           f"{m['name']}.json").read_text())
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (m["name"], m["unit"], m["layer"], "serve.tokens_per_s")
        assert (run.BENCH / "readers" / f"{spec['reader']}.py").is_file()


def test_the_traffic_is_the_issue_s():
    from generators.common import lognormal_pool
    t = json.loads((run.BENCH / "traffic" /
                    "closed8-code-agent.json").read_text())
    p = lognormal_pool(t["prompt_tokens"], t["levels"])
    o = lognormal_pool(t["output_tokens"], t["levels"])
    assert (p[0], p[-1], o[0], o[-1]) == (1224, 7168, 59, 276)
    assert (p.sum(), o.sum()) == (28008, 1135)
    # every context is 2.4 to 15 windows long and fits the rope table
    assert p[0] / CFG["sliding_window"] > 2.39
    assert p[-1] + o[-1] < CFG["max_position_embeddings"]


def test_the_configuration_keeps_every_published_width():
    published = dict(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        rms_norm_eps=1e-06, num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=512, shared_expert_intermediate_size=512,
        sliding_window=512, partial_rotary_factor=0.5, gating=True,
        moe_routed_scaling_factor=2.5, tie_word_embeddings=False,
        attention_bias=False, moe_apply_router_weight_on_input=False)
    assert {k: CFG[k] for k in published} == published
    assert CFG["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert len(CFG["layer_types"]) == len(CFG["mlp_layer_types"]) \
        == len(CFG["num_attention_heads_per_layer"]) == 40   # kept whole
    assert CFG["reduced"] == ["num_hidden_layers", "max_position_embeddings"]
    assert set(CFG["assumed"]) == {"gating", "router_scores", "qk_norm"}
    from reference import laguna_plain as plain
    mc = plain.model_cfg(CFG)
    assert mc["layer_types"] == ["full_attention"] \
        + ["sliding_attention"] * 3 + ["full_attention"]
    assert mc["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert mc["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]


def test_counts_by_hand():
    import math
    import flops_laguna as fl
    from reference import laguna_plain as plain
    mc = plain.model_cfg(CFG)
    # ISSUE 33's reckoning: 29.46 M a full layer's attention, 37.88 M a
    # sliding one's, 50.33 M the dense FFN, 809.0 M a sparse one, 411.0 M
    # embedding + head: 3,870 M, 7.74 GB
    assert fl.attention_params(mc, 0) == 2 * 2048 * 6144 + 2 * 2048 * 1024 \
        + 2048 * 48
    assert fl.attention_params(mc, 1) == 2 * 2048 * 8192 + 2 * 2048 * 1024 \
        + 2048 * 64
    assert fl.ffn_params(mc, 0) == 3 * 2048 * 8192
    assert fl.ffn_params(mc, 1) == 256 * 3 * 2048 * 512 + 3 * 2048 * 512 \
        + 2048 * 256
    assert round(fl.model_params(mc) / 1e6) == 3870
    gains_and_bias = 11 * 2048 + 4 * 256
    assert sum(math.prod(s) for _, s in plain.param_specs(mc)) \
        == fl.model_params(mc) + gains_and_bias
    assert fl.sparse_layers(mc) == 4
    # one expert is 6.29 MB; all 1,024 of a step 6.44 GB; a chunk step
    # that touches them all streams 7.33 GB (7.74 less the embedding)
    assert fl.expert_bytes(mc, 1) == 3 * 2048 * 512 * 2
    assert round(fl.expert_bytes(mc, 1024) / 1e9, 2) == 6.44
    assert round(fl.step_weight_bytes(mc, 1024) / 1e9, 2) == 7.33
    assert fl.kv_bytes_per_token(mc) == 20480     # 4 KB a layer
    assert fl.kv_bytes_per_token(mc) * 16 * 8192 == 2684354560


def test_rehearsal_end_to_end():
    args = argparse.Namespace(workload=CELL, seed=2**31 + 33, seconds=2.0,
                              trace=0, rehearse=True)
    line, checks = run.run_cell(args, {})
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve.tokens_per_s", "setup_s"}
    read = {n: v for n, v, _ in checks}
    assert read["requests_compared"] > 0
    assert 0 <= read["router_flip_share"] < 0.05
    # the planted faults: the window off, one expert fewer
    for fault in ({"window": None}, {"top_k": 1}):
        line, checks = run.run_cell(args, {"reference": fault})
        assert not line["correct"], fault


# ------------------------------------------------------------ the readers
def _trace(mods, ops):
    return {"planes": [{"name": "/device:TPU:0", "lines": {
        "XLA Modules": mods, "XLA Ops": ops}}]}


def test_scope_share_tells_two_programs_apart():
    from readers import xplane_scope_share_programs as rd
    # both programs number a ``fusion.1``; under different scopes
    big = {"fusion.1": "serve/model/attn_full", "fusion.2":
           "serve/model/moe/experts", "moe_grouped_ffn.3":
           "serve/model/moe/experts", "copy.9": ""}
    small = {"fusion.1": "serve/model/moe/router", "fusion.2":
             "serve/model/attn_sliding"}
    trace = _trace(
        [("jit_fn(1)", 0, 100), ("jit_fn(2)", 200, 50)],
        [("%fusion.1 = bf16[8]", 0, 10), ("%fusion.2 = bf16[8]", 10, 30),
         ("%moe_grouped_ffn.3 = bf16[8] custom-call", 40, 20),
         ("%copy.9 = bf16[8]", 60, 40),
         ("%fusion.1 = bf16[8]", 200, 20), ("%fusion.2 = bf16[8]", 220, 30)])
    src = {"trace": trace, "hlo_scopes_by_program": [big, small]}
    share = lambda scope: rd.read({"scope": scope}, src)   # noqa: E731
    assert share("^serve/model/moe/") == pytest.approx(100 * 70 / 150)
    assert share("^serve/model/attn_full") == pytest.approx(100 * 10 / 150)
    assert share("^serve/model/attn_sliding") == pytest.approx(100 * 30 / 150)
    # the parent: no maps
    assert rd.read({"scope": "^serve"}, {"trace": trace}) is None
    # a module no map knows is left out, not guessed
    odd = _trace([("jit_other(3)", 0, 10)], [("%weird.1 = f32[1]", 0, 10)])
    assert rd.read({"scope": "^serve"}, dict(src, trace=odd)) == 0.0


def test_moe_roofline_counts_touched_experts_inside_the_trace():
    from readers import moe_hbm_roofline as rd
    one = 3 * 2048 * 512 * 2
    steps = [{"kind": "dispatch", "start_ns": s, "end_ns": s + 5,
              "moe_experts_touched": t}
             for s, t in ((0, 1000), (10, 819), (20, 1000))]
    trace = _trace([("jit_fn(1)", 0, 10_000_000)],
                   [("%moe_grouped_ffn.3 = bf16[8] custom-call", 0,
                     int(2 * one / 1e9 * 1e9))])      # 2 x one expert's ns
    src = {"trace": trace, "steps": steps, "config": CFG,
           "peak": {"hbm_bytes_per_s": 1e9}, "trace_window_ns": (8, 18)}
    got = rd.read({"time": {"op": "^%?moe_grouped_ffn"}}, src)
    assert got == pytest.approx(100 * 819 / 2, rel=1e-6)   # only step 2
    src["hlo_scopes_by_program"] = [{"moe_grouped_ffn.3":
                                     "serve/model/moe/experts"}]
    assert rd.read({"time": {"scope": "^serve/model/moe/experts"}}, src) \
        == pytest.approx(got)
    for lacks in ("steps", "trace_window_ns"):
        assert rd.read({"time": {"op": "x"}},
                       {k: v for k, v in src.items() if k != lacks}) is None
    bare = [{"kind": "dispatch", "start_ns": 10, "end_ns": 15}]
    assert rd.read({"time": {"op": "x"}}, dict(src, steps=bare)) is None
