"""The MiMo-V2-Flash serving cell by hand on the CPU: the manifest's entries
found BY NAME and the cell's MEMBERSHIP of the ``workloads`` lists (not
equality, not position: a later cell must not break this file), the
configuration against the catalog row's published keys, the traffic against
ISSUE 49's, the mix replayed through the planner's rule (which programs a
window can ask for, how many pages it reserves), ``flops_mimo.py`` against
hand counts, the rehearsal end to end, and the reader this cell brought on a
made ring and on a source that lacks what it reads (a program without the
fields: the parent commit)."""
import argparse
import json

import pytest

import run

CELL = "mimo-v2-flash.serve.mixed32"
CONFIG = "mimo-v2-flash.serve-ep16-d7"
M = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((run.ROOT / f"benchmark/configs/{CONFIG}.json").read_text())
TRAFFIC = json.loads((run.BENCH / "traffic" /
                      "closed32-mixed-agent.json").read_text())
MINE = {"moe.experts.hbm_roofline.mimo": "expert layer",
        "kernel.paged_attn.hbm_roofline.mimo": "paged kernels",
        "kv.serve.window_dead_share": "paged kernels"}
#: read by an accepted reader off scopes of the new model (the head and the
#: dense FFN: what the step holds beside attention and the experts)
BY_SCOPE = {"serve.device.dense_ffn.mimo": "model layers"}


def named(items, name):
    found = [it for it in items if it["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_cell_s_names_resolve_and_it_is_on_the_serve_lists():
    cell = named(M["workloads"], CELL)
    assert cell == dict(cell, config=CONFIG, traffic="closed32-mixed-agent",
                        chips=1)
    assert len(cell["why"]) <= 200
    config = named(M["configs"], CONFIG)
    assert config == dict(
        config, source=CFG["source"],
        reduced=["num_hidden_layers", "n_routed_experts",
                 "max_position_embeddings"],
        file=f"benchmark/configs/{CONFIG}.json")
    assert config["source"] == ("https://huggingface.co/XiaomiMiMo/"
                                "MiMo-V2-Flash/blob/main/config.json")
    assert (run.BENCH / "drivers" / f"{CFG['driver']}.py").is_file()
    listed = {m["name"] for m in M["end_to_end"] + M["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= set(MINE) | set(BY_SCOPE) | {
        "serve.tokens_per_s", "serve.ttft_p90_ms", "serve.tpot_p90_ms",
        "engine.occupancy", "engine.chunk_steps", "serve.step_host_ms",
        "engine.compiles_in_window", "serve.step_device_ms",
        "engine.overlap_share", "engine.late_launch_share",
        "host.step_work_ms", "host.step_work_max_ms",
        "host.gc_pause_ms_per_step", "host.profiler_slowdown",
        "device.idle.serve", "idle.serve.schedule", "idle.serve.commit",
        "idle.serve.commit.retire", "idle.serve.build",
        "idle.serve.build.reserve", "idle.serve.dispatch",
        "idle.serve.fetch", "idle.serve.gc", "idle.serve.unattributed",
        "serve.dense_pad_share", "setup.trace_lower_s", "setup.compile_s",
        "kernel.paged_attn.busy", "kernel.paged_attn.ctx_useful",
        "kernel.paged_attn.walk_useful", "kernel.paged_attn.query_useful",
        "kernel.paged_attn.copy_share",
        "kernel.paged_attn.window_walk_share", "serve.device.moe",
        "serve.device.moe_router", "serve.device.attn_sliding",
        "serve.device.attn_full", "moe.serve.touched_share",
        "moe.serve.pad_share", "moe.serve.max_expert_share"}
    # no recurrent state, no latent attention; and the accepted expert
    # rooflines stay bound to Laguna's and ZAYA's counts
    assert not listed & {
        "moe.experts.hbm_roofline", "moe.experts.hbm_roofline.zaya",
        "moe_grouped_ffn_roofline", "serve.device.mamba",
        "serve.device.retention", "serve.device.cca",
        "state.serve.bytes_per_token", "state.serve.slots_used"}
    every = named(M["end_to_end"], "setup_s")
    assert "workloads" not in every
    for name, layer in {**MINE, **BY_SCOPE}.items():
        m = named(M["per_layer"], name)
        assert CELL in m["workloads"] and m["layer"] == layer
        assert (m["unit"], m["moves"]) == ("%", "serve.tokens_per_s")
        spec = json.loads((run.BENCH / "layer_metrics" /
                           f"{name}.json").read_text())
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (name, "%", layer, "serve.tokens_per_s")
        assert (run.BENCH / "readers" / f"{spec['reader']}.py").is_file()


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog row's ``config`` under the same key,
    but the three reduced; the per-layer lists whole."""
    published = {
        "attention_value_scale": 0.707, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384,
        "model_type": "mimo_v2_flash", "num_attention_heads": 64,
        "head_dim": 192, "num_key_value_heads": 4,
        "layernorm_epsilon": 1e-05, "rope_theta": 5000000,
        "tie_word_embeddings": False, "vocab_size": 152576,
        "partial_rotary_factor": 0.334, "sliding_window": 128,
        "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 128,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "sliding_window_size": 128,
        "attention_chunk_size": 128, "moe_intermediate_size": 2048,
        "n_shared_experts": None, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 8, "swa_head_dim": 192,
        "swa_v_head_dim": 128}
    assert {k: CFG[k] for k in published} == published
    assert len(CFG["hybrid_layer_pattern"]) == 48 \
        and CFG["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert CFG["moe_layer_freq"] == [0] + [1] * 47
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["max_position_embeddings"]) == (7, 16, 8192)
    assert (CFG["routed_experts_published"], CFG["held_experts_first"]) \
        == (256, 0)
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "max_position_embeddings"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"]) | {"deployment"}
    assert "16 chips share each layer" in CFG["reduced_why"]["deployment"]
    assert set(CFG["assumed"]) == {
        "sink", "sink_values", "attention_value_scale",
        "partial_rotary_factor", "score_scale", "qk_norm",
        "attention_chunk_size", "router", "mtp", "norms", "k_layout"}
    assert "NO multi-token-prediction module" in CFG["assumed"]["mtp"]
    assert "16-chip expert-parallel group" in CFG["stands_for"]
    assert CFG["control"] == {"engine": {"quantize": "w8a8"}}
    engine = CFG["driver_options"]["engine"]
    assert (engine["max_batch"], engine["page_size"],
            engine["min_table_pages"]) == (32, 16, 512)
    assert 6144 <= engine["total_pages"] <= 8192
    from reference import mimo_v2_flash_plain as plain
    mc = plain.model_cfg(CFG)
    assert (mc["n_routed_experts"], mc["held_experts"]) == (256, (0, 16))
    from paddle_tpu.models.mimo_v2_flash import MiMoV2FlashConfig
    c = MiMoV2FlashConfig(**mc)
    assert c.rotary_dim == 64 and c.hybrid_layer_pattern.count(1) == 5


def test_the_traffic_is_the_issue_s():
    from generators.common import lognormal_pool
    t = TRAFFIC
    assert (t["kind"], t["clients"], t["levels"], t["ramp_requests"],
            t["order_seed"], t["shared_prefix_tokens"]) \
        == ("closed_loop", 32, 8, 32, 1, 0)
    assert t["prompt_tokens"] == {"median": 2048, "sigma": 0.9, "lo": 128,
                                  "hi": 6144}
    assert t["output_tokens"] == {"median": 512, "sigma": 0.5, "lo": 128,
                                  "hi": 2048}
    p = lognormal_pool(t["prompt_tokens"], t["levels"])
    o = lognormal_pool(t["output_tokens"], t["levels"])
    assert (p[0], p[-1], o[0], o[-1]) == (515, 6144, 238, 1103)
    assert (p.sum(), o.sum()) == (20768, 4550)
    engine = CFG["driver_options"]["engine"]
    assert engine["max_batch"] == t["clients"]
    assert p[-1] + o[-1] == 7247 < CFG["max_position_embeddings"] \
        == engine["min_table_pages"] * engine["page_size"]
    from drivers.serve_laguna import step_spans
    assert step_spans(t, 128) == [1, 4, 32, 64, 128]


def test_the_mix_replayed_asks_for_the_warmed_programs_only():
    """The closed loop through the planner's rule (one chunk budget a step,
    first come first served, a chunk never split; a request's first token
    comes with its last chunk): once the 32 clients' first prompts are in,
    no step holds fewer than 17 rows (ONE rows bucket, 32), a step's
    longest span is one of the five the warm-up asks for, most steps carry
    a chunk, and the pages the 32 admitted requests reserve (prompt +
    output, whole pages) stay under the pool's: no request waits for a
    page or is preempted."""
    import generators.closed_loop as gen_mod
    gen = gen_mod.build(TRAFFIC, 152576, 1)
    engine = CFG["driver_options"]["engine"]
    chunk, n = engine["prefill_chunk_tokens"], TRAFFIC["clients"]
    clients, pages = [], []          # [prompt left, output left]
    for _ in range(n):
        ids, out = gen.next_request()
        clients.append([len(ids), out])
        pages.append(-(-(len(ids) + out) // 16))
    most_pages = sum(pages)
    order = list(range(n))           # who waits for the budget, in turn
    fewest, spans, chunk_steps, steps, ramp = n, set(), 0, 30000, 1500
    for step in range(steps + ramp):
        budget, rows, longest = chunk, 0, 1
        for i in [i for i in order if clients[i][0] > 0]:
            if budget <= 0:
                break
            k = min(clients[i][0], chunk)
            budget -= k
            clients[i][0] -= k
            rows, longest = rows + 1, max(longest, k)
            if clients[i][0] == 0:
                clients[i][1] -= 1           # the first token
                clients[i].append("fresh")
        for i, c in enumerate(clients):
            if c[0] == 0 and c[-1] != "fresh":
                rows += 1
                c[1] -= 1
            if c[-1] == "fresh":
                c.pop()
            if c[0] == 0 and c[1] <= 0:      # done: the client's next
                ids, out = gen.next_request()
                clients[i] = [len(ids), out]
                pages[i] = -(-(len(ids) + out) // 16)
                most_pages = max(most_pages, sum(pages))
                order.remove(i)
                order.append(i)
        if step < ramp:
            continue
        fewest = min(fewest, rows)
        spans.add(1 << (longest - 1).bit_length())
        chunk_steps += longest > 1
    assert fewest >= 17
    assert most_pages < engine["total_pages"] - 512
    assert spans <= {1, 4, 32, 64, 128} and {1, 128} <= spans
    assert chunk_steps / steps > 0.5


def test_counts_by_hand():
    import numpy as np
    import flops_mimo as fm
    from reference import mimo_v2_flash_plain as plain
    mc = plain.model_cfg(CFG)
    # ISSUE 49's reckoning: full attention 89.13 M, sliding 94.37 M, dense
    # FFN 201.33 M, an expert 25.17 M = 50.33 MB, router 1.05 M
    assert round(fm.attention_params(mc, 0) / 1e6, 2) == 89.13
    assert round(fm.attention_params(mc, 1) / 1e6, 2) == 94.37
    assert round(fm.ffn_params(mc, 0) / 1e6, 2) == 201.33
    assert round(fm.expert_bytes(mc, 1) / 1e6, 2) == 50.33
    assert round((fm.ffn_params(mc, 1) - 16 * fm.expert_params(mc)) / 1e6,
                 2) == 1.05
    built = sum(int(np.prod(s)) for _, s in plain.param_specs(mc))
    assert abs(built - fm.model_params(mc)) < 1e5       # gains, sinks
    assert round(built / 1e6, 1) == 4523.6
    assert round(built * 2 / 1e9, 2) == 9.05
    # a page index: 2 full pools of 40,960 B and 5 sliding of 81,920 B
    assert [16 * fm.kv_bytes_per_token(mc, i) for i in (0, 1)] \
        == [40960, 81920]
    assert 16 * fm.kv_bytes_per_token_all(mc) == 491520
    assert round(8192 * 491520 / 1e9, 2) == 4.03


def test_rehearsal_end_to_end():
    # 6 s: the rehearsal's four clients leave rows buckets the warm-up
    # does not make, and one compile on the CPU would eat a 2 s window
    args = argparse.Namespace(workload=CELL, seed=2**31 + 49, seconds=6.0,
                              trace=0, rehearse=True)
    line, checks = run.run_cell(args, {})
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve.tokens_per_s", "setup_s"}
    assert [n for n, _, _ in checks] == [
        "requests_compared", "served_logit_gap_max",
        "served_logit_gap_mean", "router_flip_share"]


# ------------------------------------------------ the readers, made sources
def _dispatch(start, touched, full, sliding, pinned, dead):
    return {"kind": "dispatch", "start_ns": start, "end_ns": start + 10,
            "tokens": 160, "moe_experts_touched": touched,
            "moe_expert_layers": 96, "kv_bytes_copied_full": full,
            "kv_bytes_copied_sliding": sliding, "kv_pinned_bytes": pinned,
            "kv_dead_bytes": dead}


def test_the_readers_on_a_made_ring_and_trace(monkeypatch):
    from readers import hbm_roofline_ring as roof, ring_ratio
    import xplane
    steps = [_dispatch(100, 90, 4e8, 1e8, 10e9, 8e9),
             _dispatch(200, 96, 6e8, 1e8, 10e9, 7e9),
             _dispatch(900, 96, 9e9, 9e9, 10e9, 9e9),
             {"kind": "decode", "batch": 3}]
    src = {"steps": steps, "config": CFG}
    spec = json.loads((run.BENCH / "layer_metrics" /
                       "kv.serve.window_dead_share.json").read_text())
    assert ring_ratio.read(spec["args"], src) == pytest.approx(80.0)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    traced = dict(src, peak=peak, trace_window_ns=(50, 500),
                  trace={"planes": []})
    # the paged kernels: the two records inside the host's stamps, 1.2 GB
    # at 819 GB/s, over 4 ms of the kernels' device time
    spec = json.loads((run.BENCH / "layer_metrics" /
                       "kernel.paged_attn.hbm_roofline.mimo.json").read_text())
    assert spec["args"]["op"] == "^%?paged_attention"
    monkeypatch.setattr(xplane, "matching_seconds",
                        lambda trace, op: (0.004, 14))
    assert roof.read(spec["args"], traced) \
        == pytest.approx(100 * 1.2e9 / 819e9 / 0.004)
    # the experts: 186 touched experts of 50.33 MB over 15 ms of the
    # scope's own device time, whatever implements it
    spec = json.loads((run.BENCH / "layer_metrics" /
                       "moe.experts.hbm_roofline.mimo.json").read_text())
    assert spec["args"]["scope"] == "^serve/model/moe/experts"
    monkeypatch.setattr(roof, "scope_seconds", lambda src, scope: 0.015)
    share = roof.read(spec["args"], traced)
    assert share == pytest.approx(
        100 * 186 * 3 * 4096 * 2048 * 2 / 819e9 / 0.015)
    assert 70 < share < 80


def test_the_readers_find_nothing_on_a_program_without_the_fields():
    from readers import hbm_roofline_ring as roof, ring_ratio
    peak = {"bf16_flops_per_s": 1, "hbm_bytes_per_s": 1}
    parent = {"steps": [{"kind": "dispatch", "tokens": 9, "start_ns": 1,
                         "end_ns": 2}], "config": CFG, "peak": peak,
              "trace_window_ns": (0, 10), "trace": {"planes": []}}
    for name in MINE:
        spec = json.loads((run.BENCH / "layer_metrics" /
                           f"{name}.json").read_text())
        reader = roof if spec["reader"] == "hbm_roofline_ring" else ring_ratio
        assert reader.read(spec["args"], parent) is None
        assert reader.read(spec["args"], {}) is None
    # another model's configuration under the expert reader
    other = dict(parent, config={"driver": "serve_zaya"}, steps=[
        dict(parent["steps"][0], moe_experts_touched=3)])
    assert roof.read({"fields": ["moe_experts_touched"], "per": "expert",
                      "scope": "x"}, other) is None
