"""The ZAYA serving cell by hand on the CPU: the manifest's entries found BY
NAME and the cell's membership of the ``workloads`` lists (not the
manifest's last entries: a later cell must not break this file), the
configuration against the catalog row's published keys, the traffic against
ISSUE 44's, the mix replayed through the planner's rule (which programs a
window can ask for, how many pages it reserves), ``flops_zaya.py`` against
hand counts, the rehearsal end to end, and the reader this cell brought on a
made ring and on a source that lacks what it reads (a program without the
counter: the parent commit)."""
import argparse
import json

import pytest

import run

CELL = "zaya1-8b.serve.reason64"
CONFIG = "zaya1-8b.serve-pp2-d20"
M = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((run.ROOT / f"benchmark/configs/{CONFIG}.json").read_text())
TRAFFIC = json.loads((run.BENCH / "traffic" /
                      "closed64-long-reasoning.json").read_text())
MINE = {"serve.device.cca": "model layers",
        "serve.device.cca_mix": "model layers",
        "serve.device.moe_router": "model layers",
        "moe.serve.max_expert_share": "expert layer",
        "moe.experts.hbm_roofline.zaya": "expert layer"}


def named(items, name):
    found = [it for it in items if it["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_cell_s_names_resolve_and_it_is_on_the_serve_lists():
    cell = named(M["workloads"], CELL)
    assert cell == dict(cell, config=CONFIG,
                        traffic="closed64-long-reasoning", chips=1)
    assert len(cell["why"]) <= 200
    config = named(M["configs"], CONFIG)
    assert config == dict(
        config, source=CFG["source"],
        reduced=["num_hidden_layers", "max_position_embeddings"],
        file=f"benchmark/configs/{CONFIG}.json")
    assert config["source"] \
        == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert (run.BENCH / "drivers" / f"{CFG['driver']}.py").is_file()
    listed = {m["name"] for m in M["end_to_end"] + M["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= set(MINE) | {
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
        "kernel.paged_attn.copy_share", "serve.device.moe",
        "moe.serve.touched_share", "moe.serve.pad_share",
        "state.serve.slots_used"}
    # no window, no prefix cache, no Mamba or retention layer; and the
    # accepted expert rooflines stay bound to Laguna's counts
    assert not listed & {
        "kernel.paged_attn.window_walk_share", "idle.serve.commit.prefix",
        "moe.experts.hbm_roofline", "moe_grouped_ffn_roofline",
        "serve.device.attn_sliding", "serve.device.mamba",
        "serve.device.retention", "state.serve.bytes_per_token"}
    every = named(M["end_to_end"], "setup_s")
    assert "workloads" not in every
    for name, layer in MINE.items():
        m = named(M["per_layer"], name)
        assert m["workloads"] == [CELL] and m["layer"] == layer
        assert (m["unit"], m["moves"]) == ("%", "serve.tokens_per_s")
        spec = json.loads((run.BENCH / "layer_metrics" /
                           f"{name}.json").read_text())
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (name, "%", layer, "serve.tokens_per_s")
        assert (run.BENCH / "readers" / f"{spec['reader']}.py").is_file()


def test_the_configuration_keeps_every_published_key():
    """Every number of the catalog row's ``config`` under the same key,
    but the two reduced; the nested groups whole."""
    published = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "lm_head_bias": False, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05, "router_hidden_size": 256,
        "sliding_window": None, "tie_word_embeddings": True,
        "vocab_size": 262272}
    assert {k: CFG[k] for k in published} == published
    assert CFG["layer_types"] == ["hybrid"] * 40        # whole, as published
    assert CFG["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert "hybrid_sliding" in CFG["rope_parameters"]
    assert (CFG["num_hidden_layers"], CFG["max_position_embeddings"]) \
        == (20, 8192)
    assert CFG["reduced"] == ["num_hidden_layers",
                              "max_position_embeddings"] \
        == list(CFG["reduced_why"])
    assert set(CFG["assumed"]) == {
        "cca", "qk_mean", "value_shift", "qk_norm", "router", "beta",
        "merge", "no_mod", "embedding", "norms", "scales"}
    assert "NO 17th, layer-skipping choice" in CFG["assumed"]["no_mod"]
    assert "two pipeline stages of 20 layers" in CFG["stands_for"]
    assert CFG["control"] == {"engine": {"quantize": "w8a8"}}
    engine = CFG["driver_options"]["engine"]
    assert (engine["max_batch"], engine["page_size"],
            engine["min_table_pages"]) == (64, 16, 512)
    from reference import zaya_plain as plain
    mc = plain.model_cfg(CFG)
    assert len(mc["layer_types"]) == 20
    from paddle_tpu.models.zaya import ZayaConfig
    assert ZayaConfig(**mc).latent == 1280


def test_the_traffic_is_the_issue_s():
    from generators.common import lognormal_pool
    t = TRAFFIC
    assert (t["kind"], t["clients"], t["levels"], t["ramp_requests"],
            t["order_seed"], t["shared_prefix_tokens"]) \
        == ("closed_loop", 64, 8, 32, 1, 0)
    assert t["prompt_tokens"] == {"median": 512, "sigma": 0.7, "lo": 96,
                                  "hi": 3072}
    assert t["output_tokens"] == {"median": 1536, "sigma": 0.5, "lo": 384,
                                  "hi": 4096}
    p = lognormal_pool(t["prompt_tokens"], t["levels"])
    o = lognormal_pool(t["output_tokens"], t["levels"])
    assert (p[0], p[-1], o[0], o[-1]) == (175, 1498, 713, 3308)
    assert (p.sum(), o.sum()) == (5017, 13646)
    engine = CFG["driver_options"]["engine"]
    assert engine["max_batch"] == t["clients"]
    assert p[-1] + o[-1] == 4806 < CFG["max_position_embeddings"] \
        == engine["min_table_pages"] * engine["page_size"]
    from drivers.serve_laguna import step_spans
    assert step_spans(t, 128) == [1, 32, 64, 128]


def test_the_mix_replayed_asks_for_the_warmed_programs_only():
    """The closed loop through the planner's rule (one chunk budget a
    step, first come first served, a chunk never split; a request's first
    token comes with its last chunk): over 60,000 steps, once the 64
    clients' first prompts are in (the warm-up's decoders hold the rows
    meanwhile: ``hand_over``), no step holds fewer than 33 rows (ONE rows
    bucket, 64), none more than two rows of several tokens, a step's
    longest span is one of the four the warm-up asks for, and the pages
    the 64 admitted requests reserve (prompt + output, whole pages) stay
    under the pool's: no request waits for a page or is preempted."""
    import generators.closed_loop as gen_mod
    gen = gen_mod.build(TRAFFIC, 262272, 1)
    engine = CFG["driver_options"]["engine"]
    chunk, n = engine["prefill_chunk_tokens"], TRAFFIC["clients"]
    clients, pages = [], []          # [prompt left, output left]
    for _ in range(n):
        ids, out = gen.next_request()
        clients.append([len(ids), out])
        pages.append(-(-(len(ids) + out) // 16))
    most_pages = sum(pages)
    order = list(range(n))           # who waits for the budget, in turn
    fewest, most_multi, spans, chunk_steps, steps = n, 0, set(), 0, 60000
    ramp = 800
    for step in range(steps + ramp):
        budget, rows, multi, longest = chunk, 0, 0, 1
        for i in [i for i in order if clients[i][0] > 0]:
            if budget <= 0:
                break
            k = min(clients[i][0], chunk)
            budget -= k
            clients[i][0] -= k
            rows, multi, longest = rows + 1, multi + (k > 1), max(longest, k)
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
        fewest, most_multi = min(fewest, rows), max(most_multi, multi)
        spans.add(1 << (longest - 1).bit_length())
        chunk_steps += longest > 1
    assert fewest >= 33 and most_multi <= 2
    # ISSUE 44 reckoned 8 even blocks, 9,400 pages; a closed loop keeps
    # its long requests in flight longest, and the replay reads 11,437
    assert most_pages == 11437 < engine["total_pages"] - 512
    assert spans == {1, 32, 64, 128}
    assert 0.08 < chunk_steps / steps < 0.25


def test_counts_by_hand():
    import flops_zaya as fz
    from reference import zaya_plain as plain
    mc = plain.model_cfg(CFG)
    # ISSUE 44's reckoning: 5.24 M of projections, 0.33 M of convolutions,
    # 0.66 M of router, 201.33 M of experts a layer
    assert fz.expert_params(mc) == 3 * 2048 * 2048
    assert round(fz.expert_bytes(mc, 1) / 1e6, 2) == 25.17
    assert round(fz.cca_params(mc) / 1e6, 2) == 5.58       # 5.24 + 0.33
    assert round(fz.router_params(mc) / 1e6, 2) == 0.66
    built = sum(int(__import__("numpy").prod(s))
                for _, s in plain.param_specs(mc))
    assert fz.model_params(mc) == built
    assert abs(built / 4.69e9 - 1) < 0.01
    assert round(built * 2 / 1e9, 2) == 9.38
    # K and V a token a layer: 2 heads x 128 x 2 x 2 B; a page of 16
    # tokens over 20 layers; the pool
    assert fz.kv_bytes_per_token(mc) == 1024
    pages = CFG["driver_options"]["engine"]["total_pages"]
    assert 16 * 20 * fz.kv_bytes_per_token(mc) == 327680
    assert round(pages * 327680 / 1e9, 2) == round(12288 * 327680 / 1e9, 2)
    # a slot: three tails, float32; 65 slots of 20 layers
    from paddle_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
    state = ZayaForCausalLM.recurrent_state(
        argparse.Namespace(config=ZayaConfig(**mc)))
    assert state["bytes"] == 10752 and state["layers"] == 20
    assert round(65 * 20 * 10752 / 1e6) == 14


def test_rehearsal_end_to_end():
    # 6 s: the rehearsal's four clients leave rows buckets the warm-up
    # does not make, and one compile on the CPU would eat a 2 s window
    args = argparse.Namespace(workload=CELL, seed=2**31 + 44, seconds=6.0,
                              trace=0, rehearse=True)
    line, checks = run.run_cell(args, {})
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve.tokens_per_s", "setup_s"}
    assert [n for n, _, _ in checks] == [
        "requests_compared", "served_logit_gap_max",
        "served_logit_gap_mean", "router_flip_share"]


# ------------------------------------------------ the readers, made sources
def _dispatch(start, tokens, touched, most):
    return {"kind": "dispatch", "start_ns": start, "end_ns": start + 10,
            "tokens": tokens, "moe_slots": 20 * tokens,
            "moe_experts_touched": touched, "moe_expert_layers": 320,
            "moe_max_expert_pairs": most, "moe_rows_computed": 16 * touched}


def test_the_readers_on_a_made_ring_and_trace(monkeypatch):
    from readers import moe_hbm_roofline_zaya as roof, ring_ratio
    steps = [_dispatch(100, 64, 314, 160), _dispatch(200, 64, 310, 180),
             _dispatch(900, 64, 320, 200), {"kind": "decode", "batch": 3}]
    src = {"steps": steps, "config": CFG}
    spec = json.loads((run.BENCH / "layer_metrics" /
                       "moe.serve.max_expert_share.json").read_text())
    assert ring_ratio.read(spec["args"], src) \
        == pytest.approx(100 * 540 / (3 * 20 * 64))
    # the roofline: the two records inside the host's stamps, 624 experts
    # of 25.17 MB at 819 GB/s, over 24 ms of the kernel's device time
    spec = json.loads((run.BENCH / "layer_metrics" /
                       "moe.experts.hbm_roofline.zaya.json").read_text())
    assert spec["args"] == {"op": "^%?moe_grouped_ffn"}
    import xplane
    monkeypatch.setattr(xplane, "matching_seconds",
                        lambda trace, op: (0.024, 40))
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = roof.read(spec["args"], dict(
        src, peak=peak, trace_window_ns=(50, 500), trace={"planes": []}))
    assert share == pytest.approx(
        100 * 624 * 3 * 2048 * 2048 * 2 / 819e9 / 0.024)
    assert 75 < share < 85


def test_the_readers_find_nothing_on_a_program_without_the_fields():
    from readers import moe_hbm_roofline_zaya as roof, ring_ratio
    peak = {"bf16_flops_per_s": 1, "hbm_bytes_per_s": 1}
    parent = {"steps": [{"kind": "dispatch", "tokens": 9, "start_ns": 1,
                         "end_ns": 2}], "config": CFG, "peak": peak,
              "trace_window_ns": (0, 10), "trace": {"planes": []}}
    args = {"op": "^%?moe_grouped_ffn"}
    assert roof.read(args, parent) is None
    assert roof.read(args, {}) is None
    assert ring_ratio.read(
        {"kind": "dispatch", "numerator": ["moe_max_expert_pairs"],
         "denominator": ["moe_slots"]}, parent) is None
