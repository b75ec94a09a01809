"""The Brumby serving cell by hand on the CPU: the manifest's names, the
configuration against the published widths, the counts of
``flops_brumby.py`` against hand counts, its rehearsal end to end, and the
reader this cell brought on a made ring and trace and on a source that
lacks what it reads (a program without the fields: the parent commit)."""
import argparse
import json

import pytest

import run

CELL = "brumby-14b.serve.reason16"
M = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((run.ROOT / "benchmark/configs/"
                  "brumby-14b-base.serve-pp8-d5.json").read_text())
MINE = {"serve.device.retention", "serve.device.dense_ffn.brumby",
        "kernel.retention.roofline", "state.serve.bytes_per_token",
        "state.serve.slots_used"}


def test_the_cell_s_names_resolve_and_it_is_on_the_serve_lists():
    cell = next(w for w in M["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="brumby-14b-base.serve-pp8-d5",
                        traffic="closed16-reasoning", chips=1)
    assert (run.BENCH / "drivers" / f"{CFG['driver']}.py").is_file()
    listed = {m["name"] for m in M["end_to_end"] + M["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= MINE | {
        "serve.tokens_per_s", "serve.ttft_p90_ms", "serve.tpot_p90_ms",
        "engine.occupancy", "engine.chunk_steps", "serve.step_host_ms",
        "engine.compiles_in_window", "serve.step_device_ms",
        "device.idle.serve", "idle.serve.schedule", "idle.serve.commit",
        "idle.serve.build", "idle.serve.dispatch", "idle.serve.fetch",
        "idle.serve.unattributed", "serve.dense_pad_share",
        "setup.trace_lower_s", "setup.compile_s"}
    # nothing to read without K/V pages: not listed
    assert not any(n.startswith("kernel.paged_attn.") for n in listed)
    assert "serve.pad_share" not in listed
    for m in M["per_layer"]:
        if m["name"] in MINE:
            assert m["workloads"] == [CELL]
            spec = json.loads((run.BENCH / "layer_metrics" /
                               f"{m['name']}.json").read_text())
            assert (spec["name"], spec["unit"], spec["layer"],
                    spec["moves"]) == (m["name"], m["unit"], m["layer"],
                                       "serve.tokens_per_s")
            assert (run.BENCH / "readers" / f"{spec['reader']}.py").is_file()


def test_the_traffic_is_the_issue_s():
    from generators.common import lognormal_pool
    t = json.loads((run.BENCH / "traffic" /
                    "closed16-reasoning.json").read_text())
    assert (t["kind"], t["clients"], t["levels"], t["ramp_requests"],
            t["order_seed"], t["shared_prefix_tokens"]) \
        == ("closed_loop", 16, 8, 16, 1, 0)
    p = lognormal_pool(t["prompt_tokens"], t["levels"])
    o = lognormal_pool(t["output_tokens"], t["levels"])
    assert (p[0], p[-1], o[0], o[-1]) == (816, 5141, 178, 827)
    assert (p.sum(), o.sum()) == (19035, 3410)
    # every request fits the page bookkeeping and the rope table
    engine = CFG["driver_options"]["engine"]
    assert (p[-1] + o[-1]) * engine["max_batch"] \
        <= (engine["total_pages"] - 1) * engine["page_size"]
    assert p[-1] + o[-1] < CFG["max_position_embeddings"]
    # the spans a step of this mix can be asked for
    from drivers.serve_laguna import step_spans
    assert step_spans(t, engine["prefill_chunk_tokens"]) == [1, 32, 64, 128]


def test_the_configuration_keeps_every_published_key():
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    assert {k: CFG[k] for k in published} == published
    assert CFG["num_hidden_layers"] == 5 and CFG["reduced"] \
        == ["num_hidden_layers"] == list(CFG["reduced_why"])
    assert set(CFG["assumed"]) == {"power", "gate", "qk_norm_rope",
                                   "normaliser", "state", "gates_near_one"}
    assert "eight pipeline stages" in CFG["stands_for"]
    assert CFG["control"] == {"engine": {"quantize": "w8a8"}}
    assert CFG["driver_options"]["engine"] == {
        "total_pages": 6016, "page_size": 16, "max_batch": 16,
        "prefill_chunk_tokens": 128}


def test_counts_by_hand():
    import math
    import flops_brumby as fb
    from reference import brumby_plain as plain
    mc = plain.model_cfg(CFG)
    # ISSUE 37's reckoning: 330.3 M a layer, 1.556 B embedding + head,
    # 3.21 B = 6.42 GB; a state 8 x 8,256 x 129 x 4 B = 34.1 MB a layer
    assert fb.layer_params(mc) == 2 * 5120 * 5120 + 2 * 5120 * 1024 \
        + 5120 * 8 + 3 * 5120 * 17408
    assert round(fb.layer_params(mc) / 1e6, 1) == 330.3
    assert round(fb.model_params(mc) / 1e9, 2) == 3.21
    gains = 5 * (2 * 5120 + 2 * 128) + 5120
    assert sum(math.prod(s) for _, s in plain.param_specs(mc)) \
        == fb.model_params(mc) + gains
    assert round(fb.step_weight_bytes(mc) / 1e9, 2) == 4.86
    assert fb.sym_dim(mc) == 8256
    assert fb.state_bytes(mc) == 8 * 8256 * 129 * 4 == 34080768
    # 16 slots x 5 layers: 2.73 GB; a decode step of 16 rows reads and
    # writes 5.45 GB of it
    assert round(16 * 5 * fb.state_bytes(mc) / 1e9, 2) == 2.73
    assert round(fb.state_traffic_bytes(mc, 16 * 5) / 1e9, 2) == 5.45
    # the one-token form: 3 + 2 x 5 operations an entry of S and z
    assert fb.one_token_flops(mc) == 8 * 8256 * 129 * 13
    # a 128-token chunk: phi(Q) S 1.363 G, the update 0.273 G and the
    # chunk's own scores 0.042 G a KV head: 13.4 GFLOP a layer
    head = 2 * 640 * 8256 * 129 + 2 * 128 * 8256 * 129 \
        + 2 * 5 * 128 * 128 * (128 + 129)
    assert fb.chunk_flops(mc, 128) == 8 * head
    assert round(fb.chunk_flops(mc, 128) / 1e9, 1) == 13.4
    # what the program stores is the same D in whole lane tiles
    from paddle_tpu.ops import power_retention as pr
    assert pr.state_bytes_symmetric(8, 128, 128) == fb.state_bytes(mc)


def test_rehearsal_end_to_end():
    args = argparse.Namespace(workload=CELL, seed=2**31 + 37, seconds=2.0,
                              trace=0, rehearse=True)
    line, checks = run.run_cell(args, {})
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve.tokens_per_s", "setup_s"}
    assert [n for n, _, _ in checks] == [
        "requests_compared", "served_logit_gap_max",
        "served_logit_gap_mean", "state_carry_gap"]


def test_the_state_probe_refuses_a_state_kept_in_bfloat16():
    """What the served tokens do not show (a gate's median is 1/2): the
    state op alone over gates near one, sound and with every pool
    rounded to bfloat16 in the program's place."""
    import sys
    sys.path.insert(0, str(run.ROOT / "benchmark/tests"))
    import chip_limits_brumby as limits
    a = argparse.Namespace(config=dict(CFG, **CFG["rehearsal"]))
    for seed in (3, 2**31 + 41):
        out, correct = limits.probe(a, seed)
        assert correct == {"sound": True, "state_bfloat16": False}, out


# ------------------------------------------------- the reader, made sources
def _dispatch(start, rows, chunk_rows, chunk_tokens, tokens):
    return {"kind": "dispatch", "start_ns": start, "end_ns": start + 10,
            "tokens": tokens, "state_rows": rows, "state_slots": 16,
            "state_chunk_rows": chunk_rows,
            "state_chunk_tokens": chunk_tokens,
            "state_bytes": 2 * rows * 5 * 34080768}


def test_the_reader_on_a_made_ring_and_trace(monkeypatch):
    from readers import retention_state, ring_ratio
    steps = [_dispatch(100, 16, 0, 0, 16), _dispatch(200, 16, 1, 128, 143),
             _dispatch(900, 8, 0, 0, 8), {"kind": "decode", "batch": 3}]
    src = {"steps": steps, "config": CFG}
    per_token = retention_state.read({"stat": "bytes_per_token"}, src)
    assert per_token == 2 * 40 * 5 * 34080768 / (16 + 143 + 8)
    used = ring_ratio.read({"kind": "dispatch", "numerator": ["state_rows"],
                            "denominator": ["state_slots"]}, src)
    assert used == 100.0 * 40 / 48
    # the roofline: the two records inside the host's stamps, each the
    # larger of its operations and its bytes, over 20 ms of device time
    import flops_brumby as fb
    from reference import brumby_plain as plain
    mc = plain.model_cfg(CFG)
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(retention_state, "scope_seconds",
                        lambda src, scope: 0.020)
    share = retention_state.read(
        {"stat": "roofline", "time": {"scope": "^serve/model/retention/state"}},
        dict(src, peak=peak, trace_window_ns=(50, 500)))
    bytes_s = 2 * 16 * 5 * 34080768 / 819e9         # memory-bound, both
    flops_2 = 5 * (15 * fb.one_token_flops(mc) + fb.chunk_flops(mc, 128))
    assert flops_2 / 197e12 < bytes_s
    assert share == pytest.approx(100 * 2 * bytes_s / 0.020)
    assert 60 < share < 70


def test_the_reader_finds_nothing_on_a_program_without_the_fields():
    from readers import retention_state, ring_ratio
    parent = {"steps": [{"kind": "dispatch", "tokens": 9, "start_ns": 1,
                         "end_ns": 2}], "config": CFG,
              "peak": {"bf16_flops_per_s": 1, "hbm_bytes_per_s": 1},
              "trace_window_ns": (0, 10)}
    for stat in ("bytes_per_token", "roofline"):
        assert retention_state.read(
            {"stat": stat, "time": {"op": "x"}}, parent) is None
    assert ring_ratio.read({"kind": "dispatch", "numerator": ["state_rows"],
                            "denominator": ["state_slots"]}, parent) is None
    assert retention_state.read({"stat": "bytes_per_token"}, {}) is None
