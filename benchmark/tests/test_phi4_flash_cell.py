"""The Phi-4-mini-flash serving cell by hand on the CPU: the manifest's
names, the configuration against the published widths, the traffic against
ISSUE 41's, the mix replayed through the planner's rule (which programs a
window can ask for), the counts of ``flops_phi4_flash.py`` against hand
counts, its rehearsal end to end, the limits script's probe, and the reader
this cell brought on a made ring and trace and on a source that lacks what
it reads (a program without the fields: the parent commit)."""
import argparse
import json

import pytest

import run

CELL = "phi4-flash.serve.reason32"
M = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CFG = json.loads((run.ROOT / "benchmark/configs/"
                  "phi-4-mini-flash.serve-d32.json").read_text())
TRAFFIC = json.loads((run.BENCH / "traffic" /
                      "closed32-math-reasoning.json").read_text())
MINE = {"serve.device.mamba", "serve.device.gmu", "serve.device.attn_cross",
        "serve.device.dense_ffn.phi4flash", "kernel.mamba.roofline",
        "kv.serve.shared_walk_share"}


def test_the_cell_s_names_resolve_and_it_is_on_the_serve_lists():
    assert M["workloads"][-1] == dict(
        M["workloads"][-1], name=CELL, config="phi-4-mini-flash.serve-d32",
        traffic="closed32-math-reasoning", chips=1)
    assert len(M["workloads"][-1]["why"]) <= 200
    assert M["configs"][-1] == dict(
        M["configs"][-1], name="phi-4-mini-flash.serve-d32",
        source=CFG["source"], reduced=["max_position_embeddings"],
        file="benchmark/configs/phi-4-mini-flash.serve-d32.json")
    assert (run.BENCH / "drivers" / f"{CFG['driver']}.py").is_file()
    listed = {m["name"] for m in M["end_to_end"] + M["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed >= MINE | {
        "serve.tokens_per_s", "serve.ttft_p90_ms", "serve.tpot_p90_ms",
        "engine.occupancy", "engine.chunk_steps", "serve.step_host_ms",
        "engine.compiles_in_window", "serve.step_device_ms",
        "engine.overlap_share", "engine.late_launch_share",
        "device.idle.serve", "idle.serve.schedule", "idle.serve.commit",
        "idle.serve.build", "idle.serve.dispatch", "idle.serve.fetch",
        "idle.serve.unattributed", "serve.dense_pad_share",
        "setup.trace_lower_s", "setup.compile_s", "kernel.paged_attn.busy",
        "kernel.paged_attn.walk_useful", "kernel.paged_attn.query_useful",
        "kernel.paged_attn.window_walk_share", "serve.device.attn_sliding",
        "serve.device.attn_full", "state.serve.bytes_per_token",
        "state.serve.slots_used"}
    # no expert, no retention layer, no prefix cache
    assert not any(n.startswith(("moe", "serve.device.retention",
                                 "kernel.retention"))
                   or n == "idle.serve.commit.prefix" for n in listed)
    mine = [m for m in M["per_layer"] if m["name"] in MINE]
    assert [m["name"] for m in M["per_layer"][-len(MINE):]] \
        == [m["name"] for m in mine] and len(mine) == len(MINE)
    for m in mine:
        assert m["workloads"] == [CELL]
        spec = json.loads((run.BENCH / "layer_metrics" /
                           f"{m['name']}.json").read_text())
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (m["name"], m["unit"], m["layer"], "serve.tokens_per_s")
        assert (run.BENCH / "readers" / f"{spec['reader']}.py").is_file()


def test_the_traffic_is_the_issue_s():
    from generators.common import lognormal_pool
    t = TRAFFIC
    assert (t["kind"], t["clients"], t["levels"], t["ramp_requests"],
            t["order_seed"], t["shared_prefix_tokens"]) \
        == ("closed_loop", 32, 8, 32, 1, 0)
    assert t["prompt_tokens"] == {"median": 512, "sigma": 0.7, "lo": 96,
                                  "hi": 3072}
    assert t["output_tokens"] == {"median": 1024, "sigma": 0.5, "lo": 256,
                                  "hi": 3072}
    p = lognormal_pool(t["prompt_tokens"], t["levels"])
    o = lognormal_pool(t["output_tokens"], t["levels"])
    assert (p[0], p[-1], o[0], o[-1]) == (175, 1498, 476, 2205)
    assert (p.sum(), o.sum()) == (5017, 9098)
    # every request fits the table the configuration pins (the pages:
    # the replay below)
    engine = CFG["driver_options"]["engine"]
    assert engine["max_batch"] == t["clients"]
    assert p[-1] + o[-1] < CFG["max_position_embeddings"] \
        == engine["min_table_pages"] * engine["page_size"]
    from drivers.serve_laguna import step_spans
    assert step_spans(t, engine["prefill_chunk_tokens"]) == [1, 32, 64, 128]


def test_the_mix_replayed_asks_for_the_warmed_programs_only():
    """The closed loop through the planner's rule (one chunk budget a
    step, first come first served, a chunk never split; a request's first
    token comes with its last chunk): over 40,000 steps, once the 32
    clients' first prompts are in (the warm-up's decoders hold the rows
    meanwhile: ``hand_over``), no step holds fewer than 17 rows (one rows bucket,
    32), none holds more than two rows of several tokens (``chunk_rows``
    padded to 2: a prompt is at least 175 tokens, so a tail shares its
    step with ONE whole chunk), a step's longest span is one of the
    four the warm-up asks for, and the pages the 32 admitted requests
    reserve (prompt + output, whole pages) stay under the pool's: no
    request waits for pages or is preempted for them."""
    import generators.closed_loop as gen_mod
    gen = gen_mod.build(TRAFFIC, 200064, 1)
    chunk = CFG["driver_options"]["engine"]["prefill_chunk_tokens"]
    clients, pages = [], []          # [prompt left, output left]
    for _ in range(32):
        ids, out = gen.next_request()
        clients.append([len(ids), out])
        pages.append(-(-(len(ids) + out) // 16))
    most_pages = sum(pages)
    order = list(range(32))          # who waits for the budget, in turn
    fewest, most_multi, spans, chunk_steps, steps = 32, 0, set(), 0, 40000
    for step in range(steps + 400):
        budget, rows, multi, longest = chunk, 0, 0, 1
        for i in [i for i in order if clients[i][0] > 0]:
            if budget <= 0:
                break
            n = min(clients[i][0], chunk)
            budget -= n
            clients[i][0] -= n
            rows, multi, longest = rows + 1, multi + (n > 1), max(longest, n)
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
        if step < 400:
            continue
        fewest, most_multi = min(fewest, rows), max(most_multi, multi)
        spans.add(1 << (longest - 1).bit_length())
        chunk_steps += longest > 1
    assert fewest >= 17 and most_multi <= 2
    assert most_pages == 4206 \
        < CFG["driver_options"]["engine"]["total_pages"] - 256
    assert spans == {1, 32, 64, 128}
    assert 0.05 < chunk_steps / steps < 0.15


def test_the_configuration_keeps_every_published_key():
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "mb_per_layer": 2, "model_type": "phi4flash",
        "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k: CFG[k] for k in published} == published
    assert CFG["max_position_embeddings"] == 4096 and CFG["reduced"] \
        == ["max_position_embeddings"] == list(CFG["reduced_why"])
    assert set(CFG["assumed"]) == {
        "mamba", "mamba_init", "mamba_scales", "differential_attention",
        "nope", "biases", "split", "gmu", "mlp"}
    assert "one v5e chip serving the whole model" in CFG["stands_for"]
    assert CFG["control"] == {"engine": {"quantize": "w8a8"}}
    assert CFG["driver_options"]["engine"] == {
        "total_pages": 6144, "page_size": 16, "max_batch": 32,
        "prefill_chunk_tokens": 128, "min_table_pages": 256}


def test_counts_by_hand():
    import flops_phi4_flash as fp
    from reference import phi4_flash_plain as plain
    mc = plain.model_cfg(CFG)
    assert plain.sizes(mc) == (64, 5120, 160)
    # ISSUE 41's reckoning, a layer's matrices by kind (the norms, biases
    # and the small vectors come on top)
    mlp = 2560 * 20480 + 10240 * 2560
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    for count, stated in ((mamba + mlp, 119.8),
                          (2560 * 5120 + 2560 * 2560 + mlp, 98.3),
                          (2 * 2560 * 2560 + mlp, 91.7),
                          (2 * 2560 * 5120 + mlp, 104.8)):
        assert abs(count / 1e6 - stated) < 0.06
    assert fp.mamba_layers(mc) == 9
    assert fp.model_params(mc) == 3852562944        # as built on the chip
    assert round(fp.step_weight_bytes(mc) / 1e9, 2) == 7.71
    # 9 pools of 20 heads x 64 x 2 B, K and V: 46 KB a token of context
    assert fp.kv_bytes_per_token(mc) == 9 * 5120 == 46080
    pages = CFG["driver_options"]["engine"]["total_pages"]
    assert round(pages * 16 * fp.kv_bytes_per_token(mc) / 1e9, 2) == 4.53
    # a slot: h 5120 x 16 and a tail of 3 x 5120, float32
    assert fp.state_bytes(mc) == 327680 + 61440 == 389120
    assert round(33 * 9 * fp.state_bytes(mc) / 1e9, 3) == 0.116
    # a decode step of 32 rows reads and writes 0.22 GB of it
    assert round(2 * 32 * 9 * fp.state_bytes(mc) / 1e9, 2) == 0.22
    assert fp.state_token_bytes(mc) == (4 * 5120 + 32) * 4
    assert fp.state_token_flops(mc) == 5120 * (8 + 112 + 3)
    # what the program stores is the same
    from paddle_tpu.ops import selective_scan as ss
    assert ss.state_bytes(5120, 16, 4) == fp.state_bytes(mc)


def test_rehearsal_end_to_end():
    args = argparse.Namespace(workload=CELL, seed=2**31 + 41, seconds=2.0,
                              trace=0, rehearse=True)
    line, checks = run.run_cell(args, {})
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve.tokens_per_s", "setup_s"}
    assert [n for n, _, _ in checks] == [
        "requests_compared", "served_logit_gap_max",
        "served_logit_gap_mean", "state_carry_gap"]


def test_the_state_probe_refuses_a_state_kept_in_bfloat16():
    import sys
    sys.path.insert(0, str(run.ROOT / "benchmark/tests"))
    import chip_limits_phi4_flash as limits
    a = argparse.Namespace(config=dict(CFG, **CFG["rehearsal"]))
    assert set(limits.faults(CFG)) == {
        "reset_h128", "drop_tail128", "lambda0", "cross_from15", "window511"}
    for seed in (3, 2**31 + 41):
        out, correct = limits.probe(a, seed)
        assert correct == {"sound": True, "state_bfloat16": False}, out


# ------------------------------------------------- the reader, made sources
def _dispatch(start, rows, tokens):
    return {"kind": "dispatch", "start_ns": start, "end_ns": start + 10,
            "tokens": tokens, "state_rows": rows, "state_slots": 32,
            "state_bytes": 2 * rows * 9 * 389120,
            "kv_tokens_walked": 1000.0, "kv_tokens_walked_shared": 630.0}


def test_the_reader_on_a_made_ring_and_trace(monkeypatch):
    from readers import mamba_state, retention_state, ring_ratio
    steps = [_dispatch(100, 32, 32), _dispatch(200, 32, 159),
             _dispatch(900, 8, 8), {"kind": "decode", "batch": 3}]
    src = {"steps": steps, "config": CFG}
    assert retention_state.read({"stat": "bytes_per_token"}, src) \
        == 2 * 72 * 9 * 389120 / (32 + 159 + 8)
    spec = json.loads((run.BENCH / "layer_metrics" /
                       "kv.serve.shared_walk_share.json").read_text())
    assert ring_ratio.read(spec["args"], src) == pytest.approx(63.0)
    # the roofline: the two records inside the host's stamps, each the
    # larger of its operations and its bytes, over 1 ms of device time
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    monkeypatch.setattr(mamba_state, "scope_seconds",
                        lambda src, scope: 0.001)
    spec = json.loads((run.BENCH / "layer_metrics" /
                       "kernel.mamba.roofline.json").read_text())
    assert spec["args"] == {"scope": "^serve/model/mamba/state"}
    share = mamba_state.read(spec["args"], dict(
        src, peak=peak, trace_window_ns=(50, 500)))
    a_token = 9 * (4 * 5120 + 32) * 4
    bytes_s = (2 * 2 * 32 * 9 * 389120 + (32 + 159) * a_token) / 819e9
    assert 191 * 9 * 5120 * 123 / 197e12 < bytes_s      # memory-bound
    assert share == pytest.approx(100 * bytes_s / 0.001)
    assert 70 < share < 75


def test_the_reader_finds_nothing_on_a_program_without_the_fields():
    from readers import mamba_state, ring_ratio
    peak = {"bf16_flops_per_s": 1, "hbm_bytes_per_s": 1}
    parent = {"steps": [{"kind": "dispatch", "tokens": 9, "start_ns": 1,
                         "end_ns": 2}], "config": CFG, "peak": peak,
              "trace_window_ns": (0, 10)}
    args = {"scope": "^serve/model/mamba/state"}
    assert mamba_state.read(args, parent) is None
    assert mamba_state.read(args, {}) is None
    # another model's records and configuration (the Brumby cell's)
    other = json.loads((run.ROOT / "benchmark/configs/"
                        "brumby-14b-base.serve-pp8-d5.json").read_text())
    assert mamba_state.read(args, dict(
        parent, config=other, steps=[_dispatch(1, 4, 4)])) is None
    assert ring_ratio.read(
        {"kind": "dispatch", "numerator": ["kv_tokens_walked_shared"],
         "denominator": ["kv_tokens_walked"]}, parent) is None
