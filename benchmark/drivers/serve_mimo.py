"""Driver ``serve_mimo``: one ``ContinuousBatchingEngine`` replica serving
``MiMoV2FlashForCausalLM`` (7 of MiMo-V2-Flash's 48 layers, 16 of each
layer's 256 experts, the whole vocabulary: one chip of a 16-chip
expert-parallel group), in process, under a closed-loop generator.

As ``serve_zaya`` (``serve_phi4_flash``'s warm-up: ONE rows bucket, the
widest, at every span this mix reaches; ``serve_engine``'s clients,
hand-over and sample; ``serve_laguna``'s span rule, program routing, flip
arithmetic and ``main_programs``: this model carries no recurrent state;
the window is repeated stamp for stamp, since those keep theirs inline).
What differs is the model it builds and the reference it checks with
(``reference/mimo_v2_flash_plain.py``), and what it says of the build:
``[build]`` carries the parameters, the page pools' bytes a pool kind and
the device's peak, which PERF.md's arithmetic is corrected from.
"""
from __future__ import annotations

import time

import numpy as np

import stats
from . import common
from .common import say
from .serve_engine import POLL_S, Clients, hand_over, pick_sample
from .serve_laguna import (flip_share, main_programs, program_routing,
                           set_differs, step_spans)
from .serve_phi4_flash import warm_up
from readers import ring_ratio
from reference import mimo_v2_flash_plain as plain


def build_model(model_cfg: dict, seed: int):
    """A ``MiMoV2FlashForCausalLM`` whose every leaf holds the benchmark's
    value for (seed, leaf name).  4.5 B parameters would be 18 GB in
    float32, so the model is handed an initialiser that draws nothing and
    makes every matrix bfloat16 zeros; the small float32 leaves (the
    selection bias, the sinks) are cast to the reference's type for them;
    then one donated call a group (the embedding, a layer, the head)
    rewrites the values in place, each through the reference's
    ``shape_leaf`` (the sinks' range, the zero bias)."""
    import jax
    import jax.numpy as jnp
    import weights as W
    from paddle_tpu.models.mimo_v2_flash import (MiMoV2FlashConfig,
                                                 MiMoV2FlashForCausalLM)
    from paddle_tpu.nn.initializer import Initializer

    class ZerosAsServed(Initializer):
        def __call__(self, shape, dtype):
            return jnp.zeros(shape, jnp.bfloat16)

    t0 = time.perf_counter()
    model = MiMoV2FlashForCausalLM(MiMoV2FlashConfig(**model_cfg),
                                   weight_attr=ZerosAsServed())
    for n, p in model.named_parameters():
        p._data = p._data.astype(plain.leaf_dtype(n))
    named = list(model.named_parameters())
    jax.block_until_ready([p._data for _, p in named])
    got = [(n, tuple(p.shape), str(p._data.dtype)) for n, p in named]
    want = [(n, tuple(s), str(np.dtype(plain.leaf_dtype(n))))
            for n, s in plain.param_specs(model_cfg)]
    if got != want:
        raise RuntimeError(
            "the program's parameters are not the reference's: "
            f"{[g for g in got if g not in want][:3]} vs "
            f"{[s for s in want if s not in got][:3]}")
    t1 = time.perf_counter()
    params = dict(named)
    for group in plain.param_groups(model_cfg):
        names = [n for n, _ in group]
        new = W.make_all(seed, names, [params[n]._data for n in names])
        for n, a in zip(names, new):
            params[n].set_value(plain.shape_leaf(n, a))
        jax.block_until_ready(new)
    count = sum(int(np.prod(p.shape)) for _, p in named)
    say(f"[build] {count} parameters; as bfloat16 zeros {t1 - t0:.1f}s, the "
        f"benchmark's weights {time.perf_counter() - t1:.1f}s; peak "
        f"{common.memory_now()['peak_bytes_in_use']}")
    return model


def run(ctx):
    # first of all: a tree without the model ends here, at once
    from paddle_tpu.models.mimo_v2_flash import (  # noqa: F401
        MiMoV2FlashForCausalLM)
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    cfg, model_cfg = ctx.config, plain.model_cfg(ctx.config)
    opts = dict(cfg["driver_options"]["engine"])
    opts.update(ctx.overrides.get("engine", {}))
    t = time.perf_counter()
    model = build_model(model_cfg, ctx.seed)
    say(f"[serve] model: {model_cfg['num_hidden_layers']} layers, experts "
        f"{model_cfg['held_experts']} of {model_cfg['n_routed_experts']} "
        f"held, weights from seed {ctx.seed} in "
        f"{time.perf_counter() - t:.1f}s; in use "
        f"{common.memory_now()['bytes_in_use']}")
    engine = ContinuousBatchingEngine(model, **opts)
    cache = engine.cache
    kinds = {}
    for p, shape in enumerate(cache.pool_shapes):
        kinds.setdefault(shape, []).append(cache.page_bytes(p))
    say(f"[build] engine options {opts}; {cache.num_layers} page pools, "
        f"{cache.kv_pool_bytes} bytes: " + ", ".join(
            f"{len(b)} of (kv heads, K, V) {s} at {b[0]} B a page = "
            f"{b[0] // cache.page_size} B a token" for s, b in kinds.items())
        + f"; K pools {[tuple(a.shape) for a in cache.k_pages[:2]]}...; "
        f"in use {common.memory_now()['bytes_in_use']}")
    gen = ctx.generator(model_cfg["vocab_size"])
    try:
        t = time.perf_counter()
        c0 = common.counters_now().get("jit_recompile_count", 0)
        spans = step_spans(ctx.traffic, int(opts["prefill_chunk_tokens"]))
        back = warm_up(engine, opts, spans, model_cfg["vocab_size"],
                       ctx.seed, model_cfg["max_position_embeddings"])
        c1 = common.counters_now().get("jit_recompile_count", 0)
        say(f"[serve] warm-up: {c1 - c0:.0f} programs (spans {spans}) in "
            f"{time.perf_counter() - t:.1f}s")
        t = time.perf_counter()
        clients = Clients(engine, lambda idx: gen.next_request(),
                          gen.clients)
        clients.start()
        hand_over(back, clients)
        while True:            # the ramp: four blocks of the mix, finished
            with clients.lock:
                if len(clients.records) >= gen.ramp_requests:
                    break
            time.sleep(POLL_S)
        say(f"[serve] clients took over and finished {gen.ramp_requests} "
            f"requests in {time.perf_counter() - t:.1f}s")
        # ------------------------------------------------ the window
        counters0 = common.counters_now()
        if ctx.trace:
            monitor.start_capture(max_requests=4096, max_steps=65536,
                                  host_events=False)
        produced0 = clients.produced()
        t0 = ctx.window_opens()
        if counters0.get("jit_recompile_count", 0) != c1:
            say(f"[serve] NOTE {counters0['jit_recompile_count'] - c1:.0f} "
                "programs compiled in the ramp: the warm-up missed them")
        ctx.sleep_through_window(t0)
        t1 = time.perf_counter()
        produced1 = clients.produced()
        counters1 = common.counters_now()
        if ctx.trace:
            monitor.stop_capture()
        clients.halt.set()
        with clients.lock:
            records = [r for r in clients.records if t0 < r["finished"] <= t1]
            early = [r for r in clients.records if r["finished"] <= t0]
    finally:
        engine.stop()
    clients.join(timeout=30)
    window_s = t1 - t0
    done = [r for r in records if not r["error"]]
    tokens = (sum(r["n_out"] for r in done) + produced1 - produced0)
    ttft = [(r["first"] - r["submitted"]) * 1e3 for r in done]
    tpot = [(r["finished"] - r["first"]) * 1e3 / (r["n_out"] - 1)
            for r in done if r["n_out"] > 1]
    compiled = (counters1.get("jit_recompile_count", 0)
                - counters0.get("jit_recompile_count", 0))
    say(f"[serve] window {window_s:.3f}s: {compiled:.0f} programs compiled "
        f"in it, {len(done)} requests finished, "
        f"{len(records) - len(done)} failed, {len(early)} before it; "
        f"{tokens} output tokens ({produced0} already out at its start, "
        f"{produced1} of unfinished requests at its end)")
    if tpot:      # a window in which no request finished has no tail to say
        say(f"[serve] time to first token p50/p90 "
            f"{stats.percentile(ttft, 50)[0]:.1f}/"
            f"{stats.percentile(ttft, 90)[0]:.1f} ms, time per output token "
            f"p50/p90 {stats.percentile(tpot, 50)[0]:.2f}/"
            f"{stats.percentile(tpot, 90)[0]:.2f} ms over {len(ttft)} requests")
    steps = monitor.get_tracer().step_records() if ctx.trace else []
    # what the new ring fields say, beside what was reckoned
    for name, num, den in (
            ("dead share of the pinned bytes", "kv_dead_bytes",
             "kv_pinned_bytes"),
            ("held experts touched", "moe_experts_touched",
             "moe_expert_layers")):
        got = ring_ratio.read({"kind": "dispatch", "numerator": [num],
                               "denominator": [den]}, {"steps": steps})
        if got is not None:
            say(f"[serve] the window's {name}: {got:.2f} %")
    # when the profiler ran, on the ring's clock (``perf_counter_ns``)
    traced_ns = ((ctx._prof_t * 1e9, (ctx._prof_t + ctx.trace_host_s) * 1e9)
                 if ctx.trace and ctx.trace_host_s else None)
    mem = common.memory_now()
    say(f"[build] the device's peak after the window "
        f"{mem['peak_bytes_in_use']} of {mem['bytes_limit']} bytes")
    # --------- the program once more over the sample, then it is freed
    sample = pick_sample(done, ctx.seed, int(ctx.overrides.get(
        "check_requests", cfg["check"]["requests"])))
    seqs = [(r["prompt"], np.asarray(r["req"].generated[:r["n_out"]], np.int32))
            for r in sample]
    t = time.perf_counter()
    routed = program_routing(
        engine, model, [np.concatenate([p, s])[:-1] for p, s in seqs],
        int(opts["prefill_chunk_tokens"]), int(opts["max_batch"])) \
        if seqs else {}
    say(f"[serve] the program's routing of {len(seqs)} requests in "
        f"{time.perf_counter() - t:.1f}s")
    scopes = main_programs(engine, steps) if ctx.trace else None
    for r in clients.records:
        r.pop("req", None)
    del engine, cache, model, clients
    common.free_device_memory()
    t = time.perf_counter()
    gaps, chosen, bounds = plain.served_gaps(
        model_cfg, ctx.seed, seqs, **ctx.overrides.get("reference", {}))
    allg = np.concatenate(gaps) if gaps else np.zeros(0, np.float32)
    flips = flip_share(routed, chosen, bounds) if seqs else float("inf")
    say(f"[serve] reference over {len(seqs)} requests, {allg.size} served "
        f"tokens, longest {max((len(p) + len(s) for p, s in seqs), default=0)}"
        f" in {time.perf_counter() - t:.1f}s")
    if seqs:      # how varied the stream the routers saw: greedy, no EOS
        served = np.concatenate([s for _, s in seqs])
        counts = np.sort(np.unique(served, return_counts=True)[1])
        say(f"[serve] the sample's served tokens: {counts.size} distinct ids "
            f"among {served.size}; the ten commonest hold "
            f"{counts[-10:].sum() / served.size:.4f} of them")
    # where the widest gaps are, and whether the token that chose each was
    # routed as the reference routes it: for whoever reads a run at fault
    differs = set_differs(routed, chosen, bounds) if seqs else {}
    at = np.cumsum([0] + [b - a for a, b in bounds])
    wide = sorted(((float(g), q, j) for q, gs in enumerate(gaps)
                   for j, g in enumerate(gs)), reverse=True)[:5]

    def otherwise(q, j):      # the token that chose served token j of q
        fed = at[q] + len(seqs[q][0]) - 1 + j
        return [i for i, d in differs.items() if d[fed]]

    say("[serve] the widest gaps (gap, request, served token, context, "
        "sparse layers that chose another set): " + ", ".join(
            f"({g:.3f}, {q}, {j}, {len(seqs[q][0]) + j}, {otherwise(q, j)})"
            for g, q, j in wide))
    limits = cfg["check"]["limits"]
    checks = [("requests_compared", float(len(seqs)), None),
              ("served_logit_gap_max",
               float(allg.max()) if allg.size else float("inf"),
               limits["served_logit_gap_max"]),
              ("served_logit_gap_mean",
               float(allg.mean()) if allg.size else float("inf"),
               limits["served_logit_gap_mean"]),
              ("router_flip_share", flips, limits["router_flip_share"])]
    return {
        "end_to_end": {"serve.tokens_per_s": tokens / window_s},
        "attempted": len(records), "failed": len(records) - len(done),
        "checks": checks, "memory": mem, "window_s": window_s,
        "sources": {"steps": steps, "max_batch": int(opts["max_batch"]),
                    "ttft_ms": ttft, "tpot_ms": tpot,
                    "counters0": counters0, "counters1": counters1,
                    "window": (t0, t1), "hlo_scopes_by_program": scopes,
                    "trace_window_ns": traced_ns},
    }
