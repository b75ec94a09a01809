"""Driver ``serve_zaya``: one ``ContinuousBatchingEngine`` replica serving
``ZayaForCausalLM`` (20 of ZAYA1-8B's 40 layers, every expert, the whole
vocabulary), in process, under a closed-loop generator.

As ``serve_phi4_flash`` (whose warm-up it imports: ONE rows bucket, the
widest, at every span this mix reaches; with ``serve_brumby``'s
``main_programs``, ``serve_engine``'s clients, hand-over and sample and
``serve_laguna``'s span rule and flip arithmetic; the window is repeated
stamp for stamp, since those keep theirs inline).  What differs:

* the balancing bias: ``reference/zaya_plain.py::balancing_biases`` settles
  it from the seed over a calibration batch through the REFERENCE's forward,
  before the model or the engine is built and outside every timed window;
  the program's leaves are set to it and the check's reference is handed
  the same array.  It is a weight like any other: no routing of the
  program's reaches the reference;
* the 20 alike layers are NOT scanned: each ragged program is the 20 layers
  unrolled, as every serving cell's (the paged context hands each layer its
  own pools as separate donated operands);
* ``router_flip_share`` as the Laguna cell reads it: after the window the
  engine's own decoder and cache feed the sampled requests once more in
  packed (64, 128) steps with each layer's chosen expert called back to the
  host.  Top-1: a flip replaces a token's WHOLE expert output, so the line
  after the check says what share of the gaps' sum the served tokens behind
  a flipped choice carry.
"""
from __future__ import annotations

import time

import numpy as np

import stats
from . import common
from .common import say
from .serve_brumby import main_programs
from .serve_engine import POLL_S, Clients, hand_over, pick_sample
from .serve_laguna import flip_share, set_differs, step_spans
from .serve_phi4_flash import warm_up
from readers import ring_ratio
from reference import zaya_plain as plain


def build_model(model_cfg: dict, seed: int, beta=None):
    """A ``ZayaForCausalLM`` whose every leaf holds the benchmark's value
    for (seed, leaf name), the balancing biases ``beta`` (layers, experts;
    None: zeros).  4.69 B parameters would be 18.8 GB in float32, so the
    model is handed an initialiser that draws nothing and makes every
    matrix bfloat16 zeros; the small leaves are cast to the reference's
    type for them; then one donated call a group (the embedding, a layer,
    the last norm) rewrites the values in place, each through the
    reference's ``shape_leaf``."""
    import jax
    import jax.numpy as jnp
    import weights as W
    from paddle_tpu.models.zaya import ZayaConfig, ZayaForCausalLM
    from paddle_tpu.nn.initializer import Initializer

    class ZerosAsServed(Initializer):
        def __call__(self, shape, dtype):
            return jnp.zeros(shape, jnp.bfloat16)

    t0 = time.perf_counter()
    model = ZayaForCausalLM(ZayaConfig(**model_cfg),
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
    if beta is not None:
        for i, layer in enumerate(model.model.layers):
            layer.mlp.gate.balancing_bias.set_value(
                jnp.asarray(beta[i], jnp.float32))
    count = sum(int(np.prod(p.shape)) for _, p in named)
    say(f"[build] {count} parameters; as bfloat16 zeros {t1 - t0:.1f}s, the "
        f"benchmark's weights {time.perf_counter() - t1:.1f}s; peak "
        f"{common.memory_now()['peak_bytes_in_use']}")
    return model


def program_routing(engine, model, seqs, chunk, rows):
    """{layer: (n, 1) ids} the expert the PROGRAM's every layer chose for
    every token of ``seqs`` (the tokens fed, end to end), through the
    engine's decoder and cache in steps shaped like the window's: ``rows``
    rows a step, two of them a chunk of a sequence each and the others one
    token of a scratch sequence (a decoding row), so every step is the
    packed (rows, chunk) program, slots and pages and all.  A tail is
    padded to a whole chunk (causal: the pad changes no earlier token).
    ONE program compiles: the window's own do not hand the ids out, so
    this one has the gates' callbacks in it and the decoder's logits
    tail."""
    import jax
    cache, dec = engine.cache, engine._decoder
    for sid in list(cache._seq_pages):      # what the stopped engine held
        cache.free(sid)
    gates = [layer.mlp.gate for layer in model.model.layers]
    got = {i: [] for i in range(len(gates))}
    for i, gate in enumerate(gates):
        def route(x, state=None, _route=type(gate).route_no_drop,
                  _gate=gate, _i=i):
            idx, w, r = _route(_gate, x, state)
            jax.debug.callback(lambda a, _i=_i: got[_i].append(
                np.asarray(a)), idx._data, ordered=True)
            return idx, w, r
        gate.route_no_drop = route
    fed = [np.pad(np.asarray(ids, np.int32), (0, -len(ids) % chunk))
           for ids in seqs]
    at, waiting, lanes, log = [0] * len(fed), list(range(len(fed))), [], []
    fill = [0] * rows                       # the scratch rows' lengths
    one, base = np.zeros(1, np.int32), 1 << 20
    try:
        while True:
            for i in [i for i in lanes if at[i] >= len(fed[i])]:
                lanes.remove(i)
                cache.free(base + i)
            while waiting and len(lanes) < min(2, rows):
                lanes.append(waiting.pop(0))
            if not lanes:
                break
            pads = range(rows - len(lanes))
            dec.ragged_step(
                cache, [base + i for i in lanes] + [base - 1 - j for j in pads],
                [fed[i][at[i]:at[i] + chunk] for i in lanes] + [one] * len(pads),
                [at[i] for i in lanes] + [fill[j] for j in pads])
            log.append(list(lanes))
            for i in lanes:
                at[i] += chunk
            for j in pads:
                fill[j] += 1
        jax.effects_barrier()
    finally:
        for gate in gates:
            del gate.route_no_drop
        for j in range(rows):
            cache.free(base - 1 - j)
    # a step's packed positions: the lanes' chunks in order, then scratch
    chosen = {}
    for i, steps in got.items():
        per = [[] for _ in fed]
        for lanes, ids in zip(log, steps):
            for r, q in enumerate(lanes):
                per[q].append(ids[r * chunk:(r + 1) * chunk])
        chosen[i] = np.concatenate([np.concatenate(c)[:len(ids)]
                                    for c, ids in zip(per, seqs)])
    return chosen


def run(ctx):
    # first of all: a tree without the model ends here, at once
    from paddle_tpu.models.zaya import ZayaForCausalLM  # noqa: F401
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    cfg, model_cfg = ctx.config, plain.model_cfg(ctx.config)
    opts = dict(cfg["driver_options"]["engine"])
    opts.update(ctx.overrides.get("engine", {}))
    t = time.perf_counter()
    beta, fullest = plain.balancing_biases(
        model_cfg, ctx.seed, **cfg["driver_options"].get("calibration", {}))
    say(f"[serve] balancing biases from seed {ctx.seed} in "
        f"{time.perf_counter() - t:.1f}s: the fullest expert of a layer "
        f"holds {min(fullest):.2f}-{max(fullest):.2f} of the even share on "
        f"the calibration batch; |beta| up to {np.abs(beta).max():.4f}")
    common.free_device_memory()
    t = time.perf_counter()
    model = build_model(model_cfg, ctx.seed, beta)
    say(f"[serve] model: {model_cfg['num_hidden_layers']} layers, weights "
        f"from seed {ctx.seed} in {time.perf_counter() - t:.1f}s; in use "
        f"{common.memory_now()['bytes_in_use']}")
    engine = ContinuousBatchingEngine(model, **opts)
    cache = engine.cache
    say(f"[serve] engine options {opts}; {cache.num_layers} page pools and "
        f"{len(cache.state_pools)} slot pools, {cache.kv_pool_bytes} bytes "
        f"({cache.state_pool_bytes} of them slots); in use "
        f"{common.memory_now()['bytes_in_use']}")
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
    # what ``moe.serve.max_expert_share`` reads, said beside the even share
    load = ring_ratio.read({"kind": "dispatch",
                            "numerator": ["moe_max_expert_pairs"],
                            "denominator": ["moe_slots"]}, {"steps": steps})
    if load is not None:
        say(f"[serve] the window's fullest expert a layer holds {load:.2f} % "
            f"of the layer's pairs (even: "
            f"{100 / model_cfg['num_experts']:.2f} %)")
    # when the profiler ran, on the ring's clock (``perf_counter_ns``)
    traced_ns = ((ctx._prof_t * 1e9, (ctx._prof_t + ctx.trace_host_s) * 1e9)
                 if ctx.trace and ctx.trace_host_s else None)
    mem = common.memory_now()
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
        model_cfg, ctx.seed, seqs, beta=beta,
        **ctx.overrides.get("reference", {}))
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
    # the expert histogram of the sample as the program routed it, and
    # what the flips carry: served token j of request q was chosen at the
    # fed position before it; a top-1 flip there replaces a whole expert
    differs = set_differs(routed, chosen, bounds) if seqs else {}
    at = np.cumsum([0] + [b - a for a, b in bounds])
    if seqs:
        n_e = model_cfg["num_experts"]
        hist = np.stack([np.bincount(routed[i][:, 0], minlength=n_e)
                         for i in sorted(routed)])
        share = hist.max(axis=1) * n_e / hist.sum(axis=1)
        say(f"[serve] the sample's pairs by expert (all layers): "
            f"{hist.sum(axis=0).tolist()}; a layer's fullest expert holds "
            f"{share.min():.2f}-{share.max():.2f} of the even share")
        flipped = np.concatenate([
            np.any([d[at[q] + len(p) - 1:at[q] + len(p) - 1 + len(s)]
                    for d in differs.values()], axis=0)
            for q, (p, s) in enumerate(seqs)])
        say(f"[serve] served tokens chosen behind a flipped layer: "
            f"{flipped.mean():.4f} of the tokens, "
            f"{allg[flipped].sum() / max(allg.sum(), 1e-30):.4f} of the "
            f"gaps' sum")
    wide = sorted(((float(g), q, j) for q, gs in enumerate(gaps)
                   for j, g in enumerate(gs)), reverse=True)[:5]

    def otherwise(q, j):      # the token that chose served token j of q
        fed = at[q] + len(seqs[q][0]) - 1 + j
        return [i for i, d in differs.items() if d[fed]]

    say("[serve] the widest gaps (gap, request, served token, context, "
        "layers that chose another expert): " + ", ".join(
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
