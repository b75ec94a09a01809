"""Driver ``serve_phi4_flash``: one ``ContinuousBatchingEngine`` replica
serving ``Phi4FlashForCausalLM`` (all 32 layers), in process, under a
closed-loop generator.

As ``serve_brumby`` (whose ``main_programs`` it imports, with
``serve_engine``'s clients, hand-over and sample and ``serve_laguna``'s span
rule; the window is repeated stamp for stamp, since those keep theirs
inline); what differs is the model it builds, the reference it checks with
(``reference/phi4_flash_plain.py``: the served tokens' logit gaps, and
``carry_gap``: the two state ops alone over a slowly forgetting scan), and
the warm-up (``warm_up``: ONE rows bucket, the widest.  The 31 decoders go
in together, so no step of the warm-up holds another bucket's rows, and 32
clients in a closed loop keep 32 sequences admitted, of which at most a few
wait for the chunk budget: ``benchmark/tests/test_phi4_flash_cell.py`` replays the
mix through the planner's rule and finds no step under 17 rows.  Four
programs, each 32 layers deep, instead of nine).
"""
from __future__ import annotations

import time

import numpy as np

import stats
from . import common
from .common import say
from .serve_brumby import main_programs
from .serve_engine import POLL_S, Clients, hand_over, pick_sample
from .serve_laguna import step_spans
from reference import phi4_flash_plain as plain


def build_model(model_cfg: dict, seed: int):
    """A ``Phi4FlashForCausalLM`` whose every leaf holds the benchmark's
    value for (seed, leaf name).  3.85 B parameters would be 15.4 GB in
    float32, so the model is handed an initialiser (``weight_attr``) that
    draws nothing and makes every matrix bfloat16 zeros; the small leaves
    are cast to the reference's type for them; then one donated call a
    group (the embedding, a layer, the last norm) rewrites the values in
    place, each through the reference's ``shape_leaf``."""
    import jax
    import jax.numpy as jnp
    import weights as W
    from paddle_tpu.models.phi4_flash import (Phi4FlashConfig,
                                              Phi4FlashForCausalLM)
    from paddle_tpu.nn.initializer import Initializer

    class ZerosAsServed(Initializer):
        def __call__(self, shape, dtype):
            return jnp.zeros(shape, jnp.bfloat16)

    t0 = time.perf_counter()
    model = Phi4FlashForCausalLM(Phi4FlashConfig(**model_cfg),
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


def warm_up(engine, opts, spans, vocab, seed, max_position):
    """The (rows, span) programs this mix reaches: the widest rows bucket
    at every span.  ``max_batch`` - 1 decoders go in TOGETHER (one-token
    prompts: their first step is already of the widest bucket) and are
    left decoding; then for each span s a prompt of exactly s tokens and
    one output token goes in alone and is waited for, so one step carries
    ``max_batch`` rows of which the longest spans s.  Returns the
    decoders, still running."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    long = min(2048, int(max_position) - 2)

    def ids(n):
        return rng.integers(0, vocab, n).astype(np.int32)

    with engine._cond:      # the loop admits none until all are queued
        back = [engine.submit(ids(1), max_new_tokens=long)
                for _ in range(int(opts["max_batch"]) - 1)]
    while any(r.next_token is None and not r.done.is_set() for r in back):
        time.sleep(POLL_S)
    for s in spans:
        engine.submit(ids(s), max_new_tokens=1).result(timeout=1800)
    return back


def carry_gap(seed, model_cfg, chunk, steps=64):
    """What the served tokens may not show of the state's precision: the
    layer's two state ops (``ops/selective_scan.py::conv_step`` and
    ``scan_step``, what ``paged_ctx.conv_rows`` / ``scan_rows`` call) run
    here alone against pools of the engine's slot shapes, at the model's
    channel count, on two sequences: two chunk rows of ``chunk`` tokens
    each, then ``steps`` one-token rows, every token carried from the
    first, with step sizes drawn over [1e-3, 1e-2] (a channel forgets over
    hundreds of tokens).  The scan's outputs are held against the
    reference's recurrence (``selective_scan`` over ``conv1d_causal``,
    float32, no slot) over the same values; returns the widest difference
    as a share of the reference's widest output."""
    import jax.numpy as jnp
    from paddle_tpu.ops import selective_scan as ss

    _, inner, _ = plain.sizes(model_cfg)
    n, k, rows = plain.D_STATE, plain.D_CONV, 2
    total = 2 * chunk + steps
    rng = np.random.default_rng([int(seed), 0xCA44])
    f32 = lambda x: jnp.asarray(x, jnp.float32)             # noqa: E731
    held = lambda x: f32(jnp.asarray(x, jnp.bfloat16))      # noqa: E731
    x = held(rng.standard_normal((rows, total, inner)))
    bb, cc = (held(rng.standard_normal((rows, total, n))) for _ in "bc")
    delta = f32(rng.uniform(1e-3, 1e-2, (rows, total, inner)))
    w = f32(rng.uniform(-0.5, 0.5, (k, inner)))
    b = f32(rng.uniform(-0.1, 0.1, inner))
    a_log = f32(np.log(np.arange(1, n + 1)) * np.ones((inner, 1)))
    d = f32(np.ones(inner))
    shapes = ss.state_shapes(inner, n, k)
    h_pool, t_pool = (jnp.zeros((rows + 1,) + s, jnp.float32)
                      for s in shapes)
    i32 = lambda v: jnp.asarray(v, jnp.int32)               # noqa: E731
    slots, ms, at = i32(np.arange(rows)), [], 0
    for span in [chunk, chunk] + [1] * steps:
        cut = lambda v: v[:, at:at + span].reshape(         # noqa: E731
            (rows * span,) + v.shape[2:])
        args = (slots, i32([at] * rows), i32([span] * rows), None)
        u, t_pool = ss.conv_step(t_pool, *args, cut(x), w, b, span=span)
        u = jnp.where(u > 0, u, 0.1 * u)        # any pointwise map does
        m, h_pool = ss.scan_step(
            h_pool, *args, i32(np.arange(rows) if span > 1 else np.zeros(0)),
            u, cut(delta), -jnp.exp(a_log).T, cut(bb), cut(cc), d, span=span)
        ms.append(m.reshape(rows, span, inner))
        at += span
    got = np.asarray(jnp.concatenate(ms, axis=1))
    want = []
    for r in range(rows):
        u = plain.conv1d_causal(x[r], w, b)
        u = jnp.where(u > 0, u, 0.1 * u)
        want.append(np.asarray(plain.selective_scan(
            u, delta[r], a_log, bb[r], cc[r], d)))
    want = np.stack(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


def run(ctx):
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    cfg, model_cfg = ctx.config, plain.model_cfg(ctx.config)
    opts = dict(cfg["driver_options"]["engine"])
    opts.update(ctx.overrides.get("engine", {}))
    t = time.perf_counter()
    model = build_model(model_cfg, ctx.seed)
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
    say(f"[serve] time to first token p50/p90 "
        f"{stats.percentile(ttft, 50)[0]:.1f}/{stats.percentile(ttft, 90)[0]:.1f}"
        f" ms, time per output token p50/p90 "
        f"{stats.percentile(tpot, 50)[0]:.2f}/{stats.percentile(tpot, 90)[0]:.2f}"
        f" ms over {len(ttft)} requests")
    steps = monitor.get_tracer().step_records() if ctx.trace else []
    # when the profiler ran, on the ring's clock (``perf_counter_ns``)
    traced_ns = ((ctx._prof_t * 1e9, (ctx._prof_t + ctx.trace_host_s) * 1e9)
                 if ctx.trace and ctx.trace_host_s else None)
    mem = common.memory_now()
    sample = pick_sample(done, ctx.seed, int(cfg["check"]["requests"]))
    seqs = [(r["prompt"], np.asarray(r["req"].generated[:r["n_out"]], np.int32))
            for r in sample]
    scopes = main_programs(engine, steps) if ctx.trace else None
    # ------------------------------- free the program, then the check
    for r in clients.records:
        r.pop("req", None)
    del engine, cache, model, clients
    common.free_device_memory()
    t = time.perf_counter()
    carry = carry_gap(ctx.seed, model_cfg, int(opts["prefill_chunk_tokens"]))
    say(f"[serve] the state ops alone, step sizes over [1e-3, 1e-2]: carry "
        f"gap {carry:.3e} in {time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    gaps = plain.served_gaps(model_cfg, ctx.seed, seqs,
                             **ctx.overrides.get("reference", {}))
    allg = np.concatenate(gaps) if gaps else np.zeros(0, np.float32)
    say(f"[serve] reference over {len(seqs)} requests, {allg.size} served "
        f"tokens, longest {max((len(p) + len(s) for p, s in seqs), default=0)}"
        f" in {time.perf_counter() - t:.1f}s")
    wide = sorted(((float(g), q, j) for q, gs in enumerate(gaps)
                   for j, g in enumerate(gs)), reverse=True)[:5]
    say("[serve] the widest gaps (gap, request, served token, context): "
        + ", ".join(f"({g:.3f}, {q}, {j}, {len(seqs[q][0]) + j})"
                    for g, q, j in wide))
    limits = cfg["check"]["limits"]
    checks = [("requests_compared", float(len(seqs)), None),
              ("served_logit_gap_max",
               float(allg.max()) if allg.size else float("inf"),
               limits["served_logit_gap_max"]),
              ("served_logit_gap_mean",
               float(allg.mean()) if allg.size else float("inf"),
               limits["served_logit_gap_mean"]),
              ("state_carry_gap", carry, limits["state_carry_gap"])]
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
