"""Driver ``serve_brumby``: one ``ContinuousBatchingEngine`` replica
serving ``BrumbyForCausalLM``, in process, under a closed-loop generator.

As ``serve_laguna`` (whose span rule and scope map it imports, with
``serve_engine``'s clients, hand-over and sample; the window is repeated
stamp for stamp, since both of those keep theirs inline); what differs is
the model it builds, the reference it checks with
(``reference/brumby_plain.py``: the served tokens' logit gaps, and
``carry_gap``: the state op alone over gates near one), the warm-up
(``warm_up``: the span buckets of the mix at the one row bucket a full
engine's chunk steps reach, not every pair), and ``main_programs``: a
recurrent model's ragged program takes its slot pools, the rows' slots
and the rows of several tokens as operands of its own.
"""
from __future__ import annotations

import time

import numpy as np

import stats
from . import common
from .common import say
from .serve_engine import POLL_S, Clients, hand_over, pick_sample, pow2s
from .serve_laguna import hlo_scopes, step_spans
from reference import brumby_plain as plain


def build_model(model_cfg: dict, seed: int):
    """A ``BrumbyForCausalLM`` whose every leaf holds the benchmark's value
    for (seed, leaf name).  The 3.21 B of this configuration would be
    12.8 GB in float32, so the model is handed an initialiser
    (``weight_attr``) that draws nothing and makes every matrix bfloat16
    zeros; the float32 gains are cast; then one donated call a group (the
    embedding, a layer, the head) rewrites the values in place."""
    import jax
    import jax.numpy as jnp
    import weights as W
    from paddle_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
    from paddle_tpu.nn.initializer import Initializer

    class ZerosAsServed(Initializer):
        def __call__(self, shape, dtype):
            return jnp.zeros(shape, jnp.bfloat16)

    t0 = time.perf_counter()
    model = BrumbyForCausalLM(BrumbyConfig(**model_cfg),
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
    say(f"[build] the program's parameters as bfloat16 zeros took "
        f"{t1 - t0:.1f}s, the benchmark's weights "
        f"{time.perf_counter() - t1:.1f}s; peak "
        f"{common.memory_now()['peak_bytes_in_use']}")
    return model


def warm_up(engine, opts, spans, vocab, seed, max_position):
    """The (rows, span) programs this mix can reach, each through
    ``engine.submit`` as ``serve_engine.warm_up`` does: for each rows
    bucket b, with b - 1 requests left decoding, a prompt of exactly s
    tokens and one output token goes in alone and is waited for, so one
    step carries b rows of which the longest spans s.  Every rows bucket
    at span 1 (the decoders joining walk through them); the chunk spans
    only at the widest bucket: 16 clients in a closed loop keep 16
    sequences admitted, of which a step's rows are the decoding ones and
    one or two that prefill, so a chunk step of eight rows or fewer would
    need eight prompts waiting for the chunk budget at once.  The mix's
    order is the traffic file's, the same in every run: replayed through
    the planner's rule (one chunk budget a step, first come first served)
    over 22,000 steps, a chunk step never holds fewer than 13 rows, in
    the hand-over or after it (PERF.md section 6).  Returns the decoders,
    still running."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    long = min(1024, int(max_position) - 2)

    def ids(n):
        return rng.integers(0, vocab, n).astype(np.int32)

    back, buckets = [], pow2s(int(opts["max_batch"]))
    for b in buckets:
        back += [engine.submit(ids(1), max_new_tokens=long)
                 for _ in range(b - 1 - len(back))]
        while any(r.next_token is None and not r.done.is_set() for r in back):
            time.sleep(POLL_S)
        for s in (spans if b == buckets[-1] else spans[:1]):
            engine.submit(ids(s), max_new_tokens=1).result(timeout=1200)
    return back


def main_programs(engine, steps, share=0.9, most=4):
    """[{instruction: scope}] of the ragged programs that ran ``share``
    of the window's steps (at most ``most``): lowered again from the
    shapes the ring recorded and compiled from the cache."""
    import jax
    import jax.numpy as jnp
    dec, cache = engine._decoder, engine.cache
    runs = {}
    for r in steps:
        if r["kind"] == "dispatch":
            key = (r["rows_padded"], r["span_padded"], r["table_pages"],
                   r["chunk_rows_padded"])
            runs[key] = runs.get(key, 0) + 1
    say("[serve] the window's steps by (rows, span, chunk rows) program: "
        + ", ".join(f"({b}, {s}, {c}) {n}"
                    for (b, s, _, c), n in sorted(runs.items())))
    out, seen = [], 0
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    for (b, s, w, c), n in sorted(runs.items(), key=lambda kv: -kv[1])[:most]:
        if seen >= share * sum(runs.values()):
            break
        seen += n
        args = ([sds(a) for a in dec._param_arrays()], i32(b, s), i32(b),
                i32(b), i32(b * s), i32(b * s), i32(b, w), i32(b), (),
                *[tuple(sds(a) for a in pool)
                  for pool in dec._pool_args(cache)], dec._wscale_args(),
                tuple(sds(a) for a in cache.state_pools),
                (i32(b), i32(c), i32()))
        text = dec._program("ragged", "greedy").lower(*args).compile()\
            .as_text()
        out.append(hlo_scopes(text))
        say(f"[serve] program ({b}, {s}, {c}): {n} steps, "
            f"{sum(map(bool, out[-1].values()))} of {len(out[-1])} "
            "instructions under serve/model")
    return out


def carry_gap(seed, model_cfg, slot, chunk):
    """What the served tokens cannot show of the state's precision: with
    the benchmark's zero-mean weights a gate's median is 1/2, so a slot
    forgets within a few tokens and a state kept or multiplied more
    crudely reads the same logit gaps.  So the layer's state op
    (``ops/power_retention.py::retention_step``, what ``paged_ctx.retain``
    calls) runs here alone, against a pool of the engine's own slot shape
    and dtype (``slot``: the two, of one of its pools), at the model's
    head shapes, on two sequences whose gates are drawn over [0.99, 1): two
    chunk rows of ``chunk`` tokens each, then 64 one-token rows,
    every token carried from the first.  Its outputs FROM THE SECOND
    CHUNK ON are held against the reference's first form (float32, no
    state) over the same values (a sequence's first outputs are a ratio
    of two nearly empty sums: where a token's one weight falls near eps,
    once in some ten seeds, the two forms' float32 roundings of it read
    1e-4 apart and say nothing of the state); returns the widest
    difference as a share of the reference's widest output there."""
    import jax.numpy as jnp
    from paddle_tpu.ops import power_retention as pr

    rows, d = 2, model_cfg["head_dim"]
    hq, hk = (model_cfg[f"num_{n}_heads"] for n in ("attention", "key_value"))
    steps = 64
    total = 2 * chunk + steps
    rng = np.random.default_rng([int(seed), 0xCA44])

    def draw(heads):        # what a bfloat16 activation can hold
        x = rng.standard_normal((rows, total, heads, d), np.float32)
        return jnp.asarray(x, jnp.bfloat16)

    q, k, v = draw(hq), draw(hk), draw(hk)
    log_g = jnp.log(jnp.asarray(
        rng.uniform(0.99, 1.0, (rows, total, hk)), jnp.float32))
    pool = jnp.zeros((rows + 1,) + tuple(slot[0]), slot[1])
    i32 = lambda x: jnp.asarray(x, jnp.int32)               # noqa: E731
    slots, ys, at = i32(np.arange(rows)), [], 0
    for span in [chunk, chunk] + [1] * steps:
        cut = lambda x: x[:, at:at + span].reshape(         # noqa: E731
            (rows * span,) + x.shape[2:])
        y, pool = pr.retention_step(
            pool, slots, i32([at] * rows), i32([span] * rows), None,
            i32(np.arange(rows) if span > 1 else np.zeros(0)), cut(q),
            cut(k), cut(v), cut(log_g), span=span)
        ys.append(y.reshape((rows, span) + y.shape[1:]))
        at += span
    got = np.asarray(jnp.concatenate(ys, axis=1), np.float32)
    f32 = lambda x: x.astype(jnp.float32)                   # noqa: E731
    want = np.stack([np.asarray(plain.retention(
        f32(q[r]), f32(k[r]), f32(v[r]), log_g[r], block=total))
        for r in range(rows)])
    got, want = got[:, chunk:], want[:, chunk:]
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
    say(f"[serve] engine options {opts}; slot pools "
        f"{engine.cache.state_pool_bytes} bytes; in use "
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
        while True:            # the ramp: two blocks of the mix, finished
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
    slot = (engine.cache.state_pools[0].shape[1:],
            engine.cache.state_pools[0].dtype)
    # ------------------------------- free the program, then the check
    for r in clients.records:
        r.pop("req", None)
    del engine, model, clients
    common.free_device_memory()
    t = time.perf_counter()
    carry = carry_gap(ctx.seed, model_cfg, slot,
                      int(opts["prefill_chunk_tokens"]))
    say(f"[serve] the state op alone, gates over [0.99, 1): carry gap "
        f"{carry:.3e} in {time.perf_counter() - t:.1f}s")
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
