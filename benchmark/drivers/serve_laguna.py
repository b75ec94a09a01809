"""Driver ``serve_laguna``: one ``ContinuousBatchingEngine`` replica
serving ``LagunaForCausalLM``, in process, under a closed-loop generator.

As ``serve_engine`` (whose clients and hand-over it imports, and whose
window it repeats stamp for stamp: ``serve_engine.run`` keeps its window
inline, and factoring it out for both drivers is an edit to that file);
what differs is the model it builds, the reference it checks with, and:

* the model is handed an initialiser that makes its matrices bfloat16
  zeros: whole in float32 before a cast, as ``common.build_model`` has
  it, the 3.87 B of this configuration are 15.5 GB;
* the warm-up compiles the (rows, span) programs THIS MIX can ask for
  (``step_spans``: 20 of the 32 buckets), each through ``engine.submit``
  as ``serve_engine.warm_up`` does;
* ``router_flip_share``: the chosen experts are no output of the timed
  programs (handing them out would change what is timed for the check's
  sake), so after the window the engine's own decoder and cache feed the
  sampled requests once more, in the packed (8, 128) steps the window
  ran, with each expert layer's chosen ids called back to the host; the
  share of (token, layer) pairs whose chosen SET differs from the
  reference's own is bounded.  The logit gaps are of the tokens the
  timed path served.  The reference is never handed the program's
  routing;
* ``hlo_scopes_by_program``: for ``--trace 1`` the {instruction: scope}
  maps of the ragged programs that ran most of the window's steps.
"""
from __future__ import annotations

import re
import time

import numpy as np

import stats
from . import common
from .common import say
from .serve_engine import POLL_S, Clients, hand_over, pick_sample, pow2s
from generators.common import lognormal_pool
from reference import laguna_plain as plain

_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = ')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE_DEPTH = 4                  # serve/model/<layer kind>[/<part>]


def scope_of(op_name: str) -> str:
    """``jit(fn)/jit(main)/serve/model/moe/experts/...`` -> ``serve/model/
    moe/experts``; '' for an instruction outside ``serve/model``."""
    path = re.sub(r"\w+\(", "", op_name).replace(")", "")
    at = path.find("serve/model")
    return "/".join(path[at:].split("/")[:SCOPE_DEPTH]) if at >= 0 else ""


def hlo_scopes(text: str) -> dict:
    """{instruction name: scope path} of EVERY instruction of a compiled
    program's text ('' outside ``serve/model``): the reader tells the
    programs in a trace apart by the instructions they have."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = scope_of(op.group(1)) if op else ""
    return out


def build_model(model_cfg: dict, seed: int):
    """A ``LagunaForCausalLM`` whose every leaf holds the benchmark's value
    for (seed, leaf name).  A ``Layer`` creates its parameters in float32
    with its own initialisers (``common.build_model`` casts them after);
    the 3.87 B of this configuration would be 15.5 GB, and one layer's
    eager draws alone queue 10 GB on the device.  The model is therefore handed
    an initialiser (``weight_attr``) that draws nothing and makes every
    matrix bfloat16 zeros; the few small float32 leaves (gains, the
    router's weights) are cast; then one donated call a group (the
    embedding, a layer, the head) rewrites the values in place."""
    import jax
    import jax.numpy as jnp
    import weights as W
    from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
    from paddle_tpu.nn.initializer import Initializer

    class ZerosAsServed(Initializer):
        def __call__(self, shape, dtype):
            return jnp.zeros(shape, jnp.bfloat16)

    t0 = time.perf_counter()
    model = LagunaForCausalLM(LagunaConfig(**model_cfg),
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
    # a group (the embedding, a layer, the head) a call: ``make_all`` draws
    # in float32 before it rounds
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


def step_spans(traffic: dict, chunk: int):
    """The span buckets a ragged step of this mix can be asked for: a
    step's longest span is a decoding row's one token, a whole chunk, or
    the tail of a prompt (its length modulo the chunk), rounded up to a
    power of two.  The prompt lengths are the traffic file's ``levels``
    quantiles, the same on every seed; of the 8 buckets up to a chunk of
    128 this mix reaches 1, 16, 32, 64 and 128."""
    tails = {int(p) % chunk for p in lognormal_pool(
        traffic["prompt_tokens"], int(traffic["levels"]))}
    return sorted({1, chunk} | {1 << (t - 1).bit_length()
                                for t in tails if t})


def warm_up(engine, opts, spans, vocab, seed, max_position):
    """``serve_engine.warm_up`` over ``spans`` in place of every power of
    two up to the chunk: for each rows bucket b, with b - 1 requests left
    decoding, a prompt of exactly s tokens and one output token goes in
    alone and is waited for, so one step carries b rows of which the
    longest spans s.  Returns the decoders, still running."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    long = min(1024, int(max_position) - 2)

    def ids(n):
        return rng.integers(0, vocab, n).astype(np.int32)

    back = []
    for b in pow2s(int(opts["max_batch"])):
        back += [engine.submit(ids(1), max_new_tokens=long)
                 for _ in range(b - 1 - len(back))]
        while any(r.next_token is None and not r.done.is_set() for r in back):
            time.sleep(POLL_S)
        for s in spans:
            engine.submit(ids(s), max_new_tokens=1).result(timeout=1200)
    return back


def program_routing(engine, model, seqs, chunk, rows):
    """{layer: (n, k) ids} the PROGRAM's expert layers chose for every
    token of ``seqs`` (the tokens fed, end to end), through the engine's
    decoder and cache in steps shaped like the window's: ``rows`` rows a
    step, two of them a chunk of a sequence each and the others one
    token of a scratch sequence (a decoding row), so every step is the
    packed (rows, chunk) program.  A tail is padded to a whole chunk
    (causal: the pad changes no earlier token).  ONE program compiles:
    the window's own do not hand the ids out, so this one has the gates'
    callbacks in it and the decoder's logits tail."""
    import jax
    cache, dec = engine.cache, engine._decoder
    for sid in list(cache._seq_pages):      # what the stopped engine held
        cache.free(sid)
    sparse = [(i, layer.mlp.gate) for i, layer in
              enumerate(model.model.layers) if hasattr(layer.mlp, "gate")]
    got = {i: [] for i, _ in sparse}
    for i, gate in sparse:
        def route(x, _route=type(gate).route_no_drop, _gate=gate, _i=i):
            idx, w = _route(_gate, x)
            jax.debug.callback(lambda a, _i=_i: got[_i].append(
                np.asarray(a)), idx._data, ordered=True)
            return idx, w
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
        for _, gate in sparse:
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


def set_differs(program: dict, reference: dict, bounds) -> dict:
    """{layer: (n,) bool}: for every token of the sample, end to end,
    whether the program's chosen SET is another than the reference's own
    (every token of a layer whose shapes disagree)."""
    out = {}
    for i, ref in reference.items():
        ref = np.concatenate([ref[a:b] for a, b in bounds])
        got = program[i]
        out[i] = (np.ones(len(ref), bool) if got.shape != ref.shape else
                  (np.sort(got, axis=1) != np.sort(ref, axis=1)).any(axis=1))
    return out


def flip_share(program: dict, reference: dict, bounds) -> float:
    """Share of (token, layer) pairs whose chosen set differs."""
    differs = set_differs(program, reference, bounds)
    return (sum(int(d.sum()) for d in differs.values())
            / max(sum(len(d) for d in differs.values()), 1))


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
            key = (r["rows_padded"], r["span_padded"], r["table_pages"])
            runs[key] = runs.get(key, 0) + 1
    say("[serve] the window's steps by (rows, span) program: " + ", ".join(
        f"({b}, {s}) {n}" for (b, s, _), n in sorted(runs.items())))
    out, seen = [], 0
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    for (b, s, w), n in sorted(runs.items(), key=lambda kv: -kv[1])[:most]:
        if seen >= share * sum(runs.values()):
            break
        seen += n
        args = ([sds(a) for a in dec._param_arrays()], i32(b, s), i32(b),
                i32(b), i32(b * s), i32(b * s), i32(b, w), i32(b), (),
                *[tuple(sds(a) for a in pool)
                  for pool in dec._pool_args(cache)], dec._wscale_args())
        text = dec._program("ragged", "greedy").lower(*args).compile()\
            .as_text()
        out.append(hlo_scopes(text))
        say(f"[serve] program ({b}, {s}, {w}): {n} steps, "
            f"{sum(map(bool, out[-1].values()))} of {len(out[-1])} "
            "instructions under serve/model")
    return out


def run(ctx):
    import jax
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
    say(f"[serve] engine options {opts}; in use "
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
        while True:            # the ramp: one block of the mix, finished
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
    # --------- the program once more over the sample, then it is freed
    sample = pick_sample(done, ctx.seed, int(cfg["check"]["requests"]))
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
    del engine, model, clients
    common.free_device_memory()
    t = time.perf_counter()
    gaps, chosen, bounds = plain.served_gaps(
        model_cfg, ctx.seed, seqs, **ctx.overrides.get("reference", {}))
    allg = np.concatenate(gaps) if gaps else np.zeros(0, np.float32)
    flips = flip_share(routed, chosen, bounds) if seqs else float("inf")
    say(f"[serve] reference over {len(seqs)} requests, {allg.size} served "
        f"tokens, longest {max((len(p) + len(s) for p, s in seqs), default=0)}"
        f" in {time.perf_counter() - t:.1f}s")
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
