"""Driver ``serve_engine``: one ``ContinuousBatchingEngine`` replica,
in process, under a closed-loop generator.

Set-up: model with the seed's weights, the engine with the options of
the configuration file, a warm-up that sends every (rows, span) bucket
through the engine's own submit path, then the
cell's clients until ``ramp_requests`` requests have finished, so the
window opens on a steady mix at a fixed point of the sequence.  The window: the same
clients, untouched; every timestamp is the benchmark's own
``perf_counter`` (submission and completion in the client's thread, the
first token by a 2 ms poll of the request — the engine has no streaming
call).  After the window the engine is stopped and freed, the device's
peak is read, and only then the plain reference runs over a seeded
sample of the requests the window finished.
"""
from __future__ import annotations

import threading
import time

import numpy as np

import stats
from . import common
from .common import plain, say

POLL_S = 0.002


class Clients:
    """``n`` closed-loop callers of ``engine.submit`` fed by
    ``next_request()``; records every finished request."""

    def __init__(self, engine, next_request, n):
        self.engine, self.next_request = engine, next_request
        self.lock = threading.Lock()
        self.live, self.records = {}, []
        self.halt = threading.Event()
        self.threads = [threading.Thread(target=self._client, args=(i,),
                                         daemon=True) for i in range(n)]
        self.poller = threading.Thread(target=self._poll, daemon=True)

    def start(self):
        for t in self.threads:
            t.start()
        self.poller.start()

    def _client(self, idx):
        while not self.halt.is_set():
            with self.lock:
                prompt, n_out = self.next_request(idx)
            rec = {"prompt": prompt, "n_out": n_out, "first": None,
                   "finished": None, "error": None,
                   "submitted": time.perf_counter()}
            try:
                req = self.engine.submit(prompt, max_new_tokens=n_out)
            except Exception as e:  # noqa: BLE001 — a refusal is a failure
                rec["error"] = repr(e)
                rec["finished"] = time.perf_counter()
                with self.lock:
                    self.records.append(rec)
                continue
            rec["req"] = req
            with self.lock:
                self.live[id(req)] = rec
            req.done.wait()
            now = time.perf_counter()
            with self.lock:
                rec["finished"] = now
                if rec["first"] is None:
                    rec["first"] = now
                if req.error is not None:
                    rec["error"] = repr(req.error)
                del self.live[id(req)]
                self.records.append(rec)

    def _poll(self):
        while not self.halt.is_set():
            time.sleep(POLL_S)
            now = time.perf_counter()
            with self.lock:
                for rec in self.live.values():
                    r = rec["req"]
                    if rec["first"] is None and (r.next_token is not None
                                                 or r.generated):
                        rec["first"] = now

    def produced(self):
        """Output tokens the engine has committed so far for requests
        still running."""
        with self.lock:
            return sum(len(rec["req"].generated)
                       for rec in self.live.values())

    def join(self, timeout=None):
        for t in self.threads:
            t.join(timeout)
        self.halt.set()
        self.poller.join(timeout)


def pow2s(upto):
    out, v = [], 1
    while v <= upto:
        out.append(v)
        v *= 2
    return out


def warm_up(engine, opts, vocab, seed, max_position):
    """Every (rows bucket, span bucket) program the ragged step can be
    asked for — a step's rows are the decoding requests plus the chunks
    that got this step's prefill budget, so with prompts waiting for the
    budget the rows fall through every bucket below ``max_batch``, and
    a program met first inside the window costs it a 2 s stall.
    Through ``engine.submit``, one program at a time: with b - 1
    requests left decoding, for each span bucket s a prompt of exactly s
    tokens and one output token goes in alone and is waited for, so one
    step carries b rows of which the longest spans s tokens; then more
    decoders are added for the next b.  (Tracing and lowering one
    12-layer program takes the program about 2 s even from a warm
    compile cache: 32 programs, about a minute.)  Returns the decoders,
    still running: ``hand_over`` swaps them for the clients one by one,
    so the engine stays full."""
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
        for s in pow2s(int(opts["prefill_chunk_tokens"])):
            engine.submit(ids(s), max_new_tokens=1).result(timeout=1200)
    return back


def hand_over(back, clients, timeout=120.0):
    """Cancel the warm-up's decoders one at a time, each when one more
    of the clients' requests has its first token."""
    for k, r in enumerate(back, start=1):
        if r.done.is_set():
            raise RuntimeError("a warm-up decoder ended before the "
                               "clients took over: " + repr(r.error))
        r.cancel()
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with clients.lock:
                n = sum(rec["first"] is not None for rec in
                        list(clients.live.values()) + clients.records)
            if n >= k:
                break
            time.sleep(POLL_S)
    for r in back:
        r.done.wait(60)


def run(ctx):
    import jax
    from paddle_tpu import monitor
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    cfg, model_cfg = ctx.config, common.model_cfg(ctx.config)
    opts = dict(cfg["driver_options"]["engine"])
    opts.update(ctx.overrides.get("engine", {}))
    t = time.perf_counter()
    model = common.build_model(model_cfg, ctx.seed)
    jax.block_until_ready([p._data for p in model.parameters()])
    say(f"[serve] model: {model_cfg['num_hidden_layers']} layers, "
        f"weights from seed {ctx.seed} in {time.perf_counter() - t:.1f}s; "
        f"in use {common.memory_now()['bytes_in_use']}")
    engine = ContinuousBatchingEngine(model, **opts)
    say(f"[serve] engine options {opts}; in use "
        f"{common.memory_now()['bytes_in_use']}")
    gen = ctx.generator(model_cfg["vocab_size"])
    try:
        t = time.perf_counter()
        c0 = common.counters_now().get("jit_recompile_count", 0)
        back = warm_up(engine, opts, model_cfg["vocab_size"], ctx.seed,
                       model_cfg["max_position_embeddings"])
        c1 = common.counters_now().get("jit_recompile_count", 0)
        say(f"[serve] warm-up: {c1 - c0:.0f} programs in "
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
    lag = [abs(r["first"] - r["req"].first_token_at) * 1e3 for r in done
           if r["req"].first_token_at is not None]
    compiled = (counters1.get("jit_recompile_count", 0)
                - counters0.get("jit_recompile_count", 0))
    say(f"[serve] window {window_s:.3f}s: {compiled:.0f} programs compiled "
        f"in it, {len(done)} requests finished, "
        f"{len(records) - len(done)} failed, {len(early)} before it; "
        f"{tokens} output tokens ({produced0} already out at its start, "
        f"{produced1} of unfinished requests at its end); first-token "
        f"stamps lag the engine's own by {np.mean(lag) if lag else 0:.2f} ms "
        f"(mean), {max(lag) if lag else 0:.2f} ms (max)")
    say(f"[serve] time to first token p50/p90 "
        f"{stats.percentile(ttft, 50)[0]:.1f}/{stats.percentile(ttft, 90)[0]:.1f}"
        f" ms, time per output token p50/p90 "
        f"{stats.percentile(tpot, 50)[0]:.2f}/{stats.percentile(tpot, 90)[0]:.2f}"
        f" ms over {len(ttft)} requests")
    say(f"[serve] autotune decisions: {common.decisions_summary()}")
    steps = monitor.get_tracer().step_records() if ctx.trace else []
    mem = common.memory_now()
    # ------------------------------- free the program, then the check
    sample = pick_sample(done, ctx.seed, int(cfg["check"]["requests"]))
    seqs = [(r["prompt"], np.asarray(r["req"].generated[:r["n_out"]], np.int32))
            for r in sample]
    for r in clients.records:
        r.pop("req", None)
    del engine, model, clients
    common.free_device_memory()
    t = time.perf_counter()
    gaps = plain.served_gaps(model_cfg, ctx.seed, seqs)
    allg = np.concatenate(gaps) if gaps else np.zeros(0, np.float32)
    say(f"[serve] reference over {len(seqs)} requests, {allg.size} served "
        f"tokens, longest {max((len(p) + len(s) for p, s in seqs), default=0)}"
        f" in {time.perf_counter() - t:.1f}s")
    limits = cfg["check"]["limits"]
    checks = [("requests_compared", float(len(seqs)), None),
              ("served_logit_gap_max",
               float(allg.max()) if allg.size else float("inf"),
               limits["served_logit_gap_max"]),
              ("served_logit_gap_mean",
               float(allg.mean()) if allg.size else float("inf"),
               limits["served_logit_gap_mean"])]
    return {
        "end_to_end": {"serve.tokens_per_s": tokens / window_s},
        "attempted": len(records), "failed": len(records) - len(done),
        "checks": checks, "memory": mem, "window_s": window_s,
        "sources": {"steps": steps, "max_batch": int(opts["max_batch"]),
                    "ttft_ms": ttft, "tpot_ms": tpot,
                    "counters0": counters0, "counters1": counters1,
                    "window": (t0, t1)},
    }


def pick_sample(done, seed, k):
    """The longest finished request and k - 1 others drawn from the
    seed."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i]["prompt"]) + done[i]["n_out"]))
    rest = np.random.default_rng([int(seed), 0xC4EC]).permutation(order[1:])
    return [done[order[0]]] + [done[i] for i in rest[:max(0, k - 1)]]
