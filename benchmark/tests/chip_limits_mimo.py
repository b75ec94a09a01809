#!/usr/bin/env python3
"""The readings the MiMo-V2-Flash cell's limits and choices are set from,
on the chip and at the cell's own size.

    python3 benchmark/tests/chip_limits_mimo.py --experts
    python3 benchmark/tests/chip_limits_mimo.py --seeds 1,2 --seconds 20
    python3 benchmark/tests/chip_limits_mimo.py --seeds 3 --control
    python3 benchmark/tests/chip_limits_mimo.py --seeds 3 --chunk 256 --requests 1

``--experts`` runs no cell: it times one expert layer's two products
(``moe_layer._held_experts``, every token through every held expert, and
``_grouped_experts``, the pairs that fall on the held experts grouped by
expert) at 16 held of 256, top-8, 4,096 x 2,048, over 32, 160 and 288
tokens routed by seeded scores: what ``DENSE_SHARE``'s rule is set from
(the grouped product refuses the two larger steps' layouts: see there).

Otherwise one run of the cell a seed (set-up is long).  With no other flag
the program is the cell's own and what it served is held against the
reference SEVEN ways in the one process: as the cell does (``sound``), and
with a fault planted in the REFERENCE and none in the program
(``reference/mimo_v2_flash_plain.py::faults``): the sink off, a window of
127, the value scale off, rotary on 96 channels, the first 4 KV heads of a
sliding layer serving all its query heads, top-7.  ``--control`` (the
nearest precision below the configuration's bfloat16 that the engine runs
for this model): the engine at the configuration's ``control`` options
(``quantize="w8a8"``).  ``--chunk`` runs the cell at another
``prefill_chunk_tokens`` (the sweep; ``--requests 1`` cuts the check to the
longest request, the sweep reads tokens per second and the tails).  Prints
one ``LIMITS`` JSON line a seed with every number of every reading beside
the file's limits and the harness's ``correct`` for it, and exits 1 if a
sound reading is not ``correct`` or a planted one, or the control, is.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import numpy as np  # noqa: E402

import run  # noqa: E402

CELL = "mimo-v2-flash.serve.mixed32"


def time_experts(repeats=30):
    """Milliseconds a call of each product at each token count, from the
    host's clock around ``repeats`` queued calls."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
        _grouped_experts, _held_experts)
    m, h, held, total, k = 4096, 2048, 16, 256, 8
    key = jax.random.key(0)
    w = [jax.random.normal(jax.random.fold_in(key, i), s, jnp.bfloat16) * 0.02
         for i, s in enumerate([(held, m, h), (held, m, h), (held, h, m)])]
    # the weights are ARGUMENTS: closed over they are constants of the
    # program, 400 MB each in every executable (the first call of this
    # script, PR 49, ended at the machine's 40 GiB)
    dense = jax.jit(lambda x, i, p, *w: _held_experts.raw_fn(x, i, p, 0, *w))
    grouped = jax.jit(lambda x, i, p, *w: _grouped_experts.raw_fn(
        x, i, p, None, 0, *w))
    out = {}
    for t in (32, 160, 288):
        kx = jax.random.fold_in(key, 100 + t)
        x = jax.random.normal(kx, (t, m), jnp.bfloat16)
        scores = jax.random.uniform(jax.random.fold_in(kx, 1), (t, total))
        p, idx = jax.lax.top_k(scores, k)
        idx, p = idx.astype(jnp.int32), (p / p.sum(-1, keepdims=True))
        row = {"touched": int(np.unique(np.asarray(idx)[np.asarray(idx)
                                                        < held]).size),
               "pairs_held": int((np.asarray(idx) < held).sum())}
        for name, fn in (("dense", dense), ("grouped", grouped)):
            try:
                y, _ = fn(x, idx, p, *w)
            except NotImplementedError as refused:
                # the layout's partial sums pass ``ACC_BYTES`` (from 98
                # tokens at this width): PR 49's readings there came
                # through a blocks-outermost grid that was not kept
                row[name + "_ms"] = None
                print(f"EXPERTS tokens {t}: {name} refused: {refused}",
                      flush=True)
                continue
            jax.block_until_ready(y)
            t0 = time.perf_counter()
            for _ in range(repeats):
                y, _ = fn(x, idx, p, *w)
            jax.block_until_ready(y)
            row[name + "_ms"] = (time.perf_counter() - t0) * 1e3 / repeats
        if row["grouped_ms"] is not None:
            yd, yg = dense(x, idx, p, *w)[0], grouped(x, idx, p, *w)[0]
            row["max_abs_diff"] = float(jnp.abs(
                yd.astype(jnp.float32) - yg.astype(jnp.float32)).max())
        out[t] = row
        print(f"EXPERTS tokens {t}: {row}", flush=True)
    return out


def readings(a, seed):
    """({reading: {check: value}}, {reading: ``correct``}) of one run of
    the cell.  ``correct`` is ``run.run_cell``'s own, for every reading: a
    planted reading is put to it as the result of a run of its own (what
    the one run served, the checks of the faulty reference), so the one
    comparison the harness has decides each."""
    from drivers import serve_mimo as drv
    from reference import mimo_v2_flash_plain as plain
    real_gaps, real_run, real_flips = (plain.served_gaps, drv.run,
                                       drv.flip_share)
    kept, out, correct, routes = {}, {}, {}, {}
    plain_run = not (a.control or a.chunk)

    def gaps_every_way(cfg, seed_, seqs, **switches):
        for name, fault in (plain.faults(cfg).items() if plain_run else ()):
            gaps, chosen, _ = real_gaps(cfg, seed_, seqs,
                                        **dict(switches, **fault))
            allg = np.concatenate(gaps)
            out[name] = {"served_logit_gap_max": float(allg.max()),
                         "served_logit_gap_mean": float(allg.mean())}
            routes[name] = chosen
            print(f"--- the reference with {name}: {out[name]}", flush=True)
        return real_gaps(cfg, seed_, seqs, **switches)

    def flips_every_way(routed, chosen, bounds):
        for name, theirs in routes.items():
            out[name]["router_flip_share"] = real_flips(routed, theirs,
                                                        bounds)
        return real_flips(routed, chosen, bounds)

    def run_kept(ctx):
        kept["result"] = real_run(ctx)
        kept["setup_s"] = ctx.setup_s
        return kept["result"]

    args = argparse.Namespace(workload=CELL, seed=seed, seconds=a.seconds,
                              trace=0, rehearse=a.rehearse)
    plain.served_gaps, drv.run = gaps_every_way, run_kept
    drv.flip_share = flips_every_way
    try:
        first = ("control" if a.control else f"chunk{a.chunk}" if a.chunk
                 else "sound")
        over = ({"engine": a.config["control"]["engine"]} if a.control
                else {"engine": {"prefill_chunk_tokens": a.chunk}}
                if a.chunk else {})
        if a.requests:
            over["check_requests"] = a.requests
        line, checks = run.run_cell(args, over)
        out[first] = dict({n: v for n, v, _ in checks}, **{
            k: v["value"] for k, v in line["metrics"].items()})
        correct[first] = line["correct"]
        for name in [n for n in out if n in routes]:
            planted = [(n, out[name].get(n, v), lim) for n, v, lim in checks]

            def served_again(ctx, _checks=planted):
                ctx.setup_s = kept["setup_s"]
                return dict(kept["result"], checks=_checks)

            drv.run = served_again
            print(f"--- the reading {name!r} put to run_cell", flush=True)
            correct[name] = run.run_cell(args, {})[0]["correct"]
    finally:
        plain.served_gaps, drv.run = real_gaps, real_run
        drv.flip_share = real_flips
    return out, correct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", action="store_true")
    ap.add_argument("--seeds")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--chunk", type=int)
    ap.add_argument("--requests", type=int)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.experts:
        print("EXPERTS " + json.dumps(time_experts()), flush=True)
        return
    _, _, a.config, _ = run.load_cell(CELL)
    if a.rehearse:
        a.config = dict(a.config, **a.config["rehearsal"])
    limits = a.config["check"]["limits"]
    wrong = []
    for seed in [int(s) for s in a.seeds.split(",")]:
        out, correct = readings(a, seed)
        print("LIMITS " + json.dumps({"seed": seed, "limits": limits,
                                      "correct": correct, "read": out}),
              flush=True)
        wrong += [(seed, n) for n, ok in correct.items()
                  if ok != (n == "sound" or n.startswith("chunk"))]
    if wrong:
        print(f"limits that do not separate: {wrong}", file=sys.stderr)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
