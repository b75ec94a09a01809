#!/usr/bin/env python3
"""The readings the limits of the ZAYA cell's ``correct`` are set from, on
the chip and at the cell's own size.

    python3 benchmark/tests/chip_limits_zaya.py --seeds 1,2 --seconds 20
    python3 benchmark/tests/chip_limits_zaya.py --seeds 3 --control
    python3 benchmark/tests/chip_limits_zaya.py --seeds 3 --router bfloat16
    python3 benchmark/tests/chip_limits_zaya.py --seeds 3 --chunk 64 --requests 1

One run of the cell a seed (set-up is long).  With no other flag the
program is the cell's own and what it served is held against the reference
SEVEN ways in the one process: as the cell does (``sound``), and with a
fault planted in the REFERENCE and none in the program
(``reference/zaya_plain.py::FAULTS``): the three tails zeroed at every
128th position, the value shift dropped, the q-k mean left out, tau = 1,
the previous layer's router state not added, the front pad after the first
convolution.  The other readings plant in the PROGRAM's place and leave the
reference the cell's.  ``--control`` (the nearest precision below the
configuration's bfloat16): the engine runs the configuration's ``control``
options (``quantize="w8a8"``).  ``--router bfloat16``: the router's whole
path (its state, norm, MLP and softmax) in bfloat16, as a program that
ignored ``zaya_high_prec`` would run it.  ``--chunk`` runs the cell at
another ``prefill_chunk_tokens`` (the sweep; ``--requests 1`` cuts the
check to the longest request, the sweep reads tokens per second).  Prints
one ``LIMITS`` JSON line a seed with every number of every reading beside
the file's limits and the harness's ``correct`` for it, and exits 1 if a
sound reading is not ``correct`` or a planted one other than ``late_pad``,
or the control, is.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import numpy as np  # noqa: E402

import run  # noqa: E402

CELL = "zaya1-8b.serve.reason64"
#: the planted fault no limit is asked to see (ISSUE 44: say what it reads)
UNSEEN = ("late_pad",)


def plant_router():
    """The router's path in bfloat16 in the program's place; returns what
    takes the plant out again."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.framework.tensor import wrap_array
    from paddle_tpu.incubate.distributed.models.moe.gate import (
        DepthAveragedMLPGate as Gate)
    real = Gate.route_no_drop
    bf = jnp.bfloat16

    def route(self, x, state=None):
        d = lambda p: p._data.astype(bf)                    # noqa: E731
        r = x._data.astype(bf) @ d(self.down_weight) + d(self.down_bias)
        if self.gamma is not None and state is not None:
            r = r + d(self.gamma) * state._data.astype(bf)
        ms = jnp.mean(r * r, axis=-1, keepdims=True)
        u = r * jax.lax.rsqrt(ms + jnp.asarray(self.eps, bf)) \
            * d(self.norm_weight)
        h = jax.nn.gelu(u @ d(self.w1) + d(self.b1), approximate=False)
        h = jax.nn.gelu(h @ d(self.w2) + d(self.b2), approximate=False)
        p = jax.nn.softmax(h @ d(self.w3), axis=-1)
        idx = jnp.argmax(p + d(self.balancing_bias),
                         axis=-1)[:, None].astype(jnp.int32)
        w = jnp.take_along_axis(p, idx, axis=-1).astype(jnp.float32)
        return wrap_array(idx), wrap_array(w), wrap_array(r)

    def undo():
        Gate.route_no_drop = real
        jax.clear_caches()

    Gate.route_no_drop = route
    jax.clear_caches()
    return undo


def readings(a, seed):
    """({reading: {check: value}}, {reading: ``correct``}) of one run of
    the cell.  ``correct`` is ``run.run_cell``'s own, for every reading: a
    planted reading is put to it as the result of a run of its own (what
    the one run served, the checks of the faulty reference), so the one
    comparison the harness has decides each."""
    from drivers import serve_zaya as drv
    from reference import zaya_plain as plain
    real_gaps, real_run = plain.served_gaps, drv.run
    kept, out, correct = {}, {}, {}
    plain_run = not (a.control or a.router or a.chunk)

    def gaps_every_way(cfg, seed_, seqs, **switches):
        for name in (plain.FAULTS if plain_run else ()):
            allg = np.concatenate(real_gaps(cfg, seed_, seqs, fault=name,
                                            **switches)[0])
            out[name] = {"served_logit_gap_max": float(allg.max()),
                         "served_logit_gap_mean": float(allg.mean())}
            print(f"--- the reference with {name}: {out[name]}", flush=True)
        return real_gaps(cfg, seed_, seqs, **switches)

    def run_kept(ctx):
        kept["result"] = real_run(ctx)
        kept["setup_s"] = ctx.setup_s
        return kept["result"]

    args = argparse.Namespace(workload=CELL, seed=seed, seconds=a.seconds,
                              trace=0, rehearse=a.rehearse)
    plain.served_gaps, drv.run = gaps_every_way, run_kept
    undo = plant_router() if a.router else (lambda: None)
    try:
        first = ("control" if a.control else "router_bfloat16" if a.router
                 else f"chunk{a.chunk}" if a.chunk else "sound")
        over = ({"engine": a.config["control"]["engine"]} if a.control
                else {"engine": {"prefill_chunk_tokens": a.chunk}}
                if a.chunk else {})
        if a.requests:
            over["check_requests"] = a.requests
        line, checks = run.run_cell(args, over)
        out[first] = dict({n: v for n, v, _ in checks}, **{
            k: v["value"] for k, v in line["metrics"].items()})
        correct[first] = line["correct"]
        for name in [n for n in out if n in plain.FAULTS]:
            planted = [(n, out[name].get(n, v), lim) for n, v, lim in checks]

            def served_again(ctx, _checks=planted):
                ctx.setup_s = kept["setup_s"]
                return dict(kept["result"], checks=_checks)

            drv.run = served_again
            print(f"--- the reading {name!r} put to run_cell", flush=True)
            correct[name] = run.run_cell(args, {})[0]["correct"]
    finally:
        plain.served_gaps, drv.run = real_gaps, real_run
        undo()
    return out, correct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--router", choices=("bfloat16",))
    ap.add_argument("--chunk", type=int)
    ap.add_argument("--requests", type=int)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
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
        wrong += [(seed, n) for n, ok in correct.items() if n not in UNSEEN
                  and ok != (n == "sound" or n.startswith("chunk"))]
    if wrong:
        print(f"limits that do not separate: {wrong}", file=sys.stderr)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
