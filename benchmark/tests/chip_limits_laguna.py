#!/usr/bin/env python3
"""The readings the limits of the Laguna cell's ``correct`` are set from,
on the chip and at the cell's own size.

    python3 benchmark/tests/chip_limits_laguna.py --seeds 1,2 --seconds 12
    python3 benchmark/tests/chip_limits_laguna.py --seeds 3 --control

One run of the cell a seed (set-up is long).  Without ``--control`` the
program is the cell's own and what it served is held against the
reference THREE ways in the one process: as the cell does (``sound``),
and with a fault planted in the REFERENCE and none in the program: the
window switched off (``window_off``) and one expert fewer (``top7``).
With ``--control`` (the nearest precision below the configuration's
bfloat16) the
engine runs the configuration's ``control`` options (``quantize="w8a8"``
with ``kv_quant="int8"``) in the program's place and the reference is
the cell's.  Prints one ``LIMITS`` JSON line a seed with every number of
every reading beside the file's limits and the harness's ``correct`` for
it, and exits 1 if a sound reading is not ``correct`` or a planted one,
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

CELL = "laguna-xs2.serve.agent8"


def faults(config):
    """What the reference is handed in place of the cell's own."""
    return {"window_off": {"window": None},
            "top7": {"top_k": config["num_experts_per_tok"] - 1}}



def readings(a, seed):
    """({reading: {check: value}}, {reading: ``correct``}) of one run of
    the cell.  ``correct`` is ``run.run_cell``'s own, for every reading:
    a planted reading is put to it as the result of a run of its own
    (what the one run served, the checks of the faulty reference), so
    the one comparison the harness has decides each."""
    from drivers import serve_laguna as drv
    from reference import laguna_plain as plain
    real_gaps, real_flips, real_run = plain.served_gaps, drv.flip_share, drv.run
    kept, out, correct = {}, {}, {}

    def gaps_every_way(cfg, seed_, seqs, **switches):
        sound = real_gaps(cfg, seed_, seqs, **switches)
        kept["bounds"] = sound[2]
        for name, fault in ({} if a.control else a.faults).items():
            g, chosen, _ = real_gaps(cfg, seed_, seqs, **fault)
            allg = np.concatenate(g)
            out[name] = {"served_logit_gap_max": float(allg.max()),
                         "served_logit_gap_mean": float(allg.mean())}
            kept[name] = chosen
        return sound

    def flips_kept(program, reference, bounds):
        kept["program"] = program
        return real_flips(program, reference, bounds)

    def run_kept(ctx):
        kept["result"] = real_run(ctx)
        kept["setup_s"] = ctx.setup_s
        return kept["result"]

    args = argparse.Namespace(workload=CELL, seed=seed, seconds=a.seconds,
                              trace=0, rehearse=a.rehearse)
    plain.served_gaps, drv.flip_share, drv.run = (gaps_every_way, flips_kept,
                                                  run_kept)
    try:
        first = "control" if a.control else "sound"
        line, checks = run.run_cell(
            args, {"engine": a.config["control"]["engine"]}
            if a.control else {})
        out[first] = {n: v for n, v, _ in checks}
        correct[first] = line["correct"]
        for name in [n for n in out if n in a.faults]:
            out[name]["router_flip_share"] = real_flips(
                kept["program"], kept[name], kept["bounds"])
            planted = [(n, out[name].get(n, v), lim) for n, v, lim in checks]

            def served_again(ctx, _checks=planted):
                ctx.setup_s = kept["setup_s"]
                return dict(kept["result"], checks=_checks)

            drv.run = served_again
            print(f"--- the reading {name!r} put to run_cell", flush=True)
            correct[name] = run.run_cell(args, {})[0]["correct"]
    finally:
        plain.served_gaps, drv.flip_share, drv.run = (real_gaps, real_flips,
                                                      real_run)
    return out, correct


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    _, _, a.config, _ = run.load_cell(CELL)
    if a.rehearse:
        a.config = dict(a.config, **a.config["rehearsal"])
    a.faults = faults(a.config)
    limits = a.config["check"]["limits"]
    wrong = []
    for seed in [int(s) for s in a.seeds.split(",")]:
        out, correct = readings(a, seed)
        print("LIMITS " + json.dumps({"seed": seed, "limits": limits,
                                      "correct": correct, "read": out}),
              flush=True)
        wrong += [(seed, n) for n, ok in correct.items()
                  if ok != (n == "sound")]
    if wrong:
        print(f"limits that do not separate: {wrong}", file=sys.stderr)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
