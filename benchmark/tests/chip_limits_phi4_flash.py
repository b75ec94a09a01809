#!/usr/bin/env python3
"""The readings the limits of the Phi-4-mini-flash cell's ``correct`` are set
from, on the chip and at the cell's own size.

    python3 benchmark/tests/chip_limits_phi4_flash.py --seeds 1,2 --seconds 12
    python3 benchmark/tests/chip_limits_phi4_flash.py --seeds 3 --control
    python3 benchmark/tests/chip_limits_phi4_flash.py --seeds 3 --state bfloat16
    python3 benchmark/tests/chip_limits_phi4_flash.py --seeds 1,2,3 --probe
    python3 benchmark/tests/chip_limits_phi4_flash.py --seeds 3 --chunk 64
    python3 benchmark/tests/chip_limits_phi4_flash.py --seeds 3 --page 64

One run of the cell a seed (set-up is long).  With no other flag the
program is the cell's own and what it served is held against the reference
SIX ways in the one process: as the cell does (``sound``), and with a
fault planted in the REFERENCE and none in the program (``faults``): the
scan's state zeroed at every chunk boundary, the convolution's tail dropped
there, lambda = 0 (plain attention), the cross layers reading layer 15's
keys and values, a window of 511.  The other readings plant in the
PROGRAM's place and leave the reference the cell's.  ``--control`` (the
nearest precision below the configuration's bfloat16): the engine runs the
configuration's ``control`` options (``quantize="w8a8"``).  ``--state
bfloat16`` rounds every ``h`` pool to bfloat16's eight bits of mantissa
whenever the scan has written it, as a pool stored in bfloat16 would hold
it.  ``--probe`` runs no cell: the driver's ``carry_gap`` alone, sound and
under the state plant, a few seconds a seed.  ``--chunk`` runs the cell at
another ``prefill_chunk_tokens`` (the sweep), ``--page`` at another
``page_size`` over the same bytes of pool (what the one-query paged kernel's
page copies cost).  Prints one ``LIMITS`` JSON
line a seed with every number of every reading beside the file's limits and
the harness's ``correct`` for it, and exits 1 if a sound reading is not
``correct`` or a planted one other than ``window511``, or the control, is.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import numpy as np  # noqa: E402

import run  # noqa: E402

CELL = "phi4-flash.serve.reason32"
#: the planted fault no limit is asked to see (ISSUE 41: say what it reads)
UNSEEN = ("window511",)


def faults(config):
    """What the reference is handed in place of the cell's own."""
    chunk = config["driver_options"]["engine"]["prefill_chunk_tokens"]
    half = config["num_hidden_layers"] // 2
    return {f"reset_h{chunk}": {"reset_h_every": chunk},
            f"drop_tail{chunk}": {"drop_tail_every": chunk},
            "lambda0": {"lam_zero": True},
            f"cross_from{half - 1}": {"cross_from": half - 1},
            "window511": {"window": config["sliding_window"] - 1}}


def plant_state():
    """Round ``h`` to bfloat16 wherever the scan has written it; returns
    what takes the plant out again.  The compiled programs are dropped both
    times: the jit caches do not see a function swapped under them."""
    import jax
    from paddle_tpu.ops import selective_scan as ss
    real = ss.scan_rows

    def rounded(pool, *args, **kw):
        m, pool = real(pool, *args, **kw)
        return m, jax.lax.reduce_precision(pool, exponent_bits=8,
                                           mantissa_bits=7)

    def undo():
        ss.scan_rows = real
        jax.clear_caches()

    ss.scan_rows = rounded
    jax.clear_caches()
    return undo


def probe(a, seed):
    """``carry_gap`` alone, sound and under the state plant: ({reading:
    {check: value}}, {reading: under the limit})."""
    from drivers import serve_phi4_flash as drv
    from reference import phi4_flash_plain as plain
    cfg = plain.model_cfg(a.config)
    chunk = a.config["driver_options"]["engine"]["prefill_chunk_tokens"]
    limit = a.config["check"]["limits"]["state_carry_gap"]
    out, correct = {}, {}
    for name in ("sound", "state_bfloat16"):
        undo = plant_state() if name != "sound" else (lambda: None)
        try:
            gap = drv.carry_gap(seed, cfg, chunk)
        finally:
            undo()
        out[name] = {"state_carry_gap": gap}
        correct[name] = gap <= limit
    return out, correct


def readings(a, seed):
    """({reading: {check: value}}, {reading: ``correct``}) of one run of
    the cell.  ``correct`` is ``run.run_cell``'s own, for every reading: a
    planted reading is put to it as the result of a run of its own (what
    the one run served, the checks of the faulty reference), so the one
    comparison the harness has decides each."""
    from drivers import serve_phi4_flash as drv
    from reference import phi4_flash_plain as plain
    real_gaps, real_run = plain.served_gaps, drv.run
    kept, out, correct = {}, {}, {}
    plain_run = not (a.control or a.state or a.chunk or a.page)

    def gaps_every_way(cfg, seed_, seqs, **switches):
        for name, fault in (a.faults if plain_run else {}).items():
            allg = np.concatenate(real_gaps(cfg, seed_, seqs, **fault))
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
    undo = plant_state() if a.state else (lambda: None)
    try:
        first = ("control" if a.control else "state_bfloat16" if a.state
                 else f"chunk{a.chunk}" if a.chunk
                 else f"page{a.page}" if a.page else "sound")
        engine = a.config["driver_options"]["engine"]
        tokens = engine["total_pages"] * engine["page_size"]
        over = ({"engine": a.config["control"]["engine"]} if a.control
                else {"engine": {"prefill_chunk_tokens": a.chunk}}
                if a.chunk else {"engine": {
                    "page_size": a.page, "total_pages": tokens // a.page,
                    "min_table_pages": a.config["max_position_embeddings"]
                    // a.page}} if a.page else {})
        line, checks = run.run_cell(args, over)
        out[first] = dict({n: v for n, v, _ in checks}, **{
            k: v["value"] for k, v in line["metrics"].items()})
        correct[first] = line["correct"]
        for name in [n for n in out if n in a.faults]:
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
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--state", choices=("bfloat16",))
    ap.add_argument("--chunk", type=int)
    ap.add_argument("--page", type=int)
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    _, _, a.config, _ = run.load_cell(CELL)
    if a.rehearse:
        a.config = dict(a.config, **a.config["rehearsal"])
    a.faults = faults(a.config)
    limits = a.config["check"]["limits"]
    wrong = []
    for seed in [int(s) for s in a.seeds.split(",")]:
        out, correct = (probe if a.probe else readings)(a, seed)
        print("LIMITS " + json.dumps({"seed": seed, "limits": limits,
                                      "correct": correct, "read": out}),
              flush=True)
        wrong += [(seed, n) for n, ok in correct.items()
                  if n not in UNSEEN
                  and ok != (n == "sound" or n.startswith(("chunk", "page")))]
    if wrong:
        print(f"limits that do not separate: {wrong}", file=sys.stderr)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
