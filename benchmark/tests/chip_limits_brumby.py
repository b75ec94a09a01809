#!/usr/bin/env python3
"""The readings the limits of the Brumby cell's ``correct`` are set from,
on the chip and at the cell's own size.

    python3 benchmark/tests/chip_limits_brumby.py --seeds 1,2 --seconds 12
    python3 benchmark/tests/chip_limits_brumby.py --seeds 3 --control
    python3 benchmark/tests/chip_limits_brumby.py --seeds 3 --state bfloat16
    python3 benchmark/tests/chip_limits_brumby.py --seeds 1,2,3 --probe

One run of the cell a seed (set-up is long).  With no other flag the
program is the cell's own and what it served is held against the
reference THREE ways in the one process: as the cell does (``sound``),
and with a fault planted in the REFERENCE and none in the program: the
plain dot product in place of its square (``power1``) and a state zeroed
at every chunk boundary (``reset128``: a query sees no key from before
its own block of 128 positions).  The other readings plant in the
PROGRAM's place and leave the reference the cell's.  ``--control`` (the
nearest precision below the configuration's bfloat16): the engine runs
the configuration's ``control`` options (``quantize="w8a8"``).
``--state`` (the precisions below the state's float32, ``STATE_PLANTS``):
``bfloat16`` rounds every slot pool to bfloat16's eight bits of mantissa
whenever the state op has written it, as a pool stored in bfloat16 would
hold it; ``one_pass`` runs the chunk kernel's products on the state in
one bfloat16 pass in place of three (the chip only: off the TPU the
kernel does not run).  ``--probe`` runs no cell: the driver's
``carry_gap`` alone (the state op over gates near one), sound and under
each state plant, a few seconds a seed.  Prints one ``LIMITS`` JSON line
a seed with every number of every reading beside the file's limits and
the harness's ``correct`` for it, and exits 1 if a sound reading is not
``correct`` or a planted one, or the control, is.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import numpy as np  # noqa: E402

import run  # noqa: E402

CELL = "brumby-14b.serve.reason16"


def faults(config):
    """What the reference is handed in place of the cell's own."""
    chunk = config["driver_options"]["engine"]["prefill_chunk_tokens"]
    return {"power1": {"power": 1}, f"reset{chunk}": {"reset_every": chunk}}


def plant_state(kind):
    """Put the state plant ``kind`` in the program's place; returns what
    takes it out again.  The compiled programs are dropped both times:
    the kernels' jit caches do not see a function swapped under them."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import power_retention as pr
    from paddle_tpu.ops.pallas import retention_state as kernels
    real = pr.retention_step, kernels._dot3

    def rounded_step(*args, **kw):
        y, pool = real[0](*args, **kw)
        return y, jax.lax.reduce_precision(pool, exponent_bits=8,
                                           mantissa_bits=7)

    def dot1(x, y, dims):
        return jax.lax.dot_general(
            x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
            (dims, ((), ())), preferred_element_type=jnp.float32)

    def undo():
        pr.retention_step, kernels._dot3 = real
        jax.clear_caches()

    if kind == "bfloat16":
        pr.retention_step = rounded_step
    else:
        kernels._dot3 = dot1
    jax.clear_caches()
    return undo


STATE_PLANTS = ("bfloat16", "one_pass")


def probe(a, seed):
    """``carry_gap`` alone, sound and under each state plant that runs
    here: ({reading: {check: value}}, {reading: under the limit})."""
    import jax
    import jax.numpy as jnp
    from drivers import serve_brumby as drv
    from paddle_tpu.ops import power_retention as pr
    from reference import brumby_plain as plain
    cfg = plain.model_cfg(a.config)
    chunk = a.config["driver_options"]["engine"]["prefill_chunk_tokens"]
    slot = (pr.state_shape(cfg["num_key_value_heads"], cfg["head_dim"],
                           cfg["head_dim"]), jnp.float32)
    limit = a.config["check"]["limits"]["state_carry_gap"]
    out, correct = {}, {}
    plants = STATE_PLANTS if jax.default_backend() == "tpu" \
        else STATE_PLANTS[:1]
    for name in ("sound",) + tuple(f"state_{k}" for k in plants):
        undo = plant_state(name[6:]) if name != "sound" else (lambda: None)
        try:
            gap = drv.carry_gap(seed, cfg, slot, chunk)
        finally:
            undo()
        out[name] = {"state_carry_gap": gap}
        correct[name] = gap <= limit
    return out, correct


def readings(a, seed):
    """({reading: {check: value}}, {reading: ``correct``}) of one run of
    the cell.  ``correct`` is ``run.run_cell``'s own, for every reading:
    a planted reading is put to it as the result of a run of its own
    (what the one run served, the checks of the faulty reference), so
    the one comparison the harness has decides each."""
    from drivers import serve_brumby as drv
    from reference import brumby_plain as plain
    real_gaps, real_run = plain.served_gaps, drv.run
    kept, out, correct = {}, {}, {}

    def gaps_every_way(cfg, seed_, seqs, **switches):
        for name, fault in ({} if a.control or a.state
                            else a.faults).items():
            allg = np.concatenate(real_gaps(cfg, seed_, seqs, **fault))
            out[name] = {"served_logit_gap_max": float(allg.max()),
                         "served_logit_gap_mean": float(allg.mean())}
        return real_gaps(cfg, seed_, seqs, **switches)

    def run_kept(ctx):
        kept["result"] = real_run(ctx)
        kept["setup_s"] = ctx.setup_s
        return kept["result"]

    args = argparse.Namespace(workload=CELL, seed=seed, seconds=a.seconds,
                              trace=0, rehearse=a.rehearse)
    plain.served_gaps, drv.run = gaps_every_way, run_kept
    undo = plant_state(a.state) if a.state else (lambda: None)
    try:
        first = ("control" if a.control
                 else f"state_{a.state}" if a.state else "sound")
        line, checks = run.run_cell(
            args, {"engine": a.config["control"]["engine"]}
            if a.control else {})
        out[first] = {n: v for n, v, _ in checks}
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
    ap.add_argument("--state", choices=STATE_PLANTS)
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
                  if ok != (n == "sound")]
    if wrong:
        print(f"limits that do not separate: {wrong}", file=sys.stderr)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
