#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, the numbers the limits
of ``correct`` are set from: what sound runs of the program give over
many seeds, and what the control gives.

    python3 benchmark/tests/chip_limits.py --workload <cell> --seeds 1,2,3 \
        --seconds 12 [--control]

One process, one seed after another (set-up is long; the weights are
re-made per seed).  Serving control: the engine with the
configuration's ``control`` options (``quantize="w8a8"`` with
``kv_quant="int8"``) in the program's place.  Training control: the
plain reference computed with int8 matmul operands, compared with the
float32 reference exactly as the program is.  Prints one JSON line per
seed with every number compared.
"""
import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402


def train_control(config, traffic, seed):
    import importlib
    import jax
    from drivers import common
    from reference import llama_plain as plain
    cfg = common.model_cfg(config)
    gen = importlib.import_module(f"generators.{traffic['kind']}").build(
        traffic, cfg["vocab_size"], seed)
    batches = [gen.next_batch()
               for _ in range(config["driver_options"]["warm_steps"])]
    hyper = config["driver_options"]["optimizer"]
    with jax.default_matmul_precision("highest"):
        ref = plain.train_reference(cfg, seed, batches, hyper)
        low = plain.train_reference(cfg, seed, batches, hyper, "int8")
    out = {f"loss_gap_step{i + 1}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(low["losses"], ref["losses"]))}
    out["first_grad_norm_gap"], out["grad_leaf"] = plain.worst_leaf_gap(
        low["grad_norm"], ref["grad_norm"])
    out["first_grad_gains_diff"], out["gains_leaf"] = plain.worst_gain_diff(
        low["grad_gains"], ref["grad_gains"])
    out["param_change_norm_gap"], out["change_leaf"] = plain.worst_leaf_gap(
        low["delta_norm"], ref["delta_norm"])
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12)
    ap.add_argument("--control", action="store_true")
    a = ap.parse_args()
    _, cell, config, traffic = run.load_cell(a.workload)
    for seed in [int(s) for s in a.seeds.split(",")]:
        if a.control and config["driver"] == "train_step":
            run.check_device(cell["chips"], False)
            from paddle_tpu.framework.compile_cache import (
                configure_compile_cache)
            configure_compile_cache()
            out = train_control(config, traffic, seed)
        else:
            args = argparse.Namespace(workload=a.workload, seed=seed,
                                      seconds=a.seconds, trace=0,
                                      rehearse=False)
            over = config.get("control", {}) if a.control else {}
            line, checks = run.run_cell(args, over)
            out = {n: v for n, v, _ in checks}
            out["correct"] = line["correct"]
        print("LIMITS " + json.dumps({"seed": seed, "control": a.control,
                                      **out}), flush=True)


if __name__ == "__main__":
    main()
