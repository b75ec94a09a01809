#!/usr/bin/env python3
"""The control of the Kimi-Linear cell, on the chip and at the cell's
own size: the plain reference with every dense matmul's operands rounded
to int8 (the nearest precision below the configuration's bfloat16),
compared with the float32 reference exactly as the program is.  What
sound runs give is on the ``[check]`` lines of any run of the cell.

    python3 benchmark/tests/chip_limits_kimi_linear.py --seeds 1,2,3
    python3 benchmark/tests/chip_limits_kimi_linear.py --seeds 4 --plant beta1,sign

Prints one ``LIMITS`` JSON line a seed with every number compared.
``--plant`` runs the cell itself (a 2 s window) with a fault planted in
the PROGRAM's AdamW and none in the reference, through ``run.py``'s own
comparison, and exits 1 unless every planted run is not ``correct``."""
import argparse
import contextlib
import importlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import run  # noqa: E402

CELL = "kimi-linear.train.seq8k"


def train_control(config, traffic, seed):
    import jax
    from reference import kimi_linear_plain as plain
    cfg = plain.model_cfg(config)
    gen = importlib.import_module(f"generators.{traffic['kind']}").build(
        traffic, cfg["vocab_size"], seed)
    batches = [gen.next_batch()
               for _ in range(config["driver_options"]["warm_steps"])]
    hyper = config["driver_options"]["optimizer"]
    with jax.default_matmul_precision("highest"):
        t = time.perf_counter()
        ref = plain.train_reference(cfg, seed, batches, hyper)
        t_ref = time.perf_counter() - t
        low = plain.train_reference(cfg, seed, batches, hyper, "int8")
    out = {f"loss_gap_step{i + 1}": abs(a - b) / abs(b)
           for i, (a, b) in enumerate(zip(low["losses"], ref["losses"]))}
    out["first_grad_norm_gap"], out["grad_leaf"] = plain.worst_leaf_gap(
        low["grad_norm"], ref["grad_norm"])
    out["first_grad_gains_diff"], out["gains_leaf"] = plain.worst_gain_diff(
        low["grad_gains"], ref["grad_gains"])
    out["param_change_norm_gap"], out["change_leaf"] = plain.worst_leaf_gap(
        low["delta_norm"], ref["delta_norm"])
    out["reference_s"] = t_ref
    return out


# what the program's AdamW is handed in place of the cell's own
FAULTS = {
    # a wrong first-moment decay: the first update is the same whatever
    # beta1 is (the bias correction cancels it), the second is not
    "beta1": lambda h: dict(h, beta1=0.5),
    # the update added and not subtracted: every norm stays what it was
    "sign": lambda h: dict(h, lr=-h["lr"]),
}


@contextlib.contextmanager
def planted(fault):
    """The cell's driver builds its step with ``FAULTS[fault]`` of the
    optimizer's settings; the reference keeps the file's."""
    from drivers import train_kimi_linear as drv
    real = drv.build_step
    drv.build_step = lambda model, cfg, hyper: real(
        model, cfg, FAULTS[fault](hyper))
    try:
        yield
    finally:
        drv.build_step = real


def run_planted(fault, seed, rehearse, seconds=2.0):
    """(result line, checks) of the cell with ``fault`` planted."""
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0, rehearse=rehearse)
    with planted(fault):
        return run.run_cell(args, {})


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--plant", default="",
                    help="comma-separated names of FAULTS, in place of the "
                    "control")
    a = ap.parse_args()
    if a.plant:
        missed = []
        for seed in [int(s) for s in a.seeds.split(",")]:
            for fault in a.plant.split(","):
                line, checks = run_planted(fault, seed, a.rehearse)
                print("PLANTED " + json.dumps({
                    "seed": seed, "fault": fault, "correct": line["correct"],
                    "over": [n for n, v, lim in checks if not v <= lim],
                    "read": {n: v for n, v, _ in checks}}),
                    flush=True)
                if line["correct"]:
                    missed.append((seed, fault))
        sys.exit(1 if missed else 0)
    _, cell, config, traffic = run.load_cell(CELL)
    if a.rehearse:
        small = dict(config["rehearsal"])
        traffic = dict(traffic, **small.pop("traffic", {}))
        config = dict(config, **small)
    run.check_device(cell["chips"], a.rehearse)
    if not a.rehearse:
        from paddle_tpu.framework.compile_cache import configure_compile_cache
        configure_compile_cache()
    for seed in [int(s) for s in a.seeds.split(",")]:
        out = train_control(config, traffic, seed)
        print("LIMITS " + json.dumps({"seed": seed, "control": True, **out}),
              flush=True)


if __name__ == "__main__":
    main()
