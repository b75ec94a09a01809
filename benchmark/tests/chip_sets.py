#!/usr/bin/env python3
"""Measure the spread the bounds are set from: ``--sets`` sets of runs
of one cell, the same seeds in every set, every run a new process of
the manifest's own command (this parent never touches jax, so the chip
is the child's).

    python3 benchmark/tests/chip_sets.py --workload <cell> \
        --seeds 1,2,3,4,5,6 --sets 2 --seconds 51

Prints every run's result line, then for each end-to-end metric each
set's median and quartile spread (``statistics.quantiles(n=4)``, as a
share of the median), the wider spread and five times it.
"""
import argparse
import json
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
import stats  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, default=0)
    a = ap.parse_args()
    manifest = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    seeds = [int(s) for s in a.seeds.split(",")]
    sets = []
    for k in range(a.sets):
        rows = []
        for seed in seeds:
            cmd = manifest["command"] + [
                "--workload", a.workload, "--seed", str(seed),
                "--seconds", str(a.seconds), "--trace", str(a.trace)]
            p = subprocess.run(cmd, cwd=BENCH.parent, capture_output=True,
                               text=True)
            lines = p.stdout.strip().splitlines()
            info = [ln for ln in lines if ln.startswith(
                ("[serve] window", "[serve] warm", "[serve] reference",
                 "[train] window", "[train] reference", "[check]", "[build]"))]
            print("\n".join(info), flush=True)
            if p.returncode != 0 or not lines:
                print(f"RUN set {k} seed {seed} FAILED rc {p.returncode}\n"
                      + p.stderr[-2000:], flush=True)
                continue
            line = json.loads(lines[-1])
            print(f"RUN set {k} seed {seed} " + lines[-1], flush=True)
            rows.append(line)
        sets.append(rows)
    names = sorted({n for rows in sets for r in rows for n in r["metrics"]})
    for n in names:
        spreads = []
        for k, rows in enumerate(sets):
            vals = [r["metrics"][n]["value"] for r in rows if n in r["metrics"]]
            if len(vals) < 2:
                continue
            sp = stats.quartile_spread(vals)
            spreads.append(sp)
            print(f"SPREAD {n} set {k}: median {stats.median(vals):.6g} "
                  f"spread {sp:.4%} values {[round(v, 4) for v in vals]}")
        if spreads:
            print(f"SPREAD {n}: widest {max(spreads):.4%} -> five times "
                  f"{5 * max(spreads):.4%}")
    bad = [r for rows in sets for r in rows if not r["correct"]]
    print(f"CORRECT {sum(len(r) for r in sets) - len(bad)} of "
          f"{sum(len(r) for r in sets)} runs")


if __name__ == "__main__":
    main()
