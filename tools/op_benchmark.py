#!/usr/bin/env python
"""Op-level benchmark runner (SURVEY #82).

Capability parity with the reference's op-benchmark CI gate
(reference: tools/ci_op_benchmark.sh + tools/check_op_benchmark_result.py —
run per-op benchmarks on a change, compare against a baseline run, fail on
regression; no absolute numbers are stored in-repo).

Usage:
  python tools/op_benchmark.py run  --out baseline.json     # on main
  python tools/op_benchmark.py run  --out change.json       # on the change
  python tools/op_benchmark.py compare baseline.json change.json \
      --threshold 0.05                                      # gate
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench_cases():
    """The op set gated by CI: matmul/conv/attention/norm/reduce shapes that
    represent the framework's hot paths."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rng = np.random.RandomState(0)

    def t(*shape):
        return paddle.to_tensor(rng.randn(*shape).astype("float32"))

    x2 = t(1024, 1024)
    w2 = t(1024, 1024)
    img = t(8, 16, 32, 32)
    kern = t(32, 16, 3, 3)
    seq = t(2, 256, 4, 64)
    act = t(64, 4096)

    return {
        "matmul_1024": lambda: paddle.matmul(x2, w2),
        "conv2d_32ch": lambda: F.conv2d(img, kern, padding=1),
        "flash_attention_256": lambda: F.flash_attention(
            seq, seq, seq, causal=True)[0],
        "layer_norm_4096": lambda: F.layer_norm(act, [4096]),
        "softmax_4096": lambda: F.softmax(act, axis=-1),
        "reduce_sum": lambda: act.sum(),
        "gelu": lambda: F.gelu(act),
    }


def run(out_path: str, repeats: int = 50) -> dict:
    import jax
    results = {}
    for name, fn in _bench_cases().items():
        jax.block_until_ready(fn()._data)       # compile + warm
        # min-of-N: robust against dispatch-latency noise (host jitter)
        # — the reference gate compares medians for the same
        # reason (check_op_benchmark_result.py)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn()._data)
            best = min(best, time.perf_counter() - t0)
        results[name] = best
    payload = {"unit": "seconds", "repeats": repeats, "ops": results}
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    for name, sec in results.items():
        print(f"{name:>24}: {sec * 1e6:10.1f} us")
    return payload


def compare(baseline_path: str, change_path: str,
            threshold: float = 0.05) -> int:
    with open(baseline_path) as f:
        base = json.load(f)["ops"]
    with open(change_path) as f:
        change = json.load(f)["ops"]
    failed = []
    missing = []
    for name, base_t in base.items():
        new_t = change.get(name)
        if new_t is None:
            # a baseline op vanished from the change run — that's a gate
            # failure, not a free pass
            print(f"{name:>24}: MISSING from change run")
            missing.append(name)
            continue
        ratio = (new_t - base_t) / base_t
        flag = "REGRESSION" if ratio > threshold else "ok"
        print(f"{name:>24}: {base_t*1e6:9.1f} -> {new_t*1e6:9.1f} us "
              f"({ratio:+.1%}) {flag}")
        if ratio > threshold:
            failed.append(name)
    if failed or missing:
        if failed:
            print(f"FAILED: {len(failed)} op(s) regressed > {threshold:.0%}: "
                  f"{failed}")
        if missing:
            print(f"FAILED: {len(missing)} op(s) missing from change run: "
                  f"{missing}")
        return 1
    print("PASSED: no op regressed beyond threshold")
    return 0


def main():
    p = argparse.ArgumentParser(description=__doc__)
    sub = p.add_subparsers(dest="cmd", required=True)
    pr = sub.add_parser("run")
    pr.add_argument("--out", required=True)
    pr.add_argument("--repeats", type=int, default=20)
    pc = sub.add_parser("compare")
    pc.add_argument("baseline")
    pc.add_argument("change")
    pc.add_argument("--threshold", type=float, default=0.05)
    args = p.parse_args()
    if args.cmd == "run":
        run(args.out, args.repeats)
        return 0
    return compare(args.baseline, args.change, args.threshold)


if __name__ == "__main__":
    sys.exit(main())
