#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is looked up by name: the cell in
``BENCHMARK.json``, its configuration file, ``traffic/<traffic>.json``
(whose ``kind`` names a module of ``generators/``), the configuration's
``driver`` (a module of ``drivers/``), and for ``--trace 1`` one
``layer_metrics/<metric>.json`` per per-layer metric of the manifest
(whose ``reader`` names a module of ``readers/``).  This file has no
list of cells, metrics or models.

Without a TPU (or with fewer chips than the cell asks for, or a device
kind ``peaks.json`` does not know) it exits non-zero and prints no
result.  ``--rehearse`` is for the tests: it runs the configuration's
``rehearsal`` sizes on whatever device there is, for control flow only,
and is never part of the manifest's command.

The last line of stdout is the result object; earlier lines are
information.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import xplane  # noqa: E402


def say(msg):
    print(msg, flush=True)


class Ctx:
    """What a driver is handed: the cell's files, the seed, the window's
    length, and the clock-keeping both drivers share."""

    def __init__(self, args, config, traffic, overrides):
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.config, self.traffic = config, traffic
        self.overrides = overrides
        self.setup_s = None
        self.trace_dir = str(ROOT / ".cache" / "bench_trace")
        self._prof = "off"
        self._t0 = None
        self.trace_host_s = 0.0

    def generator(self, vocab_size):
        mod = importlib.import_module(f"generators.{self.traffic['kind']}")
        return mod.build(self.traffic, vocab_size, self.seed)

    def window_opens(self):
        """The first timed instant: everything before it is set-up."""
        self._t0 = time.perf_counter()
        self.setup_s = self._t0 - T_PROCESS
        return self._t0

    def profile_tick(self):
        """Start the device trace ``trace_s`` before the window's middle
        and stop it ``trace_s`` later; a no-op without ``--trace 1``."""
        if not self.trace or self._prof == "done":
            return
        import jax
        span = min(float(self.config["trace_s"]), self.seconds / 2)
        el = time.perf_counter() - self._t0
        if self._prof == "off" and el >= (self.seconds - span) / 2:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
            self._prof, self._prof_t = "on", time.perf_counter()
        elif self._prof == "on" and \
                time.perf_counter() - self._prof_t >= span:
            self.trace_host_s = time.perf_counter() - self._prof_t
            jax.profiler.stop_trace()
            self._prof = "done"

    def profile_close(self):
        if self._prof == "on":
            import jax
            self.trace_host_s = time.perf_counter() - self._prof_t
            jax.profiler.stop_trace()
            self._prof = "done"

    def sleep_through_window(self, t0):
        while True:
            left = t0 + self.seconds - time.perf_counter()
            if left <= 0:
                break
            self.profile_tick()
            time.sleep(min(0.05, left))
        self.profile_close()


def find(items, name, what):
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def load_cell(workload):
    """(manifest, its entry for the cell, the configuration file, the
    traffic file)."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = find(manifest["workloads"], workload, "workload")
    entry = find(manifest["configs"], cell["config"], "config")
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return manifest, cell, config, traffic


def check_device(chips, rehearse):
    import jax
    devs = jax.devices()
    d = devs[0]
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if not rehearse:
        if d.platform != "tpu" or len(devs) < chips:
            print(f"benchmark: the cell needs {chips} TPU chip(s); jax found "
                  f"{len(devs)} device(s) of platform {d.platform!r}",
                  file=sys.stderr)
            raise SystemExit(2)
        if d.device_kind not in peaks:
            print(f"benchmark: device kind {d.device_kind!r} is not in "
                  "benchmark/peaks.json", file=sys.stderr)
            raise SystemExit(2)
    return ({"platform": d.platform, "kind": d.device_kind,
             "count": len(devs)}, peaks.get(d.device_kind))


def layer_metrics(manifest, cell, result, ctx, trace, peak):
    """{name: {"value", "unit"}} for every per-layer metric of the
    manifest that lists this cell (or lists none) and whose reader found
    something to read."""
    out = {}
    src = dict(result["sources"], trace=trace, memory=result["memory"],
               end_to_end=result["end_to_end"], peak=peak,
               config=ctx.config, traffic=ctx.traffic)
    for m in manifest["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = json.loads((BENCH / "layer_metrics" / f"{m['name']}.json")
                          .read_text())
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(spec.get("args", {}), src)
        if value is None:
            say(f"[layers] {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--describe-trace", metavar="FILE",
                    help="with --trace 1: also write the trace's planes, "
                         "lines and op names to FILE (inside the checkout)")
    args = ap.parse_args(argv)
    run_cell(args, {})
    return 0


def run_cell(args, overrides):
    """One run of one cell; prints the result line and returns it with
    the numbers compared.  ``overrides`` is for the control and the
    broken-path tests under ``tests/``: the manifest's command never
    passes any."""
    manifest, cell, config, traffic = load_cell(args.workload)
    if args.rehearse:
        small = dict(config["rehearsal"])
        traffic = dict(traffic, **small.pop("traffic", {}))
        config = dict(config, **small)
    device, peak = check_device(cell["chips"], args.rehearse)
    import jax
    if args.rehearse:        # control flow only: nothing worth keeping
        jax.config.update("jax_enable_compilation_cache", False)
        cache = "off"
    else:
        from paddle_tpu.framework.compile_cache import (
            configure_compile_cache)
        cache = configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(f"[run] cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace}; device {device}; jax {jax.__version__}; "
        f"compile cache {cache}")
    ctx = Ctx(args, config, traffic, overrides)
    driver = importlib.import_module(f"drivers.{config['driver']}")
    result = driver.run(ctx)

    e2e = dict(result["end_to_end"], setup_s=ctx.setup_s)
    correct = True
    for name, value, limit in result["checks"]:
        ok = limit is None or (value == value and value <= limit)
        correct = correct and ok
        say(f"[check] {name} = {value!r} (limit {limit}) "
            f"{'ok' if ok else 'NOT CORRECT'}")
    if result["attempted"] == 0 or result["failed"]:
        correct = False
    dev = dict(device, memory_peak_bytes=result["memory"]["peak_bytes_in_use"])
    wanted = {m["name"]: m for m in manifest["end_to_end"]
              if "workloads" not in m or cell["name"] in m["workloads"]}
    for name in wanted:
        say(f"[end_to_end] {name} = {e2e.get(name)!r} {wanted[name]['unit']}")
    line = {"correct": bool(correct), "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": dev}
    if args.trace:
        trace = xplane.load(ctx.trace_dir)
        busy, window = xplane.busy_and_window(trace)
        dev["busy_s"], dev["window_s"] = busy, window
        say(f"[trace] device busy {busy:.4f}s of {window:.4f}s traced "
            f"(host held the trace open {ctx.trace_host_s:.3f}s)")
        if getattr(args, "describe_trace", None):   # for whoever writes a regex against it
            out = Path(args.describe_trace)
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(xplane.describe(trace))
        line["metrics"] = layer_metrics(manifest, cell, result, ctx, trace,
                                        peak)
        line["breakdown"] = {"device_ops": xplane.top_ops(trace),
                             "idle_gaps": xplane.longest_gaps(trace)}
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    else:
        line["metrics"] = {n: {"value": e2e[n], "unit": m["unit"]}
                           for n, m in wanted.items()}
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return line, result["checks"]


if __name__ == "__main__":
    sys.exit(main())
