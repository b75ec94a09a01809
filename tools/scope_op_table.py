#!/usr/bin/env python3
"""A traced run of a training cell with the device time of EVERY operation
by (``jax.named_scope`` path, operation): what the result line's
``breakdown.device_ops`` (ten lines, no scopes) cannot say.  PR 46 read the
KDA mixer's parts from it (``train/model/kda/{proj, conv, gates, chunk,
norm, out}``).

    chiprun --timeout 1500 -- python3 tools/scope_op_table.py \\
        --out chiprun_out/ops/change.json --workload kimi-linear.train.seq8k --seed 7
    python3 tools/scope_op_table.py --tree <another checkout> --out ... --workload ...
    python3 tools/scope_op_table.py --table parent.json change.json [--under train/model/kda]

The run IS ``benchmark/run.py --trace 1 --seconds 51`` of the tree named
(its own benchmark, its own program; everything after ``--out`` goes to
it): only the function that reads the per-layer metrics is wrapped, to
write the table before the trace is deleted, and the driver's scope paths
are kept whole (the benchmark cuts them to four components).  A cell whose
driver hands no ``hlo_scopes`` gives every operation the scope ''.  Times
are own times (a ``while`` less its body) in ms a step, a step a
``jit_pure_step`` on the modules line."""
import argparse
import json
import os
import re
import sys

_NAME = re.compile(r"^%?([\w.\-]+)")


# under ``recompute`` a mixer's backward is named ``train/model/kda/train/
# model/kda/checkpoint/<part>`` and its recomputed forward ``.../checkpoint/
# rematted_computation/<part>``: one part, three passes
_REMAT = re.compile(r"^(train/model/\w+)/\1/checkpoint/"
                    r"(?:rematted_computation/)?")


def full_scope(op_name: str) -> str:
    """An instruction's ``op_name`` without the transforms' wrappers, from
    ``train/`` on ('' outside the step's scopes); a recomputed mixer's
    three passes under the forward's path."""
    path = re.sub(r"\w+\(", "", op_name).replace(")", "")
    at = path.find("train/")
    return _REMAT.sub(r"\1/", path[at:]) if at >= 0 else ""


def scope_table(events, modules, scopes, own_times, short_name, depth=4):
    """{"steps": n, "rows": [[scope, operation, ms a step, calls a step]]},
    longest first.  ``events``: a device plane's (name, start, duration)
    operations; ``scopes``: {instruction: scope path}, cut here to
    ``depth`` components.  A step is a ``jit_pure_step`` on the modules
    line; the trace cuts its first and last, so steps = their time over
    their median."""
    whole = sorted(d for n, _s, d in modules if n.startswith("jit_pure_step("))
    steps = sum(whole) / whole[len(whole) // 2] if whole else 0.0
    rows = {}
    for name, own in own_times(events):
        m = _NAME.match(name)
        scope = scopes.get(m.group(1), "") if m else ""
        key = ("/".join(scope.split("/")[:depth]), short_name(name))
        r = rows.setdefault(key, [0, 0])
        r[0] += own
        r[1] += 1
    per = steps or 1.0
    table = sorted(([s, n, ns / 1e6 / per, c / per]
                    for (s, n), (ns, c) in rows.items()), key=lambda r: -r[2])
    return {"steps": steps, "rows": table}


def by_scope(table, under=""):
    """{scope: ms a step} of the rows whose scope starts with ``under``."""
    out = {}
    for scope, _name, ms, _calls in table["rows"]:
        if scope.startswith(under):
            out[scope] = out.get(scope, 0.0) + ms
    return out


def print_tables(paths, under, top):
    tables = [json.load(open(p)) for p in paths]
    sums = [by_scope(t, under) for t in tables]
    print(f"ms a step by scope under {under!r} ({', '.join(paths)}; steps "
          f"traced {[t['steps'] for t in tables]}):")
    for scope in sorted(set().union(*sums), key=lambda s: -sums[0].get(s, 0)):
        print("  " + " ".join(f"{s.get(scope, 0.0):9.3f}" for s in sums)
              + f"  {scope or '(no scope)'}")
    print("  " + " ".join(f"{sum(s.values()):9.3f}" for s in sums) + "  total")
    for path, t in zip(paths, tables):
        print(f"{path}: the {top} longest operations under {under!r}")
        rows = [r for r in t["rows"] if r[0].startswith(under)]
        for scope, name, ms, calls in rows[:top]:
            print(f"  {ms:9.3f} ms {calls:6.1f} x  {scope:28s} {name}")


def run_traced(tree, out, rest):
    tree = os.path.abspath(tree)
    out = os.path.abspath(out)
    for p in (tree, os.path.join(tree, "benchmark")):
        sys.path.insert(0, p)
    os.chdir(tree)
    import run
    import xplane
    from drivers import train_kimi_linear
    from readers.xplane_scope_share import own_times

    train_kimi_linear.scope_of = full_scope
    read_metrics = run.layer_metrics

    def layer_metrics(manifest, cell, result, ctx, trace, peak):
        scopes = result["sources"].get("hlo_scopes") or {}
        for plane in xplane.device_planes(trace):
            if xplane.ops(plane):
                table = scope_table(
                    xplane.ops(plane),
                    plane["lines"].get(xplane.MODULES_LINE, []), scopes,
                    own_times, xplane.short_name)
                break
        else:
            table = {"steps": 0, "rows": []}
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(table, f)
        print(f"[table] {table['steps']:.2f} steps, {len(table['rows'])} rows "
              f"-> {out}", flush=True)
        return read_metrics(manifest, cell, result, ctx, trace, peak)

    run.layer_metrics = layer_metrics
    return run.main(["--trace", "1", "--seconds", "51"] + rest)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to run (this one)")
    ap.add_argument("--out", help="where the table goes (JSON)")
    ap.add_argument("--table", nargs="+", metavar="JSON",
                    help="print tables written earlier, side by side")
    ap.add_argument("--under", default="train/model/kda")
    ap.add_argument("--top", type=int, default=30)
    args, rest = ap.parse_known_args(argv)
    if args.table:
        print_tables(args.table, args.under, args.top)
        return 0
    if not args.out:
        ap.error("--out or --table")
    return run_traced(args.tree, args.out, rest)


if __name__ == "__main__":
    sys.exit(main())
