#!/usr/bin/env python3
"""One ragged paged-attention call on the chip at the serving cells'
shapes: one 128-token chunk row + k one-token rows (k = 3, 5, 7; the rest
of the 8-row bucket is the engine's pad rows, one token long) at contexts
400 / 2,000 / 4,000 / 7,000, full and window 512 — the device time of the
ragged kernel, and of the one-query kernel on the same rows.  The two
Phi-4-flash shapes are what the kernel is handed in that cell (ten
pair-heads of 128 lanes, group 4): 32 rows, the chunk row beside 31
one-token rows, at contexts 512 / 1,300 / 3,000.

    chiprun -- python3 tools/paged_ragged_micro.py --out chiprun_out/micro_change.json
    python3 tools/paged_ragged_micro.py --repo <a checkout> --out ...   # another tree's kernel
    python3 tools/paged_ragged_micro.py --table parent.json change.json

Device times are read from a profiler trace (the kernels' own events on the
device's op line, the mean of ``--iters`` calls); ``host_ms`` beside them is
the host's clock over the same calls queued back to back (the jitted call
with its two transposes).  Inputs are made from ``--seed``, so two trees'
runs see the same arrays: ``live_sha`` is the digest of the live queries'
output bytes, ``dead_nonzero`` counts dead query positions that are not
zero.  ``--rehearse`` runs tiny shapes through the interpreter on the CPU
and reports no time."""
import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

SHAPES = {
    # name: q heads, kv heads, window, pages in the pool, table pages
    "mistral": (32, 8, None, 4096, 256),
    "laguna-full": (48, 8, None, 8192, 512),
    "laguna-sliding": (64, 8, 512, 8192, 512),
    "phi4-flash-full": (40, 10, None, 6144, 256),
    "phi4-flash-sliding": (40, 10, 512, 6144, 256),
}
SPAN, PAGE, D = 128, 16, 128
# rows of the bucket, contexts, one-token rows beside the chunk row
EIGHT = (8, (400, 2000, 4000, 7000), (3, 5, 7))
THIRTY_TWO = (32, (512, 1300, 3000), (31,))
TRAFFIC = {"phi4-flash-full": THIRTY_TWO, "phi4-flash-sliding": THIRTY_TWO}
# an op's event is named by its whole HLO line: match the instruction's
# own name, not an operand that names it
RAGGED = r"^%?paged_attention_ragged[.\d]* = "
ONE_QUERY = r"^%?paged_attention[.\d]* = "


def case_inputs(np, jnp, rng, shape, ctx, k, small):
    heads, kvh, _window, pages, table = SHAPES[shape]
    n_rows = TRAFFIC.get(shape, EIGHT)[0]
    if small:
        pages, table = 1024, 32
    lens = np.ones(n_rows, np.int32)
    q_lens = np.ones(n_rows, np.int32)
    lens[0], q_lens[0] = ctx, min(SPAN, ctx)        # the chunk row
    lens[1:1 + k] = ctx                             # the one-token rows
    need = -(-lens // PAGE)
    tabs = np.zeros((n_rows, table), np.int32)
    perm = rng.permutation(pages)
    at = 0
    for i, n in enumerate(need):
        tabs[i, :n] = perm[at:at + n]
        at += n
    q = jnp.asarray(rng.standard_normal((n_rows, SPAN, heads, D)),
                    jnp.bfloat16)
    return q, jnp.asarray(lens), jnp.asarray(q_lens), jnp.asarray(tabs)


def device_ms(trace, pattern, groups, iters):
    """Mean duration of the events matching ``pattern`` on the device's op
    line, in order, ``iters`` a group; nothing where the trace does not
    hold ``groups x iters`` of them."""
    from benchmark import xplane
    ms = xplane.durations_ms(trace, pattern, xplane.OPS_LINE)
    if len(ms) != groups * iters:
        names = sorted({name[:80] for p in xplane.device_planes(trace)
                        for name, _s, _d in xplane.ops(p)})
        print(f"paged_ragged_micro: {len(ms)} events match {pattern!r}, "
              f"{groups} x {iters} were run; the line's ops: {names}",
              file=sys.stderr)
        return [None] * groups
    return [sum(ms[g * iters:(g + 1) * iters]) / iters
            for g in range(groups)]


def run(args):
    if args.repo:
        sys.path.insert(0, os.path.abspath(args.repo))
    else:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import paged_attention as pa

    if args.tile_rows:
        pa._QUERY_TILE_ROWS = args.tile_rows
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("paged_ragged_micro: needs a TPU (or --rehearse)")
    interpret = args.rehearse
    iters = 1 if args.rehearse else args.iters
    out = {"device": dev.device_kind, "tree": os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(pa.__file__)))),
        "iters": iters, "seed": args.seed, "cases": [],
        "tile_rows": getattr(pa, "_QUERY_TILE_ROWS", None)}
    for shape, (heads, kvh, window, pages, table) in SHAPES.items():
        if args.shapes and shape not in args.shapes:
            continue
        rng = np.random.default_rng(args.seed)
        _rows, contexts, ones = TRAFFIC.get(shape, EIGHT)
        if args.rehearse:
            pages, contexts, ones = 1024, (40, 300), ones[:1]
        pool = (kvh, pages, PAGE, D)
        kp = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
        ragged = jax.jit(lambda q, l, ql, t, kp, vp, w=window:
                         pa.paged_attention_ragged(
                             q, kp, vp, l, ql, t, interpret=interpret,
                             window=w))
        one = jax.jit(lambda q, l, t, kp, vp, w=window: pa.paged_attention(
            q, kp, vp, l, t, interpret=interpret, window=w))
        cases = [(c, k) for c in contexts for k in ones
                 if c <= table * PAGE or args.rehearse]
        made = [case_inputs(np, jnp, rng, shape, c, k, args.rehearse)
                for c, k in cases]
        for q, l, ql, t in made[:1]:                # compile both
            jax.block_until_ready((ragged(q, l, ql, t, kp, vp),
                                   one(q[:, 0], l, t, kp, vp)))
        trace_dir = tempfile.mkdtemp(prefix="paged_micro_")
        if not args.rehearse:
            jax.profiler.start_trace(trace_dir)
        rows = []
        for (c, k), (q, l, ql, t) in zip(cases, made):
            host = {}
            for name, fn, a in (
                    ("ragged", ragged, (q, l, ql, t, kp, vp)),
                    ("one_query", one, (q[:, 0], l, t, kp, vp))):
                t0 = time.perf_counter()
                for _ in range(iters):
                    y = fn(*a)
                jax.block_until_ready(y)
                host[name] = (time.perf_counter() - t0) / iters * 1e3
                if name == "ragged":
                    got = np.asarray(y.astype(jnp.float32))
                else:
                    one_sha = hashlib.sha256(np.asarray(
                        y.astype(jnp.float32)).tobytes()).hexdigest()[:16]
            live = np.arange(SPAN)[None, :] < np.asarray(ql)[:, None]
            rows.append({
                "shape": shape, "context": c, "one_token_rows": k,
                "ragged_host_ms": host["ragged"],
                "one_query_host_ms": host["one_query"],
                "live_sha": hashlib.sha256(
                    got[live].tobytes()).hexdigest()[:16],
                "one_query_sha": one_sha,
                "dead_nonzero": int(np.count_nonzero(got[~live])),
                "nan": bool(np.isnan(got).any())})
            if args.keep and c == contexts[0] and k == ones[0]:
                np.save(os.path.join(args.keep, f"{shape}.npy"), got[live])
        if not args.rehearse:
            jax.profiler.stop_trace()
            from benchmark import xplane
            trace = xplane.load(trace_dir)
            for row, r, o in zip(rows,
                                 device_ms(trace, RAGGED, len(rows), iters),
                                 device_ms(trace, ONE_QUERY, len(rows),
                                           iters)):
                row["ragged_ms"], row["one_query_ms"] = r, o
        out["cases"] += rows
        del kp, vp
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print("MICRO", text)


def table(parent_file, change_file):
    parent, change = (json.load(open(f)) for f in (parent_file, change_file))
    print("| shape | rows | context | one-token rows | ragged: parent ms | "
          "change ms | change / parent | one-query: parent ms | change ms | "
          "change / parent | outputs |")
    print("| --- | --- | --- | --- | --- | --- | --- | --- | --- | --- | "
          "--- |")
    for p, c in zip(parent["cases"], change["cases"]):
        assert (p["shape"], p["context"], p["one_token_rows"]) == \
            (c["shape"], c["context"], c["one_token_rows"])
        same = all(p.get(k) == c.get(k) for k in ("live_sha",
                                                   "one_query_sha"))
        cells = []
        for kind in ("ragged", "one_query"):
            key = f"{kind}_ms" if p.get(f"{kind}_ms") else f"{kind}_host_ms"
            cells += [f"{p[key]:.3f}", f"{c[key]:.3f}",
                      f"{c[key] / p[key]:.2f}"]
        rows = TRAFFIC.get(c["shape"], EIGHT)[0]
        print(f"| {c['shape']} | {rows} | {c['context']} | "
              f"{c['one_token_rows']} | " + " | ".join(cells)
              + f" | {'bit for bit' if same else 'DIFFER'} |")
    bad = [c for c in change["cases"] if c["dead_nonzero"] or c["nan"]]
    print(f"\nchange: dead query positions not zero in {len(bad)} of "
          f"{len(change['cases'])} cases")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", help="import paddle_tpu from this checkout")
    ap.add_argument("--out", help="write the JSON here too")
    ap.add_argument("--keep", help="directory for the first case's live "
                                   "outputs a shape (.npy)")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=40)
    ap.add_argument("--tile-rows", type=int,
                    help="try another height of the query tile (the "
                         "program has no such option: its rule is "
                         "query_tile_rows)")
    ap.add_argument("--shapes", nargs="+", choices=sorted(SHAPES),
                    help="these shapes only (all of them otherwise)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--table", nargs=2, metavar=("PARENT", "CHANGE"))
    args = ap.parse_args()
    if args.table:
        table(*args.table)
    else:
        run(args)


if __name__ == "__main__":
    main()
