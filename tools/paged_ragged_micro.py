#!/usr/bin/env python3
"""One ragged paged-attention call on the chip at the serving cells'
shapes, AS THE STEP MAKES IT: the queries of the step's packed tokens
(chunk rows of 128 tokens, then one-token rows, then pad) in, the
attention output of the same positions out — the ragged kernel's device
time, the whole call's (with what surrounds the kernel: the re-layout of
the step's tokens; on a tree from before ISSUE 50 the gather to the
(rows, span) rectangle, its two transposes and the way back), and the
one-query kernel's on the same rows.

Mistral and Laguna: 8 rows, one chunk row + k one-token rows (k = 3, 5, 7;
the rest of the bucket is the engine's pad rows, one token long) at
contexts 400 / 2,000 / 4,000 / 7,000, full and window 512.  Phi-4-flash:
what the kernel is handed in that cell (ten pair-heads of 128 lanes, group
4), 32 rows, a chunk row beside 31 one-token rows.  ZAYA: 64 rows over a
two-head pool.  MiMo-V2-Flash: 32 rows with TWO chunk rows, 64 query heads
over 4 KV heads (full) or 8 (window 128, a sink a query head), K heads of
192 two to a pool row of 384 beside V pages of 128.

    chiprun -- python3 tools/paged_ragged_micro.py --out chiprun_out/micro_change.json
    python3 tools/paged_ragged_micro.py --repo <a checkout> --out ...   # another tree's kernel
    python3 tools/paged_ragged_micro.py --table parent.json change.json

Device times are read from a profiler trace (the kernels' own events on the
device's op line and the jitted calls' on its module line, the mean of
``--iters`` calls); ``host_ms`` beside them is the host's clock over the
same calls queued back to back.  Inputs are made from ``--seed``, so two
trees' runs see the same arrays: ``live_sha`` is the digest of the live
queries' output bytes, ``oracle_max_gap`` and ``oracle_rms_gap`` the widest
and the root-mean-square distance of those outputs from the XLA oracle's
(``_ragged_xla`` on the chunk rows, ``_decode_xla`` on the one-token rows,
each row's table cut to the pages it maps), ``block_tokens`` the block the
ragged call's walk was cut in (two trees whose blocks differ sum a row's
softmax in another order and cannot share a digest: ``--table`` holds them
to the oracle instead), ``dead_nonzero`` counts positions no row owns that
are not zero.  ``--rehearse`` runs small shapes through the interpreter on
the CPU and reports no time."""
import argparse
import hashlib
import inspect
import json
import os
import sys
import tempfile
import time

PAGE = 16
SHAPES = {
    # name: q heads, kv heads, window, pages in the pool, table pages,
    # K and V head widths, a sink a query head
    "mistral": (32, 8, None, 4096, 256, 128, 128, False),
    "laguna-full": (48, 8, None, 8192, 512, 128, 128, False),
    "laguna-sliding": (64, 8, 512, 8192, 512, 128, 128, False),
    "phi4-flash-full": (40, 10, None, 6144, 256, 128, 128, False),
    "phi4-flash-sliding": (40, 10, 512, 6144, 256, 128, 128, False),
    "zaya": (8, 2, None, 12288, 512, 128, 128, False),
    "mimo-full": (64, 4, None, 8192, 512, 192, 128, False),
    "mimo-sliding": (64, 8, 128, 8192, 512, 192, 128, True),
}
# rows of the bucket, contexts, one-token rows beside the chunk rows,
# chunk rows
EIGHT = (8, (400, 2000, 4000, 7000), (3, 5, 7), 1)
THIRTY_TWO = (32, (512, 1300, 3000), (31,), 1)
TRAFFIC = {"phi4-flash-full": THIRTY_TWO, "phi4-flash-sliding": THIRTY_TWO,
           "zaya": (64, (1700,), (63,), 1),
           "mimo-full": (32, (1000, 2900), (30,), 2),
           "mimo-sliding": (32, (1000, 2900), (30,), 2)}
# an op's event is named by its whole HLO line: match the instruction's
# own name, not an operand that names it
RAGGED = r"^%?paged_attention_ragged[.\d]* = "
ONE_QUERY = r"^%?paged_attention[.\d]* = "
CALLS = {"ragged": r"^jit_ragged_call", "one_query": r"^jit_one_query_call"}


def case_inputs(np, jnp, rng, shape, ctx, k, span, pages, table):
    """(packed queries, lens, q_lens, row_off, tables) of one step: the
    chunk rows' spans, the one-token rows' and the pad rows' tokens one
    behind the other on an axis as long as the engine packs a step of this
    bucket to (``JittedPagedDecoder.packed_tokens``), pad behind them."""
    heads, _kvh, _w, _p, _t, dk, _dv, _s = SHAPES[shape]
    n_rows, _c, _o, chunks = TRAFFIC.get(shape, EIGHT)
    lens = np.ones(n_rows, np.int32)
    q_lens = np.ones(n_rows, np.int32)
    lens[:chunks], q_lens[:chunks] = ctx, min(span, ctx)    # the chunk rows
    lens[chunks:chunks + k] = ctx                   # the one-token rows
    need = -(-lens // PAGE)
    tabs = np.zeros((n_rows, table), np.int32)
    perm = rng.permutation(pages)
    at = 0
    for i, n in enumerate(need):
        tabs[i, :n] = perm[at:at + n]
        at += n
    tokens = -(-(2 * span - 1 + n_rows - 1) // 16) * 16
    q = jnp.asarray(rng.standard_normal((tokens, heads, dk)), jnp.bfloat16)
    off = np.cumsum(q_lens) - q_lens
    return (q, jnp.asarray(lens), jnp.asarray(q_lens),
            jnp.asarray(off, jnp.int32), jnp.asarray(tabs))


def block_tokens(pa, jnp, shape, span):
    """Tokens a block of the ragged call's walk holds on this tree: the
    kernel's own rule (``walk_cut``; before ISSUE 51 the bucket's rows
    cut the block)."""
    heads, kvh, window, _p, _t, dk, dv, sinks = SHAPES[shape]
    bf16 = jnp.bfloat16
    if hasattr(pa, "walk_cut"):
        return PAGE * pa.walk_cut(kvh, PAGE, dk, span, heads // kvh, bf16,
                                  bf16, dv, sinks, window=window)[1]
    return PAGE * pa.walk_block_pages(PAGE, dk, span * (heads // kvh), bf16,
                                      dv)


def oracle_outputs(pa, jax, jnp, np, window, span):
    """``oracle(q, lens, q_lens, off, tabs, kp, vp, sink)``: the XLA
    oracle's float32 outputs on the packed axis (zeros where no row has a
    query), a row at a time with its table cut to the pages it maps — the
    whole (rows x span x table) rectangle of the oracle does not fit the
    chip at these shapes."""
    statics = ("scale", "window")
    chunk = jax.jit(pa._ragged_xla, static_argnames=statics)
    token = jax.jit(pa._decode_xla, static_argnames=statics)

    def oracle(q, lens, q_lens, off, tabs, kp, vp, sink):
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
        kw = dict(window=window, sinks=sink)
        out = np.zeros(q.shape[:2] + (vp.shape[-1],), np.float32)
        for r, (n, ql, at) in enumerate(zip(*(np.asarray(x) for x in
                                              (lens, q_lens, off)))):
            tab = tabs[r:r + 1, :-(-int(n) // PAGE)]
            one = jnp.asarray([n], jnp.int32)
            if ql == 1:
                y = token(q[at:at + 1], kp, vp, one, tab, scale, **kw)
            else:
                rect = q[jnp.minimum(at + jnp.arange(span), q.shape[0] - 1)]
                y = chunk(rect[None], kp, vp, one,
                          jnp.asarray([ql], jnp.int32), tab, scale,
                          **kw)[0, :ql]
            out[at:at + ql] = np.asarray(y.astype(jnp.float32))
        return out

    return oracle


def device_ms(trace, pattern, groups, iters, line=None):
    """Mean duration of the events matching ``pattern`` on the device's op
    line (or ``line``), in order, ``iters`` a group; nothing where the
    trace does not hold ``groups x iters`` of them."""
    from benchmark import xplane
    line = line or xplane.OPS_LINE
    ms = xplane.durations_ms(trace, pattern, line)
    if len(ms) != groups * iters:
        names = sorted({name[:80] for p in xplane.device_planes(trace)
                        for name, _s, _d in xplane.ops(p, line)})
        print(f"paged_ragged_micro: {len(ms)} events match {pattern!r}, "
              f"{groups} x {iters} were run; the line's events: {names}",
              file=sys.stderr)
        return [None] * groups
    return [sum(ms[g * iters:(g + 1) * iters]) / iters
            for g in range(groups)]


def step_calls(pa, window, span, interpret):
    """(ragged, one_query): the step's paged call on the packed queries
    and the decode step's on the rows' first tokens, whatever the tree: one
    whose kernel reads the packed axis is handed it; an older one gets the
    rectangle, gathered and packed back as its step did."""
    from paddle_tpu.inference.paged import _packed_of_rows, _rows_of_packed
    reads_packed = "row_off" in inspect.signature(
        pa.paged_attention_ragged).parameters

    def ragged_call(q, l, ql, off, t, kp, vp, sinks):
        kw = dict(interpret=interpret, window=window, sinks=sinks)
        q = pa.packed_queries(q, kp, vp)
        if reads_packed:
            return pa.paged_attention_ragged(q, kp, vp, l, ql, t,
                                             row_off=off, span=span, **kw)
        out = pa.paged_attention_ragged(_rows_of_packed(q, off, span), kp,
                                        vp, l, ql, t, **kw)
        return _packed_of_rows(out, off, q.shape[0])

    def one_query_call(q, l, off, t, kp, vp, sinks):
        return pa.paged_attention(q[off], kp, vp, l, t, interpret=interpret,
                                  window=window, sinks=sinks)

    return ragged_call, one_query_call


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
    span = 32 if args.rehearse else 128
    out = {"device": dev.device_kind, "tree": os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.dirname(pa.__file__)))),
        "iters": iters, "seed": args.seed, "span": span, "cases": [],
        "tile_rows": getattr(pa, "_QUERY_TILE_ROWS", None)}
    for shape, (heads, kvh, window, pages, table, dk, dv,
                sinks) in SHAPES.items():
        if args.shapes and shape not in args.shapes:
            continue
        rng = np.random.default_rng(args.seed)
        _rows, contexts, ones, _chunks = TRAFFIC.get(shape, EIGHT)
        if args.rehearse:
            pages, table, contexts, ones = 2048, 32, (300,), ones[:1]
        pack = pa.k_pack(dk) if hasattr(pa, "k_pack") else 1
        kp = jnp.asarray(rng.standard_normal(
            (kvh // pack, pages, PAGE, pack * dk)), jnp.bfloat16)
        vp = jnp.asarray(rng.standard_normal((kvh, pages, PAGE, dv)),
                         jnp.bfloat16)
        sink = jnp.asarray(rng.standard_normal(heads), jnp.float32) \
            if sinks else None
        ragged, one = (jax.jit(f) for f in step_calls(pa, window, span,
                                                      interpret))
        oracle = oracle_outputs(pa, jax, jnp, np, window, span)
        block = block_tokens(pa, jnp, shape, span)
        cases = [(c, k) for c in contexts for k in ones
                 if c <= table * PAGE]
        made = [case_inputs(np, jnp, rng, shape, c, k, span, pages, table)
                for c, k in cases]
        for q, l, ql, off, t in made[:1]:           # compile both
            jax.block_until_ready((ragged(q, l, ql, off, t, kp, vp, sink),
                                   one(q, l, off, t, kp, vp, sink)))
        trace_dir = tempfile.mkdtemp(prefix="paged_micro_")
        # the oracle's calls stay out of the trace's window
        wants = [oracle(q, l, ql, off, t, kp, vp, sink)
                 for q, l, ql, off, t in made]
        rows = []
        if not args.rehearse:
            jax.profiler.start_trace(trace_dir)
        for (c, k), (q, l, ql, off, t), want in zip(cases, made, wants):
            host = {}
            for name, fn, a in (
                    ("ragged", ragged, (q, l, ql, off, t, kp, vp, sink)),
                    ("one_query", one, (q, l, off, t, kp, vp, sink))):
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
            live = np.zeros(got.shape[0], bool)
            for at, n in zip(np.asarray(off), np.asarray(ql)):
                live[at:at + n] = True
            gap = got[live] - want[live]
            rows.append({
                "shape": shape, "context": c, "one_token_rows": k,
                "tokens": int(live.sum()), "tokens_padded": got.shape[0],
                "block_tokens": block,
                "oracle_max_gap": float(np.abs(gap).max()),
                "oracle_rms_gap": float(np.sqrt(np.mean(gap * gap))),
                "ragged_host_ms": host["ragged"],
                "one_query_host_ms": host["one_query"],
                "live_sha": hashlib.sha256(
                    got[live].tobytes()).hexdigest()[:16],
                "one_query_sha": one_sha,
                "live_zero": int((~got[live].any(axis=(1, 2))).sum()),
                "dead_nonzero": int(np.count_nonzero(got[~live])),
                "nan": bool(np.isnan(got).any())})
            if args.keep and c == contexts[0] and k == ones[0]:
                np.save(os.path.join(args.keep, f"{shape}.npy"), got[live])
        if not args.rehearse:
            jax.profiler.stop_trace()
            from benchmark import xplane
            trace = xplane.load(trace_dir)
            n = len(rows)
            for key, ms in (
                    ("ragged_ms", device_ms(trace, RAGGED, n, iters)),
                    ("one_query_ms", device_ms(trace, ONE_QUERY, n, iters)),
                    ("ragged_call_ms", device_ms(
                        trace, CALLS["ragged"], n, iters,
                        xplane.MODULES_LINE)),
                    ("one_query_call_ms", device_ms(
                        trace, CALLS["one_query"], n, iters,
                        xplane.MODULES_LINE))):
                for row, x in zip(rows, ms):
                    row[key] = x
        out["cases"] += rows
        del kp, vp
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print("MICRO", text)
    return out


def table(parent_file, change_file):
    """The comparison, and whether the two trees agree: a case both trees
    walk in the same blocks bit for bit; one whose block differs as close
    to the XLA oracle as the parent's kernel holds it (the widest gap of
    the shape's cases, and each case's rms: a tenth of room, the sums'
    order is another); no position that no row owns off zero."""
    parent, change = (json.load(open(f)) for f in (parent_file, change_file))
    print("| shape | rows | context | one-token rows | block: parent | "
          "change | kernel: parent ms | "
          "change ms | change / parent | whole call: parent ms | change ms "
          "| change / parent | one-query: parent ms | change ms | outputs "
          "| gap to the oracle, widest / rms: parent | change |")
    print("|" + " --- |" * 17)
    sound = True
    widest = {}
    for p in parent["cases"]:
        if "oracle_max_gap" in p:
            widest[p["shape"]] = max(widest.get(p["shape"], 0.0),
                                     p["oracle_max_gap"])
    for p, c in zip(parent["cases"], change["cases"]):
        assert (p["shape"], p["context"], p["one_token_rows"]) == \
            (c["shape"], c["context"], c["one_token_rows"])
        same = all(p.get(k) == c.get(k) for k in ("live_sha",
                                                   "one_query_sha"))
        blocks = [x.get("block_tokens") for x in (p, c)]
        if blocks[0] == blocks[1]:
            said = "bit for bit" if same else "DIFFER"
            sound &= same
        else:
            held = (p["one_query_sha"] == c["one_query_sha"]
                    and c["oracle_max_gap"] <= 1.1 * widest[c["shape"]]
                    and c["oracle_rms_gap"] <= 1.1 * p["oracle_rms_gap"])
            said = "another block: " + ("the oracle's as the parent's"
                                        if held else "FURTHER FROM THE ORACLE")
            sound &= held
        cells = [str(x) for x in blocks]
        for kind in ("ragged", "ragged_call"):
            key = f"{kind}_ms" if p.get(f"{kind}_ms") else "ragged_host_ms"
            cells += [f"{p[key]:.3f}", f"{c[key]:.3f}",
                      f"{c[key] / p[key]:.2f}"]
        key = "one_query_ms" if p.get("one_query_ms") else \
            "one_query_host_ms"
        cells += [f"{p[key]:.3f}", f"{c[key]:.3f}", said]
        cells += [f"{x['oracle_max_gap']:.4g} / {x['oracle_rms_gap']:.4g}"
                  if "oracle_max_gap" in x else "-" for x in (p, c)]
        rows = TRAFFIC.get(c["shape"], EIGHT)[0]
        print(f"| {c['shape']} | {rows} | {c['context']} | "
              f"{c['one_token_rows']} | " + " | ".join(cells) + " |")
    bad = [c for c in change["cases"]
           if c["dead_nonzero"] or c["nan"] or c.get("live_zero")]
    print(f"\nchange: positions no row owns off zero, a live query all "
          f"zeros or a NaN in {len(bad)} of {len(change['cases'])} cases")
    return sound and not bad


def main(argv=None):
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
    args = ap.parse_args(argv)
    if args.table:
        return 0 if table(*args.table) else 1
    run(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
