#!/usr/bin/env python3
"""The three flash-attention kernels on the chip at the training cells'
shapes: correct first, then timed, then swept over block sizes.

    chiprun -- python3 tools/flash_attn_micro.py --out chiprun_out/micro/change.json
    python3 tools/flash_attn_micro.py --repo <a checkout> --out ...   # another tree's kernels
    python3 tools/flash_attn_micro.py --sweep --out ...               # block_q x block_kv
    python3 tools/flash_attn_micro.py --table parent.json change.json

``check``: forward, dq, dk, dv of the tree's kernels (its own default
blocks) against ``mha_reference`` and its autodiff in float32 at precision
highest, a kv-head group at a time, on inputs made from ``--seed`` — so two
trees' runs see the same arrays and their errors can be held side by side:
the widest absolute gap and the rms gap over the reference's rms.  Shapes:
both training cells' (Mistral 32 x 4,096 x 128 over 8 kv heads; Kimi-Linear's
MLA 32 x 8,192 x 192 with 128-wide values), a GQA shape, ``sq < sk`` causal
(the bottom-right-aligned diagonal) and a length that is no block multiple.

``time``: device time of each kernel's own events on the device's op line of
a profiler trace, the mean of ``--iters`` calls (ms), at the two cells'
shapes; beside it what XLA runs around the kernels in the same trace, and
the grid steps a head (``flash_attn_tiles_visited_total``) where the tree
counts them.  ``--sweep`` times every (block_q, block_kv) of the sweep's
grid the same way.  ``--rehearse`` runs tiny shapes through the interpreter
on the CPU and reports no time."""
import argparse
import itertools
import json
import os
import sys
import tempfile

# name: (batch, q heads, kv heads, sq, sk, q/k width, v width, causal)
CELLS = {
    "mistral-4k": (1, 32, 8, 4096, 4096, 128, 128, True),
    "kimi-mla-8k": (1, 32, 32, 8192, 8192, 192, 128, True),
}
ODD = {
    "gqa-8-2": (2, 8, 2, 1024, 1024, 128, 128, True),
    "decode-offset": (1, 4, 4, 512, 2048, 128, 128, True),
    "unaligned-1000": (1, 4, 4, 1000, 1000, 128, 128, True),
    "rectangle": (1, 4, 4, 1024, 1536, 128, 128, False),
}
TINY = {
    "mistral-4k": (1, 4, 2, 256, 256, 128, 128, True),
    "kimi-mla-8k": (1, 2, 2, 384, 384, 192, 128, True),
    "decode-offset": (1, 2, 2, 128, 384, 128, 128, True),
    "unaligned-1000": (1, 2, 2, 200, 200, 128, 128, True),
}
SWEEP_Q, SWEEP_KV = (256, 512, 1024), (256, 512, 1024, 2048)
KERNELS = {
    # an op's event is named by its whole HLO line: match the instruction's
    # own name, not an operand that names it
    "fwd": r"^%?flash_attention_fwd[.\d]* = ",
    "bwd_dkv": r"^%?flash_attention_bwd_dkv[.\d]* = ",
    "bwd_dq": r"^%?flash_attention_bwd_dq[.\d]* = ",
}
COUNTER = "flash_attn_tiles_visited_total"


def inputs(jnp, np, seed, shape):
    b, h, kvh, sq, sk, d, dv, _causal = shape
    rng = np.random.default_rng(seed)

    def draw(*dims):
        return jnp.asarray(rng.standard_normal(dims), jnp.bfloat16)
    return (draw(b, h, sq, d), draw(b, kvh, sk, d), draw(b, kvh, sk, dv),
            draw(b, h, sq, dv))


def reference(jax, jnp, fa, q, k, v, do, causal, scale):
    """out, dq, dk, dv of ``mha_reference`` in float32 at precision highest,
    one kv head's query group at a time (the scores of 32 heads at 8,192
    would be 8.6 GB)."""
    kvh, group = k.shape[1], q.shape[1] // k.shape[1]

    @jax.jit
    def one(q, k, v, do):
        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                lambda *a: fa.mha_reference(*a, causal=causal, scale=scale),
                q, k, v)
            return (out,) + vjp(do)

    f32 = [a.astype(jnp.float32) for a in (q, k, v, do)]
    parts = [one(f32[0][:, i * group:(i + 1) * group], f32[1][:, i:i + 1],
                 f32[2][:, i:i + 1], f32[3][:, i * group:(i + 1) * group])
             for i in range(kvh)]
    return [jnp.concatenate(x, axis=1) for x in zip(*parts)]


def gaps(np, got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    diff = got - want
    return {"max_abs": float(np.abs(diff).max()),
            "rel_rms": float(np.sqrt((diff ** 2).mean())
                             / np.sqrt((want ** 2).mean())),
            "nan": bool(np.isnan(got).any())}


def device_ms(trace, groups, iters):
    """{kernel: [mean ms a group]}; None where the trace does not hold
    ``groups x iters`` events of a kernel."""
    from benchmark import xplane
    out = {}
    for kernel, pattern in KERNELS.items():
        ms = xplane.durations_ms(trace, pattern, xplane.OPS_LINE)
        if len(ms) != groups * iters:
            if ms or kernel == "fwd":
                print(f"flash_attn_micro: {len(ms)} events match "
                      f"{pattern!r}, {groups} x {iters} were run",
                      file=sys.stderr)
            out[kernel] = [None] * groups
        else:
            out[kernel] = [sum(ms[g * iters:(g + 1) * iters]) / iters
                           for g in range(groups)]
    return out


def run(args):
    root = os.path.abspath(args.repo) if args.repo else os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.ops.pallas import flash_attention as fa

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("flash_attn_micro: needs a TPU (or --rehearse)")
    interpret = args.rehearse
    iters = 1 if args.rehearse else args.iters
    shapes = TINY if args.rehearse else {**CELLS, **ODD}
    out = {"device": dev.device_kind, "tree": root, "iters": iters,
           "seed": args.seed, "check": [], "time": [], "sweep": []}

    def calls(shape, block_q=None, block_kv=None):
        """The jitted forward and backward of one shape; a tree's own
        blocks where none are named."""
        causal, scale = shape[7], shape[5] ** -0.5
        named = {} if block_q is None else {"block_q": block_q,
                                            "block_kv": block_kv}
        fwd = jax.jit(lambda q, k, v: fa.flash_attention_forward(
            q, k, v, causal, scale, interpret=interpret, **named))
        bwd = jax.jit(lambda q, k, v, o, lse, do: fa.flash_attention_backward(
            q, k, v, o, lse, do, causal, scale, interpret=interpret,
            **named))
        return fwd, bwd

    def visited():
        snap = monitor.snapshot()
        return (sum(s["value"] for s in snap[COUNTER]["series"])
                if COUNTER in snap else None)

    # ------------------------------------------------ correct, on the chip
    if not args.sweep:
        for name, shape in shapes.items():
            q, k, v, do = inputs(jnp, np, args.seed, shape)
            fwd, bwd = calls(shape)
            before = visited()
            o, lse = fwd(q, k, v)
            got = (o,) + tuple(bwd(q, k, v, o, lse, do))
            tiles = visited()
            if tiles is not None:
                tiles = (tiles - (before or 0)) / (shape[0] * shape[1])
            want = reference(jax, jnp, fa, q, k, v, do, shape[7],
                             shape[5] ** -0.5)
            row = {"shape": name, "dims": shape,
                   "tiles_a_head": tiles}
            for part, g, w in zip(("out", "dq", "dk", "dv"), got, want):
                row[part] = gaps(np, g, w)
            out["check"].append(row)
            print("CHECK", json.dumps(row), flush=True)
            del q, k, v, do, o, lse, got, want

    # ----------------------------------------------- timed, from a trace
    timed = [n for n in shapes if n in CELLS]
    for name in timed:
        shape = shapes[name]
        q, k, v, do = inputs(jnp, np, args.seed, shape)
        grid = (list(itertools.product(SWEEP_Q, SWEEP_KV)) if args.sweep
                else [(None, None)])
        if args.rehearse:
            grid = grid[:2]
        ready = []
        for bq, bkv in grid:          # compile outside the trace
            fwd, bwd = calls(shape, bq, bkv)
            try:
                o, lse = jax.block_until_ready(fwd(q, k, v))
                jax.block_until_ready(bwd(q, k, v, o, lse, do))
                ready.append((bq, bkv, fwd, bwd))
            except Exception as e:  # noqa: BLE001 — a block the chip refuses
                out["sweep"].append({"shape": name, "block_q": bq,
                                     "block_kv": bkv,
                                     "refused": str(e)[:200]})
        trace_dir = tempfile.mkdtemp(prefix="flash_micro_")
        if not args.rehearse:
            jax.profiler.start_trace(trace_dir)
        for bq, bkv, fwd, bwd in ready:
            for _ in range(iters):
                o, lse = fwd(q, k, v)
            for _ in range(iters):
                g = bwd(q, k, v, o, lse, do)
            jax.block_until_ready((o, g))
        ms = {kernel: [None] * len(ready) for kernel in KERNELS}
        around = {}
        if not args.rehearse:
            jax.profiler.stop_trace()
            from benchmark import xplane
            trace = xplane.load(trace_dir)
            ms = device_ms(trace, len(ready), iters)
            # what XLA runs around the kernels (the GQA repeat and sum, delta,
            # the lane-broadcast row vectors), ms a forward + backward
            around = {op: sec * 1e3 / iters / len(ready)
                      for op, sec in xplane.top_ops(trace, 14)
                      if "flash_attention" not in op}
        for i, (bq, bkv, _f, _b) in enumerate(ready):
            row = {"shape": name, "block_q": bq, "block_kv": bkv,
                   **{f"{kernel}_ms": ms[kernel][i] for kernel in KERNELS}}
            if not args.sweep:
                row["around_ms"] = around
            out["sweep" if args.sweep else "time"].append(row)
            print("TIME", json.dumps(row), flush=True)
        del q, k, v, do
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print("MICRO", text)


def _ms(x):
    return "—" if x is None else f"{x:.3f}"


def table(parent_file, change_file):
    """Prints the comparison; False where a part of the change's reads worse
    against the reference than the parent's."""
    parent, change = (json.load(open(f)) for f in (parent_file, change_file))
    sound = True
    print("| shape | part | parent: max abs / rel rms | change: max abs / "
          "rel rms | change no worse |")
    print("| --- | --- | --- | --- | --- |")
    for p, c in zip(parent["check"], change["check"]):
        assert p["shape"] == c["shape"]
        for part in ("out", "dq", "dk", "dv"):
            a, b = p[part], c[part]
            # no worse: the rms gap within a tenth of the parent's, the one
            # widest element within twice (summation order moves with the
            # blocks)
            ok = (not b["nan"] and b["rel_rms"] <= 1.1 * a["rel_rms"]
                  and b["max_abs"] <= 2.0 * a["max_abs"])
            sound = sound and ok
            print(f"| {c['shape']} | {part} | {a['max_abs']:.4g} / "
                  f"{a['rel_rms']:.3e} | {b['max_abs']:.4g} / "
                  f"{b['rel_rms']:.3e} | {'yes' if ok else 'NO'} |")
    print("\n| shape | kernel | parent ms | change ms | change / parent |")
    print("| --- | --- | --- | --- | --- |")
    for p, c in zip(parent["time"], change["time"]):
        for kernel in KERNELS:
            a, b = p[f"{kernel}_ms"], c[f"{kernel}_ms"]
            ratio = "—" if None in (a, b) else f"{b / a:.2f}"
            print(f"| {c['shape']} | {kernel} | {_ms(a)} | {_ms(b)} | "
                  f"{ratio} |")
    for c in change["time"]:
        if c.get("around_ms"):
            print(f"\n{c['shape']}: XLA's operations around the kernels, ms a "
                  "forward + backward, change: " + ", ".join(
                      f"{op} {ms:.3f}" for op, ms in c["around_ms"].items()))
    for c in change["check"]:
        if c["shape"] in CELLS:
            print(f"\n{c['shape']}: tiles a head (forward + dkv + dq), "
                  f"change: {c['tiles_a_head']}")
    sweep_table(change)
    return sound


def sweep_table(data):
    rows = [r for r in data["sweep"] if "refused" not in r]
    for shape in dict.fromkeys(r["shape"] for r in rows):
        print(f"\n{shape}: block_q x block_kv, ms a call (fwd / bwd_dkv / "
              "bwd_dq)")
        print("| block_q | " + " | ".join(str(kv) for kv in SWEEP_KV) + " |")
        print("| --- |" + " --- |" * len(SWEEP_KV))
        for bq in SWEEP_Q:
            cells = []
            for bkv in SWEEP_KV:
                r = [x for x in rows if (x["shape"], x["block_q"],
                                         x["block_kv"]) == (shape, bq, bkv)]
                cells.append(" / ".join(_ms(r[0][f"{k}_ms"])
                                        for k in KERNELS) if r else "refused")
            print(f"| {bq} | " + " | ".join(cells) + " |")
        for kernel in KERNELS:
            best = min((r for r in rows if r["shape"] == shape
                        and r[f"{kernel}_ms"] is not None),
                       key=lambda r: r[f"{kernel}_ms"], default=None)
            if best:
                print(f"{kernel}: best {best['block_q']} x "
                      f"{best['block_kv']} at {_ms(best[f'{kernel}_ms'])}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", help="import paddle_tpu from this checkout")
    ap.add_argument("--out", help="write the JSON here too")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=47)
    ap.add_argument("--sweep", action="store_true",
                    help="time block_q x block_kv over the sweep's grid "
                         "(no check)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--table", nargs="+", metavar="JSON",
                    help="PARENT CHANGE: the comparison; one file: its "
                         "sweep")
    args = ap.parse_args()
    if args.table:
        if len(args.table) == 2:
            sys.exit(0 if table(*args.table) else 1)
        else:
            sweep_table(json.load(open(args.table[0])))
    else:
        run(args)


if __name__ == "__main__":
    main()
