#!/usr/bin/env python3
"""One layer's retention state op on the chip at the cell's widths (8 KV
heads of 128, 5 query heads a group, 16 slots): the time of a step of 16
one-token rows and of 15 one-token rows + one 128-token chunk row, and
the Pallas one-token kernel against the gather-update-scatter in XLA on
the same inputs.

    chiprun -- python3 benchmark/tests/chip_retention_micro.py

Prints one ``MICRO`` JSON line.  Times are the host's clock over ``--iters``
calls queued back to back (the pool donated and carried), so they hold a
dispatch's few tens of microseconds each."""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(BENCH.parent), str(BENCH)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import flops_brumby  # noqa: E402
from paddle_tpu.ops import power_retention as pr  # noqa: E402

SLOTS, HK, HQ, D = 16, 8, 40, 128
CFG = dict(head_dim=D, num_key_value_heads=HK, num_attention_heads=HQ)


def inputs(rng, tokens):
    k = rng.normal(size=(tokens, HK, D))
    q = np.repeat(k, HQ // HK, axis=1) + 0.5 * rng.normal(size=(tokens, HQ, D))
    return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
            jnp.asarray(rng.normal(size=(tokens, HK, D)), jnp.bfloat16),
            jnp.log(jnp.asarray(rng.uniform(0.3, 0.999, (tokens, HK)),
                                jnp.float32)))


def timed(fn, pool, iters):
    y, pool = fn(pool)
    jax.block_until_ready((y, pool))
    t = time.perf_counter()
    for _ in range(iters):
        y, pool = fn(pool)
    jax.block_until_ready((y, pool))
    return (time.perf_counter() - t) / iters * 1e3, y, pool


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    a = ap.parse_args()
    rng = np.random.default_rng(0)
    shape = (SLOTS + 1,) + pr.state_shape(HK, D, D)
    pool = jnp.asarray(rng.normal(size=shape) * 0.1, jnp.float32)
    out = {"device": jax.devices()[0].device_kind}
    i32 = lambda x: jnp.asarray(x, jnp.int32)  # noqa: E731
    slots = i32(rng.permutation(SLOTS))
    # ---- 16 one-token rows
    q, k, v, lg = inputs(rng, 16)
    ctx = i32(np.full(16, 100))
    ones = i32(np.ones(16))
    none = jnp.zeros(0, jnp.int32)
    step = jax.jit(lambda p: pr.retention_step(
        p, slots, ctx, ones, None, none, q, k, v, lg, span=1),
        donate_argnums=0)
    ref = jax.jit(lambda p: pr._decode_xla(
        p, slots, jnp.ones(16, bool), jnp.zeros(16, bool), q, k, v, lg))
    y_ref, pool_ref = ref(pool)
    y, new = step(jnp.array(pool))
    out["decode_y_diff"] = float(jnp.abs(y - y_ref).max())
    out["decode_y_scale"] = float(jnp.abs(y_ref).max())
    out["decode_state_diff"] = float(
        jnp.abs(new[:SLOTS] - pool_ref[:SLOTS]).max())
    del y_ref, pool_ref, new
    ms, _, pool = timed(step, pool, a.iters)
    floor = flops_brumby.state_traffic_bytes(CFG, 16) / 819e9 * 1e3
    out.update(decode16_ms=ms, decode16_floor_ms=floor,
               decode16_roofline=100 * floor / ms)
    # ---- 15 one-token rows + one chunk row of 128, packed to 272
    tokens = 272
    q, k, v, lg = inputs(rng, tokens)
    q_lens = i32([128] + [1] * 15)
    off = i32(np.cumsum([0, 128] + [1] * 14))
    chunk_rows = i32([0, -1])
    step = jax.jit(lambda p: pr.retention_step(
        p, slots, ctx, q_lens, off, chunk_rows, q, k, v, lg, span=128),
        donate_argnums=0)
    ms, y, pool = timed(step, pool, a.iters)
    # the chunk row against the recurrence from the state it started from
    out["mixed_ms"] = ms
    start = jnp.array(pool[slots[0]])
    y, pool = step(pool)
    y_rec, s_rec = jax.jit(pr.retention_recurrent)(
        q[:128], k[:128], v[:128], lg[:128], start)
    out["chunk_y_diff"] = float(jnp.abs(y[:128] - y_rec).max())
    out["chunk_state_rel"] = float(jnp.abs(pool[slots[0]] - s_rec).max()
                                   / jnp.abs(s_rec).max())
    print("MICRO " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
