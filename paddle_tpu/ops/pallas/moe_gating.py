"""Fused MoE top-k gating Pallas kernels.

Capability parity: the gating half of the reference's fused MoE stack
(paddle/phi/kernels/fusion/gpu/fused_moe_kernel.cu top-k gating +
python/paddle/incubate/distributed/models/moe/gate/) — SURVEY §7 lists
"MoE dispatch, top-k gating" among the Pallas kernel targets.

Produces the ragged-routing metadata (expert id, capacity slot, keep
mask, raw combine weight per assignment) that moe_ragged_dispatch
consumes — softmax, argmax and capacity positions fused VMEM-resident
instead of ~6 XLA ops per round.

Slot-assignment order is ROUND-MAJOR over all tokens (every token's
round-0 choice takes a slot before any round-1 choice), exactly the
oracle's (gate._topk_routing) semantics — which matters because the
order decides WHICH assignments a full expert drops.  One pallas_call
per round (k is 1-3 in practice): the token-tile axis is sequential so
a VMEM scratch carries per-expert fill counts across tiles, and the
counts chain between rounds through a tiny (1, E) array; each round
re-derives its `remaining` mask from the gates by replaying the earlier
argmax rounds locally (cheaper than carrying a [T, E] mask).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _ceil_to


def _argmax_rows(x):
    """Row-wise argmax as max + first-match index (reduce/compare/min
    only — Mosaic has no argmax primitive on every supported jax)."""
    E = x.shape[1]
    m = jnp.max(x, axis=1, keepdims=True)
    # tpu.iota yields integers only: build it as int32, convert after.
    # The reduce stays float (Mosaic only lowers float reductions); E is
    # far below f32's exact-integer range
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1).astype(jnp.float32)
    return jnp.min(jnp.where(x == m, col, float(E)),
                   axis=1).astype(jnp.int32)


def _round_kernel(logits_ref, fill_in_ref, eidx_ref, pos_ref, keep_ref,
                  w_ref, fill_out_ref, gsum_ref, fill_scr, gsum_scr, *,
                  round_k, capacity, n_tokens, block_t):
    t_idx = pl.program_id(0)
    n_tiles = pl.num_programs(0)

    @pl.when(t_idx == 0)
    def _init():
        fill_scr[:] = fill_in_ref[:]
        gsum_scr[:] = jnp.zeros_like(gsum_scr)

    logits = logits_ref[:].astype(jnp.float32)       # (block_t, E)
    E = logits.shape[1]
    rows = t_idx * block_t + lax.broadcasted_iota(
        jnp.int32, (block_t, 1), 0)
    valid = rows < n_tokens                          # (block_t, 1)
    z = logits - jnp.max(logits, axis=1, keepdims=True)
    ez = jnp.exp(z)
    gates = ez / jnp.sum(ez, axis=1, keepdims=True)

    # replay rounds 0..round_k-1 to mask their choices (deterministic)
    remaining = gates
    for _ in range(round_k):
        prev = _argmax_rows(remaining)
        oh = (lax.broadcasted_iota(jnp.int32, (block_t, E), 1)
              == prev[:, None]).astype(jnp.float32)
        remaining = remaining * (1.0 - oh)

    idx = _argmax_rows(remaining)                    # (block_t,)
    # counts ride in f32 end to end (Mosaic lowers only float
    # reductions); exact up to 2^24 assignments, far beyond any tile
    onehot = (lax.broadcasted_iota(jnp.int32, (block_t, E), 1)
              == idx[:, None]).astype(jnp.float32)
    onehot = onehot * valid.astype(jnp.float32)      # pad rows place none
    fill = fill_scr[0]                               # (E,) carried
    # within-tile exclusive prefix count as a strictly-lower-triangular
    # matmul (Mosaic has no cumsum primitive; this rides the MXU)
    r_i = lax.broadcasted_iota(jnp.int32, (block_t, block_t), 0)
    c_i = lax.broadcasted_iota(jnp.int32, (block_t, block_t), 1)
    strict_tril = (c_i < r_i).astype(jnp.float32)
    prefix = lax.dot_general(
        strict_tril, onehot,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    pos = jnp.sum((prefix + fill[None, :].astype(jnp.float32)) * onehot,
                  axis=1).astype(jnp.int32)
    within = (pos < capacity) & valid[:, 0]
    gate_val = jnp.sum(gates * onehot, axis=1)
    eidx_ref[0] = idx.astype(jnp.int32)
    pos_ref[0] = pos.astype(jnp.int32)
    keep_ref[0] = within.astype(jnp.int32)
    w_ref[0] = gate_val * within.astype(jnp.float32)
    fill_scr[0] = fill + jnp.sum(onehot, axis=0).astype(jnp.int32)
    if round_k == 0:
        # per-expert sum of gate probabilities over valid tokens — the
        # l_aux ingredient; only round 0's is consumed, so later rounds
        # skip the accumulation entirely (round_k is trace-static)
        gsum_scr[0] = gsum_scr[0] + jnp.sum(
            gates * valid.astype(jnp.float32), axis=0)

    @pl.when(t_idx == n_tiles - 1)
    def _flush():
        fill_out_ref[:] = fill_scr[:]
        gsum_ref[:] = gsum_scr[:]


def topk_gating_pallas(logits, top_k, capacity, normalize,
                       block_t=256, interpret=False):
    """(eidx, pos, keep, w, l_aux): the _topk_routing contract, fused.

    logits: [T, E] float.  No GShard random-keep (the oracle handles
    that branch); callers fall back when random_keep is not None.
    """
    T, E = logits.shape
    block_t = min(block_t, _ceil_to(T, 128))
    T_p = _ceil_to(T, block_t)
    if T_p != T:
        logits = jnp.pad(logits, ((0, T_p - T), (0, 0)),
                         constant_values=-1e30)
    grid = (T_p // block_t,)
    row_spec = pl.BlockSpec((1, block_t), lambda t: (0, t))
    fill_spec = pl.BlockSpec((1, E), lambda t: (0, 0))

    fill = jnp.zeros((1, E), jnp.int32)
    fill0 = None
    gsum = None
    eidx_l, pos_l, keep_l, w_l = [], [], [], []
    for k in range(top_k):
        kernel = functools.partial(
            _round_kernel, round_k=k, capacity=capacity, n_tokens=T,
            block_t=block_t)
        e_k, p_k, kp_k, w_k, fill, gsum_k = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec((block_t, E), lambda t: (t, 0)),
                      fill_spec],
            out_specs=[row_spec, row_spec, row_spec, row_spec, fill_spec,
                       fill_spec],
            out_shape=[
                jax.ShapeDtypeStruct((1, T_p), jnp.int32),
                jax.ShapeDtypeStruct((1, T_p), jnp.int32),
                jax.ShapeDtypeStruct((1, T_p), jnp.int32),
                jax.ShapeDtypeStruct((1, T_p), jnp.float32),
                jax.ShapeDtypeStruct((1, E), jnp.int32),
                jax.ShapeDtypeStruct((1, E), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((1, E), jnp.int32),
                            pltpu.VMEM((1, E), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(logits, fill)
        if k == 0:
            fill0, gsum = fill, gsum_k
        eidx_l.append(e_k[0, :T])
        pos_l.append(p_k[0, :T])
        keep_l.append(kp_k[0, :T])
        w_l.append(w_k[0, :T])

    eidx = jnp.stack(eidx_l)
    pos = jnp.stack(pos_l)
    keep = jnp.stack(keep_l).astype(bool)
    w = jnp.stack(w_l)
    if normalize:
        w = w / jnp.maximum(jnp.sum(w, axis=0, keepdims=True), 1e-9)
    w = w.astype(logits.dtype)
    # l_aux (GShard balance loss over the top-1 assignment) from the
    # kernel's own byproducts — round-0 fill IS the per-expert top-1
    # count, gsum the per-expert gate-probability mass; no [T, E]
    # softmax or one-hot replay in the epilogue
    me = gsum[0] / T
    ce = fill0[0].astype(jnp.float32) / T
    l_aux = jnp.sum(me * ce) * E
    return eidx, pos, keep, w, l_aux
