"""Flash attention for TPU: Pallas forward kernel + memory-efficient backward.

Capability parity with the reference's flash-attention stack
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu wrapping flashattn
v2/v3 via paddle/phi/backends/dynload/flashattn.cc; Python API
python/paddle/nn/functional/flash_attention.py:364).

TPU-native design (see /opt/skills/guides/pallas_guide.md):
  - one tile schedule for the three kernels (``causal_tile_schedule``): the
    (q block, kv block) pairs that hold an unmasked score, flattened into
    ONE grid axis and handed over as scalar-prefetch operands, so a tile
    above the diagonal is neither a grid step nor a copy; the index maps
    read the pair and FIRST / LAST flags reset and write a run's
    accumulators.  A causal or padded call masks every tile it visits
    (sparing the tiles under the diagonal was timed: 2 % of dk/dv alone,
    PERF.md section 6, PR 47); any other call builds no mask at all.
  - forward: online-softmax tiles, q-major, m/l/acc scratch carried along
    a q block's run; MXU matmuls via dot_general with
    preferred_element_type=f32.
  - backward: the flash-attention-2 formulation from the saved logsumexp,
    two kernels (dk/dv kv-major, dq q-major).
  - off-TPU (CPU tests) the same math runs as a plain XLA reference, the
    backward under lax.scan (``_bwd_blockwise``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...monitor.registry import counter

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# --------------------------------------------------------------- reference
def mha_reference(q, k, v, causal=False, scale=None, bias=None):
    """Plain XLA attention (correctness baseline + CPU fallback).

    Layout: q/k/v = (batch, heads, seq, head_dim); supports GQA
    (k/v heads dividing q heads).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    kv_heads = k.shape[1]
    q_heads = q.shape[1]
    if kv_heads != q_heads:
        rep = q_heads // kv_heads
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ------------------------------------------------------- the tile schedule
FIRST, LAST = 1, 2     # flags of a scheduled tile


@functools.lru_cache(maxsize=None)
def causal_tile_schedule(sq, sk, block_q, block_kv, causal, order):
    """The (q block, kv block) tiles a call visits, in grid order.

    A tile is visited when it holds a score no mask removes: a real query
    row (< sq) that sees a real key (< sk) under the bottom-right-aligned
    diagonal (offset sk - sq); ``causal=False`` yields the whole rectangle.
    ``order`` "q" is q-major with kv blocks ascending (forward and dq: a q
    block's accumulators live through its run), "kv" is kv-major with q
    blocks ascending (dk/dv).  Returns int32 vectors ``(q_idx, kv_idx,
    flags)``: FIRST / LAST mark the ends of a major block's run.  A major
    block that sees nothing (the first rows of a causal call with sq > sk)
    keeps one tile, so that its output is written.  What a query row with
    no key reads is undefined: finite, a mean of that tile's values where
    ``mha_reference`` reads the mean of all of them, and no gradient."""
    n_q, n_kv = -(-sq // block_q), -(-sk // block_kv)
    off = sk - sq
    runs = []
    if order == "q":
        for qi in range(n_q):
            last_row = min((qi + 1) * block_q, sq) - 1
            hi = (last_row + off) // block_kv if causal else n_kv - 1
            runs.append([(qi, ki) for ki in range(max(hi, 0) + 1)])
    else:
        for ki in range(n_kv):
            lo = max(ki * block_kv - off, 0) // block_q if causal else 0
            runs.append([(qi, ki) for qi in range(min(lo, n_q - 1), n_q)])
    q_idx, kv_idx, flags = [], [], []
    for run in runs:
        for i, (qi, ki) in enumerate(run):
            q_idx.append(qi)
            kv_idx.append(ki)
            flags.append((FIRST if i == 0 else 0)
                         | (LAST if i == len(run) - 1 else 0))
    return tuple(np.asarray(x, np.int32) for x in (q_idx, kv_idx, flags))


def _keep(q_idx, kv_idx, *, block_q, block_kv, causal, causal_offset,
          q_seq_len, kv_seq_len):
    """What a tile keeps: under the diagonal, and inside the lengths a
    padded call hands over (None where the axis is not padded)."""
    rows = q_idx * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    cols = kv_idx * block_kv + lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    keep = [rows + causal_offset >= cols] if causal else []
    if q_seq_len is not None:
        keep.append(rows < q_seq_len)
    if kv_seq_len is not None:
        keep.append(cols < kv_seq_len)
    return functools.reduce(jnp.logical_and, keep)


def _scheduled(sq, sk, block_q, block_kv, causal, order, heads,
               q_seq_len=None, kv_seq_len=None):
    """A call's schedule as its scalar-prefetch operands, and the
    ``keep(q_idx, kv_idx)`` of its tiles (None for a call with nothing to
    mask: not causal, no padded axis); its grid steps counted into
    ``flash_attn_tiles_visited_total`` (``heads`` = batch x heads)."""
    sched = causal_tile_schedule(sq, sk, block_q, block_kv, causal, order)
    counter("flash_attn_tiles_visited_total",
            "flash attention, grid steps over the sequence axes (each "
            "issues its matrix products), counted when a call is traced"
            ).inc(heads * len(sched[0]))
    keep = None
    if causal or q_seq_len is not None or kv_seq_len is not None:
        keep = functools.partial(
            _keep, block_q=block_q, block_kv=block_kv, causal=causal,
            causal_offset=sk - sq, q_seq_len=q_seq_len,
            kv_seq_len=kv_seq_len)
    return tuple(jnp.asarray(x) for x in sched), keep


def _block_maps(group=1):
    """Index maps of a q-sized and a kv-sized block of (b, h, s, d) arrays
    from the schedule's pair at grid step ``t``; ``group`` q heads share a
    kv head."""
    def q_block(b, h, t, q_idx, kv_idx, flags):
        return b, h, q_idx[t], 0

    def kv_block(b, h, t, q_idx, kv_idx, flags):
        return b, h // group, kv_idx[t], 0
    return q_block, kv_block


# Block sizes from a sweep on a v5e's own clock (tools/flash_attn_micro.py
# --sweep; PERF.md section 6, PR 47: {256, 512, 1024} x {256, 512, 1024,
# 2048}, causal, at 32 x 4,096 x 128 over 8 kv heads and at 32 x 8,192 x 192
# with 128-wide values).  The three kernels agreed at both shapes: forward
# 1.39 / 6.28 ms a call against 2.65 / 11.37 at 512 x 512, dk/dv 2.09 /
# 10.49 against 2.25 / 11.31, dq 1.44 / 8.34 against 1.61 / 9.03 (swept
# with no mask on the tiles under the diagonal; as shipped dk/dv reads
# 2.14 / 10.67, the others the same).
_SWEPT_BLOCK = 1024
_SWEPT_WIDTHS = {(128, 128), (192, 128)}      # (q/k width, v width)


def _blocks(block_q, block_kv, d, dv, sq, sk):
    """A call's (block_q, block_kv): what the caller names, else the
    sweep's winner where the widths were swept and both lengths are whole
    blocks of it, else 512 x 512; never past a length's 128-multiple."""
    swept = 512
    if ((d, dv) in _SWEPT_WIDTHS and sq % _SWEPT_BLOCK == 0
            and sk % _SWEPT_BLOCK == 0):
        swept = _SWEPT_BLOCK
    block_q = swept if block_q is None else block_q
    block_kv = swept if block_kv is None else block_kv
    return min(block_q, _ceil_to(sq, 128)), min(block_kv, _ceil_to(sk, 128))


def _ceil_to(x, m):
    return (x + m - 1) // m * m


_COMPILER_PARAMS = pltpu.CompilerParams(
    # batch and heads are independent; the flattened tile axis carries the
    # accumulators of a run and goes in order
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    # a 1,024 x 1,024 tile's float32 scores and their kin (four of them in
    # a backward kernel) pass the 16 MB a kernel is given unasked
    vmem_limit_bytes=64 * 1024 * 1024)


# ------------------------------------------------------------------ kernel
def _fwd_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, keep):
    t = pl.program_id(2)
    flags = fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                       # (block_q, d)
    k = k_ref[0, 0]                       # (block_kv, d)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if keep is not None:
        s = jnp.where(keep(qi_ref[t], ki_ref[t]), s, DEFAULT_MASK_VALUE)

    m_prev = m_scr[:, :1]                 # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    l_next = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[:] = acc_scr[:] * alpha + lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0, 0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:] = jnp.broadcast_to(m_next, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when((flags & LAST) != 0)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(l_safe)).astype(jnp.float32)


def flash_attention_forward(q, k, v, causal=False, scale=None,
                            block_q=None, block_kv=None, interpret=False):
    """Pallas forward. Layout (b, h, s, d). Returns (out, lse)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, sq, d = q.shape
    kv_h, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]          # the values' own width (latent attention)
    block_q, block_kv = _blocks(block_q, block_kv, d, dv, sq, sk)
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_kv)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    sched, keep = _scheduled(sq, sk, block_q, block_kv, causal, "q", b * h,
                             kv_seq_len=sk if sk_p != sk else None)
    q_block, kv_block = _block_maps(group=h // kv_h)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, keep=keep),
        name="flash_attention_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,            # the schedule
            grid=(b, h, len(sched[0])),
            in_specs=[pl.BlockSpec((1, 1, block_q, d), q_block),
                      pl.BlockSpec((1, 1, block_kv, d), kv_block),
                      pl.BlockSpec((1, 1, block_kv, dv), kv_block)],
            out_specs=[pl.BlockSpec((1, 1, block_q, dv), q_block),
                       pl.BlockSpec((1, 1, block_q, 128), q_block)],
            scratch_shapes=[pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, 128), jnp.float32),
                            pltpu.VMEM((block_q, dv), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 128), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*sched, q, k, v)
    return out[:, :, :sq, :], lse[:, :, :sq, 0]


# ------------------------------------------------- backward (Pallas, TPU)
def _bwd_dkv_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, keep):
    """FA2 backward, dk/dv: the schedule is kv-major, so dk/dv accumulate
    in VMEM scratch along a kv block's run of q tiles (reference:
    flash_attn_grad_kernel.cu dk/dv pass)."""
    t = pl.program_id(2)
    flags = fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0]                        # (block_q, d)
    k = k_ref[0, 0]                        # (block_kv, d)
    v = v_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)  # (block_q, d)
    lse = lse_ref[0, 0][:, :1]             # (block_q, 1)
    delta = delta_ref[0, 0][:, :1]

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse)
    if keep is not None:   # masked scores and q padding rows contribute 0
        p = jnp.where(keep(qi_ref[t], ki_ref[t]), p, 0.0)
    # dv += p^T @ do
    dv_scr[:] = dv_scr[:] + lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # dp = do @ v^T ; ds = p * (dp - delta) * scale
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    # dk += ds^T @ q
    dk_scr[:] = dk_scr[:] + lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((flags & LAST) != 0)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(qi_ref, ki_ref, fl_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_scr, *, scale, keep):
    """FA2 backward, dq: the schedule is q-major, so dq accumulates in
    VMEM scratch along a q block's run of kv tiles."""
    t = pl.program_id(2)
    flags = fl_ref[t]

    @pl.when((flags & FIRST) != 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    p = jnp.exp(s - lse)
    if keep is not None:   # masked scores and kv padding cols contribute 0
        p = jnp.where(keep(qi_ref[t], ki_ref[t]), p, 0.0)
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_scr[:] = dq_scr[:] + lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when((flags & LAST) != 0)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _expand_to_128(x, pad_to):
    """(b, h, s) -> (b, h, pad_to, 128) f32 — the lane-broadcast layout the
    TPU kernels read scalars-per-row from (same trick as the fwd lse out).

    Deliberate 128x HBM cost for these two per-row scalars: jax's own
    production TPU flash kernel broadcasts l/m/di identically before its
    backward pallas_calls (jax/experimental/pallas/ops/tpu/
    flash_attention.py _flash_attention_bwd_dkv) — lane-1 blocks don't
    tile; the arrays are transient within the backward step."""
    b, h, s = x.shape
    x = x.astype(jnp.float32)
    if pad_to != s:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_to - s)))
    return jnp.broadcast_to(x[..., None], (b, h, pad_to, 128))


def flash_attention_backward(q, k, v, out, lse, do, causal, scale,
                             block_q=None, block_kv=None, interpret=False):
    """Pallas FA2 backward (dq, dk, dv) in layout (b, h, s, d).

    Two kernels: dk/dv over a kv-major schedule, dq over a q-major one.
    GQA folds the head group AFTER the kernels (sum over the repeated
    q-heads), like the XLA fallback.
    """
    b, h, sq, d = q.shape
    kv_h, sk = k.shape[1], k.shape[2]
    dv_w = v.shape[-1]        # values (and do, dv) may be narrower than q/k
    group = h // kv_h
    k_full = jnp.repeat(k, group, axis=1) if group != 1 else k
    v_full = jnp.repeat(v, group, axis=1) if group != 1 else v

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                           # (b, h, sq)

    block_q, block_kv = _blocks(block_q, block_kv, d, dv_w, sq, sk)
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_kv)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k_full = jnp.pad(k_full, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v_full = jnp.pad(v_full, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    lse128 = _expand_to_128(lse, sq_p)
    delta128 = _expand_to_128(delta, sq_p)

    q_block, kv_block = _block_maps()
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d), q_block),        # q
        pl.BlockSpec((1, 1, block_kv, d), kv_block),      # k
        pl.BlockSpec((1, 1, block_kv, dv_w), kv_block),   # v
        pl.BlockSpec((1, 1, block_q, dv_w), q_block),     # do
        pl.BlockSpec((1, 1, block_q, 128), q_block),      # lse
        pl.BlockSpec((1, 1, block_q, 128), q_block),      # delta
    ]

    sched, keep = _scheduled(sq, sk, block_q, block_kv, causal, "kv", b * h,
                             q_seq_len=sq if sq_p != sq else None)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, keep=keep),
        name="flash_attention_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,            # the schedule
            grid=(b, h, len(sched[0])),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, block_kv, d), kv_block),
                       pl.BlockSpec((1, 1, block_kv, dv_w), kv_block)],
            scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                            pltpu.VMEM((block_kv, dv_w), jnp.float32)]),
        out_shape=[
            # f32 so the GQA group sum below accumulates in full precision
            # (the XLA fallback sums the group in f32 too)
            jax.ShapeDtypeStruct((b, h, sk_p, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk_p, dv_w), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*sched, q, k_full, v_full, do, lse128, delta128)

    sched, keep = _scheduled(sq, sk, block_q, block_kv, causal, "q", b * h,
                             kv_seq_len=sk if sk_p != sk else None)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, keep=keep),
        name="flash_attention_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,            # the schedule
            grid=(b, h, len(sched[0])),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, block_q, d), q_block),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*sched, q, k_full, v_full, do, lse128, delta128)

    dq = dq[:, :, :sq, :]
    dk = dk[:, :, :sk, :]
    dv = dv[:, :, :sk, :]
    if group != 1:
        dk = dk.reshape(b, kv_h, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, kv_h, group, sk, dv_w).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ------------------------------------------------ backward (XLA fallback)
def _bwd_blockwise(q, k, v, out, lse, do, causal, scale, block_kv=1024):
    """Flash-attention-2 backward via lax.scan over kv blocks (pure XLA)."""
    b, h, sq, d = q.shape
    kv_h, sk = k.shape[1], k.shape[2]
    group = h // kv_h
    if group != 1:
        k_full = jnp.repeat(k, group, axis=1)
        v_full = jnp.repeat(v, group, axis=1)
    else:
        k_full, v_full = k, v

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(out.astype(jnp.float32) * dof, axis=-1)  # (b,h,sq)

    block_kv = min(block_kv, sk)
    sk_p = _ceil_to(sk, block_kv)
    if sk_p != sk:
        k_full = jnp.pad(k_full, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v_full = jnp.pad(v_full, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    n_blocks = sk_p // block_kv

    k_blocks = k_full.reshape(b, h, n_blocks, block_kv, d).transpose(2, 0, 1, 3, 4)
    v_blocks = v_full.reshape(b, h, n_blocks, block_kv, -1).transpose(2, 0, 1, 3, 4)

    rows = jnp.arange(sq)[:, None]

    def body(dq_acc, inp):
        blk_idx, kb, vb = inp
        cols = blk_idx * block_kv + jnp.arange(block_kv)[None, :]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32)) * scale
        mask = cols < sk
        if causal:   # bottom-right aligned (offset sk - sq), like the fwd
            mask = mask & (rows + (sk - sq) >= cols)
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dv_b = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vb.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                     kb.astype(jnp.float32))
        dk_b = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
        return dq_acc, (dk_b, dv_b)

    dq0 = jnp.zeros_like(qf)
    dq, (dk_blocks, dv_blocks) = lax.scan(
        body, dq0, (jnp.arange(n_blocks), k_blocks, v_blocks))
    dk = dk_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, sk_p, d)[:, :, :sk]
    dv = dv_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, sk_p, -1)[:, :, :sk]
    if group != 1:
        dk = dk.reshape(b, kv_h, group, sk, d).sum(axis=2)
        dv = dv.reshape(b, kv_h, group, sk, -1).sum(axis=2)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# ----------------------------------------------------------- public entry
def _use_pallas():
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_bhsd(q, k, v, causal=False, scale=None):
    """Flash attention, layout (batch, heads, seq, head_dim)."""
    out, _ = _fwd_impl(q, k, v, causal, scale)
    return out


def _fwd_impl(q, k, v, causal, scale):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _use_pallas():
        out, lse = flash_attention_forward(q, k, v, causal, scale)
        return out, lse
    # XLA fallback (CPU tests): compute lse explicitly.
    kv_heads, q_heads = k.shape[1], q.shape[1]
    kk, vv = k, v
    if kv_heads != q_heads:
        rep = q_heads // kv_heads
        kk = jnp.repeat(k, rep, axis=1)
        vv = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], kk.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, DEFAULT_MASK_VALUE)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv)
    return out.astype(q.dtype), lse


def _fa_fwd(q, k, v, causal, scale):
    out, lse = _fwd_impl(q, k, v, causal, scale)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, res, do):
    q, k, v, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if _use_pallas():
        dq, dk, dv = flash_attention_backward(q, k, v, out, lse, do,
                                              causal, scale)
    else:
        dq, dk, dv = _bwd_blockwise(q, k, v, out, lse, do, causal, scale)
    return dq, dk, dv


flash_attention_bhsd.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_bshd(q, k, v, causal=False, scale=None):
    """Paddle layout (batch, seq, heads, head_dim) — the reference API layout
    (python/paddle/nn/functional/flash_attention.py)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal, scale)
    return jnp.swapaxes(out, 1, 2)
