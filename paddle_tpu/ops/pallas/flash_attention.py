"""Flash attention for TPU: Pallas forward kernel + memory-efficient backward.

Capability parity with the reference's flash-attention stack
(reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu wrapping flashattn
v2/v3 via paddle/phi/backends/dynload/flashattn.cc; Python API
python/paddle/nn/functional/flash_attention.py:364).

TPU-native design (see /opt/skills/guides/pallas_guide.md):
  - forward: online-softmax tiled kernel; grid (batch, heads, q_blocks,
    kv_blocks) with the kv axis 'arbitrary' (sequential) so m/l/acc scratch
    carries across kv tiles; MXU matmuls via dot_general with
    preferred_element_type=f32; causal tiles beyond the diagonal are skipped
    with @pl.when.
  - backward: blockwise XLA recomputation from the saved logsumexp (the
    flash-attention-2 backward formulation) under lax.scan — O(seq * block)
    memory without a second hand-written kernel.
  - off-TPU (CPU tests) the same math runs as a plain XLA reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


# ------------------------------------------------------------------ kernel
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_kv,
                kv_seq_len, causal_offset):
    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv_idx == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # For causal attention, tiles strictly above the (bottom-right-aligned,
    # offset = sk - sq) diagonal contribute nothing; predicate them off
    # (grid still visits, compute is skipped).
    if causal:
        run = (q_idx * block_q + block_q - 1 + causal_offset
               >= kv_idx * block_kv)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                       # (block_q, d)
        k = k_ref[0, 0]                       # (block_kv, d)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = q_idx * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = kv_idx * block_kv + lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows + causal_offset >= cols, s,
                          DEFAULT_MASK_VALUE)
        # mask kv padding (kv_seq_len may be < padded length)
        cols = kv_idx * block_kv + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(cols < kv_seq_len, s, DEFAULT_MASK_VALUE)

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

    @pl.when(kv_idx == n_kv - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[:] + jnp.log(l_safe)).astype(jnp.float32)


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def flash_attention_forward(q, k, v, causal=False, scale=None,
                            block_q=512, block_kv=512, interpret=False):
    """Pallas forward. Layout (b, h, s, d). Returns (out, lse)."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, sq, d = q.shape
    kv_h, sk = k.shape[1], k.shape[2]
    dv = v.shape[-1]          # the values' own width (latent attention)
    block_q = min(block_q, _ceil_to(sq, 128))
    block_kv = min(block_kv, _ceil_to(sk, 128))
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_kv)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    grid = (b, h, sq_p // block_q, sk_p // block_kv)
    group = h // kv_h

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_seq_len=sk, causal_offset=sk - sq)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dv),
                         lambda b_, h_, qi, ki: (b_, h_ // group, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq_p, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq, :], lse[:, :, :sq, 0]


# ------------------------------------------------- backward (Pallas, TPU)
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_kv, q_seq_len, causal_offset):
    """FA2 backward, dk/dv: grid (b, h, kv_blocks, q_blocks); the q axis is
    sequential so dk/dv accumulate in VMEM scratch across q tiles
    (reference: flash_attn_grad_kernel.cu dk/dv pass)."""
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(3)
    n_q = pl.num_programs(3)

    @pl.when(q_idx == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if causal:   # tiles strictly above the diagonal contribute nothing
        run = (q_idx * block_q + block_q - 1 + causal_offset
               >= kv_idx * block_kv)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                        # (block_q, d)
        k = k_ref[0, 0]                        # (block_kv, d)
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)  # (block_q, d)
        lse = lse_ref[0, 0][:, :1]             # (block_q, 1)
        delta = delta_ref[0, 0][:, :1]

        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        rows = q_idx * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        cols = kv_idx * block_kv + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = rows < q_seq_len                # q padding rows contribute 0
        if causal:
            mask = mask & (rows + causal_offset >= cols)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
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

    @pl.when(q_idx == n_q - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, block_q, block_kv,
                   kv_seq_len, causal_offset):
    """FA2 backward, dq: grid (b, h, q_blocks, kv_blocks); the kv axis is
    sequential so dq accumulates in VMEM scratch across kv tiles."""
    q_idx = pl.program_id(2)
    kv_idx = pl.program_id(3)
    n_kv = pl.num_programs(3)

    @pl.when(kv_idx == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    if causal:
        run = (q_idx * block_q + block_q - 1 + causal_offset
               >= kv_idx * block_kv)
    else:
        run = True

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]

        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        rows = q_idx * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        cols = kv_idx * block_kv + lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = cols < kv_seq_len               # kv padding cols
        if causal:
            mask = mask & (rows + causal_offset >= cols)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kv_idx == n_kv - 1)
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
                             block_q=512, block_kv=512, interpret=False):
    """Pallas FA2 backward (dq, dk, dv) in layout (b, h, s, d).

    Two kernels: dk/dv with the q axis sequential, dq with the kv axis
    sequential.  GQA folds the head group AFTER the kernels (sum over the
    repeated q-heads), like the XLA fallback.
    """
    b, h, sq, d = q.shape
    kv_h, sk = k.shape[1], k.shape[2]
    dv_w = v.shape[-1]        # values (and do, dv) may be narrower than q/k
    group = h // kv_h
    k_full = jnp.repeat(k, group, axis=1) if group != 1 else k
    v_full = jnp.repeat(v, group, axis=1) if group != 1 else v

    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                           # (b, h, sq)

    block_q = min(block_q, _ceil_to(sq, 128))
    block_kv = min(block_kv, _ceil_to(sk, 128))
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_kv)
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k_full = jnp.pad(k_full, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v_full = jnp.pad(v_full, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    lse128 = _expand_to_128(lse, sq_p)
    delta128 = _expand_to_128(delta, sq_p)

    n_q, n_kv = sq_p // block_q, sk_p // block_kv

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, q_seq_len=sq, causal_offset=sk - sq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_bwd_dkv",
        grid=(b, h, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, ki, qi: (b_, h_, qi, 0)),   # q
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, ki, qi: (b_, h_, ki, 0)),   # k
            pl.BlockSpec((1, 1, block_kv, dv_w),
                         lambda b_, h_, ki, qi: (b_, h_, ki, 0)),   # v
            pl.BlockSpec((1, 1, block_q, dv_w),
                         lambda b_, h_, ki, qi: (b_, h_, qi, 0)),   # do
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b_, h_, ki, qi: (b_, h_, qi, 0)),   # lse
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b_, h_, ki, qi: (b_, h_, qi, 0)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, dv_w),
                         lambda b_, h_, ki, qi: (b_, h_, ki, 0)),
        ],
        out_shape=[
            # f32 so the GQA group sum below accumulates in full precision
            # (the XLA fallback sums the group in f32 too)
            jax.ShapeDtypeStruct((b, h, sk_p, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, sk_p, dv_w), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, dv_w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k_full, v_full, do, lse128, delta128)

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_kv=block_kv, kv_seq_len=sk, causal_offset=sk - sq)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_bwd_dq",
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),   # q
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b_, h_, qi, ki: (b_, h_, ki, 0)),   # k
            pl.BlockSpec((1, 1, block_kv, dv_w),
                         lambda b_, h_, qi, ki: (b_, h_, ki, 0)),   # v
            pl.BlockSpec((1, 1, block_q, dv_w),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),   # do
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),   # lse
            pl.BlockSpec((1, 1, block_q, 128),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),   # delta
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k_full, v_full, do, lse128, delta128)

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
