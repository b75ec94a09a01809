"""Fused RMSNorm and rotary-embedding Pallas kernels.

Capability parity: the reference's fusion kernel family —
paddle/phi/kernels/fusion/gpu/fused_rope_{kernel,grad_kernel}.cu and the
rms_norm fusion (paddle/phi/kernels/gpu/rms_norm_kernel.cu), surfaced as
paddle.incubate.nn.functional.fused_rotary_position_embedding /
fused_rms_norm.

TPU-native role: XLA already fuses both chains well; these kernels exist
for the shapes where a single-pass VMEM-resident kernel beats the XLA
fusion (long rows, bf16), selected per shape by ops/autotune.py — the
same measured dispatch the flash-attention path uses.  Off-TPU the XLA
forms are the reference implementations the kernels are tested against
(interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _ceil_to

#: Flip to True in CPU tests to run the kernels through the Pallas
#: interpreter (Mosaic only compiles on TPU).
_INTERPRET = False


# ----------------------------------------------------------------- rmsnorm
def _rms_kernel(x_ref, w_ref, o_ref, *, epsilon, hidden):
    x = x_ref[:].astype(jnp.float32)               # (block_rows, hidden)
    var = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    y = x * lax.rsqrt(var + epsilon)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def rms_norm_pallas(x, weight, epsilon=1e-6, block_rows=256,
                    interpret=None):
    """Single-pass fused RMSNorm over the last dim.  x: (..., hidden)."""
    if interpret is None:
        interpret = _INTERPRET
    hidden = x.shape[-1]
    lead = x.shape[:-1]
    rows = 1
    for n in lead:
        rows *= n
    x2 = x.reshape(rows, hidden)
    block_rows = min(block_rows, _ceil_to(rows, 8))
    rows_p = _ceil_to(rows, block_rows)
    if rows_p != rows:
        x2 = jnp.pad(x2, ((0, rows_p - rows), (0, 0)))
    w2 = weight.reshape(1, hidden)

    out = pl.pallas_call(
        functools.partial(_rms_kernel, epsilon=epsilon, hidden=hidden),
        grid=(rows_p // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, hidden), lambda r: (r, 0)),
            pl.BlockSpec((1, hidden), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, hidden), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, hidden), x.dtype),
        interpret=interpret,
    )(x2, w2)
    return out[:rows].reshape(*lead, hidden)


def rms_norm_xla(x, weight, epsilon=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    out = (x.astype(jnp.float32) * lax.rsqrt(var + epsilon)).astype(x.dtype)
    return out * weight if weight is not None else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm_fused(x, weight, epsilon=1e-6):
    """Differentiable fused RMSNorm: Pallas forward on TPU, analytic
    XLA backward (a pallas_call has no transpose rule, so autodiff
    through the raw kernel would fail — same reason flash_attention
    wraps its kernels in custom_vjp)."""
    return rms_norm_pallas(x, weight, epsilon)


def _rms_fwd(x, weight, epsilon):
    return rms_norm_fused(x, weight, epsilon), (x, weight)


def _rms_bwd(epsilon, res, g):
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    H = x.shape[-1]
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    r = lax.rsqrt(var + epsilon)
    gw = gf * wf
    # d/dx [x_i * r * w_i] : r*gw_i - (r^3 / H) * x_i * sum_j gw_j x_j
    dot = jnp.sum(gw * xf, axis=-1, keepdims=True)
    dx = (r * gw - (r ** 3 / H) * xf * dot).astype(x.dtype)
    axes = tuple(range(x.ndim - 1))
    dw = jnp.sum(gf * xf * r, axis=axes).astype(w.dtype)
    return dx, dw


rms_norm_fused.defvjp(_rms_fwd, _rms_bwd)


# -------------------------------------------------------------------- rope
def _rope_kernel(q_ref, k_ref, cos_ref, sin_ref, oq_ref, ok_ref, *, half):
    cos = cos_ref[:][:, None, :]                   # (block_s, 1, half)
    sin = sin_ref[:][:, None, :]

    def rot(ref, out):
        x = ref[0].astype(jnp.float32)             # (block_s, heads, d)
        x1 = x[..., :half]
        x2 = x[..., half:]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out[0] = jnp.concatenate([o1, o2], axis=-1).astype(out.dtype)

    rot(q_ref, oq_ref)
    rot(k_ref, ok_ref)


#: what one rope grid step may hold in VMEM: three quarters of Mosaic's
#: 16 MiB scoped limit, the rest left to the compiler's own scratch
_ROPE_VMEM_BUDGET = 12 << 20


def _rope_block_s(s, h, kvh, d, itemsize):
    """Sequence rows per grid step, sized from the shape.  A row costs
    its q and k blocks in and out, double-buffered (heads pad to the
    dtype's sublane packing: 8 rows of f32, 16 of bf16), plus about
    three f32 copies of the wider of the two while it is rotated
    (measured against the v5e compiler's own VMEM report)."""
    sub = 32 // itemsize
    h_p, kvh_p = _ceil_to(h, sub), _ceil_to(kvh, sub)
    per_row = (h_p + kvh_p) * d * itemsize * 4 + 3 * max(h_p, kvh_p) * d * 4
    rows = max(8, _ROPE_VMEM_BUDGET // per_row // 8 * 8)
    return min(rows, _ceil_to(s, 8))


def fused_rope_pallas(q, k, cos, sin, interpret=None):
    """Rotate q and k in ONE kernel.  q: (b, s, h, d), k: (b, s, kvh, d);
    cos/sin: (s, d/2) already sliced to the position window."""
    if interpret is None:
        interpret = _INTERPRET
    b, s, h, d = q.shape
    kvh = k.shape[2]
    half = d // 2
    block_s = _rope_block_s(s, h, kvh, d, q.dtype.itemsize)
    s_p = _ceil_to(s, block_s)
    if s_p != s:
        pad = ((0, 0), (0, s_p - s), (0, 0), (0, 0))
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        cos = jnp.pad(cos, ((0, s_p - s), (0, 0)))
        sin = jnp.pad(sin, ((0, s_p - s), (0, 0)))
    cosf = cos.astype(jnp.float32)
    sinf = sin.astype(jnp.float32)

    oq, ok = pl.pallas_call(
        functools.partial(_rope_kernel, half=half),
        grid=(b, s_p // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s, h, d), lambda b_, si: (b_, si, 0, 0)),
            pl.BlockSpec((1, block_s, kvh, d),
                         lambda b_, si: (b_, si, 0, 0)),
            pl.BlockSpec((block_s, half), lambda b_, si: (si, 0)),
            pl.BlockSpec((block_s, half), lambda b_, si: (si, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, h, d), lambda b_, si: (b_, si, 0, 0)),
            pl.BlockSpec((1, block_s, kvh, d),
                         lambda b_, si: (b_, si, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_p, h, d), q.dtype),
            jax.ShapeDtypeStruct((b, s_p, kvh, d), k.dtype),
        ],
        interpret=interpret,
    )(q, k, cosf, sinf)
    return oq[:, :s], ok[:, :s]


def fused_rope_xla(q, k, cos, sin):
    """XLA reference: same math, compiler-fused."""
    c = cos[None, :, None, :].astype(jnp.float32)
    si = sin[None, :, None, :].astype(jnp.float32)

    def rot(x):
        half = x.shape[-1] // 2
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        return jnp.concatenate(
            [x1 * c - x2 * si, x2 * c + x1 * si], axis=-1).astype(x.dtype)

    return rot(q), rot(k)


@jax.custom_vjp
def fused_rope_fused(q, k, cos, sin):
    """Differentiable fused rope: Pallas forward, rotation-transpose
    backward (the adjoint of a rotation by theta is a rotation by -theta,
    so the backward reuses the SAME kernel with negated sin)."""
    return fused_rope_pallas(q, k, cos, sin)


def _rope_fwd(q, k, cos, sin):
    return fused_rope_fused(q, k, cos, sin), (q, k, cos, sin)


def _rope_bwd(res, g):
    q, k, cos, sin = res
    gq, gk = g
    dq, dk = fused_rope_pallas(gq, gk, cos, -sin)

    # true table cotangents (matching the XLA path's autodiff — tables
    # are usually frozen buffers, but a learned/scaled rope experiment
    # must not get silent zeros): with o1 = x1 c - x2 s, o2 = x2 c + x1 s,
    #   dc = Σ g1 x1 + g2 x2,   ds = Σ g2 x1 - g1 x2   (over batch, heads)
    def table_grads(x, gx):
        half = x.shape[-1] // 2
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        g1 = gx[..., :half].astype(jnp.float32)
        g2 = gx[..., half:].astype(jnp.float32)
        dc = jnp.sum(g1 * x1 + g2 * x2, axis=(0, 2))
        ds = jnp.sum(g2 * x1 - g1 * x2, axis=(0, 2))
        return dc, ds

    dc_q, ds_q = table_grads(q, gq)
    dc_k, ds_k = table_grads(k, gk)
    return (dq, dk, (dc_q + dc_k).astype(cos.dtype),
            (ds_q + ds_k).astype(sin.dtype))


fused_rope_fused.defvjp(_rope_fwd, _rope_bwd)
