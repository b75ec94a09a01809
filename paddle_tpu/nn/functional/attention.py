"""Attention functional API.

Capability parity: python/paddle/nn/functional/flash_attention.py:364
(flash_attention, scaled_dot_product_attention) in the reference.

Implementation selection (SURVEY #86 kernel autotune): at short sequence /
small head_dim the plain XLA fusion beats the Pallas online-softmax kernel
on TPU (measured: v5e, d=64, s=1024 — the s x s score matrix still fits and
XLA's fusion pipeline wins); at long sequence its O(s^2) f32 residuals OOM
and the Pallas kernel is the only viable path.  Eager calls autotune per
shape (cached); traced calls use the cache or the memory heuristic.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.dispatch import def_op
from ...ops import autotune as _autotune
from ...ops.pallas.flash_attention import flash_attention_bshd, mha_reference

# per-call f32 score-matrix bytes above which the XLA path is assumed to
# OOM/thrash during training (backward keeps one s x s residual per layer)
_XLA_SCORE_BYTES_LIMIT = 1 << 29


def _flashmask_pallas_module():
    """The Pallas flashmask module when it should handle dispatch, else
    None.  _FORCE_DISPATCH (tests) is separate from _INTERPRET so the
    dense path below stays reachable as the correctness ORACLE while the
    kernels run interpreted."""
    from ...ops.pallas import flashmask_attention as _fm
    if jax.default_backend() == "tpu" or getattr(_fm, "_FORCE_DISPATCH",
                                                 False):
        return _fm
    return None


def _mha_ref_bshd(q, k, v, causal):
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    return jnp.swapaxes(mha_reference(qt, kt, vt, causal=causal), 1, 2)


def _choose_flash_impl(q, k, causal) -> str:
    b, sq, h, d = q.shape
    sk = k.shape[1]
    score_bytes = b * h * sq * sk * 4
    heuristic = "xla" if score_bytes <= _XLA_SCORE_BYTES_LIMIT else "pallas"
    key = (f"flash_attention:{tuple(q.shape)}:{tuple(k.shape)}:"
           f"{q.dtype}:{causal}")
    if isinstance(q, jax.core.Tracer):
        hit = _autotune.lookup(key)
        return _autotune.note(key, hit or heuristic,
                              "cached" if hit else "heuristic")
    if heuristic == "pallas":
        # don't risk OOM timing the XLA candidate on huge scores
        return _autotune.note(key, "pallas", "heuristic")
    return _autotune.autotune(
        key,
        {"xla": lambda: _mha_ref_bshd(q, k, k, causal),
         "pallas": lambda: flash_attention_bshd(q, k, k, causal=causal)},
        default=heuristic)


def _flash_impl(q, k, v, causal):
    if _choose_flash_impl(q, k, causal) == "xla":
        return _mha_ref_bshd(q, k, v, causal)
    return flash_attention_bshd(q, k, v, causal=causal)


@def_op("flash_attention")
def _flash(q, k, v, causal):
    return _flash_impl(q, k, v, causal)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    """reference API: paddle.nn.functional.flash_attention.flash_attention.

    Layout (batch, seq, num_heads, head_dim).  Dropout inside attention is
    not fused (XLA/Pallas path); apply dropout on the output if needed.
    """
    out = _flash(query, key, value, causal)
    if return_softmax:
        return out, None
    return out, None


@def_op("sdpa")
def _sdpa(q, k, v, attn_mask, causal, dropout_p):
    if attn_mask is None:
        return _flash_impl(q, k, v, causal)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = mha_reference(qt, kt, vt, causal=causal, bias=attn_mask)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """reference: paddle.nn.functional.scaled_dot_product_attention
    (flash_attention.py).  Layout (batch, seq, heads, head_dim)."""
    return _sdpa(query, key, value, attn_mask, is_causal, dropout_p)


@def_op("flash_attn_qkvpacked")
def _flash_qkvpacked(qkv, causal):
    # [B, S, 3, H, D] -> three [B, S, H, D]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    return _flash_impl(q, k, v, causal)


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, fixed_seed_offset=None,
                         rng_name="", training=True, name=None):
    """reference: F.flash_attn_qkvpacked (flash_attention.py) — packed
    [batch, seq, 3, heads, head_dim] input."""
    out = _flash_qkvpacked(qkv, causal)
    return out, None


def _varlen_segment_mask(cu_seqlens, total, dtype):
    """Segment ids from cumulative sequence lengths: position i belongs to
    the sequence whose [cu[j], cu[j+1]) interval contains it."""
    pos = jnp.arange(total)
    seg = jnp.searchsorted(cu_seqlens[1:-1], pos, side="right") \
        if cu_seqlens.shape[0] > 2 else jnp.zeros((total,), jnp.int32)
    return seg


@def_op("flash_attn_varlen_qkvpacked")
def _flash_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k, causal, scale):
    # qkv: [total, 3, H, D] — ragged batch packed along axis 0.  On TPU the
    # ragged batch runs as ONE attention with a block-diagonal segment mask
    # (the reference's varlen kernel iterates cu_seqlens on the GPU side).
    total = qkv.shape[0]
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    seg_q = _varlen_segment_mask(cu_seqlens_q, total, q.dtype)
    seg_k = _varlen_segment_mask(cu_seqlens_k, k.shape[0], k.dtype)
    mask = (seg_q[:, None] == seg_k[None, :])
    if causal:
        mask = mask & (jnp.arange(total)[:, None] >= jnp.arange(
            k.shape[0])[None, :])
    bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # [total, H, D] -> heads-leading matmul
    qt = jnp.swapaxes(q, 0, 1) * s                  # [H, total, D]
    kt = jnp.swapaxes(k, 0, 1)
    vt = jnp.swapaxes(v, 0, 1)
    scores = qt @ jnp.swapaxes(kt, -1, -2) + bias[None]
    probs = jax.nn.softmax(scores, axis=-1)
    out = probs @ vt                                # [H, total, D]
    return jnp.swapaxes(out, 0, 1)


def flash_attn_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k,
                                max_seqlen_q=None, max_seqlen_k=None,
                                scale=None, dropout=0.0, causal=False,
                                return_softmax=False, varlen_padded=False,
                                training=True, name=None):
    """reference: F.flash_attn_varlen_qkvpacked — ragged sequences packed
    as [total_tokens, 3, heads, head_dim] with cu_seqlens boundaries."""
    out = _flash_varlen_qkvpacked(qkv, cu_seqlens_q, cu_seqlens_k, causal,
                                  scale)
    return out, None


@def_op("flashmask_attention")
def _flashmask_attention(q, k, v, startend_row_indices, causal):
    # startend_row_indices: [B, H or 1, Sk, 1|2|4] — FlashMask (the
    # reference's flashmask_attention): column j of the score matrix is
    # masked for rows r in [start_j, end_j).  1 col: causal LT mask with
    # rows >= start masked; 2 cols: [start, end); 4 cols: LT + UT bands.
    # On TPU the Pallas interval-mask kernels run (O(seq) mask memory +
    # fully-masked tiles skipped — ops/pallas/flashmask_attention.py);
    # _flashmask_dense below is the CPU fallback and oracle.
    _fm = _flashmask_pallas_module()
    if _fm is not None:
        qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        out = _fm.flashmask_attention_fused(qt, kt, vt,
                                            startend_row_indices, causal)
        return jnp.swapaxes(out, 1, 2)
    return _flashmask_dense(q, k, v, startend_row_indices, causal)


def _flashmask_dense(q, k, v, startend_row_indices, causal):
    """Dense-bias FlashMask (CPU fallback + the kernels' oracle)."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    idx = startend_row_indices
    rows = jnp.arange(Sq)[:, None]                  # [Sq, 1]

    def band(lo, hi):
        # mask rows lo <= r < hi, per column: [B, h, Sq, Sk]
        return (rows[None, None] >= lo[:, :, None, :]) & \
               (rows[None, None] < hi[:, :, None, :])

    ncol = idx.shape[-1]
    if ncol == 1:
        masked = band(idx[..., 0], jnp.full_like(idx[..., 0], Sq))
    elif ncol == 2:
        masked = band(idx[..., 0], idx[..., 1])
    else:                                           # 4: LT start/end + UT
        masked = band(idx[..., 0], idx[..., 1]) | \
                 band(idx[..., 2], idx[..., 3])
    if causal:
        masked = masked | (rows[None, None] < jnp.arange(Sk)[None, None,
                                                            None, :])
    bias = jnp.where(masked, -1e30, 0.0).astype(jnp.float32)
    qt, kt, vt = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    out = mha_reference(qt, kt, vt, causal=False, bias=bias)
    return jnp.swapaxes(out, 1, 2)


def flashmask_attention(query, key, value, startend_row_indices,
                        dropout=0.0, causal=False, window_size=None,
                        return_softmax_lse=False, return_seed_offset=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """reference: F.flashmask_attention — sparse attention masks encoded
    as per-column row intervals (FlashMask, PaddlePaddle 3.0)."""
    out = _flashmask_attention(query, key, value, startend_row_indices,
                               causal)
    if return_softmax_lse or return_seed_offset:
        return (out, None) + ((None,) if return_seed_offset else ())
    return out


@def_op("sparse_attention")
def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """reference: F.sparse_attention — per-row CSR sparsity pattern over
    the score matrix.  [B, H, S, D] layout (reference layout).  On TPU the
    pattern is applied as a dense additive bias — XLA fuses it into the
    softmax; true block-sparse compute belongs to the Pallas kernel when
    the pattern is block-structured."""
    B, H, S, D = query.shape
    # dense mask[b, h, r, c] = 1 iff c in columns[offset[r]:offset[r+1]]
    nnz = sparse_csr_columns.shape[-1]
    pos = jnp.arange(nnz)

    def one_mask(offset, columns):
        row_of_nnz = jnp.searchsorted(offset[1:], pos, side="right")
        return jnp.zeros((S, S), bool).at[row_of_nnz, columns].set(True)

    mask = jax.vmap(one_mask)(
        sparse_csr_offset.reshape(B * H, -1),
        sparse_csr_columns.reshape(B * H, -1)).reshape(B, H, S, S)
    bias = jnp.where(mask, 0.0, -1e30).astype(jnp.float32)
    if attn_mask is not None:
        bias = bias + jnp.where(attn_mask.astype(bool), 0.0, -1e30)
    if key_padding_mask is not None:
        bias = bias + jnp.where(key_padding_mask.astype(bool), 0.0,
                                -1e30)[:, None, None, :]
    return mha_reference(query, key, value, causal=False, bias=bias)
