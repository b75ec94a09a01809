"""Paged-KV-cache serving for causal LMs (reference: the
block_multihead_attention serving path,
python/paddle/incubate/nn/functional/block_multihead_attention.py +
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu).

``PagedGenerator`` drives a LlamaForCausalLM-shaped model: prefill runs
dense causal flash attention while writing K/V into fixed-size pages;
each decode step attends one token per sequence against the paged cache
via the Pallas decode kernel (ops/pallas/paged_attention.py).  Sequences
share one page pool and hold only length-proportional pages (no
rectangular max-seq allocation — the serving win the reference gets
from its block allocator); the whole batch's pages are reclaimed when
the batch finishes (per-sequence early free on EOS would change the
batch shape mid-decode and recompile — a continuous-batching scheduler
is the follow-up that needs it).
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .. import monitor
from ..framework.tensor import Tensor, wrap_array
from ..framework.tape import no_grad
from ..ops.pallas.flash_attention import DEFAULT_MASK_VALUE
from ..ops.pallas.paged_attention import (PagedKVCache, _gather_dequant,
                                          _packed_of_rows, _rows_of_packed,
                                          paged_layout,
                                          _round_up, append_rows,
                                          dequantize_kv, kv_tokens_visible,
                                          kv_pages_copied, kv_tokens_walked,
                                          packed_k_rows, packed_queries,
                                          paged_attention,
                                          paged_attention_ragged,
                                          q_positions_computed,
                                          q_positions_moved,
                                          quantize_kv, walk_cut)
from ..testing import faults as _faults


def _maybe_lose_buffers(cache: PagedKVCache, seq_ids) -> None:
    """The ``buffer_loss`` device-fault site (ISSUE 8): when a rule
    fires here, DELETE the cache's pool buffers before re-raising, so
    the caller's ``_recover_pools`` sees consumed donated buffers and
    rebuilds the pools zeroed — the exact failure mode of a real
    device-side step fault, reproducible on CPU CI.  No plan installed
    = one ``is None`` check."""
    if _faults.active() is None:
        return
    try:
        _faults.maybe_fire("buffer_loss", seq_ids=seq_ids)
    except BaseException:
        for a in cache._device_pools():
            fn = getattr(a, "delete", None)
            if callable(fn):
                try:
                    fn()
                except Exception:   # noqa: BLE001 — already unusable
                    pass
        raise


def _fake_quant_kv(x):
    """Round-trip (quantize -> dequantize) a float K/V block through the
    int8 KV representation WITHOUT storing it: the values prefill
    attention consumes are then bit-identical to what the pages hold,
    so chunked prefill, preemption-resume, survivor replay and
    snapshot-restore stay exact in the int8 mode — a prefill that
    attended the exact in-flight suffix while decode later read the
    quantized pages would break every replay contract."""
    q, s = quantize_kv(x)
    return dequantize_kv(q, s, x.dtype)


def _tp_plan(model, mesh):
    """Megatron-style tensor-parallel placement plan for a LLaMA-shaped
    serving model over a 1-D ``('tensor',)`` mesh (ISSUE 20).

    Column-parallel (out-features on 'tensor'; weight layout is
    ``[in, out]`` so that is dim 1): q/k/v projections and the MLP
    gate/up — each chip computes its own heads / its own slice of the
    intermediate activations with NO communication.  Row-parallel
    (in-features on 'tensor', dim 0): o_proj and down_proj — their
    matmuls produce partial sums and ONE all-reduce closes each block.
    Everything else (norms, embedding, lm_head) stays replicated so the
    logits + fused sampling tail run replicated post-all-reduce.

    Returns ``(spec_by_param_id, row_parallel_layers, attn_layers)``:
    the per-param PartitionSpec map, the Linears to arm with the
    ``_tp_reduce`` hook at trace time, and the attention modules whose
    head counts are patched to their per-chip values during the trace.
    """
    from jax.sharding import PartitionSpec as P
    tp = int(mesh.size)
    layers = getattr(getattr(model, "model", None), "layers", None)
    if not layers:
        raise ValueError(
            "tensor-parallel serving needs a LLaMA-shaped model "
            "(model.model.layers with self_attn/mlp blocks)")
    spec_by_id = {}
    row_layers = []
    attn_layers = []
    col, row = P(None, "tensor"), P("tensor", None)
    for i, layer in enumerate(layers):
        attn, mlp = (getattr(layer, n, None) for n in ("self_attn", "mlp"))
        if attn is None or mlp is None:
            raise ValueError(
                f"tensor-parallel serving plans a LLaMA-shaped block; "
                f"layer {i} of {type(model).__name__} has no "
                f"self_attn / mlp pair (its mixer is a "
                f"{type(getattr(layer, 'mixer', layer)).__name__}: the "
                "plan has no placement for it)")
        # the plan knows one block: q/k/v/o and a dense gate/up/down.
        # What a layer has beyond it would be left replicated or summed
        # wrongly, so the plan names it and refuses
        lacks = [n for n in ("gate_proj", "up_proj", "down_proj")
                 if not hasattr(mlp, n)]
        extra = [n for n, sub in attn.named_children()
                 if n not in ("q_proj", "k_proj", "v_proj", "o_proj")]
        if lacks or extra:
            raise ValueError(
                f"tensor-parallel serving plans a LLaMA-shaped block; "
                f"layer {i} of {type(model).__name__} has "
                + (f"no mlp.{'/'.join(lacks)} (an expert block: the plan "
                   f"has no placement for experts) " if lacks else "")
                + (f"attention projections it does not place: "
                   f"{', '.join(extra)}" if extra else ""))
        if attn.num_heads % tp or attn.num_kv_heads % tp:
            raise ValueError(
                f"layer {i}: num_heads ({attn.num_heads}) and "
                f"num_kv_heads ({attn.num_kv_heads}) must divide the "
                f"tensor-parallel degree ({tp})")
        if mlp.gate_proj.out_features % tp:
            raise ValueError(
                f"layer {i}: intermediate_size "
                f"({mlp.gate_proj.out_features}) must divide the "
                f"tensor-parallel degree ({tp})")
        for lin in (attn.q_proj, attn.k_proj, attn.v_proj,
                    mlp.gate_proj, mlp.up_proj):
            spec_by_id[id(lin.weight)] = col
        for lin in (attn.o_proj, mlp.down_proj):
            if lin.bias is not None:
                # a per-shard bias would be summed tp times by the
                # closing all-reduce — the serving plan only arms
                # bias-free row-parallel projections
                raise ValueError(
                    "row-parallel projections must be bias-free under "
                    "tensor parallelism")
            spec_by_id[id(lin.weight)] = row
            row_layers.append(lin)
        attn_layers.append(attn)
    return spec_by_id, row_layers, attn_layers


def fused_sample(logits, seeds, ctrs, temps, flags):
    """On-device fused sampling tail for the compiled decode/prefill
    programs: per row, greedy argmax AND a temperature categorical draw
    (threefry key = fold_in(PRNGKey(seed), ctr) — the counter is the
    token's absolute position, so a (seed, position) pair replays the
    same draw), selected by ``flags``.  All inputs are traced; only the
    (batch,) int32 token ids ever cross the host boundary.

    logits (batch, vocab) f32; seeds (batch,) uint32; ctrs (batch,)
    int32; temps (batch,) f32; flags (batch,) bool (True = sample).
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(seed, ctr, row, temp):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), ctr)
        return jax.random.categorical(key,
                                      row / jnp.maximum(temp, 1e-6))

    sampled = jax.vmap(draw)(seeds, ctrs, logits, temps).astype(jnp.int32)
    return jnp.where(flags, sampled, greedy)


def _prefix_suffix_attention(q, k_suf, v_suf, k_pages, v_pages, tables,
                             prefix_lens, k_scales=None, v_scales=None):
    """Prompt-SUFFIX attention for a sequence whose prefix KV is already
    cached in pages: every suffix token attends to the whole gathered
    prefix plus the suffix causally.  Dense masked attention (the
    suffix is one bounded bucket per compile; a flash variant is a
    later kernel optimization).

    q (b, s, q_heads, d); k_suf/v_suf (b, s, kv_heads, d) post-rope;
    k/v_pages (kv_heads, total, page, d); tables (b, P) int32 pointing
    at the prefix pages; prefix_lens (b,) int32 page-aligned.
    ``k/v_scales`` (kv_heads, total, page, 1) mark int8 pages (ISSUE 9:
    dequant fused into the gather; the SUFFIX k/v must already be
    round-tripped by the caller).  Returns (b, s, q_heads, d).
    """
    b, s, qh, d = q.shape
    kvh = k_suf.shape[2]
    group = qh // kvh
    page = k_pages.shape[2]
    t_pre = tables.shape[1] * page

    def gather(pages, scales):
        # the ONE gather+dequant helper the decode/multi fallbacks use
        # — prefix-path and decode-path dequant can never drift
        return _gather_dequant(pages, scales, tables, b, kvh, t_pre, d,
                               q.dtype)

    k_all = jnp.concatenate(
        [gather(k_pages, k_scales), jnp.swapaxes(k_suf, 1, 2)],
        axis=2)                                   # (b, kvh, t_pre + s, d)
    v_all = jnp.concatenate(
        [gather(v_pages, v_scales), jnp.swapaxes(v_suf, 1, 2)],
        axis=2)
    if group != 1:
        k_all = jnp.repeat(k_all, group, axis=1)
        v_all = jnp.repeat(v_all, group, axis=1)
    qt = jnp.swapaxes(q, 1, 2)                    # (b, qh, s, d)
    scores = jnp.einsum("bhsd,bhtd->bhst", qt, k_all,
                        preferred_element_type=jnp.float32) \
        / math.sqrt(d)
    t = jnp.arange(t_pre + s, dtype=jnp.int32)
    # prefix cols: valid below the row's (page-aligned) prefix length;
    # suffix cols: causal within the suffix (right-padded bucket pads
    # sit after every real token, so causality masks them out)
    valid_pre = (t[None, :] < prefix_lens[:, None])[:, None, None, :]
    i = jnp.arange(s, dtype=jnp.int32)
    valid_suf = ((t[None, :] >= t_pre)
                 & (t[None, :] - t_pre <= i[:, None]))[None, None]
    scores = jnp.where(valid_pre | valid_suf, scores, DEFAULT_MASK_VALUE)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", p.astype(v_all.dtype), v_all)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def next_pow2(n: int) -> int:
    """Smallest power of two >= n — the shared bucketing rule for prefill
    length, decode page-table width, and the continuous-batching engine's
    running-batch size (all three must stay in sync: each bucket is one
    compiled program)."""
    b = 1
    while b < n:
        b *= 2
    return b


#: what a path with no window says to a sliding-attention layer
_NO_WINDOW = ("a sliding-attention layer (window=...) reached {}, which "
              "has no window and would attend the full context: serve "
              "this model through the ragged unified step "
              "(ContinuousBatchingEngine(prefill_chunk_tokens=...)), "
              "whose paged kernels apply it")


#: what a path that appends in every call says to a layer that reads
#: another layer's pages (or scales its scores itself)
_NO_SHARED = ("a layer that attends another layer's pages (k=None) or "
              "sets its own score scale reached {}, which appends K/V in "
              "every call at the head's own scale: serve this model "
              "through the ragged unified step (ContinuousBatchingEngine("
              "prefill_chunk_tokens=...))")


#: what a path whose attention is one width and one plain softmax says to
#: a layer whose K heads are wider than its V heads or that hands a sink
_NO_SPLIT = ("a layer whose K heads are wider than its V heads, or whose "
             "softmax holds a learned sink (sinks=...), reached {}, whose "
             "attention takes K and V of one width and no sink: serve "
             "this model through the ragged unified step "
             "(ContinuousBatchingEngine(prefill_chunk_tokens=...)), "
             "whose paged kernels take both")


#: what a path with no slots says to a retention layer
_NO_SLOTS = ("a retention layer, a Mamba layer or a convolutional-"
             "attention layer (a recurrent state a sequence) reached {}, "
             "which carries no slot pools: serve this model through the "
             "ragged unified step (ContinuousBatchingEngine("
             "prefill_chunk_tokens=...)), whose rows each update their own "
             "slot in place")


def _sink_array(sinks):
    """``attend``'s ``sinks`` (a Tensor, an array or None) as the kernels
    take it: (q_heads,) float32 or None."""
    if sinks is None:
        return None
    return jnp.asarray(getattr(sinks, "_data", sinks), jnp.float32)


class _PagedContext:
    """Per-forward attention driver handed down to attention layers.

    BOTH branches are the EAGER ORACLE the jitted steps
    (JittedPagedDecoder/_TracedPagedContext) are equivalence-tested
    against — production prefill AND decode run through the compiled
    paths; keep the write/lens protocols in sync
    (tests/test_paged_attention.py eager-vs-jitted parity)."""

    def __init__(self, cache: PagedKVCache, seq_ids: Sequence[int],
                 prefill: bool):
        self.cache = cache
        self.seq_ids = list(seq_ids)
        self.prefill = prefill
        self.layer_idx = 0

    def retain(self, *args):
        raise NotImplementedError(_NO_SLOTS.format(
            "the eager paged context of PagedGenerator"))

    conv_rows = scan_rows = shift_rows = retain

    def attend(self, q: Tensor, k: Tensor, v: Tensor,
               window: Optional[int] = None, scale=None,
               sinks=None) -> Tensor:
        """q/k/v: (batch, s, heads, head_dim) post-rope.  Writes k/v into
        the pages, returns the attention output (batch, s, q_heads, d).
        ``window``: the calling layer is a sliding-attention layer of
        that width (decode applies it; the dense prefill has none and
        refuses, as it refuses ``sinks`` and a K wider than V)."""
        cache = self.cache
        layer = self.layer_idx
        if self.prefill and (sinks is not None or (
                k is not None and k.shape[-1] != v.shape[-1])):
            raise NotImplementedError(_NO_SPLIT.format(
                "the eager prefill's dense flash attention"))
        if window is not None and self.prefill:
            raise NotImplementedError(_NO_WINDOW.format(
                "the eager prefill's dense flash attention"))
        if k is None or scale is not None:
            raise NotImplementedError(_NO_SHARED.format(
                "the eager paged context of PagedGenerator"))
        # whole batch in ONE scatter per pool (not per sequence — the
        # per-seq loop copied the full pool batch times per step)
        cache.write_batch(layer, self.seq_ids, k._data, v._data)
        if self.prefill:
            # fresh sequences: the cache holds exactly this prompt, so
            # dense causal attention over the batch is equivalent; in
            # the int8 mode the attended values must be the ROUND-
            # TRIPPED ones the pages hold, or later decode steps (which
            # read quantized pages) would diverge from this prefill
            if cache.kv_quant:
                k = wrap_array(_fake_quant_kv(k._data))
                v = wrap_array(_fake_quant_kv(v._data))
            from ..nn import functional as F
            out, _ = F.flash_attention(q, k, v, causal=True)
            return out
        tab, lens = cache.page_table(self.seq_ids)
        if layer < cache.num_layers - 1:
            # length advances when the LAST layer writes; earlier layers
            # must already count the token they just wrote
            lens = lens + k.shape[1]
        out = paged_attention(
            q._data[:, 0], cache.k_pages[layer], cache.v_pages[layer],
            lens, tab,
            k_scales=(cache.k_scales[layer] if cache.kv_quant else None),
            v_scales=(cache.v_scales[layer] if cache.kv_quant else None),
            window=window, sinks=_sink_array(sinks))
        return wrap_array(out[:, None])      # (batch, 1, q_heads, d)


class _TracedPagedContext:
    """Paged-attention driver for the JITTED decode/prefill steps: page
    pools, (page, slot) write targets, lengths and tables are all TRACED
    values carried through one compiled program — no host bookkeeping
    inside.  Appends are ``append_rows`` on the carried pools (donated
    at the jit boundary: the rows are written in place, and a pool keeps
    one layout from the boundary to the paged kernel and back out).

    Prefill mode: ``pg``/``sl`` are (batch*seq,) flat targets — pad
    positions carry an out-of-bounds page index (``total_pages``), which
    ``append_rows`` DROPS for every head, so a right-padded bucketed
    prompt never writes garbage KV; attention is dense causal flash over
    the padded batch (pads sit to the RIGHT of every real token, so
    causality keeps them out of real tokens' windows).

    Prefix-prefill mode (``prefill=True`` with ``prefix_lens`` set):
    the batch's tokens are a prompt SUFFIX whose page-aligned prefix KV
    already sits in the pages ``tables`` points at — suffix K/V scatter
    into fresh pages exactly as in prefill, but attention runs over
    [gathered prefix; suffix] so the cached tokens are visible.

    Ragged mode (``q_lens`` and ``span`` set): the model runs over the
    step's tokens PACKED along the batch axis, one token a "row" —
    ``q``/``k``/``v`` arrive as (tokens, 1, heads, d) and ``pg``/``sl``
    are (tokens,), row ``r``'s ``q_lens[r]`` tokens standing together
    from ``row_off[r]`` on (``row_off=None``: the packed axis is the
    whole rectangle, row-major).  The append takes the packed rows as
    they come, and so does the paged kernel: it is told where each row
    starts and copies the row's own queries from there (off the chip its
    XLA oracle gathers the (rows, ``span``) rectangle and packs the
    output back).  ``lens``, ``q_lens`` and ``tables`` are per ROW.

    What a model may ask of it beyond ``attend``: ``token_mask`` — which
    positions of the batch axis are tokens (a pad position's write page
    is out of range); ``count(**named)`` — counters of the model's own,
    summed by name over whoever calls and handed out of the compiled step
    beside its tokens: they reach the ``dispatch`` record under their
    names (``counted``); ``retain`` — what a retention layer asks in
    place of ``attend``: the step's rows' recurrent slots (``states``, a
    pool a retention layer, donated at the jit boundary like the page
    pools; ``slots`` the rows' slots, ``chunk_rows`` the rows of several
    tokens) updated in place by the ragged step, each row against its
    own, a pad row's untouched; ``conv_rows`` / ``scan_rows`` — the same for
    a Mamba layer, whose slot is two arrays (the convolution's tail and the
    scan's ``h``: two pools a layer, side by side in ``states``);
    ``shift_rows`` — for a layer whose slot is one-token tails (a token's
    predecessor: ``models/zaya.py``, where the layer ALSO calls ``attend``
    on a page pool of its own: a slot layer need not be a layer without
    pages).  Only the ragged step carries slots.  ``attend(q, None, None)`` attends WITHOUT
    appending, against the pool ``layer_idx`` names: a layer that reads
    the pages another layer wrote earlier in the same program.
    ``attend(q, k, v, window=, scale=, sinks=)``: ``window`` a sliding
    layer's width; ``scale`` the scores' scale where it is not the K
    head's width^-1/2; ``sinks`` (q_heads,) float32, a learned sink a query
    head in the softmax's denominator (``models/mimo_v2_flash.py``).  K and
    V are handed as the layer's pool holds them (``paged_layout``'s
    ``pool_shapes``): their heads are the pool's, and K may be wider than
    V (192 beside 128) — the output is as wide as V.  The decode and the
    ragged programs' kernels take all of these; the prefill programs'
    dense attention takes none and refuses at trace time."""

    def __init__(self, k_pages, v_pages, pg, sl, lens=None, tables=None,
                 prefill=False, prefix_lens=None, k_scales=None,
                 v_scales=None, q_lens=None, row_off=None, span=None,
                 states=(), slots=None, chunk_rows=None, n_pages=None):
        self.k_pages = list(k_pages)
        self.v_pages = list(v_pages)
        # recurrent slot pools, one a retention layer in the layers'
        # order, and which one the next ``retain`` takes
        self.states = list(states)
        self.slots = slots              # (rows,) traced: a row's slot
        self.chunk_rows = chunk_rows    # (C,) traced: rows of several tokens
        self.state_idx = 0
        self._n_pages = n_pages         # for a model with no page pool
        # int8 KV mode (ISSUE 9): parallel per-slot scale pools carried
        # through the program exactly like the data pools (donated at
        # the jit boundary); empty/None means full-precision storage
        self.k_scales = list(k_scales) if k_scales else None
        self.v_scales = list(v_scales) if v_scales else None
        self.pg = pg
        self.sl = sl
        self.lens = lens                # POST-write lengths (decode)
        self.tables = tables
        self.prefill = prefill
        self.prefix_lens = prefix_lens  # (b,) traced, prefix-prefill only
        self.q_lens = q_lens            # (rows,) traced, ragged step only
        self.row_off = row_off          # (rows,) a row's start when packed
        self.span = span                # the rectangle's width (static)
        self.layer_idx = 0
        self._counts = {}               # name -> the layers' sum

    @property
    def token_mask(self):
        """(positions,) bool: False where the position is pad — its
        (page, slot) write target is the dropped out-of-range page."""
        return self.pg < (self.k_pages[0].shape[1] if self.k_pages
                          else self._n_pages)

    def retain(self, q, k, v, log_g):
        """One retention layer of the ragged step: ``q`` (tokens, 1,
        q_heads, d), ``k`` / ``v`` (tokens, 1, kv_heads, d) and ``log_g``
        (tokens, kv_heads) float32, packed as ``attend`` takes them.
        Every row's slot of this layer's pool is read, updated and
        written in place (``ops/power_retention.py::retention_step``);
        returns (tokens, 1, q_heads, d) float32."""
        from ..ops.power_retention import retention_step
        slots, ctx, q_lens, off = self._recur_rows()
        i = self.state_idx
        self.state_idx += 1
        y, self.states[i] = retention_step(
            self.states[i], slots, ctx, q_lens, off, self.chunk_rows,
            q._data[:, 0], k._data[:, 0], v._data[:, 0], log_g,
            span=self.span)
        self._count_state_rows(self.states[i])
        return wrap_array(y[:, None])

    def _count_state_rows(self, pool):
        """The rows that carry a token, a layer: the dispatch record's
        ``state_rows`` and ``state_bytes`` are read off this sum."""
        self.count(state_row_layers=jnp.sum(self.slots < pool.shape[0] - 1))

    def _recur_rows(self):
        """What a state op is told of the step's rows."""
        if self.q_lens is None or self.slots is None:
            raise NotImplementedError(_NO_SLOTS.format(
                "the prefill / prefix / chunk_prefill programs" if
                self.prefill else "the decode programs"))
        return (self.slots, self.lens - self.q_lens, self.q_lens,
                self.row_off)

    def shift_rows(self, x, part=0, parts=1):
        """Every packed token's predecessor in its own sequence, ``x``
        (tokens, channels) -> x_{t-1} float32: a row's first token reads
        array ``part`` of the layer's slot (zeros at context 0 whatever
        the slot held), and that array moves on to the row's last token
        (``ops/selective_scan.py::shift_step``).  A slot is whatever
        arrays ``recurrent_state()["shapes"]`` lists, in the order the
        layer names them by ``part``; the call on the LAST of its
        ``parts`` moves on to the next layer's slot and counts the
        layer's rows."""
        from ..ops.selective_scan import shift_step
        rows = self._recur_rows()       # refuses where there are no slots
        i = self.state_idx + part
        y, self.states[i] = shift_step(self.states[i], *rows, x,
                                       span=self.span)
        if part == parts - 1:
            self.state_idx += parts
            self._count_state_rows(self.states[i])
        return y

    def conv_rows(self, x, w, b):
        """A Mamba layer's causal convolution over the step's packed
        tokens ``x`` (tokens, channels), each row continuing from its
        slot's tail, which moves on by the row's tokens
        (``ops/selective_scan.py::conv_step``).  A Mamba layer's slot is
        two arrays: the tail is the SECOND; ``scan_rows``, which the
        layer calls next, takes the first and moves on to the next
        layer's.  (A slot need not be Mamba's: ``shift_rows`` serves a
        layer whose slot is tails alone, each named by its index.)"""
        from ..ops.selective_scan import conv_step
        rows = self._recur_rows()
        i = self.state_idx + 1
        y, self.states[i] = conv_step(self.states[i], *rows, x, w, b,
                                      span=self.span)
        return y

    def scan_rows(self, u, delta, a, B, C, d):
        """A Mamba layer's selective scan over the step's packed tokens
        (``ops/selective_scan.py::scan_step``): every row's ``h`` read,
        moved on by the row's tokens and written back in place; returns m
        (tokens, channels) float32."""
        from ..ops.selective_scan import scan_step
        slots, ctx, q_lens, off = self._recur_rows()
        i = self.state_idx
        self.state_idx += 2
        m, self.states[i] = scan_step(
            self.states[i], slots, ctx, q_lens, off, self.chunk_rows, u,
            delta, a, B, C, d, span=self.span)
        self._count_state_rows(self.states[i])
        return m

    def count(self, **named):
        for name, value in named.items():
            self._counts[name] = self._counts.get(name, 0.0) + value

    def counted(self):
        """(names, outputs): ``()`` twice for a model that counts nothing
        (no output is added to its programs), else the names and ONE
        float32 vector of their sums, in the names' order."""
        names = tuple(sorted(self._counts))
        if not names:
            return (), ()
        return names, (jnp.stack([jnp.asarray(self._counts[n], jnp.float32)
                                  for n in names]),)

    def _scatter(self, layer, ks, vs):
        """One layer's append: ``ks``/``vs`` (kvh, tokens, d) float.
        In the int8 mode quantization is FUSED into the scatter (per
        slot, per head) and the scale pools scatter alongside; returns
        the values attention must consume — the round-tripped ones, so
        every consumer sees exactly what the pages hold."""
        pg, sl = self.pg, self.sl
        if self.k_scales is not None:
            k8, ksc = quantize_kv(ks)
            v8, vsc = quantize_kv(vs)
            self.k_scales[layer] = append_rows(self.k_scales[layer], pg, sl,
                                               ksc)
            self.v_scales[layer] = append_rows(self.v_scales[layer], pg, sl,
                                               vsc)
            ks_att = dequantize_kv(k8, ksc, ks.dtype)
            vs_att = dequantize_kv(v8, vsc, vs.dtype)
            ks, vs = k8, v8
        else:
            ks_att, vs_att = ks, vs
        self.k_pages[layer] = append_rows(
            self.k_pages[layer], pg, sl,
            packed_k_rows(ks, self.k_pages[layer]))
        self.v_pages[layer] = append_rows(self.v_pages[layer], pg, sl, vs)
        return ks_att, vs_att

    def _layer_scales(self, layer):
        if self.k_scales is None:
            return None, None
        return self.k_scales[layer], self.v_scales[layer]

    def attend(self, q, k, v, window=None, scale=None, sinks=None):
        """``window``: the calling layer is a sliding-attention layer of
        that width.  The paged kernels apply it; the prefill modes'
        dense attention has none and refuses at trace time.  ``k`` /
        ``v`` None: nothing is appended, the queries attend what pool
        ``layer_idx`` holds for the rows (ragged step only).  ``scale``:
        the scores' scale where it is not the page head's width^-1/2.
        ``sinks``: a learned sink a query head (class docstring); ``v``
        may be narrower than ``k``."""
        layer = self.layer_idx
        sinks = _sink_array(sinks)
        if (k is None or scale is not None) and self.q_lens is None:
            raise NotImplementedError(_NO_SHARED.format(
                "the prefill / prefix / chunk_prefill programs" if
                self.prefill else "the decode programs"))
        if k is None:
            return self._attend_ragged(q, layer, window, scale, sinks)
        b, s = k.shape[0], k.shape[1]
        kvh, d = k.shape[2], k.shape[3]
        dv = v.shape[3]
        if self.prefill and (sinks is not None or dv != d):
            raise NotImplementedError(_NO_SPLIT.format(
                "the prefix-suffix attention of chunk_prefill / "
                "prefix_prefill / batch_context_prefill"
                if self.prefix_lens is not None else
                "the prefill program's dense flash attention"))
        if window is not None and self.prefill:
            raise NotImplementedError(_NO_WINDOW.format(
                "the prefix-suffix attention of chunk_prefill / "
                "prefix_prefill / batch_context_prefill"
                if self.prefix_lens is not None else
                "the prefill program's dense flash attention"))
        ks = jnp.swapaxes(k._data.reshape(b * s, kvh, d), 0, 1)
        vs = jnp.swapaxes(v._data.reshape(b * s, kvh, dv), 0, 1)
        ks_att, vs_att = self._scatter(layer, ks, vs)
        ksc, vsc = self._layer_scales(layer)
        kp, vp = self.k_pages[layer], self.v_pages[layer]
        if self.prefill:
            # the suffix attends its own (round-tripped, in the int8
            # mode) values — identical to the page contents, so chunked
            # prefill and replay reproduce decode-written KV exactly
            k_att = jnp.swapaxes(ks_att, 0, 1).reshape(b, s, kvh, d)
            v_att = jnp.swapaxes(vs_att, 0, 1).reshape(b, s, kvh, d)
            if self.prefix_lens is not None:
                return wrap_array(_prefix_suffix_attention(
                    q._data, k_att, v_att, kp, vp, self.tables,
                    self.prefix_lens, k_scales=ksc, v_scales=vsc))
            from ..nn import functional as F
            out, _ = F.flash_attention(q, wrap_array(k_att),
                                       wrap_array(v_att), causal=True)
            return out
        # ragged unified step (ISSUE 17): every row attends its OWN
        # span — decode rows, chunk spans and verify blocks mix in one
        # kernel call with per-row traced lengths and offsets on the
        # packed tokens (b of them, s == 1); the pad queries come back
        # as zeros and what the layers behind make of them is discarded
        # by the program's tail
        if self.q_lens is not None:
            return self._attend_ragged(q, layer, window, scale, sinks)
        # decode (``step``, the scan of ``multi_step``): one token a row
        out = paged_attention(q._data[:, 0], kp, vp, self.lens,
                              self.tables, k_scales=ksc,
                              v_scales=vsc, window=window, sinks=sinks)
        return wrap_array(out[:, None])

    def _attend_ragged(self, q, layer, window, scale, sinks=None):
        """The ragged step's kernel call against pool ``layer`` as it
        stands, on the packed tokens: the kernel finds each row's queries
        at the row's offset and writes its outputs back there."""
        ksc, vsc = self._layer_scales(layer)
        # queries against K rows that hold several heads are packed HERE,
        # on the step's tokens
        out = paged_attention_ragged(
            packed_queries(q._data[:, 0], self.k_pages[layer],
                           self.v_pages[layer]),
            self.k_pages[layer], self.v_pages[layer], self.lens,
            self.q_lens, self.tables, scale=scale, k_scales=ksc,
            v_scales=vsc, window=window, sinks=sinks, row_off=self.row_off,
            span=self.span)
        return wrap_array(out[:, None])


#: rows the feed's index and token vectors are padded to (the rows
#: bucket where that is more), so that neither of its two programs
#: follows BOTH steps' shapes: the gather compiles once a rows bucket of
#: the step fed from, the select once a (rows, span) of the step fed
FEED_ROWS = 256


@jax.jit
def _feed_tokens(prev_out, src):
    """(R,) the token each fed row continues from: row ``src`` of the
    earlier step's ``out`` (B',), still on the device; a row that is not
    fed (-1) reads row 0 and is not selected."""
    return jnp.take(prev_out, jnp.maximum(src, 0), axis=0)


@jax.jit
def _feed_ids(ids, tokens, src):
    """The (B, S) ``ids`` operand of a step with a predecessor in
    flight: a fed row's first token from ``tokens``, everything else as
    the host packed it."""
    b = ids.shape[0]
    return ids.at[:, 0].set(
        jnp.where(src[:b] >= 0, tokens[:b], ids[:, 0]))


class RaggedFlight:
    """One ragged step that is dispatched and not fetched
    (:meth:`JittedPagedDecoder.ragged_launch`): the program's three small
    outputs, still on the device; what undoes the step if they never
    arrive (``seq_ids`` at ``before``); and the step's OWN dispatch
    record, which becomes the decoder's ``last_dispatch`` when it is
    fetched (a later launch has started another by then)."""

    __slots__ = ("cache", "seq_ids", "before", "out", "accept", "counted",
                 "record")

    def __init__(self, cache, seq_ids, before, out, accept, counted,
                 record):
        self.cache, self.seq_ids, self.before = cache, seq_ids, before
        self.out, self.accept, self.counted = out, accept, counted
        self.record = record


class JittedPagedDecoder:
    """One-compiled-program decode step: embed + every layer's rope /
    paged write / paged attention / MLP + logits, with the page pools
    donated through the step.  Replaces per-op eager dispatch in the
    decode hot loop (dozens of ops x layers per generated token).

    Shared by PagedGenerator and ContinuousBatchingEngine; retraces per
    (batch, pool-shape) signature and reuses the compile cache after.

    Quantized serving (ISSUE 9): ``quantize="w8"`` swaps every Linear
    projection's weight for a per-out-channel int8 twin inside the
    compiled programs (the streaming weight-only kernel;
    ``quantization.serving`` calibrates the scales through the PTQ
    observers); ``"w8a8"`` adds dynamic per-token activation
    quantization in-program.  The scales ride as TRACED arguments —
    never baked consts — so one compiled program serves any
    calibration.  An int8 cache (``PagedKVCache(kv_dtype="int8")``)
    composes orthogonally: its scale pools are donated through every
    program beside the data pools.

    ``step_tokens`` is a caller's promise about ``ragged_step``: no
    step carries more tokens.  The ragged programs then run everything
    but the paged kernel over that many packed positions instead of
    the whole (rows, span) rectangle (``packed_tokens``); the engine
    derives it from its planner's options
    (``ContinuousBatchingEngine._step_token_bound``).
    """

    #: per-mode donated arg positions (page pools + scale pools) —
    #: shared between the jit call and the analysis auditor so both
    #: see one contract.  The scale-pool slots hold empty tuples (no
    #: leaves) for full-precision caches.  A model with a recurrent state
    #: adds its slot pools to the ragged program's (slot 14, behind the
    #: weight scales: the signature every other caller knows is kept).
    DONATE_ARGNUMS = {"decode": (8, 9, 10, 11), "prefill": (6, 7, 8, 9),
                      "prefix": (8, 9, 10, 11), "ragged": (9, 10, 11, 12)}

    def __init__(self, model, min_table_pages: int = 1,
                 quantize: Optional[str] = None, mesh=None,
                 tp_quant_collectives: bool = False,
                 step_tokens: Optional[int] = None):
        from ..quantization.serving import SERVING_QUANT_MODES
        if quantize not in SERVING_QUANT_MODES:
            raise ValueError(
                f"quantize must be one of {SERVING_QUANT_MODES}, got "
                f"{quantize!r}")
        self.model = model
        self.params = model.parameters()
        self.max_position = int(model.config.max_position_embeddings)
        # what each paged call looks like (``paged_layout``), for the
        # dispatch record's count of the kernels' walk: how many calls
        # there are of each (query heads a page's KV head, window or None,
        # walks a pool another call opened, the call's pool's own KV heads,
        # K width and V width, a learned sink).  A model whose layers carry a
        # recurrent state a sequence says so (``recurrent_state``: layers,
        # a slot's arrays, the bytes of them the equations count): its
        # ragged program takes the slot pools as one more donated operand
        # and hands them back
        layout = paged_layout(model)
        self._state = layout["state"]
        if self._state is not None:
            self.DONATE_ARGNUMS = dict(self.DONATE_ARGNUMS,
                                       ragged=(9, 10, 11, 12, 14))
        self._attn_kinds = {}
        order = {p: i for i, p in enumerate(dict.fromkeys(
            pool for _, _, pool, _ in layout["calls"]))}
        for (heads, window, pool, shared), sinks in zip(layout["calls"],
                                                        layout["sinks"]):
            kv_heads, k_dim, v_dim = layout["pool_shapes"][order[pool]]
            kind = (heads // kv_heads, window, shared, kv_heads, k_dim,
                    v_dim, sinks)
            self._attn_kinds[kind] = self._attn_kinds.get(kind, 0) + 1
        # the names of what the model counts in a ragged step
        # (``_TracedPagedContext.count``), noted when a program is traced
        self._step_counts = ()
        self.quantize = quantize
        # tensor-parallel serving (ISSUE 20): every compiled program is
        # shard_map'd over the ('tensor',) mesh — weights land as their
        # Megatron twins, pools shard on the kv-head axis, and exactly
        # one all-reduce per block closes the row-parallel matmuls.
        # Committing the params here (device_put with NamedShardings)
        # is load-bearing three ways: each chip holds 1/tp of the
        # sharded weights, the jit input shardings are pinned so no
        # per-dispatch transfer sneaks in, and the analysis auditor's
        # engine_program_spec copies the placements into its abstract
        # args — which is what auto-triggers the tier-3 SPMD audit.
        if mesh is not None and int(mesh.size) <= 1:
            mesh = None                  # a mesh of one is the 1-chip path
        self.mesh = mesh
        self.tp = int(mesh.size) if mesh is not None else 1
        self.tp_quant_collectives = bool(tp_quant_collectives and
                                         mesh is not None)
        if mesh is not None:
            if quantize is not None:
                raise ValueError(
                    "quantize='w8'/'w8a8' does not compose with a "
                    "tensor-parallel mesh yet: the int8 weight twins "
                    "are calibrated per full out-channel and the "
                    "streaming kernel is single-chip (documented "
                    "limitation; kv_quant='int8' DOES compose)")
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            spec_by_id, self._tp_row_layers, self._tp_attn = \
                _tp_plan(model, mesh)
            self._tp_param_specs = [spec_by_id.get(id(p), P())
                                    for p in self.params]
            for p, spec in zip(self.params, self._tp_param_specs):
                p._data = jax.device_put(p._data,
                                         NamedSharding(mesh, spec))
            self._tp_reduce_fn = self._make_tp_reduce()
        else:
            self._tp_row_layers = []
            self._tp_attn = []
            self._tp_param_specs = []
            self._tp_reduce_fn = None
        if quantize is not None:
            from ..quantization.serving import quantize_linear_weights
            self._quant = quantize_linear_weights(model)
            by_id = {id(layer.weight): qi
                     for qi, (layer, _, _) in enumerate(self._quant)}
            # param-list position -> quant entry, so _param_arrays can
            # substitute the int8 twins in place
            self._quant_idx = {i: by_id[id(p)]
                               for i, p in enumerate(self.params)
                               if id(p) in by_id}
        else:
            self._quant = []
            self._quant_idx = {}
        # page-table width floor: with the default 1 the table width is
        # next_pow2(longest sequence's pages), which recompiles the
        # decode/chunk/ragged programs every time the running batch
        # crosses a width bucket; pinning it at the pool's worst case
        # (ceil(max_position / page_size) rounded up) trades a bounded
        # amount of gather work for a FIXED program signature — the
        # scenario-matrix serving lane runs mixed short/long traffic
        # compile-free this way
        self.min_table_pages = max(1, int(min_table_pages))
        # the most tokens (pad rows' one each included) the caller will
        # ever hand one ``ragged_step``: the width the ragged programs'
        # dense layers are packed to (``packed_tokens``).  None = no
        # promise: they compute the whole (rows, span) rectangle
        self.step_tokens = None if step_tokens is None else int(step_tokens)
        self._programs = {}              # (mode, sample) -> jitted fn
        self._program_fns = {}           # (mode, sample) -> raw traced fn
        self._jitted_multi = None        # built on first multi_step use
        self.last_dispatch = None        # the last FETCHED step's record
        # seconds by span name, added to by the spans of ``ragged_launch``
        # and ``ragged_fetch`` (``monitor.span(..., into=)``); whoever
        # drives the decoder reads and clears it (the engine: once an
        # iteration, into its phase counters)
        self.host_seconds: dict = {}
        self._feed_warm = set()          # (rows, span) whose feed compiled

    # -------------------------------------------------- compiled programs
    def packed_tokens(self, rows: int, span: int) -> int:
        """Positions the dense layers of the (rows, span) ragged program
        compute: the rectangle, or ``step_tokens`` rounded up to the
        bf16 tile where that is less (never under one a row).  A
        function of the program's key, so it adds no key."""
        if self.step_tokens is None:
            return rows * span
        return min(rows * span, max(rows, _round_up(self.step_tokens, 16)))

    def _param_arrays(self):
        """The param operands a program call ships: the model's arrays,
        with quantized Linears' weights replaced by their int8 twins —
        half (vs bf16) or a quarter (vs f32) of the weight HBM traffic
        the decode step streams."""
        if not self.quantize:
            return [p._data for p in self.params]
        return [self._quant[self._quant_idx[i]][1]
                if i in self._quant_idx else p._data
                for i, p in enumerate(self.params)]

    def _wscale_args(self):
        """Per-out-channel weight scales as one traced tuple operand
        (empty when unquantized)."""
        return tuple(s for _, _, s in self._quant)

    def _pool_args(self, cache):
        """(k_pages, v_pages, k_scales, v_scales) operand tuples — the
        scale tuples are empty for full-precision caches, so one
        program signature covers both storage modes."""
        return (tuple(cache.k_pages), tuple(cache.v_pages),
                tuple(cache.k_scales), tuple(cache.v_scales))

    @staticmethod
    def _store_pools(cache, k_pages, v_pages, k_scales, v_scales,
                     states=None):
        cache.k_pages = list(k_pages)
        cache.v_pages = list(v_pages)
        if cache.kv_quant:
            cache.k_scales = list(k_scales)
            cache.v_scales = list(v_scales)
        if states is not None:
            cache.state_pools = list(states)

    def _swap_params(self, param_arrays, wscales=()):
        saved = [p._data for p in self.params]
        for p, a in zip(self.params, param_arrays):
            p._data = a
        if wscales:
            # arm the Linear hook: mode + TRACED scale per layer —
            # cleared by _restore_params so nothing leaks outside the
            # program trace
            for (layer, _, _), s in zip(self._quant, wscales):
                layer._serving_quant = (self.quantize, s)
        if self.mesh is not None:
            # TP trace arming (same trace-time pattern as the quant
            # hook): inside the shard_map body the swapped param arrays
            # are LOCAL shards, so each attention module's head counts
            # drop to their per-chip values for the duration of the
            # trace, and the row-parallel projections get the mesh
            # all-reduce that closes their partial sums
            tp = self.tp
            for attn in self._tp_attn:
                attn._tp_saved_heads = (attn.num_heads, attn.num_kv_heads)
                attn.num_heads //= tp
                attn.num_kv_heads //= tp
            for layer in self._tp_row_layers:
                layer._tp_reduce = self._tp_reduce_fn
        return saved

    def _restore_params(self, saved):
        for p, s in zip(self.params, saved):
            p._data = s
        for layer, _, _ in self._quant:
            layer._serving_quant = None
        if self.mesh is not None:
            for attn in self._tp_attn:
                attn.num_heads, attn.num_kv_heads = attn._tp_saved_heads
            for layer in self._tp_row_layers:
                layer._tp_reduce = None

    def _make_tp_reduce(self):
        """The all-reduce closing each row-parallel block: a plain f32
        ``psum`` by default, or (``tp_quant_collectives=True``) the
        EQuARX-style int8 variant — absmax-scale the local partial sum
        to s8, all-gather the int8 shards + f32 scales over 'tensor',
        dequantize and sum locally.  On the ring that moves (n-1)·S
        bytes against the f32 psum's 2·(n-1)/n·4S — 8/n fewer, the
        EQuARX 4x at tp=2 — at the cost of one absmax round-trip of
        numeric error per block, which is why it sits behind a knob
        that defaults OFF and the logits escape hatch is the parity
        oracle for it."""
        if not self.tp_quant_collectives:
            return lambda x: jax.lax.psum(x, "tensor")
        tp = self.tp

        def quant_psum(x):
            amax = jnp.max(jnp.abs(x))
            scale = jnp.maximum(amax, 1e-8) / 127.0
            q = jnp.clip(jnp.round(x / scale),
                         -127.0, 127.0).astype(jnp.int8)
            qg = jax.lax.all_gather(q, "tensor")        # (tp, ...) s8
            sg = jax.lax.all_gather(scale, "tensor")    # (tp,) f32
            return jnp.sum(
                qg.astype(x.dtype)
                * sg.astype(x.dtype).reshape((tp,) + (1,) * x.ndim),
                axis=0)

        return quant_psum

    #: replicated positional args between ``param_arrays`` and the pool
    #: tuple, per program mode — the shard_map in_specs contract
    #: (everything host-shaped rides replicated; pools shard on the
    #: kv-head axis; the param list gets its per-param spec list)
    _TP_N_REPLICATED = {"decode": 7, "prefill": 5, "prefix": 7,
                        "ragged": 8}

    def _mesh_wrap(self, mode, fn):
        """shard_map a program body over the tensor mesh (identity on
        the 1-chip decoder).  in/out specs are pytree prefixes: P()
        broadcasts over the sampling tuple and the (possibly empty)
        wscales tuple, P('tensor') over each per-layer pool tuple —
        rank-4 pools shard dim 0, the kv-head axis.  Replication checks
        are off (the compat wrapper maps check_vma across jax
        versions): the outputs ARE replicated by construction — every
        chip holds the full hidden state after each block's closing
        all-reduce, so logits, accept arithmetic and the fused sampling
        tail compute identically everywhere."""
        if self.mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P
        from ..framework.jax_compat import shard_map
        rep, pool = P(), P("tensor")
        in_specs = (list(self._tp_param_specs),
                    *([rep] * self._TP_N_REPLICATED[mode]),
                    pool, pool, pool, pool, rep)
        n_out = 3 if mode == "ragged" else 1
        out_specs = (*([rep] * n_out), pool, pool, pool, pool)
        return shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def _program(self, mode: str, sample):
        """Lazily build one compiled program per (mode, sample) pair.
        ``sample`` is the static tail kind: "draw" ends in the full
        fused_sample tail, "greedy" in a bare argmax (same (batch,)
        int32 host transfer, none of the threefry/categorical compute —
        all-greedy batches are the serving default), and False keeps
        returning full last-token logits (the escape hatch the
        eager-oracle parity tests diff against)."""
        key = (mode, sample)
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        model = self.model

        def tail(logits, sampling):
            if sample == "draw":
                return fused_sample(logits, *sampling)
            if sample == "greedy":
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return logits

        def last_logits(hidden, last_idx):
            # per-row last REAL position (bucketed prompts are
            # right-padded past it)
            b = hidden.shape[0]
            last = hidden._data[jnp.arange(b), last_idx.astype(jnp.int32)]
            logits = model._logits_of(wrap_array(last[:, None]))
            return logits._data[:, -1].astype(jnp.float32)

        def ctx_pools(ctx):
            return (tuple(ctx.k_pages), tuple(ctx.v_pages),
                    tuple(ctx.k_scales or ()), tuple(ctx.v_scales or ()))

        if mode == "decode":
            def fn(param_arrays, tokens, pos, pg, sl, lens, tables,
                   sampling, k_pages, v_pages, k_scales, v_scales,
                   wscales):
                saved = self._swap_params(param_arrays, wscales)
                try:
                    ctx = _TracedPagedContext(k_pages, v_pages, pg, sl,
                                              lens, tables,
                                              k_scales=k_scales,
                                              v_scales=v_scales)
                    with no_grad():
                        hidden = model.model(wrap_array(tokens), pos,
                                             paged_ctx=ctx)
                        logits = model._logits_of(hidden)
                    return (tail(logits._data[:, -1].astype(jnp.float32),
                                 sampling),
                            *ctx_pools(ctx))
                finally:
                    self._restore_params(saved)

        elif mode == "prefill":
            def fn(param_arrays, ids, last_idx, pg, sl, sampling,
                   k_pages, v_pages, k_scales, v_scales, wscales):
                saved = self._swap_params(param_arrays, wscales)
                try:
                    ctx = _TracedPagedContext(k_pages, v_pages, pg, sl,
                                              prefill=True,
                                              k_scales=k_scales,
                                              v_scales=v_scales)
                    with no_grad():
                        hidden = model.model(wrap_array(ids), 0,
                                             paged_ctx=ctx)
                        logits = last_logits(hidden, last_idx)
                    return (tail(logits, sampling), *ctx_pools(ctx))
                finally:
                    self._restore_params(saved)

        elif mode == "prefix":
            def fn(param_arrays, ids, last_idx, pg, sl, ptabs,
                   plens, sampling, k_pages, v_pages, k_scales,
                   v_scales, wscales):
                saved = self._swap_params(param_arrays, wscales)
                try:
                    ctx = _TracedPagedContext(k_pages, v_pages, pg, sl,
                                              tables=ptabs, prefill=True,
                                              prefix_lens=plens,
                                              k_scales=k_scales,
                                              v_scales=v_scales)
                    with no_grad():
                        # plens doubles as the per-row rope offset: the
                        # suffix starts right after the cached prefix
                        # (traced, so one compile serves every prefix
                        # length at a given bucket shape)
                        hidden = model.model(wrap_array(ids), plens,
                                             paged_ctx=ctx)
                        logits = last_logits(hidden, last_idx)
                    return (tail(logits, sampling), *ctx_pools(ctx))
                finally:
                    self._restore_params(saved)

        elif mode == "ragged":
            def fn(param_arrays, ids, ctx_lens, q_lens, pg, sl, tables,
                   nd, sampling, k_pages, v_pages, k_scales, v_scales,
                   wscales, states=(), recur=()):
                """Ragged UNIFIED serving step (ISSUE 17): one compiled
                dispatch processes a batch mixing decode rows
                (q_len 1), prefill/chunk spans, and speculative verify
                blocks (q_len = nd + 1).  The operands are the (B, S)
                rectangle, each row's span LEFT-aligned in it;
                ``ctx_lens`` is the pre-write cached length (the row's
                first rope position), ``q_lens`` the span length,
                ``nd`` the draft count (0 for non-verify rows, which
                makes the accept arithmetic degenerate to 'pick the
                last real token').

                The program runs the model over ONE axis of
                ``packed_tokens(B, S)`` positions, as (T, 1): embedding,
                projections, feed-forward, norms, the head and the
                argmax compute T positions, the append takes them as
                they come, and so does the paged kernel, which is told
                where each row starts (``_TracedPagedContext.attend``):
                no activation has the rectangle's size.  Where
                T is less than B x S the step's tokens are PACKED onto
                it — row 0's span, row 1's, ..., then pad (id 0, the
                dropped page, position 0); where it is not, the axis is
                the rectangle itself, row-major, nothing moved.
                Accept lengths and the output token's position select
                ON DEVICE, so the host boundary stays (B,) ids + (B,)
                accepts whatever the batch mixes.

                ``states`` and ``recur`` are a recurrent model's: its
                slot pools (donated, handed back last) and (the rows'
                slots (B,), the rows of several tokens (C,), the
                cache's page count ())."""
                saved = self._swap_params(param_arrays, wscales)
                try:
                    b, s = ids.shape
                    t = self.packed_tokens(b, s)
                    # the page a pad position writes to and loses: past
                    # the pool's last (a model with no page pool is told)
                    drop = k_pages[0].shape[1] if k_pages else recur[2]
                    # ids, write targets and rope positions of the
                    # rectangle, taken to the packed axis together
                    cols = jnp.stack(
                        [ids, pg.reshape(b, s), sl.reshape(b, s),
                         ctx_lens[:, None]
                         + jnp.arange(s, dtype=jnp.int32)[None, :]], axis=-1)
                    if t < b * s:
                        # the pack bites: rows stand together from the
                        # start, and past the step's tokens stands pad
                        off = (jnp.cumsum(q_lens) - q_lens).astype(jnp.int32)
                        real = jnp.arange(t, dtype=jnp.int32) \
                            < jnp.sum(q_lens)
                        cols = jnp.where(
                            real[:, None], _packed_of_rows(cols, off, t),
                            jnp.zeros(4, jnp.int32).at[1].set(drop))
                    else:
                        # the packed axis holds the whole rectangle:
                        # every row keeps its place, the host's pads too
                        off = None
                        cols = _packed_of_rows(cols, off, t)
                    ctx = _TracedPagedContext(
                        k_pages, v_pages, cols[:, 1], cols[:, 2],
                        ctx_lens + q_lens, tables, q_lens=q_lens,
                        row_off=off, span=s, k_scales=k_scales,
                        v_scales=v_scales, states=states, n_pages=drop,
                        **(dict(slots=recur[0], chunk_rows=recur[1])
                           if recur else {}))
                    with no_grad():
                        hidden = model.model(wrap_array(cols[:, :1]),
                                             cols[:, 3], paged_ctx=ctx)
                        logits = model._logits_of(hidden)
                    lg = logits._data[:, 0].astype(jnp.float32)  # (T, V)
                    # each row's own targets, back in the rectangle (a
                    # row's tail holds its successors': never read)
                    targets = _rows_of_packed(
                        jnp.argmax(lg, axis=-1).astype(jnp.int32), off, s)
                    # verify-row accept arithmetic, gated to the first
                    # nd positions so chunk/decode rows (nd == 0) can
                    # never 'accept' their own prompt tokens
                    j = jnp.arange(1, s, dtype=jnp.int32)[None, :]
                    match = ((ids[:, 1:] == targets[:, :-1])
                             & (j <= nd[:, None])).astype(jnp.int32)
                    accept = jnp.sum(jnp.cumprod(match, axis=1),
                                     axis=1).astype(jnp.int32)  # (B,)
                    # the row's OUTPUT position: last real token for
                    # decode/chunk rows (q_lens - 1), the bonus
                    # position (accept) for verify rows
                    sel = (q_lens - 1 - nd + accept).astype(jnp.int32)
                    # what the model counted rides out beside the
                    # accepts: an empty tuple (no output at all) for a
                    # model that counts nothing
                    self._step_counts, counted = ctx.counted()
                    rest = (counted, *ctx_pools(ctx))
                    if self._state is not None:
                        rest += (tuple(ctx.states),)
                    if sample == "greedy":
                        ids_out = jnp.take_along_axis(
                            targets, sel[:, None], axis=1)[:, 0]
                        return ids_out, accept, *rest
                    first = off if off is not None else \
                        jnp.arange(b, dtype=jnp.int32) * s
                    lg_sel = lg[first + sel]                    # (B, V)
                    if sample == "draw":
                        seeds, temps, flags = sampling
                        # absolute position of the emitted token —
                        # ctx + q_len for decode/chunk rows, the
                        # bonus position ctx + accept + 1 for verify
                        # rows: the SAME (seed, position) threefry
                        # draw the decode and prefill programs make
                        ctrs = (ctx_lens + q_lens - nd
                                + accept).astype(jnp.int32)
                        ids_out = fused_sample(lg_sel, seeds, ctrs,
                                               temps, flags)
                        return ids_out, accept, *rest
                    return lg_sel, accept, *rest  # logits escape hatch
                finally:
                    self._restore_params(saved)

        else:
            raise ValueError(f"unknown program mode {mode!r}")
        # TP: the shard_map wrapping applies to the RAW fn so the
        # auditor's program_fn trace sees the sharded program too —
        # donation stays at the jit level, aliasing the global sharded
        # pool buffers through the step exactly as on one chip
        fn = self._mesh_wrap(mode, fn)
        prog = jax.jit(fn, donate_argnums=self.DONATE_ARGNUMS[mode])
        self._program_fns[key] = fn
        self._programs[key] = prog
        return prog

    def program_fn(self, mode: str, sample):
        """(raw traced fn, donate_argnums) for a program — the analysis
        auditor's entry: ``jax.make_jaxpr`` over this fn with abstract
        args sees exactly what the jitted program compiles, without
        running anything (paddle_tpu.analysis.audit_engine)."""
        self._program(mode, sample)
        return self._program_fns[(mode, sample)], \
            self.DONATE_ARGNUMS[mode]

    @staticmethod
    def _recover_pools(cache, ran=False):
        """After a failed compiled call, rebuild the page pools ONLY if
        the donated buffers were actually consumed (dispatch reached
        the device/runtime).  A host-side failure before dispatch — a
        planning bug, an injected fault, a shape error — leaves them
        valid, and keeping them preserves every OTHER sequence's cached
        KV and the prefix index: the quarantine machinery (ISSUE 4)
        depends on a poisoned request not zeroing its batchmates'
        state.

        ``ran``: the call was dispatched and its outputs never arrived
        (:meth:`ragged_fetch`).  The cache then holds the program's
        returned pools: they are rebuilt if the execution left an error
        in them, and always where rows hold a recurrent slot, which the
        step updated in place and no length rolls back."""
        def dead(a):
            fn = getattr(a, "is_deleted", None)
            try:
                return bool(fn()) if callable(fn) else False
            except Exception:   # noqa: BLE001 — treat unknown as dead
                return True
        pools = cache._device_pools()
        lost = any(dead(a) for a in pools)
        if ran and not lost:
            try:
                jax.block_until_ready(pools)
                lost = bool(cache.state_pools)
            except Exception:   # noqa: BLE001 — the execution's own error
                lost = True
        if lost:
            cache.reset_pools()

    def _rollback_lengths(self, cache, seq_ids, before):
        """Undo this call's ``advance`` after a failed compiled step so
        the sequences sit at their pre-call lengths and the SAME step
        can be retried (ISSUE 4 failure isolation: the engine's
        retry/bisect replays depend on this).  Pages allocated for the
        call stay mapped — they are within the admission reservation
        and the retry rewrites their slots."""
        for sid, n in zip(seq_ids, before):
            cache.truncate(sid, n)

    @staticmethod
    def _sampling_args(sampling):
        if sampling is None:
            return False, ()
        seeds, ctrs, temps, flags = sampling
        if not np.any(flags):
            return "greedy", ()      # argmax-only tail, no RNG compute
        return "draw", (jnp.asarray(np.asarray(seeds, np.uint32)),
                        jnp.asarray(np.asarray(ctrs, np.int32)),
                        jnp.asarray(np.asarray(temps, np.float32)),
                        jnp.asarray(np.asarray(flags, bool)))

    @staticmethod
    def _pad_prefill_plan(cache, ids_np, pg, sl, b, s, s_b):
        """Right-pad a bucketed prompt's ids and (page, slot) targets;
        pad positions scatter to an out-of-bounds page (dropped)."""
        pad = s_b - s
        ids_np = np.pad(ids_np, ((0, 0), (0, pad)))
        pg = np.concatenate(
            [pg.reshape(b, s),
             np.full((b, pad), cache.total_pages, np.int32)],
            axis=1).reshape(-1)
        sl = np.concatenate(
            [sl.reshape(b, s), np.zeros((b, pad), np.int32)],
            axis=1).reshape(-1)
        return ids_np, pg, sl

    def prefill(self, cache: PagedKVCache, seq_ids, ids_np,
                bucket: bool = False, sampling=None) -> np.ndarray:
        """Prompt pass as ONE compiled program: embed + all layers
        (dense causal flash + paged KV writes) + last-token logits.

        ids_np (batch, s) int32, all rows the same real length s.  With
        ``bucket=True`` the sequence pads right to a power of two so the
        engine's per-request prefills compile once per bucket, not once
        per prompt length; pad positions scatter to an out-of-bounds
        page (dropped) and sit after every real token (causal-masked).
        Returns last-real-token logits (batch, vocab) float32 — or,
        with ``sampling=(seeds, ctrs, temps, flags)``, the fused-sampled
        first token ids (batch,) int32 (the logits never leave device).
        """
        b, s = ids_np.shape
        if s > self.max_position:
            raise ValueError(
                f"prompt length {s} exceeds max_position_embeddings "
                f"({self.max_position})")
        before = [cache.length(sid) for sid in seq_ids]
        for sid in seq_ids:
            cache.allocate(sid, s)
        pg, sl = cache.plan_write(seq_ids, s)
        cache.advance(seq_ids, s)
        s_b = s
        if bucket:
            # never pad past the rope table: a 600-token prompt on a
            # 1000-position model must bucket to 1000, not 1024
            s_b = min(next_pow2(s), self.max_position)
        if s_b != s:
            ids_np, pg, sl = self._pad_prefill_plan(cache, ids_np, pg, sl,
                                                    b, s, s_b)
        last_idx = np.full(b, s - 1, np.int32)
        sample, s_args = self._sampling_args(sampling)
        try:
            _maybe_lose_buffers(cache, seq_ids)
            out, *pools = self._program("prefill", sample)(
                self._param_arrays(),
                jnp.asarray(ids_np.astype(np.int32)),
                jnp.asarray(last_idx), jnp.asarray(pg), jnp.asarray(sl),
                s_args, *self._pool_args(cache), self._wscale_args())
        except BaseException:
            self._recover_pools(cache)
            self._rollback_lengths(cache, seq_ids, before)
            raise
        self._store_pools(cache, *pools)
        return np.asarray(out)

    def prefix_prefill(self, cache: PagedKVCache, seq_ids, ids_np,
                       prefix_tokens: int, bucket: bool = True,
                       sampling=None) -> np.ndarray:
        """Suffix-only prompt pass for sequences whose first
        ``prefix_tokens`` prompt tokens (page-aligned) are already
        cached — the prefix-cache TTFT win: only the suffix runs
        through the model, attending to the gathered prefix pages.

        Every sequence must already hold its shared prefix pages at
        length ``prefix_tokens`` (PagedKVCache.acquire_prefix).  ids_np
        (batch, s) int32 is the UNCACHED prompt tail.  Returns logits
        (batch, vocab) f32, or sampled ids (batch,) with ``sampling``.
        """
        k = int(prefix_tokens)
        if k <= 0 or k % cache.page_size:
            raise ValueError(
                f"prefix_tokens must be a positive multiple of the page "
                f"size ({cache.page_size}), got {k}")
        return self._context_prefill(cache, seq_ids, ids_np, k, bucket,
                                     sampling)

    def chunk_prefill(self, cache: PagedKVCache, seq_ids, ids_np,
                      context_tokens: int, bucket: bool = True,
                      sampling=None) -> np.ndarray:
        """Chunked-prefill continuation (ISSUE 7): ingest the next
        ``ids_np`` (batch, s) slice of a prompt whose first
        ``context_tokens`` tokens are already in the cache, at ANY
        length — unlike :meth:`prefix_prefill` the context need not be
        page-aligned, because the sequence OWNS its pages (a partially
        filled page is never shared; the chunk's first tokens simply
        fill its remaining slots).  Same compiled program as the
        prefix path (the context length is traced), so interleaving
        chunk sizes never multiplies program count."""
        k = int(context_tokens)
        if k <= 0:
            raise ValueError(
                f"context_tokens must be positive, got {k} (use "
                "prefill() for a fresh sequence)")
        return self._context_prefill(cache, seq_ids, ids_np, k, bucket,
                                     sampling)

    def _context_prefill(self, cache: PagedKVCache, seq_ids, ids_np,
                         k: int, bucket: bool, sampling) -> np.ndarray:
        b, s = ids_np.shape
        if k + s > self.max_position:
            raise ValueError(
                f"prompt length {k + s} exceeds max_position_embeddings "
                f"({self.max_position})")
        before = []
        for sid in seq_ids:
            if cache.length(sid) != k:
                raise ValueError(
                    f"sequence {sid!r} is at length {cache.length(sid)}, "
                    f"expected the cached context length {k}")
            before.append(cache.length(sid))
            cache.allocate(sid, s)
        pg, sl = cache.plan_write(seq_ids, s)
        cache.advance(seq_ids, s)
        s_b = min(next_pow2(s), self.max_position - k) if bucket else s
        if s_b != s:
            ids_np, pg, sl = self._pad_prefill_plan(cache, ids_np, pg, sl,
                                                    b, s, s_b)
        # the context may end mid-page (chunked prefill): gather the
        # partial page too — attention masks cols past k, and this
        # chunk's own tokens reach themselves through the suffix path
        n_pre = -(-k // cache.page_size)
        ptabs = np.zeros(
            (b, max(next_pow2(n_pre), self.min_table_pages)), np.int32)
        for i, sid in enumerate(seq_ids):
            ptabs[i, :n_pre] = cache._seq_pages[sid][:n_pre]
        plens = np.full(b, k, np.int32)
        last_idx = np.full(b, s - 1, np.int32)
        sample, s_args = self._sampling_args(sampling)
        return self._dispatch_prefix(
            cache, seq_ids, before, sample, s_args,
            ids_np.astype(np.int32), last_idx, pg, sl, ptabs, plens)

    def _dispatch_prefix(self, cache, seq_ids, before, sample, s_args,
                         ids, last_idx, pg, sl, ptabs, plens):
        """The "prefix" program's dispatch + failure-recovery contract,
        shared by the uniform-context and batched (per-row ``ks``)
        prefill paths: on ANY failure the donated pools are recovered
        and the advanced lengths roll back to ``before`` — one
        implementation, so the recovery semantics can never drift
        between the two builders."""
        try:
            _maybe_lose_buffers(cache, seq_ids)
            out, *pools = self._program("prefix", sample)(
                self._param_arrays(), jnp.asarray(ids),
                jnp.asarray(last_idx),
                jnp.asarray(pg), jnp.asarray(sl), jnp.asarray(ptabs),
                jnp.asarray(plens), s_args,
                *self._pool_args(cache), self._wscale_args())
        except BaseException:
            self._recover_pools(cache)
            self._rollback_lengths(cache, seq_ids, before)
            raise
        self._store_pools(cache, *pools)
        return np.asarray(out)

    def batch_context_prefill(self, cache: PagedKVCache, seq_ids, rows,
                              ks, sampling=None) -> np.ndarray:
        """Batched context-prefill continuation (ISSUE 9 satellite:
        batched survivor replay): ingest ``rows[i]`` (a 1-D int32 token
        slice) for ``seq_ids[i]`` whose cached context length is
        ``ks[i]`` — ONE compiled dispatch for the whole batch, through
        the SAME traced "prefix" program the chunked/prefix prefill
        paths compile (context lengths and rope offsets are per-row
        TRACED values, so mixed-progress rows batch together).

        Rows right-pad to a power-of-two bucket (pad positions scatter
        to the dropped out-of-bounds page and are causality/last_idx-
        masked); ``ks[i] == 0`` rows ride the same program — a zero
        prefix length masks every prefix column, making the dispatch a
        fresh prefill for that row.  Returns the last-real-token output
        per row (ids under ``sampling``, logits otherwise)."""
        b = len(seq_ids)
        ns = [len(r) for r in rows]
        if b == 0 or min(ns) < 1:
            raise ValueError("every row needs at least one token")
        before = []
        for sid, k, n in zip(seq_ids, ks, ns):
            if cache.length(sid) != int(k):
                raise ValueError(
                    f"sequence {sid!r} is at length {cache.length(sid)}, "
                    f"expected the cached context length {k}")
            if int(k) + n > self.max_position:
                raise ValueError(
                    f"context {k} + chunk {n} exceeds "
                    f"max_position_embeddings ({self.max_position})")
            before.append(int(k))
            cache.allocate(sid, n)
        # never pad past the rope table when the bucket round-up is
        # what crosses it: clamp the bucket by the deepest context,
        # the SAME ``min(next_pow2(s), max_position - k)`` discipline
        # as _context_prefill — falling all the way back to the raw
        # max(ns) would trace a fresh prefix program per distinct
        # chunk length on the MTTR-critical recovery path.  With MIXED
        # context lengths a shallow-context row can still force
        # s_b > max_position - k for a DEEPER row (each row alone
        # validated k + n <= max_position) — that row's pad positions
        # gather CLAMPED rope angles, which is safe by construction:
        # pad K/V scatters to the dropped out-of-bounds page, pad
        # columns are causality-masked, and pad rows' outputs are
        # discarded (last_idx picks the real last token) — but nothing
        # downstream may ever start reading pad-position outputs.
        s_b = max(max(ns),
                  min(next_pow2(max(ns)),
                      self.max_position - max(int(k) for k in ks)))
        # the BATCH dimension buckets too (the decode path's
        # discipline): recovery waves of 3 and 4 survivors must share
        # one compiled (b, s_b, W) shape, not trace a fresh prefix
        # program per distinct survivor count on the MTTR-critical
        # path.  Pad rows have no sequence: their scatters drop on the
        # out-of-bounds page, plens 0 masks every prefix column, and
        # their outputs are sliced off before returning.
        b_b = next_pow2(b)
        ids = np.zeros((b_b, s_b), np.int32)
        pg = np.full((b_b, s_b), cache.total_pages, np.int32)  # drop
        sl = np.zeros((b_b, s_b), np.int32)
        for i, (sid, row, n) in enumerate(zip(seq_ids, rows, ns)):
            ids[i, :n] = np.asarray(row, np.int32)
            rpg, rsl = cache.plan_write([sid], n)
            pg[i, :n] = rpg
            sl[i, :n] = rsl
            cache.advance([sid], n)
        n_pre = max(1, max(-(-int(k) // cache.page_size) for k in ks))
        W = max(next_pow2(n_pre), self.min_table_pages)
        ptabs = np.zeros((b_b, W), np.int32)
        for i, (sid, k) in enumerate(zip(seq_ids, ks)):
            npg = -(-int(k) // cache.page_size)
            ptabs[i, :npg] = cache._seq_pages[sid][:npg]
        plens = np.zeros(b_b, np.int32)
        plens[:b] = np.asarray(ks, np.int32)
        last_idx = np.zeros(b_b, np.int32)
        last_idx[:b] = np.asarray([n - 1 for n in ns], np.int32)
        if sampling is not None and b_b != b:
            seeds, ctrs, temps, flags = sampling
            pad = b_b - b
            sampling = (
                np.concatenate([np.asarray(seeds, np.uint32),
                                np.zeros(pad, np.uint32)]),
                np.concatenate([np.asarray(ctrs, np.int32),
                                np.zeros(pad, np.int32)]),
                np.concatenate([np.asarray(temps, np.float32),
                                np.ones(pad, np.float32)]),
                np.concatenate([np.asarray(flags, bool),
                                np.zeros(pad, bool)]))
        sample, s_args = self._sampling_args(sampling)
        out = self._dispatch_prefix(
            cache, seq_ids, before, sample, s_args,
            ids, last_idx, pg.reshape(-1), sl.reshape(-1), ptabs, plens)
        return out[:b]

    @staticmethod
    def _ragged_sampling_args(sampling):
        """The ragged program's variant of ``_sampling_args``: no
        host-side counters — a row's draw position is ``ctx + span -
        drafts + accept``, computed IN-PROGRAM from the device-side
        accept length."""
        if sampling is None:
            return False, ()
        seeds, temps, flags = sampling
        if not np.any(flags):
            return "greedy", ()
        return "draw", (jnp.asarray(np.asarray(seeds, np.uint32)),
                        jnp.asarray(np.asarray(temps, np.float32)),
                        jnp.asarray(np.asarray(flags, bool)))

    def ragged_step(self, cache: PagedKVCache, seq_ids, rows, ctxs,
                    n_drafts=None, sampling=None):
        """ONE compiled dispatch for a RAGGED serving step (ISSUE 17):
        ``rows[i]`` is a 1-D int32 token span for ``seq_ids[i]`` whose
        cached context length is ``ctxs[i]`` — a decode row is the one
        last-sampled token, a prefill/chunk row is the next prompt
        slice, a speculative verify row is the last fed token followed
        by ``n_drafts[i]`` draft proposals.  All rows run through the
        single "ragged" program: per-row traced context lengths, span
        lengths and draft counts, so ANY mix compiles once per
        (B, S, W) bucket — rows, span and table width, each rounded up
        to a power of two.

        The (B, S) bucket is what the host hands over and what the
        paged kernel sees: spans right-pad to S (the kernel computes a
        row's own queries in whole tiles and writes zeros for the pad
        queries) and
        the batch pads with ctx-0 single-token rows exactly like
        ``batch_context_prefill``.  Everything else in the program runs
        over ``packed_tokens(B, S)`` positions: the rectangle's, or
        where ``step_tokens`` is less the step's tokens packed to that
        (pad positions there scatter to the dropped out-of-bounds
        page), so a step may not carry more than that:
        with ``step_tokens`` set, a step over it raises ``ValueError``
        before any page is reserved.  Page allocation is all-or-nothing
        across the batch (per-row counts), and on ANY failure the
        donated pools recover and every length rolls back to ``ctxs``
        so the engine can replay or decompose the step.

        Returns ``(out, accept)`` for the real rows: ``accept[i]``
        counts the leading draft tokens the target reproduced (0 for
        non-verify rows); ``out`` is the emitted token ids (batch,)
        int32 under ``sampling=(seeds, temps, flags)`` / greedy, or the
        selected position's logits rows on the ``sampling=None`` escape
        hatch.  The CALLER rolls verify rows back to their accepted
        length with ``cache.truncate(sid, ctx + accept + 1)`` (pages
        stay mapped inside the admission reservation, rejected slots
        are rewritten later).

        This is :meth:`ragged_launch` followed at once by
        :meth:`ragged_fetch`: a caller that has host work to do while
        the step runs calls the halves itself."""
        return self.ragged_fetch(
            self.ragged_launch(cache, seq_ids, rows, ctxs,
                               n_drafts=n_drafts, sampling=sampling))

    #: the composition above, for whoever must know that nothing stands in
    #: for it (the engine leaves a step in flight only then)
    _RAGGED_STEP = ragged_step

    def ragged_launch(self, cache: PagedKVCache, seq_ids, rows, ctxs,
                      n_drafts=None, sampling=None, feed=None, after=None):
        """The first half of :meth:`ragged_step`: check, reserve, pack
        and DISPATCH the step, and come back without waiting for it.
        The cache is advanced and holds the program's returned pools at
        once (they are its outputs whether or not it has run: the next
        call donates them in order); the three small outputs stay on the
        device in the :class:`RaggedFlight` handed back, which
        :meth:`ragged_fetch` turns into ``(out, accept)``.

        ``feed`` = (an earlier step's flight, not necessarily fetched;
        for each row here the index of the row in THAT step's ``out``
        whose emitted token is this row's first, or -1).  A fed row's
        first token is taken from the device array (``_feed_ids``: a
        small program of its own, so the ragged program and its operands
        are what they are without a feed), whatever ``rows`` holds
        there; the earlier step must have sampled (``out`` is ids).

        ``after`` = the flight of the step dispatched before this one
        and not fetched, if there is one.  Just before the program call
        its output is asked whether it has arrived (``is_ready()``: no
        wait, no transfer); if it has, or there is none, the device had
        nothing left to run when this step reached it: the record's
        ``late`` is 1, else 0."""
        into = self.host_seconds
        with monitor.span("engine/build", into=into):
            b = len(seq_ids)
            ns = [len(r) for r in rows]
            if b == 0 or min(ns) < 1:
                raise ValueError("every row needs at least one token")
            nds = ([0] * b if n_drafts is None
                   else [int(x) for x in n_drafts])
            if self._state is not None and any(nds):
                raise ValueError(
                    "a verify row cannot run against a recurrent state: a "
                    "rejected draft cannot be rolled out of it")
            before = []
            for sid, k, n, nd in zip(seq_ids, ctxs, ns, nds):
                if nd and n != nd + 1:
                    raise ValueError(
                        f"verify row for {sid!r} must be 1 fed token + "
                        f"{nd} drafts, got {n} tokens")
                if cache.length(sid) != int(k):
                    raise ValueError(
                        f"sequence {sid!r} is at length "
                        f"{cache.length(sid)}, expected the cached "
                        f"context length {k}")
                if int(k) + n > self.max_position:
                    raise ValueError(
                        f"context {k} + span {n} exceeds "
                        f"max_position_embeddings ({self.max_position})")
                before.append(int(k))
            # span bucket: clamp by the deepest context (the
            # batch_context_prefill discipline) so the round-up never
            # walks pad positions past the rope table on its own
            s_b = max(max(ns),
                      min(next_pow2(max(ns)),
                          self.max_position - max(int(k) for k in ctxs)))
            b_b = next_pow2(b)
            # what the program's dense layers are packed to; the pad
            # rows' one token each is packed with the rest
            t_b = self.packed_tokens(b_b, s_b)
            if sum(ns) + b_b - b > t_b:
                raise ValueError(
                    f"a step of {sum(ns)} tokens in {b} rows (padded to "
                    f"{b_b}) exceeds the {t_b} positions this decoder's "
                    f"ragged programs pack (step_tokens="
                    f"{self.step_tokens})")
            # all-or-nothing page reservation with PER-ROW counts: a
            # mid-batch exhaustion must not strand earlier rows' pages
            # (a pool with no free page evicts prefix-index entries here)
            evicted = cache.prefix_evictions
            with monitor.span("engine/build/reserve", into=into):
                cache.allocate_batch_atomic(seq_ids, ns)
            evicted = cache.prefix_evictions - evicted
            with monitor.span("engine/build/pack", into=into):
                ids = np.zeros((b_b, s_b), np.int32)
                pg = np.full((b_b, s_b), cache.total_pages, np.int32)  # drop
                sl = np.zeros((b_b, s_b), np.int32)
                for i, (sid, row, n) in enumerate(zip(seq_ids, rows, ns)):
                    ids[i, :n] = np.asarray(row, np.int32)
                    rpg, rsl = cache.plan_write([sid], n)
                    pg[i, :n] = rpg
                    sl[i, :n] = rsl
                    cache.advance([sid], n)
                needed = max(len(cache._seq_pages.get(sid, ()))
                             for sid in seq_ids)
                # a model with no K/V layer reads no table: one column, so
                # no program's shape follows the longest context
                W = (max(next_pow2(needed), self.min_table_pages)
                     if cache.num_layers else 1)
                tabs = np.zeros((b_b, W), np.int32)
                for i, sid in enumerate(seq_ids):
                    t = cache._seq_pages[sid][:W]
                    tabs[i, :len(t)] = t
                recur = ()
                if self._state is not None:
                    # the rows' slots (a pad row: the scratch slot) and the
                    # rows of several tokens, which take the chunk form: a
                    # static few, -1 where there are fewer (none when every
                    # row holds one token)
                    slots = np.full(b_b, cache.scratch_slot, np.int32)
                    slots[:b] = [cache.take_slot(sid) for sid in seq_ids]
                    multi = [i for i, n in enumerate(ns) if n > 1]
                    c_b = 0 if s_b == 1 else max(2, next_pow2(len(multi)))
                    chunk_rows = np.full(c_b, -1, np.int32)
                    chunk_rows[:len(multi)] = multi
                    recur = (slots, chunk_rows,
                             np.asarray(cache.total_pages, np.int32))
                ctx_arr = np.zeros(b_b, np.int32)
                ctx_arr[:b] = np.asarray([int(k) for k in ctxs], np.int32)
                ql = np.ones(b_b, np.int32)          # pad rows: 1-token span,
                ql[:b] = np.asarray(ns, np.int32)    # ctx 0, dropped scatter
                nd_arr = np.zeros(b_b, np.int32)
                nd_arr[:b] = np.asarray(nds, np.int32)
                if sampling is not None and b_b != b:
                    seeds, temps, flags = sampling
                    pad = b_b - b
                    sampling = (
                        np.concatenate([np.asarray(seeds, np.uint32),
                                        np.zeros(pad, np.uint32)]),
                        np.concatenate([np.asarray(temps, np.float32),
                                        np.ones(pad, np.float32)]),
                        np.concatenate([np.asarray(flags, bool),
                                        np.zeros(pad, bool)]))
        # what this dispatch computes against what it was asked for: the
        # engine writes it into the step ring as the ``dispatch`` record
        # (the flight's own: a later launch starts another).
        # ``rows_padded`` x ``span_padded`` is the program's bucket (no
        # activation of that size is made: the paged kernel takes a row's
        # queries from the packed tokens), ``tokens_padded`` the positions
        # every layer computes.  ``kv_tokens_walked`` is what the paged
        # kernel walks for these rows, each row's context in whole blocks, and
        # ``q_positions_computed`` the query positions it computes for
        # them, each row's own queries in whole tiles: the kernel's own
        # rules (pad rows are one token long)
        record = {
            "rows": b, "rows_padded": b_b, "span_padded": s_b,
            "tokens": sum(ns), "tokens_padded": t_b,
            "table_pages": W, "page_size": cache.page_size,
            "prefix_evicted": evicted,
            **self._walk_counts(cache, ctx_arr + ql, ql, s_b, b, W)}
        if self._state is not None:
            # the rows of several tokens (the chunk form; the program is
            # built for ``chunk_rows_padded`` of them) and their tokens,
            # the slots there are, and the rows that enter their slot
            # with an empty context: the step that zeroes it
            record.update(
                chunk_rows_padded=len(recur[1]),
                state_chunk_rows=len(multi),
                state_chunk_tokens=sum(ns[i] for i in multi),
                state_slots=cache.state_slots,
                slots_zeroed=sum(1 for k in ctxs if int(k) == 0))
        with monitor.span("engine/dispatch", into=into):
            sample, s_args = self._ragged_sampling_args(sampling)
            try:
                _maybe_lose_buffers(cache, seq_ids)
                if sample:
                    self._warm_feed(b_b, s_b)
                ids = jnp.asarray(ids)
                if feed is not None:
                    ids = self._fed_ids(ids, b, *feed)
                program = self._program("ragged", sample)
                operands = (
                    self._param_arrays(), ids,
                    jnp.asarray(ctx_arr), jnp.asarray(ql),
                    jnp.asarray(pg.reshape(-1)),
                    jnp.asarray(sl.reshape(-1)),
                    jnp.asarray(tabs), jnp.asarray(nd_arr), s_args,
                    *self._pool_args(cache), self._wscale_args(),
                    *((tuple(cache.state_pools),
                       tuple(jnp.asarray(a) for a in recur))
                      if recur else ()))
                # asked last, with every operand uploaded: what is left
                # between the answer and the device is the call itself
                record["late"] = int(after is None or after.out.is_ready())
                out, accept, counted, *pools = program(*operands)
            except BaseException:
                self._recover_pools(cache)
                self._rollback_lengths(cache, seq_ids, before)
                raise
            self._store_pools(cache, *pools)
        return RaggedFlight(cache, list(seq_ids), before, out, accept,
                            counted, record)

    def _warm_feed(self, rows: int, span: int) -> None:
        """The feed's two programs compile where the ragged program of
        their shape does: the first launch of a (rows, span) bucket runs
        the gather on a step of ``rows`` rows' ``out`` and the select on
        a (rows, span) ``ids``, over zeros and no fed row, so a window
        that meets no new ragged program meets no new feed either,
        whichever step feeds which."""
        if (rows, span) in self._feed_warm:
            return
        src = jnp.asarray(np.full(max(FEED_ROWS, rows), -1, np.int32))
        tokens = _feed_tokens(jnp.asarray(np.zeros(rows, np.int32)), src)
        _feed_ids(jnp.asarray(np.zeros((rows, span), np.int32)), tokens, src)
        self._feed_warm.add((rows, span))

    @staticmethod
    def _fed_ids(ids, b, flight, src):
        """``ids`` with each fed row's first token taken from
        ``flight.out`` on the device (``src``: the row's index there, or
        -1), the rest as the host packed them."""
        src = np.asarray(src, np.int32)
        if src.shape != (b,) or int(src.max(initial=-1)) >= len(
                flight.seq_ids):
            raise ValueError(
                f"the feed names rows {src.tolist()} of a step of "
                f"{len(flight.seq_ids)} rows, for {b} rows")
        if flight.out.ndim != 1:
            raise ValueError("a step that handed back logits has no token "
                             "on the device to feed the next one")
        padded = np.full(max(FEED_ROWS, ids.shape[0]), -1, np.int32)
        padded[:b] = src
        padded = jnp.asarray(padded)
        return _feed_ids(ids, _feed_tokens(flight.out, padded), padded)

    def ragged_fetch(self, flight: "RaggedFlight"):
        """The second half of :meth:`ragged_step`: wait for the step's
        three small outputs, finish its dispatch record (which becomes
        ``last_dispatch``) and hand back ``(out, accept)`` for its real
        rows.  If they do not arrive the step is undone as a failed
        ``ragged_step`` is (:meth:`ragged_discard`) and the error
        raised; a step launched after this one is the caller's to
        discard too."""
        with monitor.span("engine/fetch", into=self.host_seconds):
            try:
                out = np.asarray(flight.out)
                accept = np.asarray(flight.accept)
                counted = (np.asarray(flight.counted[0])
                           if flight.counted else ())
            except BaseException:
                self.ragged_discard(flight, failed=True)
                raise
            record = flight.record
            record.update(zip(self._step_counts, (int(v) for v in counted)))
            if self._state is not None:
                # the rows that carried a token, and the bytes of state
                # they read and wrote as the equations count them
                n = record.pop("state_row_layers", 0)
                record["state_rows"] = n // self._state["layers"]
                record["state_bytes"] = 2 * n * self._state["bytes"]
            self.last_dispatch = record
            b = len(flight.seq_ids)
            return out[:b], accept[:b]

    def ragged_discard(self, flight: "RaggedFlight", failed=False) -> None:
        """Undo a launched step that will not be fetched: its rows sit
        at their lengths from before it again, so the same step can be
        planned anew.  ``failed``: its outputs did not arrive, so what
        it left in the pools is not to be read either
        (:meth:`_recover_pools`)."""
        if failed:
            self._recover_pools(flight.cache, ran=True)
        self._rollback_lengths(flight.cache, flight.seq_ids, flight.before)

    def _walk_counts(self, cache, lens, q_lens, span, rows, table_pages):
        """The dispatch record's count of the paged kernels' work for a
        step whose padded rows hold ``lens`` positions after the write
        and ``q_lens`` queries, a LAYER's worth each (the mean over the
        layers where they differ): ``ctx_tokens`` the positions some
        query attends, ``kv_tokens_walked`` what the kernel walks for
        them in whole blocks, ``q_positions_computed`` the query
        positions it computes for them in whole tiles and
        ``q_positions_moved`` those it copies into VMEM (the same tiles,
        of the packed stream: never the bucket), ``page_copies``
        the copy descriptors its walks issue (one a page for each group of
        kv heads a grid step owns and each pool) and ``head_page_reads``
        the (page, head, pool) reads they serve, all by the kernel's own
        rule (a chip's own heads under ``tp``).  A model with sliding
        layers adds one sliding layer's own two counts
        (``..._window``), what that layer would have walked with no
        window (``kv_tokens_walked_nowindow``) and the pages its real
        rows hold wholly behind their next query's window
        (``kv_window_dead_pages``; a page two rows share counts
        twice).  A model some of whose calls walk a pool they do not own
        adds ``kv_tokens_walked_shared``: their part of
        ``kv_tokens_walked``, in the same unit.

        In BYTES, summed over ALL the step's calls and every kv head, each
        call's pool at its own page shape (K and V, the int8 scales apart):
        ``kv_bytes_copied_full`` / ``kv_bytes_copied_sliding`` what the
        full and the sliding calls' walks copy (``kv_pages_copied`` x the
        pool's bytes a page), ``kv_pinned_bytes`` what the pages the
        step's real rows map hold in every pool, and ``kv_dead_bytes`` the
        part of that in the sliding layers' own pools wholly behind the
        rows' next query's window: what a table a pool kind would free."""
        ps, total = cache.page_size, sum(self._attn_kinds.values())
        if not total:                   # no K/V layer: nothing is walked
            return {}
        means = ["ctx_tokens", "kv_tokens_walked", "q_positions_computed",
                 "q_positions_moved", "page_copies", "head_page_reads"]
        kv_dtype = cache.k_pages[0].dtype
        pools = 4 if cache.kv_quant else 2
        if any(kind[2] for kind in self._attn_kinds):
            means.append("kv_tokens_walked_shared")
        out = dict.fromkeys(means, 0)
        mapped = int((-(-lens[:rows] // ps)).sum())     # pages the rows map
        out.update(kv_bytes_copied_full=0, kv_bytes_copied_sliding=0,
                   kv_pinned_bytes=0, kv_dead_bytes=0)
        for kind, n in self._attn_kinds.items():
            group, window, shared, kv_heads, k_dim, v_dim, sinks = kind
            heads = kv_heads // cache.tp
            page_bytes = kv_heads * ps * (k_dim + v_dim) * kv_dtype.itemsize
            # the call's own cut (a span of one is the one-query kernel)
            shapes = (heads, ps, k_dim, span, group, kv_dtype,
                      cache.compute_dtype, v_dim, sinks, span > 1)
            _tile, block_pages, head_group = walk_cut(*shapes, window)
            block, steps = ps * block_pages, heads // head_group
            copied = kv_pages_copied(lens, ps, table_pages, window, q_lens)
            pages = pools * copied
            out["kv_bytes_copied_full" if window is None
                else "kv_bytes_copied_sliding"] += n * copied * page_bytes
            if not shared:              # the call's own pool
                out["kv_pinned_bytes"] += n * mapped * page_bytes
            # the real rows' context; a pad row's one position is walked
            seen = kv_tokens_visible(lens[:rows], q_lens[:rows], window)
            walked = kv_tokens_walked(lens, block, window, q_lens, ps)
            out["ctx_tokens"] += n * seen
            out["kv_tokens_walked"] += n * walked
            if shared:
                out["kv_tokens_walked_shared"] += n * walked
            for name, rule in (("q_positions_computed", q_positions_computed),
                               ("q_positions_moved", q_positions_moved)):
                out[name] += n * rule(q_lens, span, group,
                                      cache.compute_dtype)
            out["page_copies"] += n * steps * pages
            out["head_page_reads"] += n * heads * pages
            if window is not None:
                dead = int((np.maximum(lens[:rows] + 1 - window, 0)
                            // ps).sum())
                out.update(ctx_tokens_window=seen,
                           kv_tokens_walked_window=walked,
                           # in the blocks of a call with no window
                           kv_tokens_walked_nowindow=kv_tokens_walked(
                               lens, ps * walk_cut(*shapes)[1]),
                           kv_window_dead_pages=dead)
                if not shared:
                    out["kv_dead_bytes"] += n * dead * page_bytes
        for name in means:                                  # a layer's
            out[name] = (out[name] // total if len(self._attn_kinds) == 1
                         else out[name] / total)
        return out

    def _build_multi(self):
        """Jitted N-step GREEDY decode: lax.scan over the single-step
        body with the page pools as carry — N tokens per host dispatch
        instead of one: fusing the loop removes N-1 host
        synchronizations per chunk."""
        import jax
        from jax import lax

        def multi_fn(param_arrays, tokens0, pg_steps, sl_steps, pos_steps,
                     tables, k_pages, v_pages, k_scales, v_scales,
                     wscales):
            saved = self._swap_params(param_arrays, wscales)
            try:
                def body(carry, xs):
                    toks, kp, vp, ksc, vsc = carry
                    pg, sl, pos = xs
                    ctx = _TracedPagedContext(
                        list(kp), list(vp), pg, sl, pos + 1, tables,
                        k_scales=ksc, v_scales=vsc)
                    with no_grad():
                        hidden = self.model.model(
                            wrap_array(toks[:, None]), pos, paged_ctx=ctx)
                        logits = self.model._logits_of(hidden)
                    nxt = jnp.argmax(
                        logits._data[:, -1].astype(jnp.float32),
                        axis=-1).astype(jnp.int32)
                    return ((nxt, tuple(ctx.k_pages), tuple(ctx.v_pages),
                             tuple(ctx.k_scales or ()),
                             tuple(ctx.v_scales or ())),
                            nxt)

                (last, kp, vp, ksc, vsc), toks = lax.scan(
                    body,
                    (tokens0, tuple(k_pages), tuple(v_pages),
                     tuple(k_scales), tuple(v_scales)),
                    (pg_steps, sl_steps, pos_steps))
                return toks, kp, vp, ksc, vsc
            finally:
                self._restore_params(saved)

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P
            from ..framework.jax_compat import shard_map
            rep, pool = P(), P("tensor")
            multi_fn = shard_map(
                multi_fn, mesh=self.mesh,
                in_specs=(list(self._tp_param_specs), rep, rep, rep,
                          rep, rep, pool, pool, pool, pool, rep),
                out_specs=(rep, pool, pool, pool, pool),
                check_vma=False)
        return jax.jit(multi_fn, donate_argnums=(6, 7, 8, 9))

    def multi_step(self, cache: PagedKVCache, seq_ids, tokens_np,
                   positions_np, n_steps: int) -> np.ndarray:
        """Decode ``n_steps`` GREEDY tokens for every sequence in ONE
        compiled program.  tokens_np (batch,) int32 — the last sampled
        token per row; positions_np (batch,) int32 — each row's current
        length.  Pages for all n_steps are reserved up front; returns
        (batch, n_steps) int32 of generated tokens."""
        b = len(seq_ids)
        if int(positions_np.max()) + n_steps > self.max_position:
            raise ValueError(
                f"decode through position "
                f"{int(positions_np.max()) + n_steps} exceeds "
                f"max_position_embeddings ({self.max_position})")
        if self._jitted_multi is None:
            self._jitted_multi = self._build_multi()
        before = [cache.length(sid) for sid in seq_ids]
        # all-or-nothing: a mid-batch exhaustion must not leave earlier
        # rows hoarding a chunk's worth of pages the fallback then starves on
        cache.allocate_batch_atomic(seq_ids, n_steps)
        pg, sl = cache.plan_write(seq_ids, n_steps)
        cache.advance(seq_ids, n_steps)
        # per-step (pg, sl): plan_write is row-major (batch, n)
        pg_steps = pg.reshape(b, n_steps).T.copy()       # (n, b)
        sl_steps = sl.reshape(b, n_steps).T.copy()
        pos_steps = (positions_np[None, :]
                     + np.arange(n_steps, dtype=np.int32)[:, None])
        # table covers the FINAL length (pages reserved above); per-step
        # attention masks by lens = pos + 1, so later slots stay unseen
        needed = max(len(cache._seq_pages.get(s, ())) for s in seq_ids)
        tabs, _ = cache.page_table(
            seq_ids, max_pages=max(next_pow2(needed),
                                   self.min_table_pages))
        try:
            _maybe_lose_buffers(cache, seq_ids)
            toks, *pools = self._jitted_multi(
                self._param_arrays(),
                jnp.asarray(tokens_np.astype(np.int32)),
                jnp.asarray(pg_steps), jnp.asarray(sl_steps),
                jnp.asarray(pos_steps), tabs,
                *self._pool_args(cache), self._wscale_args())
        except BaseException:
            # same contract as step(): rebuild the donated
            # pools only if they were actually consumed, and roll the
            # lengths back so the exact chunk can be replayed — a
            # host-side fault must not zero batchmates' KV (the engine's
            # speculative draft cache rides on this)
            self._recover_pools(cache)
            self._rollback_lengths(cache, seq_ids, before)
            raise
        self._store_pools(cache, *pools)
        return np.asarray(toks).T                        # (batch, n)

    def step(self, cache: PagedKVCache, seq_ids, tokens_np,
             positions_np, sampling=None) -> np.ndarray:
        """One decode token for every sequence.  tokens_np (batch, 1)
        int32; positions_np (batch,) int32 — each row's current length.
        Allocates+advances cache bookkeeping host-side, runs the
        compiled step, writes the updated pools back.  Returns the last
        logits (batch, vocab) float32 numpy — or, with
        ``sampling=(seeds, ctrs, temps, flags)``, the next token ids
        (batch,) int32 sampled INSIDE the compiled step, so only 4
        bytes/row cross the host boundary instead of the full vocab row
        (the logits path stays as the parity/debug escape hatch)."""
        if int(positions_np.max()) + 1 > self.max_position:
            raise ValueError(
                f"decode position {int(positions_np.max()) + 1} exceeds "
                f"max_position_embeddings ({self.max_position})")
        before = [cache.length(sid) for sid in seq_ids]
        for sid in seq_ids:
            cache.allocate(sid, 1)
        pg, sl = cache.plan_write(seq_ids, 1)
        cache.advance(seq_ids, 1)
        # bucket the page-table width to a power of two: an exact width
        # would change shape every time the longest sequence crosses a
        # page boundary, recompiling the whole decode program mid-serving
        # (min_table_pages pins the floor for fully stable signatures)
        needed = max(len(cache._seq_pages.get(s, ())) for s in seq_ids)
        tabs, lens = cache.page_table(
            seq_ids, max_pages=max(next_pow2(needed),
                                   self.min_table_pages))
        sample, s_args = self._sampling_args(sampling)
        try:
            _maybe_lose_buffers(cache, seq_ids)
            out, *pools = self._program("decode", sample)(
                self._param_arrays(),
                jnp.asarray(tokens_np), jnp.asarray(positions_np),
                jnp.asarray(pg), jnp.asarray(sl), lens, tabs, s_args,
                *self._pool_args(cache), self._wscale_args())
        except BaseException:
            # the pools were DONATED: after a mid-step failure (e.g.
            # device OOM) they may be invalidated — rebuild them so the
            # cache object stays usable, and roll the lengths back so
            # the engine's retry/bisect can replay the exact step
            # (sequence KV content is lost only if the program actually
            # ran; a pre-dispatch failure leaves it intact)
            self._recover_pools(cache)
            self._rollback_lengths(cache, seq_ids, before)
            raise
        self._store_pools(cache, *pools)
        return np.asarray(out)


def sample_token(logits_row, do_sample, temperature, rng) -> int:
    """One row's next token: greedy argmax or temperature sampling —
    the single sampling definition shared by PagedGenerator and the
    continuous-batching engine."""
    if do_sample:
        z = np.asarray(logits_row, np.float32) / max(temperature, 1e-6)
        p = np.exp(z - z.max())
        p /= p.sum()
        return int(rng.choice(p.shape[-1], p=p))
    return int(np.asarray(logits_row).argmax())


class PagedGenerator:
    """Batched greedy/sampled decoding over a shared page pool.

    Usage::

        gen = PagedGenerator(model, total_pages=512, page_size=16)
        out_ids = gen.generate(input_ids, max_new_tokens=64)
    """

    def __init__(self, model, total_pages: int = 256, page_size: int = 16,
                 quantize: Optional[str] = None,
                 kv_dtype: Optional[str] = None):
        self.model = model
        self._next_seq = 0
        self.cache = PagedKVCache.from_model(
            model, total_pages=total_pages, page_size=page_size,
            kv_dtype=kv_dtype)
        self._decoder = JittedPagedDecoder(model, quantize=quantize)
        # per-phase wall times of the last generate() call, so callers
        # (bench, schedulers) can split prefill from steady-state decode
        # without a second subtraction run
        self.last_prefill_seconds = 0.0
        self.last_decode_seconds = 0.0

    def generate(self, input_ids, max_new_tokens: int = 32,
                 eos_token_id: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 seed: int = 0):
        """Returns (batch, prompt + generated) token ids (numpy)."""
        ids = np.asarray(input_ids._data if isinstance(input_ids, Tensor)
                         else input_ids)
        b, s = ids.shape
        seq_ids = list(range(self._next_seq, self._next_seq + b))
        self._next_seq += b
        rng = np.random.default_rng(seed)

        try:
            return self._generate(ids, seq_ids, max_new_tokens,
                                  eos_token_id, do_sample, temperature, rng)
        finally:
            # an exception mid-generation (e.g. page-pool exhaustion)
            # must not leak the batch's pages
            for sid in seq_ids:
                self.cache.free(sid)

    def _generate(self, ids, seq_ids, max_new_tokens, eos_token_id,
                  do_sample, temperature, rng):
        import time as _time

        b, s = ids.shape
        with no_grad():
            t0 = _time.perf_counter()
            # ONE compiled prefill program (keyed by prompt length)
            step = self._decoder.prefill(self.cache, seq_ids,
                                         ids.astype(np.int32))
            self.last_prefill_seconds = _time.perf_counter() - t0
            t0 = _time.perf_counter()

            out = [ids]
            if (not do_sample and max_new_tokens > 1
                    and s + max_new_tokens <= self._decoder.max_position):
                # greedy fast path: ALL remaining tokens decode inside
                # ONE compiled lax.scan program (one host dispatch per
                # generation instead of one per token).  eos semantics
                # are applied post-hoc: everything after a row's first
                # eos becomes eos — same output as the stepwise path
                # (whose cache also keeps writing after finish).
                first = np.asarray(step).argmax(axis=-1).astype(np.int32)
                pieces = [first[:, None]]
                cur, pos, remaining = first, s, max_new_tokens - 1
                done = (first == eos_token_id) if eos_token_id is not None \
                    else None
                # power-of-two chunks (rounded UP, extra truncated) so any
                # max_new_tokens reuses a bounded set of compiled scan
                # programs; the round-up must stay inside the rope table.
                # A chunk reservation hitting pool pressure (atomic, rolled
                # back) drops to the per-token continuation below, which
                # decodes from the exact (cur, pos) the chunks reached and
                # can still finish early on eos.
                while remaining > 0:
                    if done is not None and done.all():
                        break           # every row has emitted eos
                    n = min(next_pow2(remaining), 64,
                            self._decoder.max_position - pos)
                    try:
                        chunk = self._decoder.multi_step(
                            self.cache, seq_ids, cur,
                            np.full(b, pos, np.int32), n)
                    except RuntimeError as e:
                        if "out of pages" not in str(e):
                            raise   # device failure: lengths rolled
                            # back, but the chunk's KV content is gone
                        break       # pool pressure: per-token continuation
                    pieces.append(chunk[:, :remaining])
                    if done is not None:
                        done |= (pieces[-1] == eos_token_id).any(axis=1)
                    cur = chunk[:, -1].astype(np.int32)
                    pos += n
                    remaining -= n
                while remaining > 0:
                    if done is not None and done.all():
                        break
                    logits = self._decoder.step(
                        self.cache, seq_ids, cur[:, None].astype(np.int32),
                        np.full(b, pos, np.int32))
                    cur = logits.argmax(axis=-1).astype(np.int32)
                    pieces.append(cur[:, None])
                    if done is not None:
                        done |= cur == eos_token_id
                    pos += 1
                    remaining -= 1
                gen = np.concatenate(pieces, axis=1)
                if eos_token_id is not None:
                    hit = gen == eos_token_id
                    after = (np.cumsum(hit, axis=1) - hit.astype(int)) > 0
                    gen = np.where(after, eos_token_id, gen)
                    # stepwise width contract: stop at the step where the
                    # LAST row finished
                    alldone = (np.cumsum(hit, axis=1) > 0).all(axis=0)
                    if alldone.any():
                        gen = gen[:, :int(np.argmax(alldone)) + 1]
                out.append(gen.astype(ids.dtype))
                self.last_decode_seconds = _time.perf_counter() - t0
                return np.concatenate(out, axis=1)

            finished = np.zeros(b, bool)
            pos = s
            for _ in range(max_new_tokens):
                nxt = np.array([
                    sample_token(row, do_sample, temperature, rng)
                    for row in step])
                if eos_token_id is not None:
                    nxt = np.where(finished, eos_token_id, nxt)
                    finished |= nxt == eos_token_id
                out.append(nxt[:, None].astype(ids.dtype))
                if eos_token_id is not None and finished.all():
                    break
                # ONE compiled program per decode token (embed + all
                # layers + logits), pools donated through the step
                step = self._decoder.step(
                    self.cache, seq_ids,
                    out[-1].astype(np.int32),
                    np.full(b, pos, np.int32))
                pos += 1
            self.last_decode_seconds = _time.perf_counter() - t0

        return np.concatenate(out, axis=1)
