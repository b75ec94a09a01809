"""The ragged step's pack (ISSUE 32): a decoder that was promised a
bound on a step's tokens (``step_tokens``) runs the ragged program's
dense layers over the step's tokens packed to that many positions, and
hands only the paged kernel the (rows, span) rectangle.  Every real
token must come out as from the SAME decoder built without a bound —
which computes the whole rectangle — on the same weights and pools:
emitted ids, accept counts, and the pools' contents page for page."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.paged import JittedPagedDecoder
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache

VOCAB = 64
PAGE = 8
CHUNK = 128
ROWS = 8
#: what the engine promises at prefill_chunk_tokens=128, max_batch=8, no
#: draft model: a prompt's 127-token tail and the next prompt's full
#: chunk in one step, and a token for each of the other seven rows
BOUND = (2 * CHUNK - 1) + (ROWS - 1)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=1024)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def decoders(model):
    """(packed, whole): the same model behind a decoder with the
    engine's bound and behind one without."""
    return (JittedPagedDecoder(model, step_tokens=BOUND),
            JittedPagedDecoder(model))


def _tokens(rng, n):
    return rng.integers(0, VOCAB, (n,)).astype(np.int32)


def _greedy_next(whole, model, context, n, kv_dtype=None):
    """The target's own next ``n`` greedy tokens after ``context``: what
    a perfect draft would propose."""
    cache = PagedKVCache.from_model(model, total_pages=160, page_size=PAGE,
                                    kv_dtype=kv_dtype)
    greedy = (np.zeros(1, np.uint32), np.ones(1, np.float32),
              np.zeros(1, bool))
    out, _ = whole.ragged_step(cache, ["g"], [context], [0],
                               sampling=greedy)
    toks = [int(out[0])]
    for i in range(n - 1):
        out, _ = whole.ragged_step(
            cache, ["g"], [np.asarray(toks[-1:], np.int32)],
            [len(context) + i], sampling=greedy)
        toks.append(int(out[0]))
    return toks


def _warm_caches(whole, model, rng, ctx_lens, kv_dtype=None):
    """Two caches holding the same sequences at ``ctx_lens``: both
    filled by the SAME (unbounded) decoder, so they agree bit for bit
    and hand out the same pages from here on."""
    contexts = [_tokens(rng, k) for k in ctx_lens]
    caches = []
    for _ in range(2):
        cache = PagedKVCache.from_model(model, total_pages=160,
                                        page_size=PAGE, kv_dtype=kv_dtype)
        for sid, ctx in enumerate(contexts):
            if len(ctx):
                whole.ragged_step(cache, [sid], [ctx], [0])
        caches.append(cache)
    return contexts, caches


def _pools(cache):
    return [np.asarray(a) for a in cache._device_pools()]


#: name -> rows of (cached context, span tokens, drafts of which this
#: many are the target's own)
MIXES = {
    # a chunk step of the serving cell: 8 x 128 positions for 135 tokens
    "chunk_and_7_decodes": [(128, 128, None)] + [(k, 1, None) for k in
                                                 (9, 40, 64, 3, 200, 17, 1)],
    # the most the planner hands out: a 127-token tail, a full chunk
    # and six decode rows, 261 tokens in 8 rows (one under the bound)
    "tail_and_chunk_at_the_bound": [(129, 127, None), (0, 128, None)]
    + [(k, 1, None) for k in (5, 33, 8, 70, 12, 2)],
    # five rows pad to eight: the pad rows' tokens are packed too
    "three_short_tails": [(40, 5, None), (16, 17, None), (100, 40, None),
                          (7, 1, None), (21, 1, None)],
    # verify rows (1 fed token + 4 drafts) beside a chunk
    "verify_rows_beside_a_chunk": [(64, 64, None), (30, 5, 4), (11, 5, 2),
                                   (50, 5, 0)],
    # 4 x 8 = 32 positions, under the bound: nothing is packed away
    "rectangle_under_the_bound": [(10, 8, None), (3, 1, None), (25, 6, None),
                                  (9, 2, None)],
}


def _build_step(whole, model, rng, mix, kv_dtype=None):
    ctx_lens = [k for k, _n, _d in mix]
    contexts, caches = _warm_caches(whole, model, rng, ctx_lens, kv_dtype)
    rows, nds = [], []
    for ctx, (_k, n, own) in zip(contexts, mix):
        if own is None:
            rows.append(_tokens(rng, n))
            nds.append(0)
            continue
        # a verify row: the last fed token, then drafts of which the
        # first ``own`` are what the target itself emits after it
        fed = _tokens(rng, 1)
        good = _greedy_next(whole, model, np.concatenate([ctx, fed]), n - 1,
                            kv_dtype)
        drafts = [good[i] if i < own else (good[i] + 1) % VOCAB
                  for i in range(n - 1)]
        rows.append(np.concatenate([fed, np.asarray(drafts, np.int32)]))
        nds.append(n - 1)
    return caches, list(range(len(mix))), rows, ctx_lens, nds


def _sampling(kind, rng, n):
    if kind == "logits":
        return None
    flags = np.zeros(n, bool)
    if kind == "draw":
        flags[::2] = True
    return (rng.integers(0, 2 ** 31, n).astype(np.uint32),
            np.linspace(0.7, 1.3, n).astype(np.float32), flags)


CASES = [(name, None, "greedy") for name in MIXES] + [
    ("chunk_and_7_decodes", "int8", "greedy"),
    ("tail_and_chunk_at_the_bound", None, "draw"),
    ("verify_rows_beside_a_chunk", None, "draw"),
    ("chunk_and_7_decodes", None, "logits"),
    ("verify_rows_beside_a_chunk", "int8", "logits"),
]


@pytest.mark.parametrize("name,kv_dtype,tail", CASES,
                         ids=[f"{n}-{k or 'fp'}-{t}" for n, k, t in CASES])
def test_packed_step_matches_the_whole_rectangle(model, decoders, name,
                                                 kv_dtype, tail):
    packed, whole = decoders
    rng = np.random.default_rng(sorted(MIXES).index(name))
    (cache_p, cache_w), sids, rows, ctxs, nds = _build_step(
        whole, model, rng, MIXES[name], kv_dtype)
    sampling = _sampling(tail, rng, len(rows))
    n_drafts = nds if any(nds) else None
    out_w, acc_w = whole.ragged_step(cache_w, sids, rows, ctxs,
                                     n_drafts=n_drafts, sampling=sampling)
    out_p, acc_p = packed.ragged_step(cache_p, sids, rows, ctxs,
                                      n_drafts=n_drafts, sampling=sampling)

    d_p, d_w = packed.last_dispatch, whole.last_dispatch
    rect = d_w["rows_padded"] * d_w["span_padded"]
    assert d_w["tokens_padded"] == rect
    assert d_p["tokens_padded"] == min(rect, -(-BOUND // 16) * 16)
    assert d_p["tokens"] <= d_p["tokens_padded"] <= rect
    assert {k: v for k, v in d_p.items() if k != "tokens_padded"} \
        == {k: v for k, v in d_w.items() if k != "tokens_padded"}

    np.testing.assert_array_equal(acc_p, acc_w)
    want = [own for _k, _n, own in MIXES[name]]
    for got, own in zip(acc_w, want):
        # under int8 KV a verify row reads quantized pages where the
        # stepwise continuation read them too: the count still holds
        assert got == (own or 0)
    if tail == "logits":
        # the tolerance of the jitted-vs-eager and quantized-serving
        # parity tests
        np.testing.assert_allclose(out_p, out_w, rtol=2e-5, atol=2e-5)
    else:
        np.testing.assert_array_equal(out_p, out_w)
    for sid in sids:
        assert cache_p.length(sid) == cache_w.length(sid)
        assert cache_p._seq_pages[sid] == cache_w._seq_pages[sid]
    for a, b in zip(_pools(cache_p), _pools(cache_w)):
        if a.dtype == np.int8:
            np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_a_step_over_the_bound_raises_before_any_page_is_reserved(
        model, decoders):
    packed, whole = decoders
    rng = np.random.default_rng(99)
    # 127 + 128 + 13 + 5 decode rows = 273 in 8 rows: one over the 272
    # positions the bound of 262 rounds up to
    mix = [(129, 127, None), (0, 128, None), (16, 13, None)] \
        + [(k, 1, None) for k in (5, 33, 8, 70, 12)]
    (cache, _), sids, rows, ctxs, _nds = _build_step(whole, model, rng, mix)
    assert sum(len(r) for r in rows) == 273
    lengths = [cache.length(s) for s in sids]
    free, mapped = list(cache._free), dict(cache._seq_pages)
    dispatched = packed.last_dispatch
    with pytest.raises(ValueError, match="exceeds the 272 positions"):
        packed.ragged_step(cache, sids, rows, ctxs)
    assert [cache.length(s) for s in sids] == lengths
    assert cache._free == free and cache._seq_pages == mapped
    assert packed.last_dispatch is dispatched
    # one token fewer fills the packed axis and goes through
    rows[2] = rows[2][:12]
    out, _ = packed.ragged_step(cache, sids, rows, ctxs)
    assert len(out) == len(rows)


@pytest.mark.parametrize("rows,span,bound,want", [
    (8, 128, None, 1024), (8, 128, 262, 272), (4, 128, 262, 272),
    (8, 64, 262, 272), (2, 128, 262, 256), (8, 8, 262, 64),
    (8, 1, 262, 8), (8, 128, 272, 272), (8, 128, 273, 288),
    (32, 1, 4, 32), (32, 2, 4, 32),
])
def test_packed_tokens_is_a_function_of_the_program_key(model, rows, span,
                                                        bound, want):
    dec = JittedPagedDecoder(model, step_tokens=bound)
    assert dec.packed_tokens(rows, span) == want
