"""Quantized serving end-to-end (ISSUE 9): int8 KV cache + w8/w8a8
weights through the compiled serving hot path, batched survivor replay,
and the audit rules that certify the quantized programs.

The A/B discipline: the ``sampling=None`` logits escape hatch makes
comparisons exact — every parity test runs the host-logits path on both
engines (host argmax over f32 logits), so a greedy match is a real
numeric statement, not sampler luck.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.inference.paged import JittedPagedDecoder
from paddle_tpu.ops.pallas.paged_attention import (
    PagedKVCache, paged_attention, paged_attention_multi, quantize_kv)
from paddle_tpu.ops.pallas import quant_matmul as qm
from paddle_tpu.testing import faults


VOCAB = 64


def _build_model(seed=0):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def model():
    return _build_model()


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(5)
    return [rng.integers(0, VOCAB, (n,)).astype(np.int32)
            for n in (5, 9, 13, 20)]


# What int8 can promise against full precision.  w8 weights + int8 KV
# move a logit of this model by up to 0.007 (read at every position of
# the four rows below), and the baseline's own top-two margin goes down
# to 4e-5: "the same greedy tokens" holds only where the margin is
# wider than the error on both sides of it, and a pinned seed keeps it
# only until an initialiser changes (the 9-token prompt's first token:
# margin 0.0006, tokens 1 and 26 — what failed here for six PRs).  So:
# every logit within LOGIT_TOL of the baseline's under teacher forcing,
# and every token the quantized engine chose within 2 x LOGIT_TOL of
# the baseline's best at that position — which IS token equality
# wherever the baseline's margin is wider than that.
LOGIT_TOL = 0.02


def _teacher_forced_logits(model, decoder, row, n_prompt, **cache_kw):
    """[n_new, VOCAB]: the logits ``decoder`` chooses row[t] from, given
    row[:t], for every generated position t (one prefill a prefix,
    bucketed as the engine's own prefills are: a program a power of
    two, not one a length)."""
    out = []
    for t in range(n_prompt, len(row)):
        cache = PagedKVCache.from_model(model, total_pages=16, page_size=8,
                                        **cache_kw)
        out.append(np.asarray(
            decoder.prefill(cache, [0], row[:t][None], bucket=True))[0])
    return np.stack(out)


@pytest.fixture(scope="module")
def quant_rows(model, prompts):
    """The quantized engine's greedy rows (monolithic prefill, host
    logits): what every other quantized composition must reproduce
    EXACTLY — same arithmetic, another schedule."""
    return _serve(model, prompts, quantize="w8", kv_quant="int8")


@pytest.fixture(scope="module")
def base_rows(model, prompts):
    """Full-precision greedy reference on the logits escape hatch,
    shared by the parity tests (one engine build instead of one per
    test — tier-1 runtime discipline)."""
    return _serve(model, prompts)


def _serve(model, prompts, max_new=8, **kw):
    """Submit all prompts concurrently (covers decode buckets up to
    max_batch) on the host-logits greedy path; returns output rows."""
    kw.setdefault("sample_on_device", False)
    with ContinuousBatchingEngine(model, total_pages=128, page_size=8,
                                  max_batch=4, **kw) as eng:
        reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
        return [r.result(timeout=600) for r in reqs]


# ------------------------------------------------------------- kernels
class TestQuantKernels:
    def test_weight_only_interpret_matches_xla(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(9, 40)), jnp.float32)
        w = jnp.asarray(rng.integers(-127, 128, (40, 24)), jnp.int8)
        s = jnp.asarray(rng.uniform(0.01, 0.1, (24,)), jnp.float32)
        ref = qm.weight_only_matmul_xla(x, w, s)
        out = qm.weight_only_matmul_pallas(x, w, s, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_w8a8_interpret_matches_xla(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(7, 33)), jnp.float32)
        w = jnp.asarray(rng.integers(-127, 128, (33, 17)), jnp.int8)
        s = jnp.asarray(rng.uniform(0.01, 0.1, (17,)), jnp.float32)
        xq, xs = qm.dynamic_act_quant(x)
        ref = qm.w8a8_matmul_xla(xq, xs, w, s, jnp.float32)
        out = qm.w8a8_matmul_pallas(xq, xs, w, s, jnp.float32,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_dynamic_act_quant_roundtrip_bound(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.normal(size=(5, 64)), jnp.float32)
        q, s = qm.dynamic_act_quant(x)
        back = np.asarray(q, np.float32) * np.asarray(s)
        err = np.abs(back - np.asarray(x))
        # symmetric rounding: at most half a quantization step per row
        bound = np.asarray(s)[:, 0] * 0.5 + 1e-7
        assert (err.max(axis=1) <= bound).all()
        # a zero row must round-trip to exactly zero
        q0, s0 = qm.dynamic_act_quant(jnp.zeros((1, 8), jnp.float32))
        assert np.asarray(q0).max() == 0 and float(s0[0, 0]) > 0

    def test_quantize_kv_roundtrip_bound(self):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(2, 12, 16)), jnp.float32)
        q, s = quantize_kv(x)
        back = np.asarray(q, np.float32) * np.asarray(s)
        err = np.abs(back - np.asarray(x)).max(axis=-1)
        assert (err <= np.asarray(s)[..., 0] * 0.5 + 1e-7).all()


class TestInt8PagedAttention:
    def _pools(self, rng, kvh=2, total=8, page=8, d=16, layers=1):
        kp = jnp.asarray(rng.integers(-127, 128, (kvh, total, page, d)),
                         jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, (kvh, total, page, d)),
                         jnp.int8)
        ks = jnp.asarray(rng.uniform(0.01, 0.1, (kvh, total, page, 1)),
                         jnp.float32)
        vs = jnp.asarray(rng.uniform(0.01, 0.1, (kvh, total, page, 1)),
                         jnp.float32)
        return kp, vp, ks, vs

    def test_decode_kernel_interpret_matches_xla(self):
        rng = np.random.default_rng(4)
        kp, vp, ks, vs = self._pools(rng)
        q = jnp.asarray(rng.normal(size=(3, 4, 16)), jnp.float32)
        tabs = jnp.asarray(rng.permutation(8)[:6].reshape(3, 2), jnp.int32)
        lens = jnp.asarray([5, 11, 16], jnp.int32)
        ref = paged_attention(q, kp, vp, lens, tabs, k_scales=ks,
                              v_scales=vs)                 # XLA fallback
        out = paged_attention(q, kp, vp, lens, tabs, k_scales=ks,
                              v_scales=vs, interpret=True)  # Pallas
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_multi_query_kernel_interpret_matches_xla(self):
        rng = np.random.default_rng(5)
        kp, vp, ks, vs = self._pools(rng)
        q = jnp.asarray(rng.normal(size=(2, 3, 4, 16)), jnp.float32)
        tabs = jnp.asarray(rng.permutation(8)[:4].reshape(2, 2), jnp.int32)
        lens = jnp.asarray([7, 13], jnp.int32)
        ref = paged_attention_multi(q, kp, vp, lens, tabs, k_scales=ks,
                                    v_scales=vs)
        out = paged_attention_multi(q, kp, vp, lens, tabs, k_scales=ks,
                                    v_scales=vs, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_cache_int8_mode_and_reset(self, model):
        cache = PagedKVCache.from_model(model, total_pages=8, page_size=8,
                                        kv_dtype="int8")
        assert cache.kv_quant
        assert cache.k_pages[0].dtype == jnp.int8
        assert cache.k_scales[0].shape == (2, 8, 8, 1)
        assert cache.kv_scale_bytes > 0
        # int8 pages store a quarter of the f32 baseline's bytes
        base = PagedKVCache.from_model(model, total_pages=8, page_size=8)
        assert cache.kv_pool_bytes * 4 == base.kv_pool_bytes
        gen = cache.generation
        cache.reset_pools()
        assert cache.generation == gen + 1
        assert cache.k_scales[0].dtype == jnp.float32
        assert float(jnp.max(jnp.abs(cache.k_scales[0]))) == 0.0
        with pytest.raises(ValueError):
            PagedKVCache.from_model(model, kv_dtype="fp4")


# ------------------------------------------------- engine-level parity
class TestQuantEngineParity:
    """Logits-escape-hatch A/B of int8-KV and w8/w8a8 vs the f32
    baseline across batch sizes, prefix hits, chunked prefill,
    spec-decode verify, and buffer-loss replay (ISSUE 9 satellite)."""

    def test_w8_int8kv_greedy_exact_across_batch_sizes(self, model,
                                                       prompts,
                                                       base_rows,
                                                       quant_rows):
        # the concurrent 4-row wave passes through every decode bucket
        # (4 -> 2 -> 1) as shorter rows retire, so one wave covers the
        # batch-size matrix
        base_dec = JittedPagedDecoder(model)
        quant_dec = JittedPagedDecoder(model, quantize="w8")
        exact = 0
        for p, a, b in zip(prompts, base_rows, quant_rows):
            lb = _teacher_forced_logits(model, base_dec, b, len(p))
            lq = _teacher_forced_logits(model, quant_dec, b, len(p),
                                        kv_dtype="int8")
            assert float(np.max(np.abs(lb - lq))) < LOGIT_TOL
            chosen = lb[np.arange(len(lb)), b[len(p):]]
            assert float(np.max(lb.max(-1) - chosen)) < 2 * LOGIT_TOL
            exact += bool(np.array_equal(a, b))
        # the tolerance is no licence: a near-tie that flips is rare
        assert exact >= len(prompts) - 1

    def test_w8a8_logits_close(self, model, prompts):
        """w8a8 adds activation quantization noise: logits stay close
        but near-tie argmaxes MAY flip — the documented accuracy
        caveat (README "when w8a8 loses"); the gate here is the error
        bound plus a match-ratio floor, not exactness."""
        cache_b = PagedKVCache.from_model(model, total_pages=16,
                                          page_size=8)
        cache_q = PagedKVCache.from_model(model, total_pages=16,
                                          page_size=8, kv_dtype="int8")
        lb = JittedPagedDecoder(model).prefill(
            cache_b, [0], prompts[3][None])
        lq = JittedPagedDecoder(model, quantize="w8a8").prefill(
            cache_q, [0], prompts[3][None])
        assert float(np.max(np.abs(lb - lq))) < 0.05

    def test_w8a8_greedy_mostly_matches(self, model, prompts, base_rows):
        quant = _serve(model, prompts, quantize="w8a8", kv_quant="int8")
        matches = sum(np.array_equal(a, b)
                      for a, b in zip(base_rows, quant))
        assert matches >= len(prompts) - 1

    def test_prefix_cache_hit_parity(self, model):
        rng = np.random.default_rng(11)
        system = rng.integers(0, VOCAB, (16,)).astype(np.int32)
        shared = [np.concatenate([system,
                                  rng.integers(0, VOCAB, (4,))
                                  .astype(np.int32)]) for _ in range(3)]
        outs = {}
        for name, kw in (("base", {}),
                         ("quant", dict(quantize="w8", kv_quant="int8"))):
            with ContinuousBatchingEngine(
                    model, total_pages=128, page_size=8, max_batch=4,
                    sample_on_device=False, **kw) as eng:
                # sequenced: the first prefill registers the prefix so
                # the rest take the prefix-HIT suffix path
                rows = [eng.submit(shared[0], max_new_tokens=6)
                        .result(timeout=600)]
                later = [eng.submit(p, max_new_tokens=6)
                         for p in shared[1:]]
                rows += [r.result(timeout=600) for r in later]
                hit_pages = eng.cache.cached_prefix_pages
            outs[name] = rows
            assert hit_pages > 0
        for a, b in zip(outs["base"], outs["quant"]):
            assert np.array_equal(a, b)

    def test_chunked_prefill_parity(self, model, prompts, quant_rows):
        # quant CHUNKED vs quant MONOLITHIC: the int8 invariant that
        # every attention consumer sees the round-tripped KV (the
        # cross-precision half is the batch-size test's)
        quant = _serve(model, prompts, prefill_chunk_tokens=8,
                       quantize="w8", kv_quant="int8")
        for a, b in zip(quant_rows, quant):
            assert np.array_equal(a, b)

    def test_spec_decode_verify_parity(self, model, prompts, quant_rows):
        draft = _build_model(seed=0)      # clone of model: accept ~1.0
        with ContinuousBatchingEngine(
                model, total_pages=128, page_size=8, max_batch=4,
                draft_model=draft, spec_tokens=2, quantize="w8",
                kv_quant="int8") as eng:
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            spec = [r.result(timeout=600) for r in reqs]
        # greedy speculative decoding through the QUANTIZED verify
        # program stays exact: == the quantized target alone
        for a, b in zip(quant_rows, spec):
            assert np.array_equal(a, b)

    def test_on_device_sampling_matches_host_logits(self, model,
                                                    prompts, quant_rows):
        # on-device greedy on the quant engine == host-logits argmax
        dev = _serve(model, prompts, quantize="w8", kv_quant="int8",
                     sample_on_device=True)
        for a, b in zip(quant_rows, dev):
            assert np.array_equal(a, b)


# ------------------------------------------- replay / crash recovery
class TestQuantReplay:
    def test_buffer_loss_replay_bit_exact_with_scales(self, model,
                                                      prompts):
        """A donated-buffer loss on an int8 engine: the batched replay
        must rewrite pages AND scale pools so survivors continue
        bit-identically, and re-registered prefix pages must serve
        later sharers with correct (re-scaled) content."""
        rng = np.random.default_rng(21)
        system = rng.integers(0, VOCAB, (16,)).astype(np.int32)
        mk = lambda: np.concatenate(  # noqa: E731
            [system, rng.integers(0, VOCAB, (4,)).astype(np.int32)])
        wave = [mk() for _ in range(4)]
        tail = mk()

        def run(plan=None):
            import contextlib
            ctx = (faults.installed(plan) if plan is not None
                   else contextlib.nullcontext())
            with ctx, ContinuousBatchingEngine(
                    model, total_pages=128, page_size=8, max_batch=4,
                    quantize="w8", kv_quant="int8") as eng:
                reqs = [eng.submit(p, max_new_tokens=6) for p in wave]
                rows = [r.result(timeout=600) for r in reqs]
                # a PREFIX-HIT request after the loss: its shared pages
                # were re-registered by replay — content must be right
                rows.append(eng.submit(tail, max_new_tokens=6)
                            .result(timeout=600))
                return rows

        refs = run()
        plan = faults.FaultPlan([{"site": "buffer_loss", "nth": 10}])
        got = run(plan)
        assert any(s["fires"] for s in plan.snapshot())
        for a, b in zip(refs, got):
            assert np.array_equal(a, b)

    def test_batched_replay_amortizes_dispatches(self, model, prompts):
        from paddle_tpu import monitor

        def run(replay_batch):
            before = monitor.snapshot()
            plan = faults.FaultPlan([{"site": "buffer_loss", "nth": 10}])
            with faults.installed(plan), ContinuousBatchingEngine(
                    model, total_pages=128, page_size=8, max_batch=4,
                    kv_quant="int8", replay_batch=replay_batch) as eng:
                reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
                rows = [r.result(timeout=600) for r in reqs]
            after = monitor.snapshot()

            def delta(name):
                def v(s):
                    m = s.get(name)
                    return (m["series"][0]["value"]
                            if m and m["series"] else 0.0)
                return v(after) - v(before)
            assert any(s["fires"] for s in plan.snapshot())
            return rows, delta("survivor_replays_total"), \
                delta("replay_dispatches_total")

        rows_b, replays_b, disp_b = run(True)
        rows_u, replays_u, disp_u = run(False)
        for a, b in zip(rows_b, rows_u):
            assert np.array_equal(a, b)        # batching changes nothing
        assert replays_b == replays_u >= 2
        # the satellite's point: many survivors per compiled dispatch
        assert disp_b < disp_u
        assert disp_b < replays_b

    def test_batched_replay_sticky_row_quarantined_alone(self, model,
                                                         prompts):
        """A row whose replay persistently fails must be quarantined
        ALONE under batched replay: the batched dispatch cannot name
        the poison, so the engine falls back to per-row isolation."""
        plan = faults.FaultPlan([
            {"site": "buffer_loss", "nth": 10},
            {"site": "buffer_loss", "seq_id": 2, "kind": "error"}])
        with faults.installed(plan), ContinuousBatchingEngine(
                model, total_pages=128, page_size=8, max_batch=4,
                kv_quant="int8", replay_batch=True) as eng:
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            errs = []
            for i, r in enumerate(reqs):
                try:
                    r.result(timeout=600)
                except Exception:  # noqa: BLE001 — the poisoned row
                    errs.append(i)
        assert errs == [2]

    def test_batch_context_prefill_matches_per_row(self, model):
        """The batched context-prefill program (mixed per-row context
        lengths, k == 0 rows included) produces the same logits as
        per-row chunk_prefill/prefill dispatches."""
        rng = np.random.default_rng(31)
        toks = [rng.integers(0, VOCAB, (n,)).astype(np.int32)
                for n in (12, 9, 6)]
        dec = JittedPagedDecoder(model)
        # per-row reference: row 0 continues from context 8, row 1 from
        # 4, row 2 is fresh (context 0)
        cache_a = PagedKVCache.from_model(model, total_pages=32,
                                          page_size=8)
        refs = []
        for sid, (t, k) in enumerate(zip(toks, (8, 4, 0))):
            if k:
                dec.prefill(cache_a, [sid], t[None, :k], bucket=True)
                refs.append(dec.chunk_prefill(cache_a, [sid], t[None, k:],
                                              context_tokens=k))
            else:
                refs.append(dec.prefill(cache_a, [sid], t[None],
                                        bucket=True))
        cache_b = PagedKVCache.from_model(model, total_pages=32,
                                          page_size=8)
        for sid, (t, k) in enumerate(zip(toks, (8, 4, 0))):
            if k:
                dec.prefill(cache_b, [sid], t[None, :k], bucket=True)
        out = dec.batch_context_prefill(
            cache_b, [0, 1, 2], [t[k:] for t, k in zip(toks, (8, 4, 0))],
            [8, 4, 0])
        for i, ref in enumerate(refs):
            np.testing.assert_allclose(out[i], ref[0], rtol=1e-5,
                                       atol=1e-5)
        for sid, t in enumerate(toks):
            assert cache_b.length(sid) == len(t)


# ----------------------------------------------------------- auditing
class TestQuantAudit:
    def test_quantized_engine_programs_certified(self, model):
        from paddle_tpu import analysis
        with ContinuousBatchingEngine(
                model, total_pages=64, page_size=8, max_batch=4,
                prefill_chunk_tokens=8, quantize="w8a8",
                kv_quant="int8") as eng:
            for mode in ("decode", "chunk"):
                audit = analysis.audit_engine(eng, mode=mode,
                                              publish=False)
                assert not audit.host_transfer_findings
                assert not audit.by_rule("quant-scale-const")
                assert not audit.by_rule("missed-donation")

    def test_dtype_creep_exempts_int8_casts(self):
        from paddle_tpu.analysis import audit_callable
        sds = jax.ShapeDtypeStruct

        def quant_math(x8, s):
            # int8 -> f32 dequant + widened accumulate: intended
            return x8.astype(jnp.float32) * s

        audit = audit_callable(
            quant_math, sds((8, 8), jnp.int8), sds((8, 1), jnp.float32),
            expect_dtype="bfloat16", publish=False, quantized=True)
        assert not audit.by_rule("dtype-promotion")
        # the exemption is SCOPED to quantized audits: the same cast in
        # a program not declared quantized still counts as creep
        audit = audit_callable(
            quant_math, sds((8, 8), jnp.int8), sds((8, 1), jnp.float32),
            expect_dtype="bfloat16", publish=False)
        assert audit.by_rule("dtype-promotion")

        def creep(x):
            return x.astype(jnp.float32) * 2.0   # bf16 -> f32: creep

        audit = audit_callable(creep, sds((8, 8), jnp.bfloat16),
                               expect_dtype="bfloat16", publish=False,
                               quantized=True)
        assert audit.by_rule("dtype-promotion")

    def test_dtype_creep_exempts_quantizer_sources(self):
        """The quantizer's OWN f32 math has no int8 invar (dynamic-quant
        absmax chain, s32-accumulator -> f32 cast) — the exemption must
        cover eqns located in the quantizer modules too, or a bf16
        quantized audit eats the per-rule cap on sanctioned math and
        buries a real model-code leak."""
        from paddle_tpu.analysis import audit_callable
        sds = jax.ShapeDtypeStruct
        rng = np.random.default_rng(0)
        w8 = jnp.asarray(rng.integers(-127, 128, (32, 16)), jnp.int8)
        ws = jnp.asarray(rng.uniform(0.01, 0.1, (16,)), jnp.float32)

        def f(x):
            return qm.w8a8_matmul(x, w8, ws)

        audit = audit_callable(f, sds((4, 32), jnp.bfloat16),
                               expect_dtype="bfloat16", publish=False,
                               quantized=True)
        assert not audit.by_rule("dtype-promotion")
        # control: undeclared, the same program IS creep
        audit = audit_callable(f, sds((4, 32), jnp.bfloat16),
                               expect_dtype="bfloat16", publish=False)
        assert audit.by_rule("dtype-promotion")

        def g(x):
            leak = jnp.ones((4, 16), jnp.float32)   # model-code f32
            return (qm.w8a8_matmul(x, w8, ws).astype(jnp.float32)
                    + leak).astype(jnp.bfloat16)

        audit = audit_callable(g, sds((4, 32), jnp.bfloat16),
                               expect_dtype="bfloat16", publish=False,
                               quantized=True)
        assert audit.by_rule("dtype-promotion")

    def test_baked_scale_const_flagged(self):
        from paddle_tpu.analysis import audit_callable
        sds = jax.ShapeDtypeStruct
        baked = jnp.full((16,), 0.05, jnp.float32)

        def bad(x):
            return x * baked            # scale closed over, not traced

        audit = audit_callable(bad, sds((4, 16), jnp.float32),
                               quantized=True, publish=False)
        assert audit.by_rule("quant-scale-const")
        # the same program audited unquantized stays silent (rope
        # tables etc. are legitimate 2-D consts either way)
        audit = audit_callable(bad, sds((4, 16), jnp.float32),
                               publish=False)
        assert not audit.by_rule("quant-scale-const")
        # scale_lens narrows the 1-D rule to the program's actual
        # scale lengths: a legitimate 1-D f32 table of another size
        # (alibi slopes, inv_freq) passes, a matching length is still
        # flagged — audit_engine derives these from the decoder
        audit = audit_callable(bad, sds((4, 16), jnp.float32),
                               quantized=True, scale_lens={32},
                               publish=False)
        assert not audit.by_rule("quant-scale-const")
        audit = audit_callable(bad, sds((4, 16), jnp.float32),
                               quantized=True, scale_lens={16},
                               publish=False)
        assert audit.by_rule("quant-scale-const")


class TestQuantServing:
    def test_health_reports_quant_modes(self, model):
        import json
        import urllib.request
        from paddle_tpu.inference.server import GenerationServer
        with GenerationServer(model, total_pages=64, page_size=8,
                              max_batch=2, quantize="w8",
                              kv_quant="int8") as srv:
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/health") as r:
                payload = json.load(r)
        assert payload["quantize"] == "w8"
        assert payload["kv_quant"] == "int8"
        assert payload["kv_pool_bytes"] > 0
        assert payload["kv_scale_bytes"] > 0

    def test_engine_rejects_unknown_modes(self, model):
        with pytest.raises(ValueError):
            ContinuousBatchingEngine(model, kv_quant="int4")
        with pytest.raises(ValueError):
            JittedPagedDecoder(model, quantize="w4")

    def test_ptq_observer_scales_match_serving(self, model):
        """The serving calibration rides the PTQ observer: scales must
        equal per-out-channel absmax / 127."""
        from paddle_tpu.quantization.serving import (
            iter_quant_linears, quantize_linear_weights)
        spec = quantize_linear_weights(model)
        layers = dict(iter_quant_linears(model))
        assert len(spec) == len(layers) > 0
        layer, w_q, scale = spec[0]
        w = np.asarray(layer.weight._data, np.float32)
        np.testing.assert_allclose(
            np.asarray(scale),
            np.maximum(np.abs(w).max(axis=0), 1e-30) / 127.0, rtol=1e-6)
        back = np.asarray(w_q, np.float32) * np.asarray(scale)[None, :]
        assert np.abs(back - w).max() <= np.asarray(scale).max() * 0.5 + 1e-7
