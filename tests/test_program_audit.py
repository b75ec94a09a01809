"""paddle_tpu.analysis program auditor (ISSUE 3 tentpole).

Planted-hazard detection on synthetic programs, the engine decode
program's enforced "ids-only host boundary" invariant (PR 2 regression
lock), audits of static Programs and to_static functions, and the
jit_recompile_count runtime mirror.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import analysis, monitor


class TestPlantedHazards:
    def test_host_callback_detected(self):
        def f(x):
            y = jax.pure_callback(
                lambda a: a * 2,
                jax.ShapeDtypeStruct(x.shape, x.dtype), x)
            return y + 1

        audit = analysis.audit_callable(f, jnp.ones(4), name="planted")
        found = audit.by_rule("host-callback")
        assert found and found[0].severity == "error"
        assert audit.host_transfer_findings
        assert "pure_callback" in found[0].message

    def test_clean_program_reports_nothing(self):
        audit = analysis.audit_callable(
            lambda x: jnp.sum(x * 2), jnp.ones((8, 8)))
        assert audit.findings == [], audit.report()

    def test_f32_upcast_detected_in_bf16_program(self):
        def f(x):
            return x.astype(jnp.float32) * 2   # planted upcast

        audit = analysis.audit_callable(
            f, jnp.ones(8, jnp.bfloat16), expect_dtype="bfloat16")
        found = audit.by_rule("dtype-promotion")
        assert found, audit.report()
        assert "float32" in found[0].message
        # the same program audited WITHOUT a working-dtype expectation
        # is clean — f32 is only creep relative to a narrower intent
        assert not analysis.audit_callable(
            f, jnp.ones(8, jnp.bfloat16)).by_rule("dtype-promotion")

    def test_missed_donation_detected_and_fixed_by_donating(self):
        state = jax.ShapeDtypeStruct((512, 512), jnp.float32)   # 1 MiB
        limits = dict(donation_bytes=1 << 18,
                      output_transfer_bytes=1 << 30)
        bad = analysis.audit_callable(lambda s: s + 1, state, **limits)
        assert bad.by_rule("missed-donation")
        good = analysis.audit_callable(lambda s: s + 1, state,
                                       donate_argnums=(0,), **limits)
        assert not good.findings, good.report()

    def test_const_capture_detected(self):
        big = jnp.ones((512, 512))

        audit = analysis.audit_callable(
            lambda x: x @ big, jnp.ones((2, 512)), const_bytes=1 << 18,
            output_transfer_bytes=1 << 30)
        assert audit.by_rule("const-capture")

    def test_output_transfer_detected(self):
        audit = analysis.audit_callable(
            lambda x: x * 2, jnp.ones((64, 64)),
            output_transfer_bytes=1024)
        found = audit.by_rule("output-transfer")
        assert found and found[0].severity == "error"

    def test_nonhashable_static_arg(self):
        audit = analysis.audit_callable(
            lambda x, cfg: x, jnp.ones(2), [1, 2], static_argnums=(1,))
        assert audit.by_rule("nonhashable-static") and audit.errors

    def test_weak_type_input_flagged(self):
        audit = analysis.audit_callable(lambda x, s: x * s,
                                        jnp.ones(4), 2.0)
        assert audit.by_rule("weak-type")

    def test_findings_are_structured_and_published(self):
        def f(x):
            return jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

        audit = analysis.audit_callable(f, jnp.ones(3), name="pubcheck")
        d = audit.to_dict()
        assert d["program"] == "pubcheck"
        f0 = d["findings"][0]
        assert {"rule_id", "severity", "message", "hint", "path",
                "line"} <= set(f0)
        snap = monitor.snapshot()
        series = snap["audit_findings_total"]["series"]
        assert any(s["labels"]["program"] == "pubcheck" and
                   s["labels"]["rule_id"] == "host-callback"
                   for s in series)


def _tiny_model():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    return LlamaForCausalLM(cfg)


class TestEngineDecodeAudit:
    """PR 2's '(batch,) ids are the only per-step host transfer' claim,
    promoted from changelog prose to an enforced static invariant."""

    def test_sampled_path_is_transfer_free(self):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        model = _tiny_model()
        with ContinuousBatchingEngine(model, total_pages=32, page_size=8,
                                      max_batch=4,
                                      sample_on_device=True) as eng:
            audit = analysis.audit_engine(eng)
            assert audit.host_transfer_findings == [], audit.report()
            # the sampled draw variant ships the same (batch,) ids
            audit_draw = analysis.audit_engine(eng, sample="draw")
            assert audit_draw.host_transfer_findings == [], \
                audit_draw.report()

    def test_logits_path_is_flagged(self):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        model = _tiny_model()
        with ContinuousBatchingEngine(model, total_pages=32, page_size=8,
                                      max_batch=4,
                                      sample_on_device=False) as eng:
            audit = analysis.audit_engine(eng)
            found = audit.by_rule("output-transfer")
            assert found, audit.report()
            # the flagged buffer is the (batch, vocab) logits row
            assert "float32[4, 64]" in found[0].message

    def test_decode_pools_are_donated(self):
        # the page pools ride through the step donated — the auditor
        # must NOT see them as per-step transfers or donation misses
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        model = _tiny_model()
        with ContinuousBatchingEngine(model, total_pages=32, page_size=8,
                                      max_batch=4) as eng:
            # threshold == one pool's size, so the pools ARE donation
            # candidates and only the donate_argnums contract clears them
            pool_bytes = int(np.prod(eng.cache.k_pages[0].shape)) * 4
            audit = analysis.audit_engine(eng,
                                          donation_bytes=pool_bytes)
            assert not audit.by_rule("missed-donation"), audit.report()


class TestEngineVerifyAudit:
    """ISSUE 6 CI satellite: the ragged program of a SPECULATING engine
    (every row a verify block of spec_k + 1 tokens) is certified
    transfer-free (ids + accept counts only), donation-intact on BOTH
    page pools, and free of baked [B, k]-shaped host constants — the
    draft block must ride as a traced argument, never a const."""

    def _spec_engine(self):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        return ContinuousBatchingEngine(
            _tiny_model(), total_pages=32, page_size=8, max_batch=4,
            draft_model=_tiny_model(), spec_tokens=3)

    def test_verify_is_transfer_free_and_bakes_no_block(self):
        with self._spec_engine() as eng:
            audit = analysis.audit_engine(eng, mode="ragged")
            assert audit.host_transfer_findings == [], audit.report()
            # no [B, k]-shaped (or any other) host constant baked in
            assert not audit.by_rule("const-capture"), audit.report()
            # the fused-draw variant keeps the same contract
            draw = analysis.audit_engine(eng, mode="ragged",
                                         sample="draw")
            assert draw.host_transfer_findings == [], draw.report()
            assert not draw.by_rule("const-capture"), draw.report()

    def test_verify_keeps_both_pools_donated(self):
        with self._spec_engine() as eng:
            pool_bytes = int(np.prod(eng.cache.k_pages[0].shape)) * 4
            audit = analysis.audit_engine(eng, mode="ragged",
                                          donation_bytes=pool_bytes)
            assert not audit.by_rule("missed-donation"), audit.report()
            assert not audit.by_rule("output-transfer"), audit.report()

    def test_the_ragged_audit_spans_the_verify_block(self):
        """The audited shape is the widest row the engine composes: the
        verify block of a speculating engine, one token without a draft;
        no program of its own carries a verify block any more."""
        from paddle_tpu.analysis.program_audit import engine_program_spec
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        with self._spec_engine() as eng:
            _, _, args, meta = engine_program_spec(eng, "ragged")
            assert args[1].shape == (meta["batch"], 4)   # pow2(3 + 1)
        with ContinuousBatchingEngine(_tiny_model(), total_pages=32,
                                      page_size=8) as eng:
            _, _, args, meta = engine_program_spec(eng, "ragged")
            assert args[1].shape == (meta["batch"], 1)
            with pytest.raises(ValueError, match="'chunk' or 'ragged'"):
                analysis.audit_engine(eng, mode="verify")


class TestEngineChunkAudit:
    """ISSUE 7 CI satellite: the chunked-prefill continuation program
    (shared with the prefix-cache suffix path) is certified
    transfer-free with donation intact — interleaving prefill chunks
    with decode must never smuggle a host sync or a dropped donation
    into the serving loop."""

    def test_chunk_program_transfer_free_donation_intact(self):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        with ContinuousBatchingEngine(_tiny_model(), total_pages=32,
                                      page_size=8, max_batch=4,
                                      prefill_chunk_tokens=8) as eng:
            audit = analysis.audit_engine(eng, mode="chunk")
            assert audit.host_transfer_findings == [], audit.report()
            assert not audit.by_rule("missed-donation"), audit.report()
            # the fused-draw tail (sampled final chunk) keeps the
            # same contract
            draw = analysis.audit_engine(eng, mode="chunk",
                                         sample="draw")
            assert draw.host_transfer_findings == [], draw.report()
            assert not draw.by_rule("missed-donation"), draw.report()

    def test_unknown_mode_rejected(self):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        with ContinuousBatchingEngine(_tiny_model(), total_pages=32,
                                      page_size=8) as eng:
            with pytest.raises(ValueError, match="chunk"):
                analysis.audit_engine(eng, mode="prefill")


class TestEngineRaggedAudit:
    """ISSUE 17 CI satellite: the unified ragged step — the ONE
    program a serving iteration dispatches — certified transfer-free
    with both page pools' donation intact, on the greedy and the
    fused-draw sampling variants."""

    def test_ragged_program_transfer_free_donation_intact(self):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        with ContinuousBatchingEngine(_tiny_model(), total_pages=32,
                                      page_size=8, max_batch=4,
                                      prefill_chunk_tokens=8) as eng:
            audit = analysis.audit_engine(eng, mode="ragged")
            assert audit.host_transfer_findings == [], audit.report()
            assert not audit.by_rule("missed-donation"), audit.report()
            draw = analysis.audit_engine(eng, mode="ragged",
                                         sample="draw")
            assert draw.host_transfer_findings == [], draw.report()
            assert not draw.by_rule("missed-donation"), draw.report()


class TestStaticProgramAudit:
    def test_program_audit_clean_math(self):
        prog = paddle.static.Program()
        with paddle.static.program_guard(prog):
            x = paddle.static.data("x", [2, 4], "float32")
            w = paddle.create_parameter([4, 3], "float32")
            y = paddle.matmul(x, w)
        audit = prog.audit(feed={"x": np.zeros((2, 4), "float32")},
                           fetch_list=[y])
        assert isinstance(audit, analysis.ProgramAudit)
        assert not audit.host_transfer_findings, audit.report()

    def test_to_static_audit(self):
        lin = paddle.nn.Linear(4, 3)

        @paddle.jit.to_static
        def fwd(t):
            return lin(t)

        audit = fwd.audit(paddle.to_tensor(np.ones((2, 4), "float32")))
        assert not audit.errors, audit.report()


class TestCompileHooks:
    def test_recompile_counter_tracks_backend_compiles(self):
        if not monitor.install_compile_hooks():
            pytest.skip("this jax build has no monitoring hook")

        def count():
            m = monitor.get_registry().get("jit_recompile_count")
            return m.value() if m is not None else 0.0

        before = count()
        f = jax.jit(lambda x: x * 3.25 + 0.125)
        f(jnp.ones(5))
        f(jnp.ones(5))          # cache hit: no compile
        f(jnp.ones((2, 5)))     # new shape: recompile
        assert count() - before >= 2
        s, c = monitor.get_registry().get(
            "jit_compile_seconds").sum_count()
        assert c >= 2 and s > 0

    def test_install_is_idempotent(self):
        first = monitor.install_compile_hooks()
        assert monitor.install_compile_hooks() == first
