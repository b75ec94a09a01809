"""Speculative decoding (draft-verify; Leviathan et al. greedy variant).

The load-bearing property: greedy speculative output is BIT-IDENTICAL
to target-only greedy decoding regardless of draft quality — with a
random (bad) draft, with the target as its own draft (100% acceptance,
exercising the all-accepted cache gap-fill), and across eos cuts.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import SpeculativeGenerator
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _model(layers, seed):
    paddle.seed(seed)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=32, intermediate_size=64,
        num_hidden_layers=layers, num_attention_heads=2,
        max_position_embeddings=128))


def _prompt(n=7, seed=0):
    return paddle.to_tensor(np.random.default_rng(seed).integers(
        0, 96, (1, n)).astype("int32"))


class TestSpeculativeGreedyExactness:
    # 8 new tokens, not 24: the generator's caches are concat caches,
    # so every cache length is a new shape and every eager op on it a
    # new compile (some 17 a round) — more rounds add compiles, not
    # cases: with this draft every round rejects
    NEW_TOKENS = 8

    @pytest.fixture(scope="class")
    def bad_draft(self):
        target, draft = _model(4, 0), _model(2, 99)
        x = _prompt()
        return target, draft, x, np.asarray(
            target.generate(x, max_new_tokens=self.NEW_TOKENS))

    @pytest.mark.parametrize("k", [1, 2, 4, 7])
    def test_matches_target_greedy_with_bad_draft(self, bad_draft, k):
        target, draft, x, ref = bad_draft
        gen = SpeculativeGenerator(target, draft,
                                   num_speculative_tokens=k)
        got = gen.generate(x, max_new_tokens=self.NEW_TOKENS)
        np.testing.assert_array_equal(ref, got)
        assert gen.last_stats["rounds"] >= 1

    def test_self_draft_accepts_everything(self):
        # draft == target: every proposal must be accepted; the
        # all-accepted path exercises the draft-cache gap-fill
        target = _model(3, 1)
        gen = SpeculativeGenerator(target, target,
                                   num_speculative_tokens=4)
        x = _prompt(seed=1)
        got = gen.generate(x, max_new_tokens=20)
        ref = target.generate(x, max_new_tokens=20)
        np.testing.assert_array_equal(np.asarray(ref), got)
        assert gen.last_stats["acceptance_rate"] == 1.0
        # k accepted + 1 bonus token per round
        assert gen.last_stats["tokens_per_round"] > 4.0

    def test_eos_cuts_emission(self):
        target, draft = _model(3, 2), _model(2, 3)
        x = _prompt(seed=2)
        ref = np.asarray(target.generate(x, max_new_tokens=16,
                                         eos_token_id=5))
        gen = SpeculativeGenerator(target, draft,
                                   num_speculative_tokens=3)
        got = gen.generate(x, max_new_tokens=16, eos_token_id=5)
        # both stop at the same place with identical tokens
        n = min(ref.shape[1], got.shape[1])
        np.testing.assert_array_equal(ref[:, :n], got[:, :n])

    def test_rejects_batched_input(self):
        target = _model(2, 4)
        gen = SpeculativeGenerator(target, target)
        bad = paddle.to_tensor(np.zeros((2, 4), np.int32))
        try:
            gen.generate(bad, max_new_tokens=4)
        except ValueError as e:
            assert "batch 1" in str(e)
        else:
            raise AssertionError("batched input should raise")


class TestSpeculativeMoeTarget:
    def test_moe_target_dense_draft_exact(self):
        # the generator is model-agnostic: a sparse-MoE target verified
        # by a dense draft still reproduces target-only greedy exactly
        from paddle_tpu.models import LlamaMoeConfig, LlamaMoeForCausalLM
        paddle.seed(10)
        target = LlamaMoeForCausalLM(LlamaMoeConfig(
            vocab_size=96, hidden_size=32, intermediate_size=64,
            num_hidden_layers=2, num_attention_heads=2,
            max_position_embeddings=128, num_experts=4,
            gate_type="naive"))
        target.eval()
        draft = _model(1, 11)
        x = _prompt(seed=10)
        ref = np.asarray(target.generate(x, max_new_tokens=12))
        got = SpeculativeGenerator(target, draft, 3).generate(
            x, max_new_tokens=12)
        np.testing.assert_array_equal(ref, got)


class TestRollbackNeverCopiesFullCache:
    """ISSUE 6 satellite: rejected speculative suffixes roll back by
    slicing only the APPENDED block — the pre-round cache survives by
    identity, never as a fresh O(T) copy (the old _trim_caches rebuilt
    every layer's full cache every round)."""

    def test_absorb_preserves_base_identity_and_slices_only_tail(self):
        import jax.numpy as jnp
        from paddle_tpu.framework.tensor import wrap_array
        from paddle_tpu.inference.speculative import _RollbackKV

        T, k, accepted = 10, 4, 2
        base = [(wrap_array(jnp.zeros((1, T, 2, 8))),
                 wrap_array(jnp.zeros((1, T, 2, 8))))]
        kv = _RollbackKV(base)
        fed = kv.feed()
        assert fed is base and fed[0][0] is base[0][0]   # no-op merge
        full = [(wrap_array(jnp.ones((1, T + k + 1, 2, 8))),
                 wrap_array(jnp.ones((1, T + k + 1, 2, 8))))]
        kv.absorb(full, T + accepted + 1)
        # the base was NOT rebuilt: same objects, untouched
        assert kv.base is base and kv.base[0][0] is base[0][0]
        # only the accepted prefix of the block was sliced out
        assert int(kv.tail[0][0].shape[1]) == accepted + 1
        assert kv.length == T + accepted + 1
        merged = kv.feed()
        assert int(merged[0][0].shape[1]) == T + accepted + 1
        assert kv.tail is None

    def test_generator_rollback_keeps_base_alive_across_rounds(self):
        """After a full generate() with a rejecting draft, the live
        cache state must show base+tail structure (identity-preserving
        absorb ran) and output stays exact."""
        target, draft = _model(2, 5), _model(2, 77)
        x = _prompt(n=6, seed=5)
        ref = np.asarray(target.generate(x, max_new_tokens=10))
        gen = SpeculativeGenerator(target, draft,
                                   num_speculative_tokens=3)
        got = gen.generate(x, max_new_tokens=10)
        np.testing.assert_array_equal(ref, got)
        assert gen.last_stats["accepted"] < gen.last_stats["proposed"], \
            "draft never rejected — rollback path unexercised"
        # the generator exposes its rollback caches; a completed run
        # leaves them consistent with the emitted length
        covered = gen._tgt_kv.length
        assert covered == got.shape[1] - 1 or covered == got.shape[1]
