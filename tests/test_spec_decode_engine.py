"""Paged speculative decoding in the continuous-batching engine
(ISSUE 6).  The correctness anchor is EXACTNESS: whatever the draft
proposes, the engine's speculative output is token-for-token identical
to target-only greedy — across batch sizes, prefix-cache hits, and
mid-stream quarantine/eviction of a speculating sequence.  The perf
anchor is structural: one verify dispatch advances a row by up to
spec_k + 1 tokens, so a perfect draft finishes in ~budget/(k+1) engine
steps instead of ~budget."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def tiny_model(seed=0, layers=2, max_pos=128):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=layers, num_attention_heads=4,
                      num_key_value_heads=2,
                      max_position_embeddings=max_pos)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def target():
    return tiny_model(0)


@pytest.fixture(scope="module")
def clone_draft():
    """Same seed + config as ``target`` → identical weights: the
    perfect draft (acceptance ~1.0)."""
    return tiny_model(0)


@pytest.fixture(scope="module")
def bad_draft():
    """Different seed → proposals rarely match: near-zero acceptance,
    the adversarial exactness case."""
    return tiny_model(7)


def _prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, (n,)).astype(np.int32) for n in sizes]


def _run(model, prompts, budgets, draft_model=None, timeout=300, **kw):
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine

    with ContinuousBatchingEngine(model, total_pages=128, page_size=8,
                                  max_batch=4, draft_model=draft_model,
                                  **kw) as eng:
        reqs = [eng.submit(p, max_new_tokens=m)
                for p, m in zip(prompts, budgets)]
        outs = [r.result(timeout=timeout) for r in reqs]
        steps = eng.steps
    return outs, steps


class TestSpecExactness:
    @pytest.mark.parametrize("sizes,budgets", [
        ([5], [12]),                         # solo sequence
        ([5, 9, 4], [10, 6, 8]),             # ragged batch
    ])
    def test_perfect_and_bad_draft_match_plain_greedy(
            self, target, clone_draft, bad_draft, sizes, budgets):
        prompts = _prompts(sizes)
        ref, ref_steps = _run(target, prompts, budgets)
        for draft in (clone_draft, bad_draft):
            got, _ = _run(target, prompts, budgets, draft_model=draft,
                          spec_tokens=3)
            for a, b in zip(ref, got):
                np.testing.assert_array_equal(a, b)

    def test_eos_semantics_match(self, target, clone_draft):
        """eos emitted mid-acceptance must cut the emission exactly
        where the plain path would stop."""
        prompts = _prompts([6], seed=3)
        # discover the greedy stream, then use its 3rd generated token
        # as eos so it lands inside a speculative acceptance run
        ref, _ = _run(target, prompts, [10])
        eos = int(ref[0][len(prompts[0]) + 2])

        def run(draft):
            from paddle_tpu.inference.continuous import \
                ContinuousBatchingEngine
            with ContinuousBatchingEngine(
                    target, total_pages=64, page_size=8, max_batch=2,
                    draft_model=draft, spec_tokens=3) as eng:
                return eng.submit(prompts[0], max_new_tokens=10,
                                  eos_token_id=eos).result(timeout=300)

        np.testing.assert_array_equal(run(None), run(clone_draft))

    def test_exact_with_prefix_cache_hits(self, target, clone_draft):
        """Sharers admitted after the prefix is cached suffix-prefill on
        the target while the draft full-prefills — lockstep must hold
        and output stay exact."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        rng = np.random.default_rng(5)
        system = rng.integers(0, 64, (16,)).astype(np.int32)  # 2 pages
        prompts = [np.concatenate([system,
                                   rng.integers(0, 64, (4,))]).astype(
                       np.int32) for _ in range(3)]
        ref = []
        for p in prompts:
            out, _ = _run(target, [p], [8], prefix_cache=False)
            ref.append(out[0])
        with ContinuousBatchingEngine(target, total_pages=128, page_size=8,
                                      max_batch=4, prefix_cache=True,
                                      draft_model=clone_draft,
                                      spec_tokens=3) as eng:
            # sequence: first seeds the prefix cache, the rest hit it
            outs = [eng.submit(prompts[0], max_new_tokens=8)
                    .result(timeout=300)]
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts[1:]]
            outs += [r.result(timeout=300) for r in reqs]
            hits = eng.cache._prefix_index
            assert hits, "prefix cache never registered the system prompt"
        for a, b in zip(ref, outs):
            np.testing.assert_array_equal(a, b)

    def test_sampled_rows_ride_along_unaccelerated(self, target,
                                                   clone_draft):
        """do_sample rows in a speculative batch advance one token per
        step with the SAME (seed, position) threefry draws as the plain
        engine — outputs must match a draft-free engine run."""
        prompts = _prompts([5, 6], seed=9)

        def run(draft):
            from paddle_tpu.inference.continuous import \
                ContinuousBatchingEngine
            with ContinuousBatchingEngine(
                    target, total_pages=128, page_size=8, max_batch=4,
                    draft_model=draft, spec_tokens=3) as eng:
                r1 = eng.submit(prompts[0], max_new_tokens=8)
                r2 = eng.submit(prompts[1], max_new_tokens=8,
                                do_sample=True, temperature=0.8, seed=11)
                return r1.result(timeout=300), r2.result(timeout=300)

        g_ref, s_ref = run(None)
        g_spec, s_spec = run(clone_draft)
        np.testing.assert_array_equal(g_ref, g_spec)
        np.testing.assert_array_equal(s_ref, s_spec)


class TestSpecScheduling:
    def test_perfect_draft_cuts_steps(self, target, clone_draft):
        prompts = _prompts([5], seed=1)
        _, plain_steps = _run(target, prompts, [12])
        _, spec_steps = _run(target, prompts, [12],
                             draft_model=clone_draft, spec_tokens=3)
        assert plain_steps >= 12
        # k=3 + bonus = up to 4 tokens per step; admission overhead adds
        # at most a step
        assert spec_steps <= 5, (
            f"{spec_steps} engine steps for 12 tokens with a perfect "
            "k=3 draft — the verify step is not advancing multi-token")

    def test_verify_is_one_dispatch_per_step(self, target, clone_draft):
        """No per-proposed-token host loop: exactly ONE verify-bearing
        dispatch per engine decode step — a ``ragged_step`` call that
        carries draft rows (ISSUE 17)."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        calls = []
        with ContinuousBatchingEngine(target, total_pages=64, page_size=8,
                                      max_batch=2,
                                      draft_model=clone_draft,
                                      spec_tokens=3) as eng:
            orig_r = eng._decoder.ragged_step

            def counting_ragged(*a, **kw):
                nds = kw.get("n_drafts")
                if nds is not None and any(int(x) for x in nds):
                    calls.append(1)
                return orig_r(*a, **kw)

            eng._decoder.ragged_step = counting_ragged
            eng.submit(_prompts([5], seed=2)[0],
                       max_new_tokens=12).result(timeout=300)
            assert len(calls) == eng.steps

    def test_pools_reclaim_and_draft_capacity_accounted(
            self, target, clone_draft):
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        with ContinuousBatchingEngine(target, total_pages=64, page_size=8,
                                      max_batch=4,
                                      draft_model=clone_draft,
                                      spec_tokens=3) as eng:
            reqs = [eng.submit(p, max_new_tokens=6)
                    for p in _prompts([4, 5], seed=4)]
            for r in reqs:
                r.result(timeout=300)
            # let the scheduler observe idle and release the pads
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with eng._cond:
                    idle = not eng._active and not len(eng._sched)
                if idle and eng.draft_cache.free_pages \
                        == eng.draft_cache.total_pages:
                    break
                time.sleep(0.02)
            assert eng.cache.free_pages == eng.cache.total_pages
            assert eng.draft_cache.free_pages \
                == eng.draft_cache.total_pages
            assert eng._reserved_draft_pages == eng._pad_pages
        snap = monitor.snapshot()
        for name in ("spec_proposed_tokens_total",
                     "spec_accepted_tokens_total", "spec_accept_len",
                     "spec_rollback_total", "spec_draft_pages"):
            assert name in snap, f"missing monitor series {name}"

    def test_cancel_mid_stream_frees_both_caches(self, target,
                                                 clone_draft):
        """Evicting a speculating sequence (cooperative cancel) must
        reclaim its pages in BOTH pools while batchmates keep decoding
        exactly."""
        from paddle_tpu.inference.continuous import (
            ContinuousBatchingEngine, RequestCancelled)

        from paddle_tpu.testing import faults

        prompts = _prompts([5, 6], seed=6)
        ref, _ = _run(target, [prompts[0]], [24])
        with ContinuousBatchingEngine(target, total_pages=128, page_size=8,
                                      max_batch=4,
                                      draft_model=clone_draft,
                                      spec_tokens=3) as eng:
            # a sticky delay keeps every decode step slow enough that
            # the cancel reliably lands MID-STREAM (victim needs >= 16
            # verify rounds for its 64-token budget)
            faults.install({"rules": [{"site": "decode_step",
                                       "kind": "delay",
                                       "delay_s": 0.05}]})
            try:
                keeper = eng.submit(prompts[0], max_new_tokens=24)
                victim = eng.submit(prompts[1], max_new_tokens=64)
                time.sleep(0.15)       # a few slowed steps in
                assert victim.cancel()
            finally:
                faults.clear()
            with pytest.raises(RequestCancelled):
                victim.result(timeout=300)
            out = keeper.result(timeout=300)
            np.testing.assert_array_equal(ref[0], out)
            assert victim.seq_id not in eng.draft_cache._seq_pages
            assert victim.seq_id not in eng.cache._seq_pages

    def test_quarantine_of_speculating_sequence_is_isolated(
            self, target, clone_draft):
        """A sticky decode-step fault on one speculating sequence must
        quarantine exactly that request; its batchmate's output stays
        bit-exact."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine
        from paddle_tpu.testing import faults

        prompts = _prompts([5, 6], seed=8)
        ref, _ = _run(target, [prompts[0]], [10])
        with ContinuousBatchingEngine(target, total_pages=128, page_size=8,
                                      max_batch=4,
                                      draft_model=clone_draft,
                                      spec_tokens=3) as eng:
            # poison the SECOND admitted sequence (seq ids are assigned
            # in admission order: keeper 0, victim 1); the plan is
            # installed BEFORE submission so the very first specu-
            # lative step already sees it — retry, then bisect, then
            # quarantine exactly the victim
            with faults.installed({"rules": [{"site": "decode_step",
                                              "seq_id": 1}]}):
                keeper = eng.submit(prompts[0], max_new_tokens=10)
                victim = eng.submit(prompts[1], max_new_tokens=10)
                with pytest.raises(faults.FaultError):
                    victim.result(timeout=300)
                out = keeper.result(timeout=300)
        np.testing.assert_array_equal(ref[0], out)

    def test_draft_prefill_failure_downgrades_not_quarantines(
            self, target, clone_draft):
        """Draft-side failures degrade the request to plain decode —
        the output is still produced and still exact."""
        from paddle_tpu import monitor
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        prompts = _prompts([5], seed=10)
        ref, _ = _run(target, prompts, [8])

        def val(name):
            m = monitor.snapshot().get(name)
            return m["series"][0]["value"] if m and m["series"] else 0.0

        before = val("spec_draft_failures_total")
        with ContinuousBatchingEngine(target, total_pages=64, page_size=8,
                                      max_batch=2,
                                      draft_model=clone_draft,
                                      spec_tokens=3) as eng:
            orig = eng._draft_decoder.prefill

            def boom(*a, **kw):
                raise RuntimeError("injected draft prefill failure")

            eng._draft_decoder.prefill = boom
            req = eng.submit(prompts[0], max_new_tokens=8)
            out = req.result(timeout=300)
            assert not req.use_draft          # downgraded, not errored
            assert eng._reserved_draft_pages == eng._pad_pages
            eng._draft_decoder.prefill = orig
        np.testing.assert_array_equal(ref[0], out)
        assert val("spec_draft_failures_total") == before + 1


class TestSpecSubmitValidation:
    def test_draft_true_without_draft_model_rejected(self, target):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        with ContinuousBatchingEngine(target, total_pages=32,
                                      page_size=8) as eng:
            with pytest.raises(ValueError, match="draft"):
                eng.submit(np.zeros(4, np.int32), max_new_tokens=4,
                           draft=True)

    def test_draft_true_with_sampling_rejected(self, target, clone_draft):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        with ContinuousBatchingEngine(target, total_pages=32, page_size=8,
                                      draft_model=clone_draft) as eng:
            with pytest.raises(ValueError, match="greedy"):
                eng.submit(np.zeros(4, np.int32), max_new_tokens=4,
                           draft=True, do_sample=True)

    def test_spec_overhang_tightens_rope_bound(self, target, clone_draft):
        """prompt + max_new + spec_k must fit the rope table — the
        verify block writes the overhang before rolling back."""
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        with ContinuousBatchingEngine(target, total_pages=64, page_size=8,
                                      draft_model=clone_draft,
                                      spec_tokens=4) as eng:
            # 120 + 4 = 124 fits 128 with the 4-token overhang
            eng.submit(np.zeros(100, np.int32), max_new_tokens=20,
                       draft=False).result(timeout=300)
            with pytest.raises(ValueError, match="overhang"):
                eng.submit(np.zeros(100, np.int32), max_new_tokens=26)

    def test_opt_out_rows_never_touch_draft_pool(self, target,
                                                 clone_draft):
        from paddle_tpu.inference.continuous import ContinuousBatchingEngine

        with ContinuousBatchingEngine(target, total_pages=64, page_size=8,
                                      max_batch=2,
                                      draft_model=clone_draft,
                                      spec_tokens=3) as eng:
            req = eng.submit(_prompts([5], seed=12)[0], max_new_tokens=6,
                             draft=False)
            req.result(timeout=300)
            assert not req.use_draft
            assert req.seq_id not in eng.draft_cache._seq_pages
