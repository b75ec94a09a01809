"""Kernel autotune tests: cache behavior, flash dispatch policy."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops import autotune
from paddle_tpu.nn.functional.attention import (
    _choose_flash_impl, _XLA_SCORE_BYTES_LIMIT,
)


class TestAutotuneCache:
    def test_measures_and_caches_winner(self, tmp_path, monkeypatch):
        monkeypatch.setattr(autotune, "_CACHE_PATH",
                            str(tmp_path / "at.json"))
        monkeypatch.setattr(autotune, "_cache", None)
        calls = {"fast": 0, "slow": 0}

        import jax.numpy as jnp

        def fast():
            calls["fast"] += 1
            return jnp.zeros(4)

        def slow():
            calls["slow"] += 1
            import time
            time.sleep(0.01)
            return jnp.zeros(4)

        w = autotune.autotune("k1", {"fast": fast, "slow": slow},
                              default="slow")
        assert w == "fast"
        # cached now: no re-measurement
        calls["fast"] = calls["slow"] = 0
        assert autotune.autotune("k1", {"fast": fast, "slow": slow},
                                 default="slow") == "fast"
        assert calls == {"fast": 0, "slow": 0}
        assert autotune.lookup("k1") == "fast"

    def test_failing_candidate_loses_and_is_reported(self, tmp_path,
                                                     monkeypatch):
        monkeypatch.setattr(autotune, "_CACHE_PATH",
                            str(tmp_path / "at2.json"))
        monkeypatch.setattr(autotune, "_cache", None)
        monkeypatch.setattr(autotune, "_failures", [])
        monkeypatch.setattr(autotune, "_decisions", {})

        import jax.numpy as jnp

        def boom():
            raise MemoryError("refused")

        with pytest.warns(UserWarning, match="candidate 'boom' failed"):
            assert autotune.autotune(
                "k2", {"boom": boom, "ok": lambda: jnp.zeros(2)},
                default="boom") == "ok"
        # which, and why — never skipped in silence
        assert autotune.failures() == [("k2", "boom",
                                        "MemoryError('refused')")]
        assert autotune.decisions() == {"k2": ("ok", "measured")}

    def test_disabled_returns_default(self, monkeypatch):
        monkeypatch.setattr(autotune, "_enabled", False)
        assert autotune.autotune("k3", {}, default="d") == "d"


class TestFlashDispatch:
    def test_dispatch_under_tracing(self):
        """Traced calls (no cache entry) must follow the memory heuristic:
        small scores -> xla, huge scores -> pallas."""
        import jax
        import jax.numpy as jnp
        choices = {}

        def probe(name, b, s, h, d):
            def f(q, k):
                choices[name] = _choose_flash_impl(q, k, True)
                return q
            jax.eval_shape(f, jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16),
                           jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16))

        probe("small", 2, 256, 4, 64)     # 2 MB scores
        probe("big", 8, 8192, 16, 64)     # 128 GB scores
        assert choices["small"] == "xla"
        assert choices["big"] == "pallas"

    def test_eager_concrete_big_never_times_xla(self):
        """Concrete big-score inputs must skip XLA timing (OOM risk)."""
        import jax.numpy as jnp

        class Big:
            shape = (8, 8192, 16, 64)
            dtype = jnp.bfloat16
        assert _choose_flash_impl(Big(), Big(), True) == "pallas"

    def test_flash_attention_correct_both_sizes(self):
        # small (xla route) and a shape forced through pallas agree with ref
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_bshd, mha_reference)
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(2, 256, 4, 64).astype("float32"))
        out_p = flash_attention_bshd(q, q, q, causal=True)
        qt = jnp.swapaxes(q, 1, 2)
        ref = jnp.swapaxes(mha_reference(qt, qt, qt, causal=True), 1, 2)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref),
                                   atol=2e-2, rtol=2e-2)

    def test_functional_flash_attention_end_to_end(self):
        import paddle_tpu.nn.functional as F
        x = paddle.to_tensor(
            np.random.randn(2, 128, 4, 32).astype("float32"))
        out, _ = F.flash_attention(x, x, x, causal=True)
        assert tuple(out.shape) == (2, 128, 4, 32)
        out2 = F.scaled_dot_product_attention(x, x, x, is_causal=True)
        np.testing.assert_allclose(out.numpy(), out2.numpy(), atol=1e-5)
