"""Int8 weight-only matmul Pallas kernel (ops/pallas/quant_matmul.py)
vs its XLA oracle, through the interpreter on CPU (Mosaic lowering is
covered by test_pallas_mosaic_lowering.py; the kernels of the main
paths run on the chip in chip_smoke.py's kernel phase).

Reference capability: fused weight-only linear,
paddle/phi/kernels/fusion/gpu (weight-only linear family) behind
python/paddle/nn/quant/quantized_linear.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.ops.pallas.quant_matmul as QM


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setattr(QM, "_INTERPRET", True)


def _mk(m, k, n, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)).astype("float32"), dtype)
    w = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    s = jnp.asarray(rng.uniform(0.001, 0.02, (n,)).astype("float32"))
    return x, w, s


class TestWeightOnlyMatmul:
    @pytest.mark.parametrize("shape", [(8, 128, 128), (16, 256, 384),
                                       (130, 300, 200)])  # ragged tiles
    def test_matches_xla_oracle(self, shape):
        x, w, s = _mk(*shape)
        got = QM.weight_only_matmul_pallas(x, w, s,
                                           block_m=64, block_n=128,
                                           block_k=128, interpret=True)
        ref = QM.weight_only_matmul_xla(x, w, s)
        # blocked-K accumulation reorders the f32 sums vs one fused dot
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)

    def test_bf16_activation(self):
        x, w, s = _mk(16, 128, 128, dtype=jnp.bfloat16)
        got = QM.weight_only_matmul_pallas(x, w, s, interpret=True)
        ref = QM.weight_only_matmul_xla(x, w, s)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32),
            np.asarray(ref, dtype=np.float32), rtol=2e-2, atol=2e-2)

    def test_grad_dx_and_dscale_match_dense_math(self):
        x, w, s = _mk(8, 128, 128, seed=3)

        def via_kernel(x, s):
            return jnp.sum(QM.weight_only_matmul(x, w, s) ** 2)

        def via_dense(x, s):
            w_fp = w.astype(jnp.float32) * s[None, :]
            return jnp.sum(jnp.matmul(x, w_fp) ** 2)

        gx1, gs1 = jax.grad(via_kernel, argnums=(0, 1))(x, s)
        gx2, gs2 = jax.grad(via_dense, argnums=(0, 1))(x, s)
        np.testing.assert_allclose(np.asarray(gx1), np.asarray(gx2),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(gs1), np.asarray(gs2),
                                   rtol=1e-3, atol=1e-3)


class TestWeightOnlyLinearIntegration:
    def test_framework_op_uses_same_math(self):
        # the user-facing nn.quant op (3-D activations, bias) must agree
        # with the dense dequant reference whichever backend dispatched
        import paddle_tpu as paddle
        from paddle_tpu.nn.quant import (weight_only_linear,
                                         weight_quantize)
        rng = np.random.default_rng(5)
        xw = rng.standard_normal((256, 128)).astype("float32")
        q, s = paddle.to_tensor(np.asarray(
            jnp.clip(jnp.round(jnp.asarray(xw) / 0.01), -127, 127)
            .astype(jnp.int8))), paddle.to_tensor(
                np.full((128,), 0.01, np.float32))
        x = paddle.to_tensor(
            rng.standard_normal((2, 4, 256)).astype("float32"))
        b = paddle.to_tensor(rng.standard_normal((128,)).astype("float32"))
        y = weight_only_linear(x, q, weight_scale=s, bias=b)
        ref = (np.asarray(x._data).reshape(-1, 256)
               @ (np.asarray(q._data, np.float32) * 0.01)
               ).reshape(2, 4, 128) + np.asarray(b._data)
        np.testing.assert_allclose(np.asarray(y._data), ref, rtol=1e-4,
                                   atol=1e-4)

    def test_weight_quantize_roundtrip_through_linear(self):
        import paddle_tpu as paddle
        from paddle_tpu.nn.quant import (weight_only_linear,
                                         weight_quantize)
        rng = np.random.default_rng(6)
        w = paddle.to_tensor(rng.standard_normal((64, 32))
                             .astype("float32") * 0.3)
        q, s = weight_quantize(w, algo="weight_only_int8")
        x = paddle.to_tensor(rng.standard_normal((5, 64))
                             .astype("float32"))
        y = weight_only_linear(x, q, weight_scale=s)
        ref = np.asarray(x._data) @ np.asarray(w._data)
        # int8 quantization error bound, not numerics error
        np.testing.assert_allclose(np.asarray(y._data), ref,
                                   rtol=0.05, atol=0.05)
