"""Analytical cost model (ISSUE 10): FLOPs oracles vs hand-counted
tiny programs, int8 width accounting, control-flow multipliers, the
engine program estimate, and the MFU plumbing."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.analysis import cost


class TestFlopsOracles:
    def test_matmul_hand_count(self):
        # (4,8) @ (8,16): 2*M*K*N = 2*4*8*16 = 1024 FLOPs; bytes =
        # (4*8 + 8*16 + 4*16) * 4 = 896 at f32
        def mm(a, b):
            return a @ b

        est = cost.estimate_callable(
            mm, jnp.zeros((4, 8), jnp.float32),
            jnp.zeros((8, 16), jnp.float32))
        f, b = est.by_primitive["dot_general"]
        assert f == 2 * 4 * 8 * 16
        assert b == (4 * 8 + 8 * 16 + 4 * 16) * 4

    def test_batched_dot_hand_count(self):
        # batch dims count once: (3,4,8) @ (3,8,5) = 2*3*4*8*5
        def bmm(a, b):
            return jnp.einsum("bik,bkj->bij", a, b)

        est = cost.estimate_callable(
            bmm, jnp.zeros((3, 4, 8), jnp.float32),
            jnp.zeros((3, 8, 5), jnp.float32))
        f, _ = est.by_primitive["dot_general"]
        assert f == 2 * 3 * 4 * 8 * 5

    def test_tiny_attention_hand_count(self):
        # QK^T (2*s*s*d) + AV (2*s*s*d) with s=4, d=8: dot FLOPs 512
        s, d = 4, 8

        def attn(q, k, v):
            a = jax.nn.softmax(q @ k.T / np.sqrt(d), axis=-1)
            return a @ v

        est = cost.estimate_callable(
            attn, jnp.zeros((s, d), jnp.float32),
            jnp.zeros((s, d), jnp.float32),
            jnp.zeros((s, d), jnp.float32))
        f, _ = est.by_primitive["dot_general"]
        assert f == 2 * s * s * d + 2 * s * s * d

    def test_conv_hand_count(self):
        # NCHW (1,3,8,8) * OIHW (4,3,3,3), SAME: out (1,4,8,8);
        # 2 * out_size * Cin * Kh * Kw = 2*256*3*9
        def conv(x, w):
            return jax.lax.conv_general_dilated(
                x, w, (1, 1), "SAME",
                dimension_numbers=("NCHW", "OIHW", "NCHW"))

        est = cost.estimate_callable(
            conv, jnp.zeros((1, 3, 8, 8), jnp.float32),
            jnp.zeros((4, 3, 3, 3), jnp.float32))
        f, _ = est.by_primitive["conv_general_dilated"]
        assert f == 2 * (1 * 4 * 8 * 8) * 3 * 9

    def test_scan_multiplies_by_trip_count(self):
        def scanned(a, b):
            def body(c, _):
                return c @ b, ()
            out, _ = jax.lax.scan(body, a, None, length=5)
            return out

        est = cost.estimate_callable(
            scanned, jnp.zeros((4, 8), jnp.float32),
            jnp.zeros((8, 8), jnp.float32))
        assert est.by_primitive["dot_general"][0] == 5 * 2 * 4 * 8 * 8

    def test_gather_scatter_are_movement_not_flops(self):
        def g(x, idx):
            return x[idx]

        est = cost.estimate_callable(
            g, jnp.zeros((16, 8), jnp.float32),
            jnp.zeros((4,), jnp.int32))
        for prim in ("gather", "dynamic_slice"):
            if prim in est.by_primitive:
                assert est.by_primitive[prim][0] == 0
                assert est.by_primitive[prim][1] > 0

    def test_remat_mlp_prices_the_recompute(self):
        # ISSUE 11 satellite: a remat'd (jax.checkpoint) MLP grad must
        # price the recomputed forward — fwd dot + remat'd-recompute
        # dot + bwd dx dot + bwd dw dot = 4 dot_generals of 2*B*D*D
        B = D = 8

        def mlp(x, w):
            h = jax.checkpoint(lambda a: jnp.tanh(a @ w))(x)
            return jnp.sum(h)

        grad_both = jax.grad(mlp, argnums=(0, 1))
        est = cost.estimate_callable(
            grad_both, jnp.zeros((B, D), jnp.float32),
            jnp.zeros((D, D), jnp.float32))
        f, b = est.by_primitive["dot_general"]
        assert f == 4 * 2 * B * D * D
        assert b > 0
        # HBM is priced too: the remat body's tanh traffic is counted
        assert est.by_primitive["tanh"][1] > 0
        # and the un-remat'd twin prices the SAME flops minus one
        # recompute dot — remat is more FLOPs, never fewer

        def mlp_plain(x, w):
            return jnp.sum(jnp.tanh(x @ w))

        est_plain = cost.estimate_callable(
            jax.grad(mlp_plain, argnums=(0, 1)),
            jnp.zeros((B, D), jnp.float32),
            jnp.zeros((D, D), jnp.float32))
        f_plain, _ = est_plain.by_primitive["dot_general"]
        assert f == f_plain + 2 * B * D * D

    def test_custom_vjp_body_priced_once(self):
        # the fun_jaxpr body is priced; fwd/bwd thunks are not walked
        # (they are functions, not jaxprs), so no double count
        @jax.custom_vjp
        def f(x, w):
            return x @ w

        def fwd(x, w):
            return f(x, w), (x, w)

        def bwd(res, g):
            x, w = res
            return g @ w.T, x.T @ g

        f.defvjp(fwd, bwd)
        est = cost.estimate_callable(
            f, jnp.zeros((4, 8), jnp.float32),
            jnp.zeros((8, 16), jnp.float32))
        assert est.by_primitive["dot_general"][0] == 2 * 4 * 8 * 16

    def test_int8_ops_costed_at_their_width(self):
        # same shapes, same FLOPs — int8 operands are 1/4 the bytes
        def mm8(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)

        def mmf(a, b):
            return a @ b

        e8 = cost.estimate_callable(
            mm8, jnp.zeros((8, 8), jnp.int8), jnp.zeros((8, 8), jnp.int8))
        ef = cost.estimate_callable(
            mmf, jnp.zeros((8, 8), jnp.float32),
            jnp.zeros((8, 8), jnp.float32))
        f8 = e8.by_primitive["dot_general"]
        ff = ef.by_primitive["dot_general"]
        assert f8[0] == ff[0]
        # int8 in, int32 accumulator out: (64+64)*1 + 64*4 vs (3*64)*4
        assert f8[1] == (64 + 64) * 1 + 64 * 4
        assert ff[1] == 3 * 64 * 4


class TestEngineEstimate:
    @pytest.fixture(scope="class")
    def engine(self):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.inference.continuous import \
            ContinuousBatchingEngine

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=2, num_key_value_heads=2,
                          max_position_embeddings=64)
        model = LlamaForCausalLM(cfg)
        eng = ContinuousBatchingEngine(model, total_pages=32, page_size=8,
                                       max_batch=4)
        yield eng
        eng.stop()

    def test_decode_program_estimate_and_gauges(self, engine):
        est = cost.estimate_engine(engine, mode="decode")
        assert est.flops > 0 and est.hbm_bytes > 0
        # a transformer decode step is dot-dominated
        assert est.by_primitive["dot_general"][0] > 0
        snap = monitor.snapshot()
        series = {s["labels"]["program"]: s["value"]
                  for s in snap["program_flops_total"]["series"]}
        assert series[est.name] == est.flops

    def test_ragged_program_estimate(self, engine):
        """The unified ragged step prices as ONE program (ISSUE 17).
        Without chunking or speculation every span is one token, so
        the ragged program costs what the decode step costs (a few
        flops of span-index arithmetic aside); a chunked engine's
        ragged program carries the span bucket and must cost more
        than its decode step."""
        est = cost.estimate_engine(engine, mode="ragged")
        assert est.flops > 0 and est.hbm_bytes > 0
        assert est.by_primitive["dot_general"][0] > 0
        assert est.flops == pytest.approx(
            cost.estimate_engine(engine, mode="decode").flops, rel=1e-3)

        from paddle_tpu.inference.continuous import \
            ContinuousBatchingEngine
        with ContinuousBatchingEngine(
                engine.model, total_pages=32, page_size=8, max_batch=4,
                prefill_chunk_tokens=8) as chunked:
            ragged = cost.estimate_engine(chunked, mode="ragged")
            decode = cost.estimate_engine(chunked, mode="decode")
            assert ragged.flops > decode.flops

    def test_publish_engine_cost_sets_mfu(self, engine):
        out = cost.publish_engine_cost(engine)
        assert out["program_flops"] > 0
        assert out["flops_per_token"] == pytest.approx(
            out["program_flops"] / engine.max_batch)
        snap = monitor.snapshot()
        assert "mfu" in snap

    def test_estimate_traces_without_compiling(self, engine):
        monitor.install_compile_hooks()
        before = monitor.snapshot()
        cost.estimate_engine(engine, mode="decode")
        after = monitor.snapshot()

        def compiles(s):
            m = s.get("jit_compile_seconds")
            return m["series"][0]["count"] if m and m["series"] else 0
        assert compiles(after) == compiles(before)


class TestMfuPlumbing:
    def test_peak_flops_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "2.5e13")
        assert cost.peak_flops() == 2.5e13

    def test_peak_flops_cpu_nominal(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        if jax.default_backend() != "tpu":
            assert cost.peak_flops() == cost.DEFAULT_PEAK_FLOPS

    def test_record_mfu_gauge(self):
        v = cost.record_mfu(5e11, 1.0, peak=1e12)
        assert v == pytest.approx(0.5)
        snap = monitor.snapshot()
        assert snap["mfu"]["series"][0]["value"] == pytest.approx(0.5)
        assert cost.record_mfu(1.0, 0.0, peak=1e12) is None


class _FakeDevice:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class TestPeaksAreNeverAssumed:
    """A utilization against an assumed peak is not a measurement: a
    TPU kind that is not in a table raises, in every table."""

    def test_longest_prefix_wins(self):
        table = {"TPU v5": 459.0, "TPU v5 lite": 197.0}
        assert cost.by_device_kind(table, "TPU v5 lite", "peak") == 197.0
        assert cost.by_device_kind(table, "TPU v5p", "peak") == 459.0

    @pytest.mark.parametrize("which", ["peak_flops", "link_bandwidth",
                                       "bench"])
    def test_unknown_tpu_kind_raises(self, monkeypatch, which):
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        monkeypatch.delenv("PADDLE_TPU_ICI_BYTES_PER_S", raising=False)
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [_FakeDevice("tpu", "TPU v99")])
        if which == "peak_flops":
            fn = cost.peak_flops
        elif which == "link_bandwidth":
            from paddle_tpu.analysis import spmd
            fn = spmd.link_bandwidth
        else:
            import os
            import sys
            sys.path.insert(0, os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            import bench
            fn = bench._peak_tflops
        with pytest.raises(ValueError, match="TPU v99"):
            fn()

    def test_known_kind_and_cpu_nominal_name_their_source(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
        assert cost.peak_source() == "cpu_nominal"
        assert cost.peak_flops() == cost.DEFAULT_PEAK_FLOPS
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
        assert cost.peak_flops() == 197e12
        assert cost.peak_source() == "table:TPU v5 lite"
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "5e12")
        assert (cost.peak_flops(), cost.peak_source()) == (5e12, "env")


class TestBenchNeedsTheChip:
    def test_exits_nonzero_without_a_tpu(self):
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        res = subprocess.run(
            [sys.executable, os.path.join(repo, "bench.py")], cwd=repo,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            capture_output=True, text=True, timeout=300)
        assert res.returncode != 0
        assert res.stdout.strip() == ""       # no result is printed
        assert "no TPU" in res.stderr

    def test_a_config_that_raises_ends_the_run(self, monkeypatch):
        import os
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import bench
        monkeypatch.setattr(jax, "devices",
                            lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
        monkeypatch.setattr(
            "paddle_tpu.framework.compile_cache.configure_compile_cache",
            lambda: "unused")

        def boom():
            raise RuntimeError("config failed")

        monkeypatch.setattr(bench, "bench_resnet_cifar", boom)
        with pytest.raises(RuntimeError, match="config failed"):
            bench.main()
