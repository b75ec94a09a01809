"""chip_smoke.py's phases at tiny sizes on the CPU (rehearsals 1 and 2 of
the on-chip-measurement guide): wrong paths, arguments and control flow
surface here and not on the chip.  The kernels run interpreted and the
serving / training programs take their XLA paths — chosen here, by the
arguments the phase functions already take."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from paddle_tpu.framework import compile_cache  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, llama_7b  # noqa: E402


def tiny_cfg(layers=2, max_position=128):
    return LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=layers, num_attention_heads=4,
                       num_key_value_heads=4,
                       max_position_embeddings=max_position)


class TestSizing:
    def test_param_counts_match_the_model(self):
        from paddle_tpu.models.llama import LlamaForCausalLM
        cfg = tiny_cfg(layers=3)
        layer, outer = chip_smoke.llama_param_counts(cfg)
        n = sum(int(np.prod(p.shape))
                for p in LlamaForCausalLM(cfg).parameters())
        assert n == outer + 3 * layer

    def test_llama_7b_arithmetic(self):
        layer, outer = chip_smoke.llama_param_counts(llama_7b())
        assert round(layer / 1e6) == 202 and round(outer / 1e6) == 262

    def test_depth_from_a_16gb_chip(self):
        limit = int(15.75 * 2**30)
        cfg = llama_7b()
        assert chip_smoke.serve_depth(cfg, limit, 8 * 4096) == 8
        assert chip_smoke.train_depth(cfg, limit, 2, 2048) == 2
        # a smaller device cuts deeper, never below one layer
        assert chip_smoke.serve_depth(cfg, limit // 8, 8 * 4096) == 1

    def test_cut_config_keeps_every_width(self):
        cfg, full = chip_smoke.cut_config(3, max_position=2048), llama_7b()
        assert cfg.num_hidden_layers == 3
        assert cfg.max_position_embeddings == 2048
        for f in ("hidden_size", "intermediate_size", "vocab_size",
                  "num_attention_heads", "num_key_value_heads"):
            assert getattr(cfg, f) == getattr(full, f)


class TestPhasesTiny:
    def test_kernels_interpreted(self):
        chip_smoke.phase_kernels(
            tiny_cfg(), 0, page_size=8, decode_batch=2, table_pages=4,
            chunk_tokens=8, train_batch=1, train_seq=128, compiled=False)

    def test_tune_train_ops_takes_defaults_off_the_chip(self, monkeypatch):
        from paddle_tpu.ops import autotune
        monkeypatch.setattr(autotune, "_decisions", {})
        chip_smoke.tune_train_ops(tiny_cfg(), 0, batch=1, seq=16)
        took = {k.split(":")[0]: v for k, v in autotune.decisions().items()}
        assert took["fused_rope"] == ("xla", "default")
        assert took["rms_norm"] == ("xla", "default")

    def test_tune_train_ops_fails_on_a_refused_candidate(self, monkeypatch):
        from paddle_tpu.ops import autotune
        monkeypatch.setattr(autotune, "_failures", [])
        real = autotune.select

        def refusing(key, arr, candidates, default, tpu_only=True):
            if key.startswith("fused_rope"):
                autotune._failures.append((key, "pallas", "refused"))
            return real(key, arr, candidates, default, tpu_only)

        monkeypatch.setattr(autotune, "select", refusing)
        with pytest.raises(AssertionError, match="refused"):
            chip_smoke.tune_train_ops(tiny_cfg(), 0, batch=1, seq=16)

    def test_serve_phase(self):
        chip_smoke.phase_serve(
            tiny_cfg(), 0, param_dtype="float32", total_pages=64,
            page_size=8, max_batch=4, chunk_tokens=16, short_len=5,
            long_len=64, prefix_len=32, new_tokens=4, expect_kernel=False,
            logits_tol=1e-4)

    def test_serve_phase_fails_on_a_recompile_after_warm_up(self, monkeypatch):
        # the measured wave is requests 8-12 (5 of wave A, 2 of wave B
        # before it): dropping the compiled programs inside it must fail
        # the phase, not pass it
        real, calls = chip_smoke._generate, {"n": 0}

        def forgetful(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 9:
                jax.clear_caches()
            return real(*a, **kw)

        monkeypatch.setattr(chip_smoke, "_generate", forgetful)
        with pytest.raises(AssertionError, match="compiled after the warm-up"):
            chip_smoke.phase_serve(
                tiny_cfg(), 0, param_dtype="float32", total_pages=64,
                page_size=8, max_batch=4, chunk_tokens=16, short_len=5,
                long_len=64, prefix_len=32, new_tokens=4,
                expect_kernel=False, logits_tol=1e-4)

    def test_serve_phase_demands_the_kernel_when_asked(self):
        with pytest.raises(AssertionError, match="paged-attention kernel"):
            chip_smoke.phase_serve(
                tiny_cfg(), 0, param_dtype="float32", total_pages=64,
                page_size=8, max_batch=2, chunk_tokens=16, short_len=5,
                long_len=32, prefix_len=16, new_tokens=2,
                expect_kernel=True, logits_tol=1e-4, bytes_limit=1 << 30)

    def test_train_phase(self):
        losses = chip_smoke.phase_train(
            tiny_cfg(max_position=32), 0, batch=2, seq=32, steps=3,
            k_fused=2, expect_flash=False)
        assert len(losses) == 3 and losses[-1] < losses[0]

    def test_train_phase_demands_flash_when_asked(self):
        with pytest.raises(AssertionError, match="flash-attention"):
            chip_smoke.phase_train(
                tiny_cfg(max_position=32), 0, batch=2, seq=32, steps=1,
                k_fused=1, expect_flash=True)


class TestFourChipPhasesOnVirtualDevices:
    def test_tp4_serving_against_one_device(self):
        assert len(jax.devices()) >= 4
        agree = chip_smoke.phase_four_serve(
            tiny_cfg(), 0, param_dtype="float32", total_pages=64,
            page_size=8, max_batch=2, chunk_tokens=16,
            prompt_lens=(5, 24), new_tokens=4, logits_tol=1e-4,
            read_memory=False)
        assert agree == [4, 4]

    def test_dp2_mp2_train_step_against_one_device(self):
        chip_smoke.phase_four_train(tiny_cfg(max_position=32), 0,
                                    batch=4, seq=32, tol=1e-3)


class TestScriptContract:
    def test_exits_nonzero_without_a_tpu(self):
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
            capture_output=True, text=True, timeout=300)
        assert res.returncode != 0
        assert '"ok": true' not in res.stdout
        assert "needs a TPU" in res.stderr

    def test_four_chip_option_exits_nonzero_without_a_tpu(self):
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py"),
             "--chips", "4"],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
            capture_output=True, text=True, timeout=300)
        assert res.returncode != 0 and '"ok": true' not in res.stdout


class TestCompileCachePlacement:
    @pytest.fixture
    def restore(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_places_it_and_code_sets_nothing(self, monkeypatch, restore):
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert compile_cache.configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir is None

    def test_unset_names_one_fixed_path_in_the_checkout(self, monkeypatch,
                                                        restore):
        import tempfile
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.configure_compile_cache()
        assert path == os.path.join(REPO, ".cache", "jax")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.configure_compile_cache() == path
        assert str(os.getpid()) not in path
        assert not path.startswith(tempfile.gettempdir())
        ignored = subprocess.run(["git", "check-ignore", "-q", path],
                                 cwd=REPO).returncode
        assert ignored == 0, ".cache/ must be listed in .gitignore"

    def test_tests_cache_in_a_temp_dir_of_the_session(self):
        # conftest gives the session one cache of its own, outside the
        # checkout, and removes it at the end: nothing an earlier run
        # left is read, nothing is written beside the sources
        import tempfile
        path = jax.config.jax_compilation_cache_dir
        assert path == os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert path.startswith(tempfile.gettempdir())
        assert not path.startswith(REPO)

    def test_autotune_winners_live_in_the_same_directory(self):
        # conftest points the tests' own cache at a temp file through the
        # variable; the default the program uses is under the checkout
        import paddle_tpu.ops.autotune as at
        assert compile_cache.CACHE_ROOT == os.path.join(REPO, ".cache")
        src = open(at.__file__).read()
        assert 'os.path.join(CACHE_ROOT, "autotune.json")' in src
        assert "expanduser" not in src
