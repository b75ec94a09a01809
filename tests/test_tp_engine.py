"""Tensor-parallel serving engine (ISSUE 20): the unified ragged step
compiled TP-sharded over a ``Mesh(('tensor',))``.

The acceptance core is BIT-EXACT greedy parity: the same prompt set
through a 1-chip engine and a TP=2 engine (virtual CPU devices — the
conftest splits the host into 8) must produce identical tokens on the
host-logits escape hatch, across every serving composition the engine
dispatches — the unified ragged step, its fused sampling tail,
chunked prefill, prefix-cache hits, and speculative verify.
Column-parallel projections are exact by construction; the one f32
``psum`` per block closes each row-parallel projection with the same
summands on every chip, so greedy argmax never diverges.

Also covered: ``make_tp_mesh`` (in-suite + the pre-init CPU guard in a
subprocess), KV pools sharded on the kv-head axis (per-chip bytes =
global / tp), the quantize+mesh composition rejection, head-count
divisibility validation, int8 quantized collectives
(``tp_quant_collectives``) within the documented tolerance on the
logits hatch, the /health TP fields, and a supervised fleet with a TP
replica in the mix (a sharded engine is ONE replica — the supervisor
and router must not notice the mesh behind it).
"""
import json
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.jax_compat import make_tp_mesh
from paddle_tpu.inference.continuous import ContinuousBatchingEngine
from paddle_tpu.inference.paged import JittedPagedDecoder
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas.paged_attention import PagedKVCache


def tiny_model(seed=0, kv_heads=2):
    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=kv_heads,
                      max_position_embeddings=128)
    return LlamaForCausalLM(cfg)


def _prompts(ns=(5, 9, 13), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 64, (n,)).astype(np.int32) for n in ns]


def greedy_run(prompts, draft=False, **engine_kw):
    """The same seeded model through an engine on the host-logits path
    (host argmax over f32 logits — exact and deterministic); sequenced
    submission per prompt ORDER is not required for greedy parity, but
    prefix-hit tests pass ``sequence=True`` via max_batch=1-style
    waits themselves."""
    kw = dict(total_pages=128, page_size=8, max_batch=4,
              sample_on_device=False)
    kw.update(engine_kw)
    if draft:
        kw.update(draft_model=tiny_model(), spec_tokens=2)
    with ContinuousBatchingEngine(tiny_model(), **kw) as eng:
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        return [np.asarray(r.result(timeout=600)) for r in reqs]


def assert_parity(prompts, **engine_kw):
    base = greedy_run(prompts, **engine_kw)
    shard = greedy_run(prompts, tp=2, **engine_kw)
    for i, (a, b) in enumerate(zip(base, shard)):
        assert np.array_equal(a, b), \
            f"request {i}: 1-chip {a.tolist()} vs tp=2 {b.tolist()}"


class TestMakeTpMesh:
    def test_in_suite_mesh(self):
        # the conftest pre-split the CPU host into 8 virtual devices,
        # so TP=2 meshes build directly inside tier-1 tests
        mesh = make_tp_mesh(2)
        assert dict(mesh.shape) == {"tensor": 2}
        assert dict(make_tp_mesh(1).shape) == {"tensor": 1}

    def test_rejects_degree_below_one(self):
        with pytest.raises(ValueError, match="tp degree"):
            make_tp_mesh(0)

    def test_post_init_overask_names_the_escape_hatch(self):
        make_tp_mesh(2)        # force backend init at 8 virtual devices
        with pytest.raises(RuntimeError,
                           match="xla_force_host_platform_device_count"):
            make_tp_mesh(64)

    @pytest.mark.slow
    def test_preinit_guard_provisions_cpu_devices(self):
        # a FRESH process with no XLA_FLAGS: make_tp_mesh(2) called
        # before any jax operation must provision the virtual devices
        # itself (the in-process equivalent of the env flag)
        code = (
            "from paddle_tpu.framework.jax_compat import make_tp_mesh\n"
            "mesh = make_tp_mesh(2)\n"
            "print('SHAPE', dict(mesh.shape))\n")
        env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
               "PYTHONPATH": ".", "HOME": "/tmp"}
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=".",
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        assert "SHAPE {'tensor': 2}" in out.stdout


class TestDecoderTP:
    def test_prefill_decode_parity_and_pool_sharding(self):
        mesh = make_tp_mesh(2)
        m1, m2 = tiny_model(), tiny_model()
        d1 = JittedPagedDecoder(m1)
        c1 = PagedKVCache.from_model(m1, total_pages=32, page_size=8)
        d2 = JittedPagedDecoder(m2, mesh=mesh)
        c2 = PagedKVCache.from_model(m2, total_pages=32, page_size=8,
                                     mesh=mesh)
        assert c2.tp == 2
        assert c2.kv_pool_bytes_per_chip * 2 == c2.kv_pool_bytes
        assert c1.kv_pool_bytes == c2.kv_pool_bytes    # GLOBAL bytes
        # the committed placement: pools sharded on the leading
        # kv-head axis
        spec = c2.k_pages[0].sharding.spec
        assert tuple(spec)[:1] == ("tensor",)

        prompt = _prompts((8,))[0][None]
        l1 = np.asarray(d1.prefill(c1, [0], prompt))
        l2 = np.asarray(d2.prefill(c2, [0], prompt))
        t1, t2 = np.argmax(l1, -1), np.argmax(l2, -1)
        assert np.array_equal(t1, t2)
        pos = np.array([prompt.shape[1]], np.int32)
        tok = t1.astype(np.int32).reshape(1, 1)
        for _ in range(6):
            s1 = np.asarray(d1.step(c1, [0], tok, pos))
            s2 = np.asarray(d2.step(c2, [0], tok, pos))
            n1, n2 = np.argmax(s1, -1), np.argmax(s2, -1)
            assert np.array_equal(n1, n2)
            tok = n1.astype(np.int32).reshape(1, 1)
            pos = pos + 1

    def test_quantize_plus_mesh_rejected(self):
        with pytest.raises(ValueError, match="quantize"):
            JittedPagedDecoder(tiny_model(), quantize="w8",
                               mesh=make_tp_mesh(2))

    def test_indivisible_kv_heads_rejected(self):
        with pytest.raises(ValueError, match="kv"):
            JittedPagedDecoder(tiny_model(kv_heads=1),
                               mesh=make_tp_mesh(2))

    def test_reset_pools_stay_sharded(self):
        # recovery rebuilds pools from scratch — they must come back
        # SHARDED, or the next sharded dispatch recompiles/reshards
        mesh = make_tp_mesh(2)
        m = tiny_model()
        cache = PagedKVCache.from_model(m, total_pages=32, page_size=8,
                                        mesh=mesh)
        before = cache.k_pages[0].sharding
        cache.reset_pools()
        assert cache.k_pages[0].sharding == before
        assert cache.kv_pool_bytes_per_chip * 2 == cache.kv_pool_bytes


class TestEngineParity:
    """Greedy token parity, 1-chip vs TP=2, per serving composition."""

    def test_unified_ragged_step(self):
        assert_parity(_prompts((5, 9, 13, 20)))

    def test_fused_sampling_tail(self):
        # the programs that end in the on-device sampler (ids cross the
        # host boundary, not logits), sharded against one chip
        assert_parity(_prompts((5, 9, 3)), sample_on_device=True)

    def test_chunked_prefill(self):
        # 40-token prompts chunk at 8 through the prefix program
        assert_parity(_prompts((40, 37, 6)), prefill_chunk_tokens=8)

    def test_prefix_hit(self):
        rng = np.random.default_rng(3)
        system = rng.integers(0, 64, (16,)).astype(np.int32)
        suffixed = [np.concatenate([system,
                                    rng.integers(0, 64, (n,))
                                    .astype(np.int32)])
                    for n in (5, 7)]

        def run(**kw):
            with ContinuousBatchingEngine(
                    tiny_model(), total_pages=128, page_size=8,
                    max_batch=4, sample_on_device=False,
                    prefix_cache=True, **kw) as eng:
                # sequenced: the second submission must HIT the prefix
                # the first registered
                outs = [np.asarray(
                    eng.submit(p, max_new_tokens=8).result(timeout=600))
                    for p in suffixed]
                hits = eng.cache.cached_prefix_pages
            return outs, hits

        base, _ = run()
        shard, hits = run(tp=2)
        assert hits > 0      # the TP engine actually took the hit path
        for a, b in zip(base, shard):
            assert np.array_equal(a, b)

    def test_speculative_verify(self):
        # same-seed draft accepts ~everything: the verify program is
        # the hot path, and its sharded twin must match token-for-token
        assert_parity(_prompts((6, 11, 4)), draft=True)

    def test_failure_ladder_over_a_mesh(self):
        """The ladder over the SHARDED ragged step: one transient loss
        of the donated (sharded) pools — rebuilt sharded, survivors
        replayed — and a decode fault that follows seq 1.  Exactly that
        request goes; the others' tokens are the 1-chip engine's."""
        from paddle_tpu.testing import faults
        prompts = _prompts((5, 9, 13))
        want = greedy_run(prompts, prefill_chunk_tokens=8)
        # buffer_loss fires once a program call: 3 is a step that holds
        # every row (the three prompts are one chunk step or two)
        plan = faults.FaultPlan([
            {"site": "buffer_loss", "nth": 3},
            {"site": "decode_step", "seq_id": 1}])
        try:
            with faults.installed(plan), ContinuousBatchingEngine(
                    tiny_model(), total_pages=128, page_size=8,
                    max_batch=4, sample_on_device=False, tp=2,
                    prefill_chunk_tokens=8) as eng:
                reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
                with pytest.raises(faults.FaultError):
                    reqs[1].result(timeout=600)
                got = {i: np.asarray(reqs[i].result(timeout=600))
                       for i in (0, 2)}
                sharding = eng.cache.k_pages[0].sharding
                assert dict(sharding.mesh.shape) == {"tensor": 2}
                assert eng.cache.free_pages == eng.cache.total_pages
        finally:
            faults.clear()
        assert {f[0] for f in plan.fired} == {"buffer_loss", "decode_step"}
        for i in (0, 2):
            assert np.array_equal(got[i], want[i])

    def test_int8_collectives_within_tolerance(self):
        # quantized all-reduces are NOT bit-exact (absmax-int8 round
        # trip per block) — the documented tolerance on the logits
        # hatch: prefill logits within 0.05, at most one flipped
        # greedy request out of six
        m1, m2 = tiny_model(), tiny_model()
        mesh = make_tp_mesh(2)
        d1 = JittedPagedDecoder(m1)
        c1 = PagedKVCache.from_model(m1, total_pages=16, page_size=8)
        d2 = JittedPagedDecoder(m2, mesh=mesh, tp_quant_collectives=True)
        c2 = PagedKVCache.from_model(m2, total_pages=16, page_size=8,
                                     mesh=mesh)
        prompt = _prompts((13,))[0][None]
        l1 = np.asarray(d1.prefill(c1, [0], prompt))
        l2 = np.asarray(d2.prefill(c2, [0], prompt))
        assert float(np.max(np.abs(l1 - l2))) < 0.05

        prompts = _prompts((5, 9, 13, 20, 7, 16))
        base = greedy_run(prompts)
        quant = greedy_run(prompts, tp=2, tp_quant_collectives=True)
        matches = sum(bool(np.array_equal(a, b))
                      for a, b in zip(base, quant))
        assert matches >= len(prompts) - 1


class TestServerAndFleetTP:
    def test_health_reports_tp_fields(self):
        from paddle_tpu.inference.server import GenerationServer
        srv = GenerationServer(tiny_model(), total_pages=32, page_size=8,
                               max_batch=2, tp=2).start()
        try:
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/health",
                    timeout=60) as r:
                payload = json.loads(r.read())
        finally:
            srv.stop()
        assert payload["tp"] == 2
        assert payload["mesh_shape"] == {"tensor": 2}
        assert payload["tp_quant_collectives"] is False
        assert payload["kv_pool_bytes_per_chip"] * 2 \
            == payload["kv_pool_bytes"]

    def test_health_meshless_engine_reports_tp_one(self):
        from paddle_tpu.inference.server import GenerationServer
        srv = GenerationServer(tiny_model(), total_pages=32, page_size=8,
                               max_batch=2).start()
        try:
            with urllib.request.urlopen(
                    f"http://{srv.host}:{srv.port}/health",
                    timeout=60) as r:
                payload = json.loads(r.read())
        finally:
            srv.stop()
        assert payload["tp"] == 1
        assert payload["mesh_shape"] is None
        assert payload["kv_pool_bytes_per_chip"] \
            == payload["kv_pool_bytes"]

    def test_fleet_probes_and_routes_with_tp_replica(self, tmp_path):
        # one 1-chip replica + one TP=2 replica behind the supervisor:
        # probes pass, the router serves through both, and greedy
        # outputs match the single-engine oracle wherever round-robin
        # lands each request
        from paddle_tpu.inference.fleet import (FleetRouter,
                                                ReplicaSupervisor)

        built = []

        def factory(name, jdir):
            from paddle_tpu.inference.server import GenerationServer
            tp = 2 if len(built) % 2 else 1
            built.append(name)
            return GenerationServer(tiny_model(), total_pages=128,
                                    page_size=8, max_batch=4,
                                    journal_dir=jdir,
                                    journal_fsync="always", tp=tp)

        sup = ReplicaSupervisor(factory=factory, replicas=2,
                                journal_root=str(tmp_path),
                                probe_interval_s=0.1,
                                probe_failure_threshold=2,
                                probe_timeout_s=2.0,
                                heartbeat_timeout_s=10.0)
        router = FleetRouter(sup, attach_timeout_s=300.0)
        prompts = _prompts((6, 10, 5, 8), seed=7)
        oracle = greedy_run(prompts)
        try:
            sup.start()
            router.start()
            t0 = time.monotonic()
            while time.monotonic() - t0 < 300 \
                    and len(sup.routable_replicas()) < 2:
                time.sleep(0.05)
            assert len(sup.routable_replicas()) == 2
            url = f"http://{router.host}:{router.port}/generate"
            for i, (p, ref) in enumerate(zip(prompts, oracle)):
                body = {"input_ids": [p.tolist()], "max_new_tokens": 8,
                        "request_id": f"tp-fleet-{i}"}
                req = urllib.request.Request(
                    url, data=json.dumps(body).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=600) as r:
                    out = json.loads(r.read())
                assert out["output_ids"][0] == ref.tolist(), \
                    f"request {i} diverged"
        finally:
            router.stop()
            sup.stop()
