"""analysis.spmd — the SPMD auditor (ISSUE 11 tentpole).

Hand-counted collective-pricing oracles (shard_map dp-allreduce, TP
row/col-parallel matmuls, mesh-size monotonicity), the GSPMD HLO tier
on a dp>1 fused ``run_steps`` program (the acceptance program: the
gradient-sync all-reduces must be NAMED with non-zero priced bytes),
the peak-HBM lifetime walk against XLA's own compiled memory analysis
(llama_tiny train step within 1.5x, predicted >= measured), the
sharding hazard rules on planted programs, and the monitor/gauge
surface."""
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.analysis import spmd
from paddle_tpu.framework.jax_compat import shard_map


def _mesh(n, axis="dp"):
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (axis,))


class TestPricingFormulas:
    def test_ring_multipliers(self):
        # one execution over n=8 at bandwidth 1e9: all_reduce moves
        # 2*(n-1)/n, gather/scatter/all_to_all (n-1)/n, ppermute 1x
        nb, t = spmd.price_collective("all_reduce", 1000.0, 8, 1e9)
        assert nb == pytest.approx(2 * 7 / 8 * 1000.0)
        assert t == pytest.approx(nb / 1e9)
        assert spmd.price_collective("all_gather", 1000.0, 8, 1e9)[0] \
            == pytest.approx(7 / 8 * 1000.0)
        assert spmd.price_collective("reduce_scatter", 1000.0, 8, 1e9)[0] \
            == pytest.approx(7 / 8 * 1000.0)
        assert spmd.price_collective("ppermute", 1000.0, 8, 1e9)[0] \
            == pytest.approx(1000.0)

    def test_mesh_of_one_prices_to_zero(self):
        assert spmd.price_collective("all_reduce", 1e9, 1) == (0.0, 0.0)

    def test_bandwidth_env_override(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_ICI_BYTES_PER_S", "5e9")
        assert spmd.link_bandwidth() == 5e9
        monkeypatch.delenv("PADDLE_TPU_ICI_BYTES_PER_S")
        if jax.default_backend() != "tpu":
            assert spmd.link_bandwidth() == spmd.DEFAULT_LINK_BANDWIDTH


class TestJaxprCollectiveOracles:
    def test_dp_allreduce_hand_count(self):
        # psum of a per-device (8, 4) f32 shard over dp=8: payload
        # 8*4*4 = 128 B, ring all-reduce 2*(7/8)*128 = 224 B over ICI
        mesh = _mesh(8)

        def f(x):
            return jax.lax.psum(x, "dp")

        sm = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P())
        audit = spmd.audit_spmd_callable(
            sm, jnp.zeros((64, 4), jnp.float32), name="dp_allreduce",
            compiled=False, publish=False)
        (c,) = audit.collectives
        assert c.kind == "all_reduce" and c.group_size == 8
        assert c.payload_bytes == 8 * 4 * 4
        assert c.ici_bytes == pytest.approx(2 * 7 / 8 * 128)
        assert c.ici_seconds == pytest.approx(
            c.ici_bytes / audit.link_bandwidth)
        assert audit.collective_bytes_total == c.ici_bytes
        assert audit.mesh_axes == {"dp": 8}

    def test_tp_row_parallel_matmul_hand_count(self):
        # row-parallel: x[(B, K/n)] @ w[(K/n, N)] then psum the (B, N)
        # partials — payload B*N*4, per-shard compute 2*B*(K/n)*N
        mesh = _mesh(8, "tensor")
        B, K, N = 16, 64, 32

        def f(x, w):
            return jax.lax.psum(x @ w, "tensor")

        sm = shard_map(f, mesh=mesh,
                       in_specs=(P(None, "tensor"), P("tensor", None)),
                       out_specs=P())
        audit = spmd.audit_spmd_callable(
            sm, jnp.zeros((B, K), jnp.float32),
            jnp.zeros((K, N), jnp.float32), name="tp_row",
            compiled=False, publish=False)
        (c,) = audit.collectives
        assert c.kind == "all_reduce" and c.group_size == 8
        assert c.payload_bytes == B * N * 4
        assert audit.compute_flops >= 2 * B * (K // 8) * N

    def test_tp_col_parallel_all_gather_hand_count(self):
        # column-parallel epilogue: all_gather the (B, N/n) shards to
        # (B, N) — priced at the FULL gathered result x (n-1)/n
        mesh = _mesh(8, "tensor")
        B, N = 16, 64

        def f(y):
            return jax.lax.all_gather(y, "tensor", axis=1, tiled=True)

        sm = shard_map(f, mesh=mesh, in_specs=P(None, "tensor"),
                       out_specs=P(), check_vma=False)
        audit = spmd.audit_spmd_callable(
            sm, jnp.zeros((B, N), jnp.float32), name="tp_col",
            compiled=False, publish=False)
        (c,) = audit.collectives
        assert c.kind == "all_gather"
        assert c.payload_bytes == B * N * 4          # the gathered full
        assert c.ici_bytes == pytest.approx(7 / 8 * B * N * 4)

    def test_ici_time_monotone_in_mesh_size(self):
        # same GLOBAL payload, growing mesh: ring all-reduce bytes
        # (2*(n-1)/n x shard) grow with n — the weak-scaling shape
        times = []
        for n in (2, 4, 8):
            mesh = _mesh(n)

            def f(x):
                return jax.lax.psum(x, "dp")

            sm = shard_map(f, mesh=mesh, in_specs=P("dp"),
                           out_specs=P())
            audit = spmd.audit_spmd_callable(
                sm, jnp.zeros((64, 64), jnp.float32),
                name=f"dp{n}", compiled=False, publish=False)
            # per-device shard shrinks with n but the ring multiplier
            # grows; normalize to the same per-device payload instead
            (c,) = audit.collectives
            times.append(spmd.price_collective(
                "all_reduce", 64 * 64 * 4, n,
                audit.link_bandwidth)[1])
        assert times[0] < times[1] < times[2]

    def test_int8_collective_half_the_bytes_of_bf16(self):
        # the EQuARX lever, priced before it is built: same shape,
        # int8 payload is 1/4 the f32 bytes
        mesh = _mesh(8)

        def f8(x):
            return jax.lax.psum(x, "dp")

        kw = dict(mesh=mesh, in_specs=P("dp"), out_specs=P())
        a8 = spmd.audit_spmd_callable(
            shard_map(f8, **kw), jnp.zeros((64, 4), jnp.int8),
            name="int8", compiled=False, publish=False)
        af = spmd.audit_spmd_callable(
            shard_map(f8, **kw), jnp.zeros((64, 4), jnp.float32),
            name="f32", compiled=False, publish=False)
        assert a8.collective_bytes_total * 4 == af.collective_bytes_total

    def test_scan_multiplies_collective_count(self):
        mesh = _mesh(8)

        def stepped(xs):
            def body(c, x):
                return c + jax.lax.psum(x, "dp"), ()
            out, _ = jax.lax.scan(body, jnp.zeros((4,), jnp.float32), xs)
            return out

        sm = shard_map(stepped, mesh=mesh, in_specs=P(None, "dp"),
                       out_specs=P(), check_vma=False)
        audit = spmd.audit_spmd_callable(
            sm, jnp.zeros((5, 32), jnp.float32), name="scanned",
            compiled=False, publish=False)
        (c,) = audit.collectives
        assert c.count == 5 and c.in_scan
        assert c.ici_bytes == pytest.approx(
            5 * spmd.price_collective("all_reduce", c.payload_bytes,
                                      8, audit.link_bandwidth)[0])
        # the scan-collective hazard names the bucketing opportunity
        assert any(f.rule_id == "scan-collective"
                   for f in audit.findings)


class TestHloTier:
    def test_gspmd_dp_grad_names_allreduce(self):
        # a NamedSharding dp program has NO psum eqn in its jaxpr —
        # only the compiled-HLO tier can see the partitioner-inserted
        # gradient sync
        mesh = _mesh(8)
        W = jax.device_put(jnp.zeros((64, 64)), NamedSharding(mesh, P()))
        x = jax.device_put(jnp.zeros((16, 64)),
                           NamedSharding(mesh, P("dp")))

        def loss(w, xx):
            return jnp.sum((xx @ w) ** 2)

        g = jax.grad(loss)
        jaxpr_colls, _ = spmd.collectives_from_jaxpr(
            jax.make_jaxpr(g)(W, x))
        assert jaxpr_colls == []          # the jaxpr really is blind
        audit = spmd.audit_spmd_callable(g, W, x, name="dp_grad",
                                         publish=False)
        hlo = [c for c in audit.collectives if c.source == "hlo"]
        assert hlo and hlo[0].kind == "all_reduce"
        assert hlo[0].group_size == 8
        # the f32[64,64] gradient: 16 KiB payload, ring-priced
        assert any(c.payload_bytes == 64 * 64 * 4 for c in hlo)
        assert audit.collective_bytes_total > 0

    def test_forced_compiled_does_not_double_price_jaxpr_collectives(self):
        # regression (review finding): compiled=True on a program with
        # explicit shard_map collectives lists BOTH tiers, but the
        # totals must price each collective once (jaxpr tier wins)
        mesh = _mesh(8)

        def f(x):
            return jax.lax.psum(x, "dp")

        sm = shard_map(f, mesh=mesh, in_specs=P("dp"), out_specs=P())
        base = spmd.audit_spmd_callable(
            sm, jnp.zeros((64, 4), jnp.float32), name="forced_base",
            compiled=False, publish=False)
        forced = spmd.audit_spmd_callable(
            sm, jnp.zeros((64, 4), jnp.float32), name="forced",
            compiled=True, publish=False)
        assert forced.collective_bytes_total == \
            pytest.approx(base.collective_bytes_total)

    def test_publish_preserves_tier1_error_gauge(self):
        # regression (review finding): SpmdAudit.publish must not
        # reset audit_last_error_findings (all spmd hazards are
        # warnings; republishing under the same program label would
        # zero a real tier-1 error count)
        from paddle_tpu.analysis.program_audit import (Finding,
                                                       ProgramAudit)
        name = "gauge-clobber-probe"
        ProgramAudit(name, [Finding("host-callback", "error",
                                    "planted")]).publish()
        audit = spmd.audit_spmd_callable(
            lambda x: x * 2.0, jnp.zeros((8,), jnp.float32),
            name=name, compiled=False, publish=True)
        assert audit is not None
        snap = monitor.snapshot()
        series = {s["labels"]["program"]: s["value"]
                  for s in snap["audit_last_error_findings"]["series"]}
        assert series[name] == 1

    def test_hlo_parser_shapes_groups_and_while_bodies(self):
        text = """
HloModule jit_f

%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%body.1 (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %ar = f32[8,4]{1,0} all-reduce(f32[8,4]{1,0} %g), replica_groups=[1,8]<=[8], to_apply=%add
}

ENTRY %main (p0: f32[8,4]) -> f32[8,4] {
  %w = (s32[], f32[8,4]{1,0}) while((s32[], f32[8,4]{1,0}) %t), condition=%cond.1, body=%body.1
  %ag = bf16[16,4]{1,0} all-gather(bf16[2,4]{1,0} %x), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %rs = f32[2,4]{1,0} reduce-scatter(f32[16,4]{1,0} %y), replica_groups=[1,8]<=[8], dimensions={0}, to_apply=%add
}
"""
        colls = spmd.collectives_from_hlo_text(text, n_devices=8,
                                               bandwidth=1e9)
        by_kind = {c.kind: c for c in colls}
        ar = by_kind["all_reduce"]
        assert ar.group_size == 8 and ar.payload_bytes == 8 * 4 * 4
        assert ar.in_scan                      # lives in the while body
        ag = by_kind["all_gather"]
        assert ag.group_size == 8
        assert ag.payload_bytes == 16 * 4 * 2  # bf16 gathered result
        assert not ag.in_scan
        rs = by_kind["reduce_scatter"]
        # the instruction result is the post-scatter SHARD: priced at
        # the full pre-scatter input (shard x n), matching the jaxpr
        # tier's psum_scatter convention
        assert rs.payload_bytes == 8 * (2 * 4 * 4)
        assert rs.ici_bytes == pytest.approx(7 / 8 * 8 * 2 * 4 * 4)

    def test_async_start_ops_priced_from_largest_tuple_element(self):
        # regression (review finding): TPU HLO emits async pairs whose
        # -start result tuple carries the operand alias next to the
        # real result — summing would double-count the payload
        text = """
ENTRY %main (p0: f32[2,4]) -> f32[16,4] {
  %ags = (f32[2,4]{1,0}, f32[16,4]{1,0}) all-gather-start(f32[2,4]{1,0} %x), replica_groups=[1,8]<=[8], dimensions={0}
}
"""
        (ag,) = spmd.collectives_from_hlo_text(text, n_devices=8,
                                               bandwidth=1e9)
        assert ag.kind == "all_gather"
        assert ag.payload_bytes == 16 * 4 * 4   # the gathered result
        assert ag.ici_bytes == pytest.approx(7 / 8 * 16 * 4 * 4)


class TestFusedRunStepsDp:
    """The ISSUE 11 acceptance program: the PR 5 fused K-step scan at
    dp>1 on the CPU mesh."""

    @pytest.fixture(scope="class")
    def dp_step(self):
        import paddle_tpu.nn as nn
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as optim
        import paddle_tpu.distributed as dist
        from paddle_tpu.jit import TrainStep

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                            nn.Linear(128, 8))
        # its OWN 8-way dp mesh: left to the default, DataParallel
        # takes whatever mesh another file of this worker last set
        dp = dist.DataParallel(net, mesh=dist.ProcessMesh(
            np.arange(jax.device_count()), ["dp"]))
        opt = optim.SGD(learning_rate=1e-2,
                        parameters=net.parameters())
        step = TrainStep(dp, lambda out, y: F.cross_entropy(out, y),
                         opt)
        rng = np.random.default_rng(0)

        def mk():
            return (paddle.to_tensor(
                        rng.standard_normal((16, 64)).astype("float32")),
                    paddle.to_tensor(
                        rng.integers(0, 8, (16,)).astype("int64")))

        return step, [mk(), mk()]

    def test_names_gradient_sync_collectives_with_bytes(self, dp_step):
        step, batches = dp_step
        audit = spmd.audit_spmd_fused(step, batches, publish=False)
        grad_sync = [c for c in audit.collectives
                     if c.kind == "all_reduce" and c.ici_bytes > 0]
        assert grad_sync, "dp gradient sync must be named and priced"
        # the (64,128) first-layer weight grad is the biggest payload:
        # 32 KiB f32, ring-priced over the 8-way mesh.  The installed
        # XLA may combine the per-parameter all-reduces into one tuple
        # all-reduce, so the 32 KiB shows alone or inside a larger sum.
        assert max(c.payload_bytes for c in grad_sync) >= 64 * 128 * 4
        assert audit.mesh_axes.get("dp") == 8
        assert audit.collective_bytes_total > 0
        assert audit.ici_time_seconds > 0

    def test_audit_fused_autoruns_spmd_on_mesh(self, dp_step):
        step, batches = dp_step
        audit = step.audit_fused(batches, publish=False)
        assert audit.spmd is not None
        assert any(c.ici_bytes > 0 for c in audit.spmd.collectives)


class TestPeakHbm:
    def test_donated_input_freed_nondonated_resident(self):
        # two (1 MiB) inputs; the program reads each once and returns
        # a like-sized output.  Donating `a` lets its buffer die after
        # its last use; non-donated `b` stays resident to the end.
        N = 1 << 18    # f32 -> 1 MiB

        def f(a, b):
            return jnp.tanh(a) + b

        closed = jax.make_jaxpr(f)(
            jnp.zeros((N,), jnp.float32), jnp.zeros((N,), jnp.float32))
        free = spmd.estimate_peak_hbm(
            closed, donated_avals=[jax.ShapeDtypeStruct((N,),
                                                        jnp.float32)])
        held = spmd.estimate_peak_hbm(closed)
        assert held > free
        # non-donated: a + b + tanh(a) + out live together at the add
        assert held >= 4 * N * 4 - 1
        assert free >= 3 * N * 4 - 1

    def test_scan_body_peak_stacks_on_carry(self):
        # the scan body's temporaries count on top of the live carry
        def f(c, xs):
            def body(c, x):
                return c + jnp.tanh(x) * 2.0, ()
            out, _ = jax.lax.scan(body, c, xs)
            return out

        N = 1024
        closed = jax.make_jaxpr(f)(
            jnp.zeros((N,), jnp.float32),
            jnp.zeros((4, N), jnp.float32))
        peak = spmd.estimate_peak_hbm(closed)
        # carry (4K) + stacked xs (16K) + body temps (>= one (N,) slice)
        assert peak >= 4 * N * 4 + N * 4 + N * 4

    def test_long_scan_body_intermediates_never_clamped(self):
        # regression (review finding): with many stacked trips the
        # caller-side operand (K*N) dwarfs the body's per-trip state —
        # subtracting it would clamp the body contribution to zero and
        # break the predicted >= measured upper-bound contract
        N, K = 1024, 16

        def f(c, xs):
            def body(c, x):
                t1 = jnp.tanh(x)
                t2 = t1 * x + c
                return c + t2, ()
            out, _ = jax.lax.scan(body, c, xs)
            return out

        closed = jax.make_jaxpr(f)(
            jnp.zeros((N,), jnp.float32),
            jnp.zeros((K, N), jnp.float32))
        peak = spmd.estimate_peak_hbm(closed)
        # stacked xs (K*N*4) + carry + at least two live body temps
        assert peak >= K * N * 4 + N * 4 + 2 * N * 4

    def test_llama_tiny_train_step_within_1p5x_of_measured(self):
        # the acceptance bound: static estimate vs XLA's own compiled
        # memory analysis (the memory gate's alias-aware formula) on
        # the llama_tiny ladder rung's cfg, CPU backend
        import paddle_tpu.nn.functional as F
        import paddle_tpu.optimizer as optim
        from paddle_tpu.jit import TrainStep
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        import sys
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import bench

        paddle.seed(0)
        cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                          intermediate_size=688, num_hidden_layers=4,
                          num_attention_heads=4,
                          max_position_embeddings=256)
        model = LlamaForCausalLM(cfg)
        opt = optim.AdamW(learning_rate=1e-3,
                          parameters=model.parameters())

        def loss_fn(logits, labels):
            return F.cross_entropy(
                logits.reshape([-1, 2048]).astype("float32"),
                labels.reshape([-1]))

        step = TrainStep(model, loss_fn, opt)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 2048, (2, 65)).astype("int32")
        x = paddle.to_tensor(ids[:, :-1])
        y = paddle.to_tensor(ids[:, 1:])
        predicted = step.static_peak_hbm(x, y)
        measured = bench.planned_peak_bytes(step.memory_analysis(x, y))
        assert measured > 0
        assert predicted >= measured          # never under-plan
        assert predicted <= 1.5 * measured    # and never cry wolf


class TestHazardRules:
    def test_replicated_large_param_planted(self):
        # a 4 MiB operand replicated over an 8-way mesh: every chip
        # stores all of it — the planted hazard must be caught
        mesh = _mesh(8)
        big = jax.device_put(jnp.zeros((1024, 1024), jnp.float32),
                             NamedSharding(mesh, P()))
        x = jax.device_put(jnp.zeros((16, 1024), jnp.float32),
                           NamedSharding(mesh, P("dp")))

        def f(w, xx):
            return xx @ w

        audit = spmd.audit_spmd_callable(f, big, x, name="planted",
                                         compiled=False, publish=False)
        hits = [f_ for f_ in audit.findings
                if f_.rule_id == "replicated-large-param"]
        assert len(hits) == 1
        assert "1024" in hits[0].message

    def test_sharded_param_not_flagged(self):
        mesh = _mesh(8)
        big = jax.device_put(jnp.zeros((1024, 1024), jnp.float32),
                             NamedSharding(mesh, P("dp", None)))

        def f(w):
            return w * 2.0

        audit = spmd.audit_spmd_callable(f, big, name="sharded",
                                         compiled=False, publish=False)
        assert [f_ for f_ in audit.findings
                if f_.rule_id == "replicated-large-param"] == []

    def test_meshless_program_exempt(self):
        # no mesh, no hazard: single-device replication is just memory
        audit = spmd.audit_spmd_callable(
            lambda w: w * 2.0, jnp.zeros((1024, 1024), jnp.float32),
            name="meshless", compiled=False, publish=False)
        assert audit.findings == []

    def test_implicit_reshard_planted(self):
        mesh = _mesh(8)
        x = jax.device_put(jnp.zeros((64, 64), jnp.float32),
                           NamedSharding(mesh, P("dp", None)))
        dst = NamedSharding(mesh, P(None, "dp"))

        def f(xx):
            return jax.lax.with_sharding_constraint(xx, dst) * 2.0

        audit = spmd.audit_spmd_callable(f, x, name="reshard",
                                         compiled=False, publish=False)
        hits = [f_ for f_ in audit.findings
                if f_.rule_id == "implicit-reshard"]
        assert len(hits) == 1

    def test_implicit_reshard_inside_scan_body(self):
        # regression (review finding): the fused run_steps body lives
        # entirely inside the K-step scan eqn — the rule must follow
        # shardings through the call boundary
        mesh = _mesh(8)
        x = jax.device_put(jnp.zeros((64, 64), jnp.float32),
                           NamedSharding(mesh, P("dp", None)))
        dst = NamedSharding(mesh, P(None, "dp"))

        def f(xx, steps):
            def body(c, _):
                return jax.lax.with_sharding_constraint(c, dst) * 2.0, ()
            out, _ = jax.lax.scan(body, xx, None, length=3)
            return out

        audit = spmd.audit_spmd_callable(f, x, 3, static_argnums=(1,),
                                         name="scan_reshard",
                                         compiled=False, publish=False)
        assert [f_.rule_id for f_ in audit.findings
                if f_.rule_id == "implicit-reshard"] \
            == ["implicit-reshard"]

    def test_matching_constraint_not_flagged(self):
        mesh = _mesh(8)
        x = jax.device_put(jnp.zeros((64, 64), jnp.float32),
                           NamedSharding(mesh, P("dp", None)))
        same = NamedSharding(mesh, P("dp"))   # trailing None normalized

        def f(xx):
            return jax.lax.with_sharding_constraint(xx, same) * 2.0

        audit = spmd.audit_spmd_callable(f, x, name="samespec",
                                         compiled=False, publish=False)
        assert [f_ for f_ in audit.findings
                if f_.rule_id == "implicit-reshard"] == []

    def test_unsharded_kv_pool_planted(self):
        # a meshed serving-shaped program whose page pool rides
        # replicated: capacity capped at one chip's HBM
        mesh = _mesh(8, "tensor")
        pool = jax.device_put(
            jnp.zeros((256, 16, 8, 32), jnp.float32),   # 4 MiB pool
            NamedSharding(mesh, P()))
        q = jax.device_put(jnp.zeros((4, 8, 32), jnp.float32),
                           NamedSharding(mesh, P()))

        def f(pool, q):
            return jnp.einsum("bhd,pshd->bps", q, pool)

        closed = jax.make_jaxpr(f)(pool, q)
        audit = spmd.audit_spmd_jaxpr(
            closed, name="kv", example_args=(pool, q),
            kv_pool_leaves=(pool,), publish=False)
        assert [f_.rule_id for f_ in audit.findings
                if f_.rule_id == "unsharded-kv-pool"] \
            == ["unsharded-kv-pool"]


class TestEngineAndGauges:
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
        eng = ContinuousBatchingEngine(LlamaForCausalLM(cfg),
                                       total_pages=32, page_size=8,
                                       max_batch=4)
        yield eng
        eng.stop()

    def test_engine_audit_and_gauges(self, engine):
        audit = spmd.audit_spmd_engine(engine, compiled=False)
        assert audit.peak_hbm_bytes > 0
        # meshless CPU engine: zero ICI is the CORRECT price
        assert audit.collective_bytes_total == 0.0
        snap = monitor.snapshot()
        for series in ("program_peak_hbm_bytes",
                       "collective_bytes_total", "ici_time_seconds"):
            assert series in snap, f"{series} gauge missing"
            labels = {s["labels"].get("program")
                      for s in snap[series]["series"]}
            assert audit.name in labels

    def test_publish_engine_cost_carries_spmd_group(self, engine):
        from paddle_tpu.analysis.cost import publish_engine_cost
        out = publish_engine_cost(engine)
        assert out["spmd"]["peak_hbm_bytes"] > 0
        assert out["spmd"]["collective_bytes_total"] == 0.0
        assert "comm_compute_ratio" in out["spmd"]

    def test_estimate_traces_without_compiling(self, engine):
        monitor.install_compile_hooks()
        before = monitor.snapshot()
        spmd.audit_spmd_engine(engine, compiled=False, publish=False)
        after = monitor.snapshot()

        def compiles(s):
            m = s.get("jit_compile_seconds")
            return m["series"][0]["count"] if m and m["series"] else 0
        assert compiles(after) == compiles(before)


class TestTensorParallelAudit:
    """ISSUE 20: the auditor prices the TP engine's programs — every
    collective NAMED with non-zero bytes on the ('tensor',) axis, the
    per-chip peak-HBM walk sees the pool shards (global ÷ tp), and the
    int8 quantized collectives quote >=3x fewer bytes than f32."""

    def _tiny(self, seed=0):
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        paddle.seed(seed)
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128)
        return LlamaForCausalLM(cfg)

    def _engine(self, **kw):
        from paddle_tpu.inference.continuous import \
            ContinuousBatchingEngine
        return ContinuousBatchingEngine(self._tiny(), total_pages=32,
                                        page_size=8, max_batch=4, **kw)

    @pytest.fixture(scope="class")
    def audits(self):
        """One pass over (tp=1, tp=2, tp=2+int8) engines: the fixtures
        every lock below reads."""
        engines = {"base": self._engine(),
                   "tp": self._engine(tp=2),
                   "quant": self._engine(tp=2, tp_quant_collectives=True)}
        out = {}
        try:
            for name, eng in engines.items():
                out[name] = {
                    mode: spmd.audit_spmd_engine(eng, mode=mode,
                                                 compiled=False,
                                                 publish=False)
                    for mode in ("decode", "ragged")}
                out[name]["kv_pool_bytes"] = eng.cache.kv_pool_bytes
                out[name]["engine"] = eng
            yield out
        finally:
            for eng in engines.values():
                eng.stop()

    def test_every_collective_named_and_priced(self, audits):
        # 2 layers x (o_proj + down_proj) row-parallel closes = 4
        # psums, nothing unattributed, all on the tensor axis, all f32
        for mode in ("decode", "ragged"):
            audit = audits["tp"][mode]
            colls = [c for c in audit.collectives if c.source == "jaxpr"]
            assert len(colls) == 4, [str(c) for c in audit.collectives]
            for c in colls:
                assert c.kind == "all_reduce"
                assert tuple(c.axes) == ("tensor",)
                assert c.ici_bytes > 0
                assert c.dtype == "float32"
            assert audit.collective_bytes_total > 0

    def test_meshless_engine_prices_zero(self, audits):
        for mode in ("decode", "ragged"):
            assert audits["base"][mode].collective_bytes_total == 0.0

    def test_per_chip_peak_sees_pool_shards(self, audits):
        # the tp=2 walk prices each pool leaf at its SHARD bytes, so
        # peak drops by at least half the global pool footprint
        pool = audits["tp"]["kv_pool_bytes"]
        base = audits["base"]["decode"].peak_hbm_bytes
        shard = audits["tp"]["decode"].peak_hbm_bytes
        assert audits["tp"]["engine"].cache.kv_pool_bytes_per_chip * 2 \
            == pool
        assert shard <= base - 0.5 * pool, (base, shard, pool)

    def test_int8_collectives_at_least_3x_fewer_bytes(self, audits):
        audit = audits["quant"]["decode"]
        total = audit.collective_bytes_total
        equiv = audit.collective_bytes_f32_equiv
        assert total > 0
        assert equiv / total >= 3.0, (equiv, total)
        # the quantized step moves STRICTLY fewer bytes than the f32
        # psum step it replaces would
        assert total < audits["tp"]["decode"].collective_bytes_total
        # and the report quotes the ratio for the operator
        assert "fewer bytes" in audit.report()

    def test_sharded_kv_pool_is_quiet(self):
        # the hazard rule must NOT fire on a pool committed the way
        # PagedKVCache(mesh=...) commits it: sharded on the kv-head
        # axis (>=1 MiB so the planted pool clears _LARGE_PARAM_BYTES)
        mesh = _mesh(8, "tensor")
        pool = jax.device_put(
            jnp.zeros((8, 256, 16, 32), jnp.float32),   # 4 MiB pool
            NamedSharding(mesh, P("tensor")))
        q = jax.device_put(jnp.zeros((4, 8, 32), jnp.float32),
                           NamedSharding(mesh, P()))

        def f(pool, q):
            return jnp.einsum("bhd,hpsd->bps", q, pool)

        closed = jax.make_jaxpr(f)(pool, q)
        audit = spmd.audit_spmd_jaxpr(
            closed, name="kv_sharded", example_args=(pool, q),
            kv_pool_leaves=(pool,), publish=False)
        assert [f_ for f_ in audit.findings
                if f_.rule_id == "unsharded-kv-pool"] == []

    def test_replicated_pool_hint_names_the_fix(self):
        mesh = _mesh(8, "tensor")
        pool = jax.device_put(jnp.zeros((256, 16, 8, 32), jnp.float32),
                              NamedSharding(mesh, P()))

        def f(pool):
            return pool.sum()

        closed = jax.make_jaxpr(f)(pool)
        audit = spmd.audit_spmd_jaxpr(
            closed, name="kv_repl", example_args=(pool,),
            kv_pool_leaves=(pool,), publish=False)
        hits = [f_ for f_ in audit.findings
                if f_.rule_id == "unsharded-kv-pool"]
        assert len(hits) == 1
        assert "PagedKVCache(mesh=...)" in hits[0].hint

    def test_audit_engine_autoruns_spmd_on_tp_engine(self, audits):
        from paddle_tpu.analysis import program_audit
        audit = program_audit.audit_engine(audits["tp"]["engine"],
                                           mode="decode", publish=False)
        assert audit.spmd is not None
        assert len([c for c in audit.spmd.collectives
                    if c.source == "jaxpr"]) == 4
        assert audit.spmd.collective_bytes_total > 0
