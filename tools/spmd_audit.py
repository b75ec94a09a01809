"""SPMD-auditor CLI (ISSUE 11 CI satellite).

One command over `paddle_tpu.analysis.spmd`, three lanes, each
printing JSON:

  * default (demo) — a self-contained pair of distributed programs on
    whatever mesh the host offers (a CPU mesh of 1 works: collectives
    price to zero ICI, which is the correct verdict, and the whole
    bandwidth-table path still executes):

      - `dp_allreduce`: a shard_map gradient-sync psum — the data-
        parallel shape;
      - `tp_matmul`: a row-parallel matmul whose partial products psum
        on the 'tensor' axis — the TP-fleet shape the ROADMAP gates on.

    The lane asserts hand-countable invariants (payload bytes at dtype
    width, ring multipliers, mesh-size monotonicity) and exits 1 on
    any mismatch — the tests/test_tools.py gate (< 10 s, no TPU).

  * --train — the fused K-step `TrainStep.run_steps` program of a tiny
    dp-wrapped MLP: at dp>1 the compiled-HLO tier names the
    GSPMD-inserted gradient-sync all-reduces with priced bytes.

  * --engine — a tiny serving engine's decode program through
    `audit_spmd_engine` (jaxpr tier + peak-HBM + pool rules).

`--report` prints the human-readable report instead of JSON;
`PADDLE_TPU_ICI_BYTES_PER_S` overrides the link-bandwidth table.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _demo_mesh(axis: str, want: int = 8):
    import numpy as np
    import jax
    from jax.sharding import Mesh
    n = min(want, jax.device_count())
    return Mesh(np.array(jax.devices()[:n]).reshape(n), (axis,)), n


def run_demo() -> dict:
    """The pricing-table demo lane: hand-checkable shard_map programs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.framework.jax_compat import shard_map
    from paddle_tpu.analysis import spmd

    out = {"device_count": jax.device_count(),
           "link_bandwidth": spmd.link_bandwidth()}

    # dp gradient sync: psum a (1024, 64) f32 "gradient" over 'dp'
    mesh, n = _demo_mesh("dp")
    rows = 8 * n   # divisible by any mesh size

    def grad_sync(g):
        return jax.lax.psum(g, "dp")

    sm = shard_map(grad_sync, mesh=mesh, in_specs=P("dp"), out_specs=P())
    audit = spmd.audit_spmd_callable(
        sm, jnp.zeros((rows, 64), jnp.float32), name="demo.dp_allreduce",
        compiled=False)
    out["dp_allreduce"] = audit.to_dict()
    c = audit.collectives[0]
    shard_bytes = (rows // n) * 64 * 4
    ok = (c.kind == "all_reduce" and c.group_size == n
          and c.payload_bytes == shard_bytes
          and abs(c.ici_bytes - 2 * (n - 1) / n * shard_bytes) < 1e-6)

    # TP row-parallel matmul: x[(B, K/n)] @ w[(K/n, N)] -> psum over
    # 'tensor' of the (B, N) partials
    mesh_tp, ntp = _demo_mesh("tensor")
    B, K, N = 16, 32 * ntp, 64

    def row_parallel(x, w):
        return jax.lax.psum(x @ w, "tensor")

    smtp = shard_map(row_parallel, mesh=mesh_tp,
                     in_specs=(P(None, "tensor"), P("tensor", None)),
                     out_specs=P())
    audit_tp = spmd.audit_spmd_callable(
        smtp, jnp.zeros((B, K), jnp.float32),
        jnp.zeros((K, N), jnp.float32), name="demo.tp_matmul",
        compiled=False)
    out["tp_matmul"] = audit_tp.to_dict()
    ctp = audit_tp.collectives[0]
    ok = ok and (ctp.kind == "all_reduce" and ctp.group_size == ntp
                 and ctp.payload_bytes == B * N * 4
                 and audit_tp.compute_flops >= 2 * B * K * N / ntp)
    out["ok"] = bool(ok)
    return out


def _ensure_virtual_devices(n: int = 8) -> None:
    """Give the --train lane a dp mesh on single-device hosts: pin n
    virtual CPU devices BEFORE the backend initializes (a no-op when a
    real accelerator or the test harness already provisioned devices;
    the knob only affects the host platform)."""
    from paddle_tpu.framework.backend_guard import backend_initialized
    if backend_initialized():
        return
    try:
        from paddle_tpu.framework.jax_compat import pin_cpu_devices
        pin_cpu_devices(n)
    except Exception:   # noqa: BLE001 — fall through to whatever exists
        pass


def run_train() -> dict:
    """dp>1 fused run_steps: name the GSPMD gradient-sync collectives."""
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    import paddle_tpu.distributed as dist
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.analysis import spmd

    paddle.seed(0)
    net = nn.Sequential(nn.Linear(64, 256), nn.ReLU(), nn.Linear(256, 8))
    dp = dist.DataParallel(net)
    opt = optim.SGD(learning_rate=1e-2, parameters=net.parameters())
    step = TrainStep(dp, lambda out, y: F.cross_entropy(out, y), opt)
    rng = np.random.default_rng(0)

    def mk():
        return (paddle.to_tensor(
                    rng.standard_normal((16, 64)).astype("float32")),
                paddle.to_tensor(
                    rng.integers(0, 8, (16,)).astype("int64")))

    audit = spmd.audit_spmd_fused(step, [mk(), mk()])
    out = audit.to_dict()
    grad_sync = [c for c in audit.collectives
                 if c.kind == "all_reduce" and c.ici_bytes > 0]
    out["ok"] = bool(grad_sync)
    return out


def run_engine() -> dict:
    """A tiny engine's decode program through the tier-3 audit."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    from paddle_tpu.analysis import spmd

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                      num_hidden_layers=1, num_attention_heads=2,
                      num_key_value_heads=2, max_position_embeddings=64)
    eng = ContinuousBatchingEngine(LlamaForCausalLM(cfg), total_pages=32,
                                   page_size=8, max_batch=4)
    try:
        audit = spmd.audit_spmd_engine(eng, compiled=False)
        out = audit.to_dict()
        out["ok"] = audit.peak_hbm_bytes > 0
        return out
    finally:
        eng.stop()


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--train" in argv:
        _ensure_virtual_devices()
        lane = "train"
        out = run_train()
    elif "--engine" in argv:
        lane = "engine"
        out = run_engine()
    else:
        lane = "demo"
        out = run_demo()
    if "--report" in argv:
        for key, val in out.items():
            if isinstance(val, dict) and "program" in val:
                print(f"== {val['program']}")
                for c in val.get("collectives", ()):
                    print(f"  {c['kind']} n={c['group_size']} "
                          f"payload={c['payload_bytes']:.3g}B "
                          f"ici={c['ici_bytes']:.3g}B/"
                          f"{c['ici_seconds']:.3g}s")
                print(f"  peak_hbm={val['peak_hbm_bytes']:.3g}B "
                      f"findings={len(val.get('findings', ()))}")
    else:
        print(json.dumps(out, sort_keys=True))
    if not out.get("ok"):
        print(f"FAIL: spmd audit {lane}-lane invariants violated",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
