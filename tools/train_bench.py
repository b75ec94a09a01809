"""Training hot-path benchmark (ISSUE 5 CI satellite).

Measures the SAME tiny LLaMA pretrain computation through both training
paths and prints ONE JSON line, every number from ``monitor.snapshot()``
deltas (the serve_bench contract, applied to training):

  * BEFORE — the seed-style loop: one ``jit.TrainStep`` dispatch per
    step with a forced ``float(loss)`` host sync per batch (what the
    fit loop used to do);
  * AFTER — the fused path: ``TrainStep.run_steps`` compiles a
    ``lax.scan`` over K micro-steps (one dispatch per K steps, lr and
    stepno computed in-program from the traced schedule), fed by the
    DataLoader's device-prefetch stage, losses left device-resident
    until the window closes.

The window gates the full ISSUE 5 acceptance workflow: the fused
program is certified by ``analysis.audit_callable`` (no host callbacks,
donation intact), ``jit_recompiles == 0`` inside both measured windows,
the fused loss trajectory is bit-comparable (fp tolerance) to k
single-step calls, and ``paddle_tpu/hapi`` is TPL005-clean (zero
per-step host syncs in the fit loop).  tests/test_tools.py runs
``main()`` as a tier-1 gate; ``python tools/train_bench.py`` is the
standalone lane.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_serve_bench():
    """ONE definition of the monitor-snapshot math (histogram deltas,
    counter deltas, histogram_quantile) lives in serve_bench; this lane
    loads it instead of forking a second copy whose semantics could
    silently drift."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_tb_serve_bench", os.path.join(REPO, "tools", "serve_bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_sb = _load_serve_bench()
_hist_delta = _sb._hist_delta
_counter_delta = _sb._counter_delta
hist_quantile = _sb.hist_quantile


def _build(vocab, hidden, layers, seed=0, lr=1e-3):
    """One tiny LLaMA pretrain TrainStep with a TRACED cosine schedule —
    the shape whose lr/stepno reads run_steps moves into the program."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle.seed(seed)
    cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=2 * hidden,
                      num_hidden_layers=layers, num_attention_heads=4,
                      max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    sched = optim.lr.CosineAnnealingDecay(learning_rate=lr, T_max=1000)
    opt = optim.AdamW(learning_rate=sched, parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, vocab]).astype("float32"),
            labels.reshape([-1]))

    return TrainStep(model, loss_fn, opt), sched


def _make_loader(vocab, seq, batch, n_samples, device_prefetch=True):
    import numpy as np
    from paddle_tpu.io import DataLoader, Dataset

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (n_samples, seq + 1)).astype("int32")

    class _Lm(Dataset):
        def __len__(self):
            return n_samples

        def __getitem__(self, i):
            return ids[i, :-1], ids[i, 1:]

    return DataLoader(_Lm(), batch_size=batch, shuffle=False,
                      drop_last=True, device_prefetch=device_prefetch)


def _tpl005_hapi_findings() -> int:
    """TPL005 count over paddle_tpu/hapi — the fit loop's zero-per-step-
    host-sync acceptance bar, loaded standalone (no package import)."""
    import importlib.util
    path = os.path.join(REPO, "paddle_tpu", "analysis", "lint.py")
    spec = importlib.util.spec_from_file_location("_tb_lint", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    findings = mod.lint_paths(os.path.join(REPO, "paddle_tpu", "hapi"),
                              rel_to=REPO)
    return sum(1 for f in findings if f.rule_id == "TPL005")


def run_bench(k: int = 4, dispatches: int = 4, single_steps: int = 8,
              batch: int = 4, seq: int = 32, vocab: int = 128,
              hidden: int = 64, layers: int = 2) -> dict:
    import jax
    import numpy as np
    from paddle_tpu import monitor

    monitor.install_compile_hooks()
    step_hist = monitor.histogram("train_step_seconds",
                                  "one train_batch wall time")

    # ---- loss parity: run_steps(k) vs k single-step calls, same init
    par_batches = [b for b in _make_loader(vocab, seq, batch, batch * k,
                                           device_prefetch=False)]
    s_single, sched_single = _build(vocab, hidden, layers)
    singles = []
    for x, y in par_batches:
        singles.append(float(np.asarray(s_single(x, y)._data)))
        sched_single.step()          # the documented run_steps cadence
    s_fused, _ = _build(vocab, hidden, layers)
    assert s_fused.fused_supported, "cosine schedule must trace"
    fused = np.asarray(s_fused.run_steps(par_batches)._data)
    parity_diff = float(np.max(np.abs(fused - np.asarray(singles))))
    parity_ok = bool(np.allclose(fused, singles, rtol=2e-3, atol=5e-4))

    # ---- audit: certify the fused program (donation, callbacks, dtypes)
    audit = s_fused.audit_fused(par_batches)
    audit_errors = [f for f in audit.findings if f.severity == "error"]

    # ---- cost/MFU accounting (ISSUE 10): price the SAME fused program
    # the audit certified (fused_program_spec is the shared trace spec)
    # — FLOPs per K-step dispatch feeds the train-lane MFU below
    from paddle_tpu.analysis import cost as _cost
    fn, cargs, _donate, cstatic = s_fused.fused_program_spec(par_batches)
    cost_est = _cost.estimate_callable(fn, *cargs, static_argnums=cstatic,
                                       name="TrainStep.run_steps",
                                       publish=True)

    # ---- SPMD/memory audit (ISSUE 11): the tier-3 distributed audit
    # of the SAME fused program (collectives priced — zero on the
    # single-device CI lane, which is the correct verdict — plus the
    # static peak-HBM estimate), and the predicted-vs-measured HBM
    # check on the single-step program: the static estimate must bound
    # XLA's own compiled memory analysis from above (fusion-blind
    # upper bound), or the memory-gate pre-verdict would under-plan
    from paddle_tpu.analysis import spmd as _spmd
    spmd_audit = _spmd.audit_spmd_fused(s_fused, par_batches,
                                        compiled=False, publish=True)
    x0, y0 = par_batches[0]
    predicted_peak = s_fused.static_peak_hbm(x0, y0)
    mem = s_fused.memory_analysis(x0, y0)
    import bench as _bench
    measured_peak = _bench.planned_peak_bytes(mem)

    # ---- BEFORE: single-step dispatch + per-step forced host sync
    bench_step, _ = _build(vocab, hidden, layers, seed=1)
    warm = par_batches[0]
    for _ in range(2):
        jax.block_until_ready(bench_step(warm[0], warm[1])._data)
    before0 = monitor.snapshot()
    t0 = time.perf_counter()
    for x, y in _make_loader(vocab, seq, batch, batch * single_steps,
                             device_prefetch=False):
        t1 = time.perf_counter()
        loss = bench_step(x, y)
        float(np.asarray(loss._data))          # the seed's per-step sync
        step_hist.observe(time.perf_counter() - t1)
    single_wall = time.perf_counter() - t0
    before1 = monitor.snapshot()

    # ---- AFTER: K-step fused dispatch, device-prefetched input, no
    # per-step sync (one block at the window boundary)
    fused_step, _ = _build(vocab, hidden, layers, seed=1)
    fused_step.run_steps(par_batches[:k])      # warm-up: compiles the scan
    after0 = monitor.snapshot()
    t0 = time.perf_counter()
    group, losses = [], None
    n_fused_steps = 0
    for x, y in _make_loader(vocab, seq, batch, batch * k * dispatches,
                             device_prefetch=True):
        group.append((x, y))
        if len(group) == k:
            t1 = time.perf_counter()
            losses = fused_step.run_steps(group)
            dt = time.perf_counter() - t1
            step_hist.observe(dt / k)          # per-micro-step, amortized
            n_fused_steps += k
            group = []
    jax.block_until_ready(losses._data)        # window boundary sync
    fused_wall = time.perf_counter() - t0
    after1 = monitor.snapshot()

    sb, ss, sc = _hist_delta(before0, before1, "train_step_seconds")
    fb, fs, fc = _hist_delta(after0, after1, "train_step_seconds")
    _, _, rec_single = _hist_delta(before0, before1, "jit_compile_seconds")
    _, _, rec_fused = _hist_delta(after0, after1, "jit_compile_seconds")
    iw_b, iw_sum, iw_n = _hist_delta(after0, after1, "input_wait_seconds")
    tokens = _counter_delta(after0, after1, "train_tokens_total")

    single_sps = single_steps / single_wall
    fused_sps = n_fused_steps / fused_wall
    # MFU over the fused measured window: analytical FLOPs actually
    # dispatched (per-K-step program cost x dispatches) over peak x wall
    # — the automated MFU ladder source (ISSUE 10; the ROADMAP's
    # "report the MFU ladder every round" instruction)
    dispatches_run = n_fused_steps // k if k else 0
    peak = _cost.peak_flops()
    mfu = _cost.record_mfu(cost_est.flops * dispatches_run, fused_wall,
                           peak=peak)
    return {
        "k": k,
        "batch": batch,
        "seq": seq,
        "device_prefetch": True,
        # BEFORE (single dispatch + sync per step)
        "single_steps": sc,
        "single_step_p50_s": hist_quantile(sb, 0.50),
        "single_step_mean_s": (ss / sc) if sc else None,
        "single_steps_per_sec": single_sps,
        # AFTER (run_steps fused)
        "fused_steps": n_fused_steps,
        "fused_step_p50_s": hist_quantile(fb, 0.50),
        "fused_step_mean_s": (fs / fc) if fc else None,
        "fused_steps_per_sec": fused_sps,
        "fused_tokens_per_sec": tokens / fused_wall if fused_wall else 0.0,
        "speedup": fused_sps / single_sps if single_sps else 0.0,
        # the ISSUE 5 monitor series, quoted from the fused window
        "train_tokens": int(tokens),
        "input_wait_p50_s": hist_quantile(iw_b, 0.50),
        "input_wait_sum_s": iw_sum,
        "input_waits": iw_n,
        # cost/MFU accounting (ISSUE 10)
        "program_flops": cost_est.flops,
        "program_hbm_bytes": cost_est.hbm_bytes,
        "peak_flops": peak,
        "peak_source": _cost.peak_source(),
        "mfu": mfu,
        # SPMD/memory audit (ISSUE 11): static HBM verdict (fused
        # program) + predicted-vs-measured on the single-step program
        "spmd": {
            "peak_hbm_bytes": spmd_audit.peak_hbm_bytes,
            "collective_bytes_total": spmd_audit.collective_bytes_total,
            "ici_time_seconds": spmd_audit.ici_time_seconds,
            "comm_compute_ratio": spmd_audit.comm_compute_ratio,
            "mesh_axes": spmd_audit.mesh_axes,
            "collectives": len(spmd_audit.collectives),
            "findings": len(spmd_audit.findings),
        },
        "static_peak_hbm_bytes": predicted_peak,
        "measured_peak_hbm_bytes": measured_peak,
        "peak_hbm_ratio": (predicted_peak / measured_peak
                           if measured_peak else None),
        # acceptance gates
        "parity_max_abs_diff": parity_diff,
        "parity_ok": parity_ok,
        "audit_error_findings": len(audit_errors),
        "audit_errors": [str(f) for f in audit_errors],
        "jit_recompiles": int(rec_single + rec_fused),
        "tpl005_hapi_findings": _tpl005_hapi_findings(),
    }


def _int_arg(argv, name, default):
    return next((int(a.split("=", 1)[1]) for a in argv
                 if a.startswith(f"--{name}=")), default)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    from paddle_tpu.framework.compile_cache import configure_compile_cache
    configure_compile_cache()
    out = run_bench(k=_int_arg(argv, "k", 4),
                    dispatches=_int_arg(argv, "dispatches", 4),
                    single_steps=_int_arg(argv, "single-steps", 8),
                    batch=_int_arg(argv, "batch", 4),
                    seq=_int_arg(argv, "seq", 32),
                    vocab=_int_arg(argv, "vocab", 128),
                    hidden=_int_arg(argv, "hidden", 64))
    print(json.dumps(out, sort_keys=True))
    if not out["parity_ok"]:
        print(f"FAIL: fused loss trajectory diverged from single-step "
              f"(max abs diff {out['parity_max_abs_diff']:.2e})",
              file=sys.stderr)
        return 1
    if out["audit_error_findings"]:
        print(f"FAIL: the fused program audit found errors: "
              f"{out['audit_errors']}", file=sys.stderr)
        return 1
    if out["jit_recompiles"] != 0:
        print(f"FAIL: {out['jit_recompiles']} compile(s) inside the "
              "measured windows; warm-up missed a shape", file=sys.stderr)
        return 1
    if out["tpl005_hapi_findings"]:
        print("FAIL: per-step host syncs crept back into the fit loop "
              "(TPL005 on paddle_tpu/hapi)", file=sys.stderr)
        return 1
    if out["fused_steps_per_sec"] <= 0 or out["train_tokens"] <= 0:
        print("FAIL: fused window measured nothing", file=sys.stderr)
        return 1
    if out["program_flops"] <= 0 or out["mfu"] is None:
        # ISSUE 10 acceptance: the train lane carries the MFU ladder
        print("FAIL: cost analyzer produced no program FLOPs / MFU",
              file=sys.stderr)
        return 1
    if out["spmd"]["peak_hbm_bytes"] <= 0 \
            or out["static_peak_hbm_bytes"] <= 0:
        print("FAIL: spmd auditor produced no peak-HBM estimate",
              file=sys.stderr)
        return 1
    if out["measured_peak_hbm_bytes"] > 0 \
            and out["static_peak_hbm_bytes"] < \
            out["measured_peak_hbm_bytes"]:
        # ISSUE 11 acceptance: the static estimate is the memory
        # gate's pessimistic planner — it must bound XLA's compiled
        # memory analysis from above on every rung that runs
        print(f"FAIL: static peak-HBM "
              f"{out['static_peak_hbm_bytes']:.0f} B under-plans the "
              f"measured {out['measured_peak_hbm_bytes']:.0f} B",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
