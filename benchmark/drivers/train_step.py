"""Driver ``train_step``: one ``jit.TrainStep`` (AdamW, bfloat16
parameters, float32 masters and moments, the unfused cross-entropy over
float32 logits — the definition ``bench.build_llama_train_step(cfg,
bf16=True, use_fused=False)`` has) fed packed pre-training rows.

Set-up builds ONE step object, drives it through its first
``warm_steps`` steps with the window's own call and feed (each row
differs), reads what the check needs from the optimizer's state (the
first gradient's per-leaf norms from ``moment1`` after step one, the
parameters' per-leaf change from the float32 masters after the last),
and hands the same object to the window.  The window dispatches one
step per call, puts the next batch on the device while the step runs,
and fetches the loss of every ``fetch_every``-th step ``fetch_lag``
steps after dispatching it (a training loop that logs its loss does not
drain the device for it).  It closes on the fetch that passes
``--seconds`` and then on the loss of the last step dispatched, so every
step counted has finished and the window's length is measured, not
assumed.
After it the program's state is freed, the device's peak is read, and
the plain reference follows the same first steps in float32.
"""
from __future__ import annotations

import time

import numpy as np

from . import common
from .common import plain, say


def _leaf_norms(arrays, scale=1.0):
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) * scale for x in xs])
    return [float(v) for v in fn(list(arrays))]


def _change_norms(seed, names, masters):
    """||master - seed's value|| per leaf, the seed's value re-made
    inside the program that subtracts it."""
    import jax
    import jax.numpy as jnp
    import weights as W
    key = W.root_key(seed)
    shapes = tuple(tuple(m.shape) for m in masters)

    def fn(ms, key):
        return [jnp.sqrt(jnp.sum(jnp.square(
            m - W.leaf_values(key, n, s, jnp.float32))))
            for m, n, s in zip(ms, names, shapes)]

    return [float(v) for v in jax.jit(fn)(list(masters), key)]


def optimizer_state(step, opt, names, slot):
    """The optimizer's arrays of one slot, in parameter order, through
    the program's public calls (``TrainStep.sync`` hands the state back
    to the optimizer; ``state_dict`` names it)."""
    step.sync()
    sd = opt.state_dict()
    params = list(step.model.parameters())
    keys = [(p.name or f"param_{i}") for i, p in enumerate(params)]
    return [sd[f"{k}.{slot}"]._data for k in keys]


def build_step(model, model_cfg, hyper):
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep

    opt = optim.AdamW(learning_rate=hyper["lr"], beta1=hyper["beta1"],
                      beta2=hyper["beta2"], epsilon=hyper["eps"],
                      weight_decay=hyper["weight_decay"],
                      parameters=model.parameters(), multi_precision=True)
    vocab = model_cfg["vocab_size"]

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, vocab]).astype("float32"),
            labels.reshape([-1]))

    return TrainStep(model, loss_fn, opt), opt


def run(ctx):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle

    cfg, model_cfg = ctx.config, common.model_cfg(ctx.config)
    hyper = cfg["driver_options"]["optimizer"]
    warm = int(cfg["driver_options"]["warm_steps"])
    gen = ctx.generator(model_cfg["vocab_size"])
    tokens_per_step = gen.batch * gen.seq
    t = time.perf_counter()
    model = common.build_model(model_cfg, ctx.seed)
    names = [n for n, _ in model.named_parameters()]
    step, opt = build_step(model, model_cfg, hyper)
    say(f"[train] model: {model_cfg['num_hidden_layers']} layers, "
        f"{sum(int(np.prod(p.shape)) for p in model.parameters())} parameters"
        f", weights from seed {ctx.seed} in {time.perf_counter() - t:.1f}s; "
        f"in use {common.memory_now()['bytes_in_use']}")

    def feed():
        x, y = gen.next_batch()
        return (x, y), (paddle.to_tensor(jax.device_put(x)),
                        paddle.to_tensor(jax.device_put(y)))

    def call(dev):
        return step(dev[0], dev[1])

    # ---- the first steps: the window's own object, call and feed
    first_batches, losses = [], []
    host, dev = feed()
    grad_norm = None
    for i in range(warm):
        t = time.perf_counter()
        loss = call(dev)
        first_batches.append(host)
        host, dev = feed()
        losses.append(float(np.asarray(loss._data)))
        say(f"[train] step {i + 1}: loss {losses[-1]:.6f} "
            f"({time.perf_counter() - t:.1f}s)")
        if i == 0:
            m1 = optimizer_state(step, opt, names, "moment1")
            scale = 1.0 / (1.0 - hyper["beta1"])
            grad_norm = dict(zip(names, _leaf_norms(m1, scale)))
            grad_gains = {n: np.asarray(a, np.float32) * scale
                          for n, a in zip(names, m1) if a.ndim == 1}
            del m1
    masters = optimizer_state(step, opt, names, "master_weight")
    delta_norm = dict(zip(names, _change_norms(ctx.seed, names, masters)))
    del masters
    say(f"[train] autotune decisions: {common.decisions_summary()}; "
        f"in use {common.memory_now()['bytes_in_use']}")

    # ------------------------------------------------------ the window
    # the loss of every ``fetch_every``-th step is fetched once
    # ``fetch_lag`` further steps are dispatched, so the device has work
    # queued while the host waits for the number and dispatches again
    fetched, group_ms, steps, due = [], [], 0, []
    t0 = ctx.window_opens()
    t_group = t0
    while True:
        loss = call(dev)
        host, dev = feed()
        steps += 1
        if steps % gen.fetch_every == 0:
            due.append((steps, loss))
        if not due or steps - due[0][0] < gen.fetch_lag:
            continue
        fetched.append(float(np.asarray(due.pop(0)[1]._data)))
        now = time.perf_counter()
        group_ms.append((now - t_group) * 1e3 / gen.fetch_every)
        t_group = now
        if now - t0 >= ctx.seconds:
            break
        ctx.profile_tick()
    fetched.append(float(np.asarray(loss._data)))   # the last step dispatched
    now = time.perf_counter()
    ctx.profile_close()
    window_s = now - t0
    say(f"[train] window {window_s:.3f}s: {steps} steps, last fetched loss "
        f"{fetched[-1]:.4f}, first {fetched[0]:.4f}")
    mem = common.memory_now()

    # ------------------------- free the program, then the reference
    del step, opt, model, dev, loss, due
    common.free_device_memory()
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = plain.train_reference(model_cfg, ctx.seed, first_batches, hyper)
    say(f"[train] reference followed {warm} steps in "
        f"{time.perf_counter() - t:.1f}s: losses {ref['losses']}")
    lim = cfg["check"]["limits"]
    checks = []
    for i, (a, b) in enumerate(zip(losses, ref["losses"]), start=1):
        checks.append((f"loss_gap_step{i}", abs(a - b) / abs(b),
                       lim[f"loss_gap_step{i}"]))
    g, where = plain.worst_leaf_gap(grad_norm, ref["grad_norm"])
    checks.append((f"first_grad_norm_gap[{where}]", g,
                   lim["first_grad_norm_gap"]))
    g, where = plain.worst_gain_diff(grad_gains, ref["grad_gains"])
    checks.append((f"first_grad_gains_diff[{where}]", g,
                   lim["first_grad_gains_diff"]))
    d, where = plain.worst_leaf_gap(delta_norm, ref["delta_norm"])
    checks.append((f"param_change_norm_gap[{where}]", d,
                   lim["param_change_norm_gap"]))
    finite = all(np.isfinite(v) for v in losses + fetched)
    checks.append(("losses_not_finite", 0.0 if finite else 1.0, 0.0))
    checks.append(("last_fetched_loss_over_first",
                   fetched[-1] / losses[0], 1.0))
    return {
        "end_to_end": {"train.tokens_per_s": steps * tokens_per_step / window_s},
        "attempted": steps, "failed": 0 if finite else steps,
        "checks": checks, "memory": mem, "window_s": window_s,
        "sources": {"group_step_ms": group_ms, "window": (t0, now)},
    }
