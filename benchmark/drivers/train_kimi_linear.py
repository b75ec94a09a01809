"""Driver ``train_kimi_linear``: ``KimiLinearForCausalLM`` through ONE
``jit.TrainStep`` (AdamW, bfloat16 parameters with float32 masters and
moments, the decay's leaves float32 throughout, the unfused
cross-entropy over float32 logits), fed packed pre-training rows — the
window, the feed and the result keys of ``drivers/train_step.py``.

What differs from that driver: the model is built from the published
keys of the configuration file (``reference/kimi_linear_plain.model_cfg``
turns the file's share — ``num_experts`` held here of
``routed_experts_published`` — into the program's ``held_experts``);
four kinds of leaf are moved into their published ranges after
``weights.make_all`` (``plain.shape_leaf``, the same function the
reference applies); set-up also counts the routed-expert slots of the
warm batches into the program's ``moe_*`` counters by one jitted routing
pass, and for ``--trace 1`` reads the compiled step's text for a map from
HLO instruction names to ``jax.named_scope`` paths
(``sources["hlo_scopes"]``: a device trace names instructions, not
scopes).  A non-finite loss makes the run not ``correct``; the expert
layer has no path that drops a slot, so there is none to count.
"""
from __future__ import annotations

import re
import time

import numpy as np

from reference import kimi_linear_plain as plain

from . import common
from .common import say
from .train_step import _leaf_norms, build_step

_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """``jit(pure_step)/transpose(jvp(train/model))/kda/...`` ->
    ``train/model/kda/...``, cut to four components: the transforms'
    wrappers are dropped and the path starts at ``train/``; '' for an
    instruction outside the step's three scopes."""
    path = re.sub(r"\w+\(", "", op_name).replace(")", "")
    at = path.find("train/")
    return "/".join(path[at:].split("/")[:4]) if at >= 0 else ""


def hlo_scopes(text: str) -> dict:
    """{HLO instruction name: scope path} of a compiled program's text."""
    out = {}
    for line in text.splitlines():
        m = _OP_NAME.match(line)
        if m and (scope := scope_of(m.group(2))):
            out[m.group(1)] = scope
    return out


def build_model(model_cfg: dict, options: dict, seed: int):
    """A ``KimiLinearForCausalLM`` whose every leaf holds the benchmark's
    value for (seed, leaf name): bfloat16 but for the decay's leaves and
    the selection bias (float32).  As ``common.build_model``: float32
    initial values drawn with the device's generator, cast, replaced in
    place by one donated call; then ``plain.bf16_exact`` (a float32
    leaf is rounded like the others) and ``plain.shape_leaf``."""
    import jax
    import paddle_tpu as paddle
    import weights as W
    from paddle_tpu.models.kimi_linear import (KimiLinearConfig,
                                               KimiLinearForCausalLM)

    cfg = KimiLinearConfig.from_dict(dict(
        model_cfg, recompute_mixers=bool(options.get("recompute_mixers"))))
    t0 = time.perf_counter()
    impl = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "rbg")
    paddle.seed(seed)
    try:
        model = KimiLinearForCausalLM(cfg)
    finally:
        jax.config.update("jax_default_prng_impl", impl)
        paddle.seed(seed)
    named = list(model.named_parameters())
    specs = plain.param_specs(model_cfg)
    frozen = plain.buffer_specs(model_cfg)
    got = [(n, tuple(p.shape)) for n, p in named if p.trainable]
    fixed = sorted((n, tuple(p.shape)) for n, p in named if not p.trainable)
    if got != [(n, tuple(s)) for n, s in specs] \
            or fixed != sorted((n, tuple(s)) for n, s in frozen):
        raise RuntimeError(
            "the program's parameters are not the reference's: "
            f"{[g for g in got if g not in specs][:3]} vs "
            f"{[s for s in specs if s not in got][:3]}; not trained: "
            f"{fixed[:2]} vs {frozen[:2]}")
    for n, p in named:
        p._data = p._data.astype(plain.leaf_dtype(n))
    jax.block_until_ready([p._data for _, p in named])
    t1 = time.perf_counter()
    new = W.make_all(seed, [n for n, _ in named], [p._data for _, p in named])
    shape = jax.jit(lambda xs: [
        plain.shape_leaf(n, plain.bf16_exact(x).astype(x.dtype))
        for (n, _), x in zip(named, xs)], donate_argnums=0)
    for (_, p), a in zip(named, shape(new)):
        p.set_value(a)
    jax.block_until_ready([p._data for _, p in named])
    say(f"[build] the program's float32 initialisers and the cast took "
        f"{t1 - t0:.1f}s, the benchmark's weights "
        f"{time.perf_counter() - t1:.1f}s")
    return model


def optimizer_state(step, opt, slot):
    """The optimizer's arrays of one slot, of the trained parameters in
    their order; a float32 leaf has no master, its parameter is the
    master."""
    import jax.numpy as jnp
    step.sync()
    sd = opt.state_dict()
    out = []
    for i, p in enumerate(step.model.parameters()):
        if not p.trainable:
            continue
        own_master = slot == "master_weight" and p._data.dtype == jnp.float32
        out.append(p._data if own_master
                   else sd[f"{p.name or f'param_{i}'}.{slot}"]._data)
    return out


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
            m.astype(jnp.float32) - plain.shape_leaf(n, plain.bf16_exact(
                W.leaf_values(key, n, s, jnp.float32))))))
            for m, n, s in zip(ms, names, shapes)]

    return [float(v) for v in jax.jit(fn)(list(masters), key)]


def run(ctx):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.models.kimi_linear import record_routing_counts

    monitor.install_compile_hooks()      # set-up by compile phase (setup.*)
    cfg, model_cfg = ctx.config, plain.model_cfg(ctx.config)
    options = cfg["driver_options"]
    hyper, warm = options["optimizer"], int(options["warm_steps"])
    gen = ctx.generator(model_cfg["vocab_size"])
    tokens_per_step = gen.batch * gen.seq
    t = time.perf_counter()
    model = build_model(model_cfg, options, ctx.seed)
    names = [n for n, p in model.named_parameters() if p.trainable]
    step, opt = build_step(model, model_cfg, hyper)
    say(f"[train] model: {model_cfg['num_hidden_layers']} layers, "
        f"{sum(int(np.prod(p.shape)) for p in model.parameters())} parameters"
        f", experts {plain.held(model_cfg)} of {model_cfg['num_experts']}, "
        f"weights from seed {ctx.seed} in {time.perf_counter() - t:.1f}s; "
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
    for i in range(warm):
        t = time.perf_counter()
        loss = call(dev)
        first_batches.append(host)
        host, dev = feed()
        losses.append(float(np.asarray(loss._data)))
        say(f"[train] step {i + 1}: loss {losses[-1]:.6f} "
            f"({time.perf_counter() - t:.1f}s)")
        if i == 0:
            m1 = optimizer_state(step, opt, "moment1")
            scale = 1.0 / (1.0 - hyper["beta1"])
            grad_norm = dict(zip(names, _leaf_norms(m1, scale)))
            grad_gains = {n: np.asarray(a, np.float32) * scale
                          for n, a in zip(names, m1) if a.ndim == 1}
            del m1
    masters = optimizer_state(step, opt, "master_weight")
    delta_norm = dict(zip(names, _change_norms(ctx.seed, names, masters)))
    del masters
    # ---- set-up's two readings of the program: where the slots went,
    # and which scope each compiled instruction came from
    t = time.perf_counter()
    routed = record_routing_counts(model, [x for x, _ in first_batches])
    say(f"[train] routing of the {warm} warm batches "
        f"({time.perf_counter() - t:.1f}s): {routed}")
    t = time.perf_counter()
    scopes = hlo_scopes(step.compiled_text() or "") if ctx.trace else {}
    say(f"[train] compiled step: {len(scopes)} instructions with a scope "
        f"({time.perf_counter() - t:.1f}s); autotune decisions: "
        f"{common.decisions_summary()}; "
        f"in use {common.memory_now()['bytes_in_use']}")

    # ------------------------------------------------------ the window
    # as drivers/train_step.py: the loss of every ``fetch_every``-th step
    # is fetched once ``fetch_lag`` further steps are dispatched
    fetched, group_ms, steps, due = [], [], 0, []
    counters0 = common.counters_now()
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
    say(f"[train] first gradient, worst leaf: norm {grad_norm[where]!r} "
        f"against the reference's {ref['grad_norm'][where]!r}")
    g, where = plain.worst_gain_diff(grad_gains, ref["grad_gains"])
    checks.append((f"first_grad_gains_diff[{where}]", g,
                   lim["first_grad_gains_diff"]))
    d, where = plain.worst_leaf_gap(delta_norm, ref["delta_norm"])
    checks.append((f"param_change_norm_gap[{where}]", d,
                   lim["param_change_norm_gap"]))
    say(f"[train] parameter change, worst leaf: norm {delta_norm[where]!r} "
        f"against the reference's {ref['delta_norm'][where]!r} (median leaf "
        f"{float(np.median(list(ref['delta_norm'].values())))!r})")
    finite = all(np.isfinite(v) for v in losses + fetched)
    checks.append(("losses_not_finite", 0.0 if finite else 1.0, 0.0))
    checks.append(("last_fetched_loss_over_first", fetched[-1] / losses[0],
                   lim.get("last_fetched_loss_over_first", 1.0)))
    return {
        "end_to_end": {"train.tokens_per_s": steps * tokens_per_step / window_s},
        "attempted": steps, "failed": 0 if finite else steps,
        "checks": checks, "memory": mem, "window_s": window_s,
        "sources": {"group_step_ms": group_ms, "window": (t0, now),
                    "counters0": counters0, "hlo_scopes": scopes},
    }
