"""What both drivers do the same way: the model with the benchmark's
weights in it, counters, the device's memory."""
from __future__ import annotations

import gc
import time

import weights as W
from reference import llama_plain as plain


def say(msg):
    print(msg, flush=True)


MODEL_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
              "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "max_position_embeddings",
              "rms_norm_eps", "rope_theta", "tie_word_embeddings")


def model_cfg(config: dict) -> dict:
    """The model's sizes out of a configuration file (they sit at its
    top level, under the published names)."""
    cfg = {k: config[k] for k in MODEL_KEYS}
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"] \
            or cfg["tie_word_embeddings"]:
        raise RuntimeError("models/llama.py takes head_dim = hidden / heads "
                           "and an untied head")
    return cfg


def build_model(model_cfg: dict, seed: int):
    """A ``LlamaForCausalLM`` of the program whose every parameter holds
    the benchmark's bfloat16 values for (seed, leaf name).  The program
    creates its parameters in float32 with its own initialisers (a
    ``Layer`` has no other way) and they are cast leaf by leaf, as
    ``bench.build_llama_train_step`` and ``chip_smoke.build_llama`` do;
    their values are then replaced in place by ONE jitted call that
    takes the cast arrays as donated arguments."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(**{k: model_cfg[k] for k in MODEL_KEYS
                         if k != "head_dim"})
    t0 = time.perf_counter()
    # the initial values are thrown away below; drawn with jax's default
    # threefry they cost 24 s per 10^9 parameters on a v5e, with the
    # device's own generator next to nothing.  The program's generator
    # is put back as it was.
    impl = jax.config.jax_default_prng_impl
    jax.config.update("jax_default_prng_impl", "rbg")
    paddle.seed(seed)
    try:
        model = LlamaForCausalLM(cfg)
    finally:
        jax.config.update("jax_default_prng_impl", impl)
        paddle.seed(seed)
    named = list(model.named_parameters())
    for _, p in named:
        p._data = p._data.astype(jnp.bfloat16)
    jax.block_until_ready([p._data for _, p in named])
    t1 = time.perf_counter()
    specs = plain.param_specs(model_cfg)
    got = [(n, tuple(p.shape)) for n, p in named]
    if got != [(n, tuple(s)) for n, s in specs]:
        raise RuntimeError(
            "the program's parameters are not the reference's: "
            f"{[g for g in got if g not in specs][:3]} vs "
            f"{[s for s in specs if s not in got][:3]}")
    new = W.make_all(seed, [n for n, _ in named], [p._data for _, p in named])
    for (_, p), a in zip(named, new):
        p.set_value(a)
    jax.block_until_ready(new)
    say(f"[build] the program's float32 initialisers and the cast took "
        f"{t1 - t0:.1f}s, the benchmark's weights {time.perf_counter() - t1:.1f}s")
    return model


def counters_now() -> dict:
    """{counter name: total over its series} from the program's
    registry."""
    from paddle_tpu import monitor
    out = {}
    for name, m in monitor.snapshot().items():
        if m["type"] == "counter":
            out[name] = sum(s["value"] for s in m["series"])
    return out


def memory_now() -> dict:
    """bytes_in_use / peak_bytes_in_use / bytes_limit of the fullest
    device (zeros where the backend reports none: the CPU rehearsal)."""
    import jax
    best = {"bytes_in_use": 0, "peak_bytes_in_use": 0, "bytes_limit": 0}
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if st.get("peak_bytes_in_use", 0) >= best["peak_bytes_in_use"]:
            best = {k: int(st.get(k, 0)) for k in best}
    return best


def free_device_memory():
    gc.collect()
    import jax
    jax.clear_caches()
    gc.collect()


def decisions_summary() -> dict:
    """{"op -> implementation (source)": how many shapes took it} from
    ``ops.autotune.decisions()``: a flipped choice shows here."""
    from paddle_tpu.ops import autotune
    out = {}
    for key, (impl, source) in sorted(autotune.decisions().items()):
        k = f"{key.split(':')[0]} -> {impl} ({source})"
        out[k] = out.get(k, 0) + 1
    return out
