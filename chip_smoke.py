#!/usr/bin/env python3
"""The quickest proof that the two main paths still run on the chip.

    python3 chip_smoke.py [--seed N]      one TPU chip (what the driver runs)
    python3 chip_smoke.py --chips 4       the four-chip paths only

One process, one chip, in order: *device* (anything but a TPU is a
non-zero exit, at once), *kernels* (every Pallas kernel the two phases
reach, compiled, against its XLA oracle at the phase's shapes), *serve*
(a ``GenerationServer`` over HTTP through the unified ragged step) and
*train* (``jit.TrainStep`` with AdamW, bf16 params and f32 masters),
all at the widths of ``llama_7b()`` — hidden 4096, intermediate 11008,
32 heads x 128, vocab 32000.  No width is cut.  Depth is cut so one chip
holds the run, sized from the chip's own ``bytes_limit`` and checked
against each program's ``memory_analysis()`` before it first executes;
the cut is printed.  Weights are random, made from ``--seed``.

A phase that fails raises; nothing is caught and carried on.  Timings on
earlier lines are plain information, never metrics.  The last line of
stdout is exactly one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

The phases are functions that take their sizes as arguments:
``tests/test_chip_smoke.py`` runs them tiny on the CPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import sys
import threading
import time
import urllib.request
from importlib import metadata

import numpy as np


def say(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------------ sizing
def llama_param_counts(cfg):
    """(parameters of one decoder layer, parameters of embedding + head
    + final norm) by the config's arithmetic."""
    h, i = cfg.hidden_size, cfg.intermediate_size
    d = h // cfg.num_attention_heads
    kv = cfg.num_key_value_heads * d
    layer = h * h + 2 * h * kv + h * h + 3 * h * i + 2 * h
    outer = cfg.vocab_size * h * (1 if cfg.tie_word_embeddings else 2) + h
    return layer, outer


def serve_depth(cfg, bytes_limit, pool_tokens, resident_fraction=0.5):
    """Layers such that bf16 weights plus a KV pool of ``pool_tokens``
    take ``resident_fraction`` of the device; the rest is left to the
    step's temporaries, the plain-forward reference of the logits check
    and the float32 initialisation (parameters are created in float32
    and cast, so building the model needs twice its final bytes)."""
    layer, outer = llama_param_counts(cfg)
    d = cfg.hidden_size // cfg.num_attention_heads
    pool_per_layer = pool_tokens * 2 * cfg.num_key_value_heads * d * 2
    n = int((resident_fraction * bytes_limit - 2 * outer)
            // (2 * layer + pool_per_layer))
    return max(1, min(n, cfg.num_hidden_layers))


def train_depth(cfg, bytes_limit, batch, seq, fraction=0.9):
    """Layers such that one AdamW step fits: 2 B/param bf16 weights held
    by the model, 2 B/param the step's own copy, 12 B/param f32 masters
    and two moments, 2 B/param gradients (18 in all), plus f32 logits,
    their gradient and a bf16 copy, plus per-layer activations."""
    layer, outer = llama_param_counts(cfg)
    tokens = batch * seq
    logits = tokens * cfg.vocab_size * (4 + 4 + 2)
    act_per_layer = tokens * (8 * cfg.hidden_size
                              + 3 * cfg.intermediate_size) * 2
    n = int((fraction * bytes_limit - 18 * outer - logits)
            // (18 * layer + act_per_layer))
    return max(1, min(n, cfg.num_hidden_layers))


def cut_config(n_layers, max_position=None):
    """``llama_7b()`` with depth (and, for training, the rope table
    length) cut; every width as published."""
    from paddle_tpu.models.llama import llama_7b
    cfg = llama_7b()
    cfg.num_hidden_layers = int(n_layers)
    if max_position is not None:
        cfg.max_position_embeddings = int(max_position)
    return cfg


# ------------------------------------------------------------------ device
def phase_device():
    """The device as jax reports it; exits non-zero at once unless it is
    a TPU.  Places the compile cache and prints where."""
    import jax
    import jaxlib
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found platform "
              f"{d.platform!r} ({len(devs)} device(s))", file=sys.stderr)
        raise SystemExit(2)
    from paddle_tpu.framework.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed as a package"
    limit = int(d.memory_stats()["bytes_limit"])
    say(f"[device] jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu}")
    say(f"[device] platform {d.platform} kind {d.device_kind!r} count "
        f"{len(devs)} bytes_limit {limit} ({limit / 2**30:.2f} GiB)")
    say(f"[device] compile cache: {cache_dir}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "bytes_limit": limit}


# ----------------------------------------------------------------- kernels
def _max_err(got, ref):
    """Largest |got - ref| over max(|ref|, 1), as numpy float32."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1.0)))


def _check_kernel(name, kernel_fn, oracle_fn, args, tol, compiled,
                  keep=None):
    """Run ``kernel_fn`` and its XLA oracle once on ``args`` and compare
    every output (where ``keep``, a boolean mask over the leading axes,
    says so).  ``compiled`` also demands a Mosaic custom call in the
    kernel's lowered program (the kernel really went to the chip's
    compiler, not to the interpreter or an XLA fallback)."""
    import jax
    jk = jax.jit(kernel_fn)
    if compiled and "tpu_custom_call" not in jk.lower(*args).as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in the lowered "
                             f"program — the kernel did not reach Mosaic")
    t0 = time.perf_counter()
    got = jax.block_until_ready(jk(*args))
    ref = jax.block_until_ready(jax.jit(oracle_fn)(*args))
    dt = time.perf_counter() - t0
    got_l = got if isinstance(got, (tuple, list)) else [got]
    ref_l = ref if isinstance(ref, (tuple, list)) else [ref]
    if keep is not None:
        got_l = [np.asarray(g, np.float32)[keep] for g in got_l]
        ref_l = [np.asarray(r, np.float32)[keep] for r in ref_l]
    err = max(_max_err(g, r) for g, r in zip(got_l, ref_l))
    for g in got_l:
        if not np.all(np.isfinite(np.asarray(g, np.float32))):
            raise AssertionError(f"{name}: non-finite output")
    if err > tol:
        raise AssertionError(f"{name}: max error {err:.4g} against the "
                             f"XLA oracle exceeds {tol}")
    say(f"[kernels] {name}: ok, max err {err:.3g} (tol {tol}), "
        f"{'compiled' if compiled else 'interpreted'}, {dt:.1f}s "
        f"with compile")


def phase_kernels(cfg, seed, *, page_size, decode_batch, table_pages,
                  chunk_tokens, train_batch, train_seq, compiled):
    """Each Pallas kernel the serve and train phases reach, once, at
    those phases' shapes, against its XLA oracle.  ``compiled=False``
    (the CPU test) runs them through the Pallas interpreter."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as PA
    from paddle_tpu.ops.pallas.flash_attention import (
        flash_attention_backward, flash_attention_forward, mha_reference)
    from paddle_tpu.ops.pallas.fused_norm_rope import (
        fused_rope_pallas, fused_rope_xla, rms_norm_pallas, rms_norm_xla)

    interpret = not compiled
    H = cfg.num_attention_heads
    KVH = cfg.num_key_value_heads
    D = cfg.hidden_size // H
    scale = 1.0 / math.sqrt(D)
    bf16 = jnp.bfloat16
    rng = np.random.default_rng(seed)

    def mk(*shape, dtype=bf16, std=0.5):
        return jnp.asarray(rng.standard_normal(shape).astype("float32")
                           * std, dtype)

    # -- serve: paged decode + the ragged span kernel over one pool
    pages = decode_batch * table_pages
    kp, vp = mk(KVH, pages, page_size, D), mk(KVH, pages, page_size, D)
    tabs = jnp.asarray(rng.permutation(pages).reshape(
        decode_batch, table_pages).astype("int32"))
    max_len = table_pages * page_size
    lens = jnp.asarray(rng.integers(max_len // 2, max_len + 1,
                                    (decode_batch,)).astype("int32"))
    _check_kernel(
        f"paged decode b{decode_batch} h{H}/{KVH} d{D} page{page_size} "
        f"table{table_pages}",
        lambda q, k, v, l, t: PA._decode_pallas(q, k, v, l, t, scale,
                                                interpret=interpret),
        lambda q, k, v, l, t: PA._decode_xla(q, k, v, l, t, scale),
        (mk(decode_batch, H, D), kp, vp, lens, tabs), 2e-2, compiled)
    nq = min(chunk_tokens, max_len // 2)
    q_lens = jnp.asarray(rng.integers(1, nq + 1, (decode_batch,))
                         .astype("int32")).at[0].set(nq)
    # pad queries (j >= q_len) are zeros on both paths: compare the
    # real ones
    real = np.arange(nq)[None, :] < np.asarray(q_lens)[:, None]
    _check_kernel(
        f"paged ragged b{decode_batch} span<={nq} h{H}/{KVH} d{D}",
        lambda q, k, v, l, ql, t: PA._decode_pallas(
            q, k, v, l, t, scale, interpret=interpret, n_query=nq,
            q_lens=ql),
        lambda q, k, v, l, ql, t: PA._ragged_xla(q, k, v, l, ql, t, scale),
        (mk(decode_batch, nq, H, D), kp, vp, lens, q_lens, tabs), 2e-2,
        compiled, keep=real)
    del kp, vp

    # -- train: flash forward + backward, rope, rmsnorm
    B, S = train_batch, train_seq
    q, k, v = mk(B, H, S, D), mk(B, KVH, S, D), mk(B, KVH, S, D)
    do = mk(B, H, S, D)

    def ref_attn(q_, k_, v_):
        rep = q_.shape[1] // k_.shape[1]
        kk = jnp.repeat(k_, rep, axis=1) if rep > 1 else k_
        vv = jnp.repeat(v_, rep, axis=1) if rep > 1 else v_
        return mha_reference(q_.astype(jnp.float32),
                             kk.astype(jnp.float32),
                             vv.astype(jnp.float32), causal=True,
                             scale=scale)

    _check_kernel(
        f"flash forward b{B} h{H}/{KVH} s{S} d{D}",
        lambda q_, k_, v_: flash_attention_forward(
            q_, k_, v_, True, scale, interpret=interpret)[0],
        ref_attn, (q, k, v), 2e-2, compiled)

    def flash_bwd(q_, k_, v_, do_):
        out, lse = flash_attention_forward(q_, k_, v_, True, scale,
                                           interpret=interpret)
        return flash_attention_backward(q_, k_, v_, out, lse, do_, True,
                                        scale, interpret=interpret)

    def ref_bwd(q_, k_, v_, do_):
        def loss(a, b, c):
            return (ref_attn(a, b, c) * do_.astype(jnp.float32)).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q_, k_, v_)

    _check_kernel(f"flash backward b{B} h{H}/{KVH} s{S} d{D}",
                  flash_bwd, ref_bwd, (q, k, v, do), 4e-2, compiled)
    del q, k, v, do

    pos = np.arange(S)
    inv = 1.0 / (cfg.rope_theta ** (np.arange(0, D, 2) / D))
    ang = np.outer(pos, inv).astype("float32")
    cos, sin = jnp.asarray(np.cos(ang)), jnp.asarray(np.sin(ang))
    _check_kernel(
        f"rope b{B} s{S} h{H}/{KVH} d{D}",
        lambda a, b, c, s_: fused_rope_pallas(a, b, c, s_,
                                              interpret=interpret),
        fused_rope_xla, (mk(B, S, H, D), mk(B, S, KVH, D), cos, sin),
        2e-2, compiled)
    _check_kernel(
        f"rmsnorm {B}x{S}x{cfg.hidden_size}",
        lambda x, w: rms_norm_pallas(x, w, cfg.rms_norm_eps,
                                     interpret=interpret),
        lambda x, w: rms_norm_xla(x, w, cfg.rms_norm_eps),
        (mk(B, S, cfg.hidden_size), mk(cfg.hidden_size, std=1.0)),
        2e-2, compiled)


def tune_train_ops(cfg, seed, *, batch, seq):
    """Call the three autotuned ops of the train step eagerly, once, at
    its shapes, the way a user's eager call would: on a TPU that
    measures the candidates and records the winner the traced step will
    look up.  A candidate the compiler refuses is an error here."""
    import jax.numpy as jnp
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import wrap_array
    from paddle_tpu.models.llama import _rope_tables, apply_rope
    from paddle_tpu.ops import autotune

    H = cfg.num_attention_heads
    KVH = cfg.num_key_value_heads
    D = cfg.hidden_size // H
    rng = np.random.default_rng(seed)

    def mk(*shape):
        return wrap_array(jnp.asarray(
            rng.standard_normal(shape).astype("float32") * 0.5,
            jnp.bfloat16))

    refused_before = len(autotune.failures())
    cos, sin = _rope_tables(D, seq, cfg.rope_theta)
    q, k = mk(batch, seq, H, D), mk(batch, seq, KVH, D)
    apply_rope(q, k, wrap_array(cos), wrap_array(sin), 0)
    F.rms_norm(mk(batch, seq, cfg.hidden_size),
               wrap_array(jnp.ones((cfg.hidden_size,), jnp.bfloat16)),
               cfg.rms_norm_eps)
    F.flash_attention(q, k, k, causal=True)
    failed = autotune.failures()[refused_before:]
    if failed:
        raise AssertionError(f"autotune candidates refused: {failed}")
    report_choices("[kernels] autotune, eager call:", (batch, seq))


def report_choices(prefix, shape):
    """Print what each autotuned op took at ``shape`` (batch, seq)."""
    from paddle_tpu.ops import autotune
    lead = f":({shape[0]}, {shape[1]}, "
    for key, (impl, source) in sorted(autotune.decisions().items()):
        if lead in key:
            say(f"{prefix} {key} -> {impl} ({source})")


# ------------------------------------------------------------------- model
def build_llama(cfg, seed, param_dtype):
    """A ``LlamaForCausalLM`` with random weights from ``seed``; created
    in float32 by the framework's initialisers and cast to
    ``param_dtype`` afterwards."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    dt = jnp.dtype(param_dtype)
    if dt != jnp.float32:
        for p in model.parameters():
            if p._data.dtype == jnp.float32:
                p._data = p._data.astype(dt)
    return model


def plain_logits(model, ids):
    """The model's plain forward — dense causal attention, no cache, no
    paging — as one jitted program; the reference the paged path is
    held to.  Returns float32 logits (batch, seq, vocab)."""
    import jax
    import jax.numpy as jnp
    from __graft_entry__ import _functional_forward

    fn, params = _functional_forward(model)
    out = jax.jit(fn)([p._data for p in params], jnp.asarray(ids, jnp.int32))
    return np.asarray(out, np.float32)


def paged_last_logits(decoder, model, row, page_size, chunk, mesh=None):
    """Last-position logits of ``row`` from the RAGGED paged program
    (its ``sampling=None`` logits escape hatch) over a scratch cache,
    fed ``chunk`` tokens a step as the engine feeds a prompt (its
    decoder packs a step to the engine's token bound and takes no
    more)."""
    from paddle_tpu.ops.pallas.paged_attention import PagedKVCache
    row = np.asarray(row, np.int32)
    pages = -(-len(row) // page_size) + 1
    cache = PagedKVCache.from_model(model, total_pages=pages,
                                    page_size=page_size, mesh=mesh)
    for k in range(0, len(row), chunk):
        out, _ = decoder.ragged_step(cache, ["logits-check"],
                                     [row[k:k + chunk]], [k], sampling=None)
    return np.asarray(out, np.float32)[0]


def _logits_agree(name, got, ref, tol):
    """|got - ref| <= tol * max|ref| everywhere, both finite."""
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(ref))):
        raise AssertionError(f"{name}: non-finite logits")
    span = float(np.max(np.abs(ref)))
    err = float(np.max(np.abs(got - ref)))
    say(f"{name}: max |diff| {err:.4g} against max |ref| {span:.4g} "
        f"(ratio {err / span:.4g}, tol {tol}); argmax "
        f"{int(got.argmax())} vs {int(ref.argmax())}")
    if err > tol * span:
        raise AssertionError(f"{name}: logits differ by {err:.4g} > "
                             f"{tol} x {span:.4g}")


# ------------------------------------------------------------------- serve
def _http(url, body=None, timeout=900):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read()
        ctype = r.headers.get("Content-Type", "")
        return r.status, (json.loads(raw) if "json" in ctype else
                          raw.decode())


def _series(snap, name):
    return {tuple(sorted(s.get("labels", {}).items())): s
            for s in (snap.get(name) or {"series": []})["series"]}


def _counter(snap, name, **labels):
    s = _series(snap, name).get(tuple(sorted(labels.items())))
    return float(s["value"]) if s else 0.0


def _generate(base, ids, new_tokens, **extra):
    """POST one single-row /generate; returns the new token ids after
    checking the answer carries exactly the asked number."""
    status, out = _http(base + "/generate",
                        {"input_ids": [list(map(int, ids))],
                         "max_new_tokens": new_tokens, **extra})
    row = out["output_ids"][0]
    if status != 200 or out["new_tokens"] != new_tokens \
            or len(row) != len(ids) + new_tokens \
            or row[:len(ids)] != list(map(int, ids)):
        raise AssertionError(f"/generate answered {status}: "
                             f"{out.get('new_tokens')} new tokens of "
                             f"{new_tokens} asked, {len(row)} ids")
    return row[len(ids):]


def phase_serve(cfg, seed, *, param_dtype, total_pages, page_size,
                max_batch, chunk_tokens, short_len, long_len, prefix_len,
                new_tokens, expect_kernel, logits_tol, bytes_limit=None):
    """A ``GenerationServer`` on port 0 (chunked prefill, the default
    unified ragged step) answering /generate over HTTP.

    Two waves warm the programs up — wave A, sequential (a short and a
    long greedy prompt, two prompts sharing a page-aligned prefix, a
    sampled one); wave B, ``max_batch // 2`` greedy requests at once —
    then wave A again with fresh tokens is the measured window: its
    shapes are fixed by the requests alone (one request at a time,
    chunk sizes are position-derived, the page-table width is pinned),
    so it must compile nothing."""
    from paddle_tpu import monitor
    from paddle_tpu.analysis.program_audit import engine_program_spec
    from paddle_tpu.inference import GenerationServer
    from paddle_tpu.inference.paged import next_pow2
    import jax

    t_start = time.perf_counter()
    monitor.install_compile_hooks()
    model = build_llama(cfg, seed, param_dtype)
    table_pages = next_pow2(-(-cfg.max_position_embeddings // page_size))
    snap0 = monitor.snapshot()
    server = GenerationServer(
        model, port=0, total_pages=total_pages, page_size=page_size,
        max_batch=max_batch, prefill_chunk_tokens=chunk_tokens,
        min_table_pages=table_pages)
    engine = server._engine
    try:
        # the step's worst shape (every slot a full chunk), compiled
        # before anything runs: the kernel is in it and it fits
        fn, donate, args, meta = engine_program_spec(engine, "ragged",
                                                     "greedy")
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        text = lowered.as_text()
        has_kernel = "tpu_custom_call" in text
        say(f"[serve] ragged step {meta['name']!r} at batch "
            f"{meta['batch']}: paged-attention kernel in the program: "
            f"{has_kernel}")
        if expect_kernel:
            if not has_kernel:
                raise AssertionError(
                    "the ragged step compiled without the Pallas "
                    "paged-attention kernel (it took _ragged_xla)")
            t0 = time.perf_counter()
            mem = lowered.compile().memory_analysis()
            planned = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                       + mem.output_size_in_bytes
                       - mem.alias_size_in_bytes)
            say(f"[serve] memory_analysis of that step: arguments "
                f"{mem.argument_size_in_bytes} temps "
                f"{mem.temp_size_in_bytes} outputs "
                f"{mem.output_size_in_bytes} aliased "
                f"{mem.alias_size_in_bytes} -> planned {planned} of "
                f"bytes_limit {bytes_limit} "
                f"({planned / bytes_limit:.2f}); compile "
                f"{time.perf_counter() - t0:.1f}s")
            if planned > 0.95 * bytes_limit:
                raise AssertionError("the ragged step does not fit the "
                                     "device")
        server.start()
        base = f"http://{server.host}:{server.port}"
        rng = np.random.default_rng(seed)

        def prompt(n):
            return rng.integers(0, cfg.vocab_size, (n,)).astype("int32")

        def wave_a(tag):
            shared = prompt(prefix_len)
            tail = long_len - prefix_len
            plan = [
                ("short greedy", prompt(short_len), {}),
                ("long greedy", prompt(long_len), {}),
                ("prefix first", np.concatenate([shared, prompt(tail)]),
                 {}),
                ("prefix second", np.concatenate([shared, prompt(tail)]),
                 {}),
                ("sampled", prompt(chunk_tokens),
                 {"do_sample": True, "temperature": 0.8, "seed": seed}),
            ]
            for name, ids, extra in plan:
                t0 = time.perf_counter()
                _generate(base, ids, new_tokens, **extra)
                say(f"[serve] {tag}: {name} ({len(ids)} prompt tokens, "
                    f"{new_tokens} asked): answered in "
                    f"{time.perf_counter() - t0:.1f}s")

        def wave_b():
            n = max(2, max_batch // 2)
            errs, threads = [], []

            def one(ids):
                try:
                    _generate(base, ids, new_tokens)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    errs.append(e)

            for _ in range(n):
                threads.append(threading.Thread(
                    target=one, args=(prompt(chunk_tokens),)))
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=1200)
                if t.is_alive():
                    raise AssertionError("a concurrent request hung")
            if errs:
                raise errs[0]
            say(f"[serve] warm-up B: {n} concurrent greedy requests "
                f"answered in {time.perf_counter() - t0:.1f}s")

        hits0 = _counter(monitor.snapshot(), "prefix_cache_hit_tokens_total")
        wave_a("warm-up A")
        wave_b()
        snap_warm = monitor.snapshot()
        compiles_warm = (_counter(snap_warm, "jit_recompile_count")
                         - _counter(snap0, "jit_recompile_count"))
        secs = _series(snap_warm, "jit_compile_seconds")
        secs0 = _series(snap0, "jit_compile_seconds")
        compile_s = (sum(s["sum"] for s in secs.values())
                     - sum(s["sum"] for s in secs0.values()))
        say(f"[serve] warm-up compiled {int(compiles_warm)} programs in "
            f"{compile_s:.1f}s of compile")
        wave_a("measured")
        snap1 = monitor.snapshot()
        recompiles = (_counter(snap1, "jit_recompile_count")
                      - _counter(snap_warm, "jit_recompile_count"))
        say(f"[serve] recompiles after warm-up: {int(recompiles)}")
        if recompiles:
            raise AssertionError(f"{int(recompiles)} programs compiled "
                                 f"after the warm-up requests")
        hit_tokens = (_counter(snap1, "prefix_cache_hit_tokens_total")
                      - hits0)
        say(f"[serve] prompt tokens served from the prefix cache: "
            f"{int(hit_tokens)}")
        if hit_tokens < 2 * (prefix_len // page_size) * page_size:
            raise AssertionError("the shared prefix was not served from "
                                 "the prefix cache in both waves")
        by_mode = {dict(k).get("mode"): s["value"] - _counter(
            snap0, "engine_dispatches_total", **dict(k))
            for k, s in _series(snap1, "engine_dispatches_total").items()}
        fallbacks = (_counter(snap1, "engine_unified_fallbacks_total")
                     - _counter(snap0, "engine_unified_fallbacks_total"))
        say(f"[serve] engine_dispatches_total by mode: {by_mode}; "
            f"unified fallbacks {int(fallbacks)}")
        legacy = {m: n for m, n in by_mode.items() if m != "ragged" and n}
        if not by_mode.get("ragged") or legacy or fallbacks:
            raise AssertionError(f"requests left the ragged step: "
                                 f"{by_mode}, fallbacks {fallbacks}")
        # one prompt's last-position logits, ragged paged program
        # against the plain forward (engine idle: nothing in flight)
        row = prompt(long_len - 3)
        _logits_agree("[serve] paged prefill vs plain forward, "
                      f"{len(row)} tokens",
                      paged_last_logits(engine._decoder, model, row,
                                        page_size, chunk_tokens),
                      plain_logits(model, row[None])[0, -1], logits_tol)
        for path in ("/health", "/metrics", "/debug/cost"):
            status, body = _http(base + path)
            if status != 200:
                raise AssertionError(f"GET {path} answered {status}")
            say(f"[serve] GET {path}: 200"
                + (f" {json.dumps(body)[:300]}" if path == "/debug/cost"
                   else ""))
        server.begin_drain(timeout=120)
        if not server.wait_drained(180) or not server._drain_result:
            raise AssertionError("the server did not drain")
        say(f"[serve] drained; phase took "
            f"{time.perf_counter() - t_start:.1f}s")
    finally:
        server.stop()


# ------------------------------------------------------------------- train
def phase_train(cfg, seed, *, batch, seq, steps, k_fused, expect_flash,
                bytes_limit=None):
    """``TrainStep`` (AdamW, bf16 params, f32 masters, the unfused loss
    path of ``bench.build_llama_train_step``) for ``steps`` steps on one
    repeated batch, then one ``run_steps`` window of ``k_fused``."""
    import bench
    import paddle_tpu as paddle

    t_start = time.perf_counter()
    paddle.seed(seed)
    step, _model = bench.build_llama_train_step(cfg, bf16=True,
                                                use_fused=False)
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype("int32")
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    t0 = time.perf_counter()
    mem = step.memory_analysis(x, y, return_hlo=True)
    planned = bench.planned_peak_bytes(mem)
    flash = "tpu_custom_call" in mem["hlo"]
    say(f"[train] memory_analysis: arguments {mem['argument_bytes']} "
        f"temps {mem['temp_bytes']} outputs {mem['output_bytes']} aliased "
        f"{mem['alias_bytes']} -> planned {planned}"
        + (f" of bytes_limit {bytes_limit} ({planned / bytes_limit:.2f})"
           if bytes_limit else "")
        + f"; compile {time.perf_counter() - t0:.1f}s")
    if bytes_limit and planned > 0.97 * bytes_limit:
        raise AssertionError("the train step does not fit the device")
    report_choices("[train] the traced step took", (batch, seq))
    say(f"[train] Pallas kernels in the compiled step: {flash}")
    if expect_flash and not flash:
        raise AssertionError(
            "the flash-attention kernels are not in the train step: "
            "_choose_flash_impl took the XLA path at this batch")
    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        losses.append(float(np.asarray(step(x, y)._data)))
        say(f"[train] step {i}: loss {losses[-1]:.4f} "
            f"({time.perf_counter() - t0:.1f}s)")
    if not np.all(np.isfinite(losses)) or not losses[-1] < losses[0]:
        raise AssertionError(f"losses not finite and falling: {losses}")
    t0 = time.perf_counter()
    window = np.asarray(step.run_steps([(x, y)] * k_fused)._data)
    say(f"[train] run_steps window of {k_fused}: losses "
        f"{[round(float(v), 4) for v in window]} "
        f"({time.perf_counter() - t0:.1f}s with compile)")
    if window.shape != (k_fused,) or not np.all(np.isfinite(window)) \
            or not window[-1] < losses[0]:
        raise AssertionError(f"fused window wrong: {window}")
    say(f"[train] phase took {time.perf_counter() - t_start:.1f}s")
    return losses


# -------------------------------------------------------------- four chips
def _bytes_in_use(tag):
    import jax
    used = [int(d.memory_stats()["bytes_in_use"]) for d in jax.devices()]
    say(f"[four] bytes_in_use {tag}: {used}")
    return used


def phase_four_serve(cfg, seed, *, param_dtype, total_pages, page_size,
                     max_batch, chunk_tokens, prompt_lens, new_tokens,
                     logits_tol, read_memory):
    """The same depth-cut model behind ``GenerationServer(tp=4)`` and
    behind a one-chip server in this process, same prompts: prompt
    logits within tolerance, greedy tokens compared."""
    from paddle_tpu.inference import GenerationServer
    from paddle_tpu.inference.paged import next_pow2

    table_pages = next_pow2(-(-cfg.max_position_embeddings // page_size))
    kw = dict(port=0, total_pages=total_pages, page_size=page_size,
              max_batch=max_batch, prefill_chunk_tokens=chunk_tokens,
              min_table_pages=table_pages)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype("int32")
               for n in prompt_lens]

    def serve(tp):
        model = build_llama(cfg, seed, param_dtype)
        server = GenerationServer(model, tp=tp, **kw)
        try:
            engine = server._engine
            if tp > 1:
                gc.collect()
                if read_memory:
                    used = _bytes_in_use(f"after tp={tp} placement")
                    pools = engine.cache.kv_pool_bytes
                    sharded = sum(
                        p._data.nbytes for p, spec in zip(
                            engine._decoder.params,
                            engine._decoder._tp_param_specs)
                        if tuple(spec))
                    whole = sum(p._data.nbytes
                                for p in engine._decoder.params) - sharded
                    want = whole + (sharded + pools) / tp
                    say(f"[four] expected per chip: {int(want)} = "
                        f"replicated {whole} + (sharded weights "
                        f"{sharded} + pools {pools}) / {tp}")
                    if max(used) > 1.1 * want or min(used) < 0.9 * want:
                        raise AssertionError(
                            "weights and pools do not sit a quarter on "
                            "each chip")
            server.start()
            base = f"http://{server.host}:{server.port}"
            toks = [_generate(base, p, new_tokens) for p in prompts]
            logits = [paged_last_logits(engine._decoder, model, p,
                                        page_size, chunk_tokens,
                                        mesh=engine.mesh)
                      for p in prompts]
            return toks, logits
        finally:
            server.stop()

    toks4, logits4 = serve(4)
    gc.collect()
    toks1, logits1 = serve(1)
    for i, (a, b) in enumerate(zip(logits4, logits1)):
        _logits_agree(f"[four] prompt {i} ({prompt_lens[i]} tokens) tp=4 "
                      f"vs one chip", a, b, logits_tol)
    agree = []
    for i, (a, b) in enumerate(zip(toks4, toks1)):
        n = next((j for j, (u, v) in enumerate(zip(a, b)) if u != v),
                 len(a))
        agree.append(n)
        if n == 0:
            # a first-token flip is only acceptable as a tie inside the
            # logit tolerance
            ref = logits1[i]
            gap = abs(float(ref[a[0]] - ref[b[0]]))
            if gap > 2 * logits_tol * float(np.max(np.abs(ref))):
                raise AssertionError(
                    f"prompt {i}: first greedy token differs "
                    f"({a[0]} vs {b[0]}) beyond a tie (gap {gap:.4g})")
    say(f"[four] greedy tokens agreeing from the first, per prompt, of "
        f"{new_tokens}: {agree}")
    return agree


def phase_four_train(cfg, seed, *, batch, seq, tol):
    """One ``TrainStep`` step over a {dp 2 x mp 2} mesh of
    ``shard_llama`` placements against the single-device loss."""
    import jax
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import shard_llama

    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq + 1)).astype("int32")

    def one(mesh):
        model = build_llama(cfg, seed, "bfloat16")
        x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
        if mesh is not None:
            shard_llama(model, mesh)
            rows_on_dp = NamedSharding(mesh.jax_mesh, P("dp"))
            x._data = jax.device_put(x._data, rows_on_dp)
            y._data = jax.device_put(y._data, rows_on_dp)
        opt = optim.AdamW(learning_rate=1e-3,
                          parameters=model.parameters(),
                          multi_precision=True)

        def loss_fn(logits, labels):
            return F.cross_entropy(
                logits.reshape([-1, cfg.vocab_size]).astype("float32"),
                labels.reshape([-1]))

        step = TrainStep(model, loss_fn, opt)
        t0 = time.perf_counter()
        loss = float(np.asarray(step(x, y)._data))
        say(f"[four] TrainStep on "
            f"{'one device' if mesh is None else 'dp2 x mp2'}: loss "
            f"{loss:.5f} ({time.perf_counter() - t0:.1f}s with compile)")
        return loss

    single = one(None)
    gc.collect()
    mesh = dist.ProcessMesh(np.arange(4).reshape(2, 2),
                            dim_names=["dp", "mp"])
    sharded = one(mesh)
    if not (math.isfinite(single) and math.isfinite(sharded)) \
            or abs(single - sharded) > tol * abs(single):
        raise AssertionError(f"sharded loss {sharded} vs single-device "
                             f"{single} beyond {tol}")
    say(f"[four] sharded vs single-device loss: |diff| "
        f"{abs(single - sharded):.3g} (tol {tol} relative)")


# -------------------------------------------------------------------- main
PAGE_SIZE = 16
MAX_BATCH = 8
CHUNK_TOKENS = 128
TRAIN_BATCH, TRAIN_SEQ = 2, 2048


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip paths and what they "
                         "are compared with")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    dev = phase_device()
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, has {dev['count']}", file=sys.stderr)
        return 2
    limit = dev["bytes_limit"]
    from paddle_tpu.models.llama import llama_7b
    full = llama_7b()
    layer, outer = llama_param_counts(full)
    pool_tokens = MAX_BATCH * full.max_position_embeddings
    n_serve = serve_depth(full, limit, pool_tokens)
    serve_cfg = cut_config(n_serve)
    total_pages = pool_tokens // PAGE_SIZE + 8
    say(f"[sizing] llama_7b widths: hidden {full.hidden_size} "
        f"intermediate {full.intermediate_size} heads "
        f"{full.num_attention_heads}x"
        f"{full.hidden_size // full.num_attention_heads} vocab "
        f"{full.vocab_size}; one layer {layer} params, embedding+head "
        f"{outer}")
    say(f"[sizing] serve: depth cut {full.num_hidden_layers} -> "
        f"{n_serve} layers (bf16 weights "
        f"{2 * (outer + n_serve * layer)} B + pool of {total_pages} pages "
        f"x {PAGE_SIZE} tokens for {MAX_BATCH} x "
        f"{full.max_position_embeddings} in half of bytes_limit)")

    if args.chips == 4:
        serve_kw = dict(
            param_dtype="bfloat16", total_pages=total_pages,
            page_size=PAGE_SIZE, max_batch=MAX_BATCH,
            chunk_tokens=CHUNK_TOKENS, new_tokens=8, logits_tol=0.05)
        phase_four_serve(serve_cfg, args.seed, prompt_lens=(24, 200, 384),
                         read_memory=True, **serve_kw)
        gc.collect()
        four_cfg = cut_config(1, max_position=1024)
        say("[sizing] four-chip train: 1 layer, batch 4 x 1024 (the "
            "single-device side of the comparison must fit one chip; "
            "at this size _choose_flash_impl takes the XLA path)")
        phase_four_train(four_cfg, args.seed, batch=4, seq=1024, tol=0.02)
    else:
        n_train = train_depth(full, limit, TRAIN_BATCH, TRAIN_SEQ)
        train_cfg = cut_config(n_train, max_position=TRAIN_SEQ)
        say(f"[sizing] train: depth cut {full.num_hidden_layers} -> "
            f"{n_train} layers at batch {TRAIN_BATCH} x {TRAIN_SEQ} "
            f"(18 B/param with AdamW, f32 masters and gradients)")
        phase_kernels(serve_cfg, args.seed, page_size=PAGE_SIZE,
                      decode_batch=MAX_BATCH, table_pages=32,
                      chunk_tokens=CHUNK_TOKENS, train_batch=TRAIN_BATCH,
                      train_seq=TRAIN_SEQ, compiled=True)
        tune_train_ops(train_cfg, args.seed, batch=TRAIN_BATCH,
                       seq=TRAIN_SEQ)
        gc.collect()
        phase_serve(serve_cfg, args.seed, param_dtype="bfloat16",
                    total_pages=total_pages, page_size=PAGE_SIZE,
                    max_batch=MAX_BATCH, chunk_tokens=CHUNK_TOKENS,
                    short_len=24, long_len=4 * CHUNK_TOKENS,
                    prefix_len=2 * CHUNK_TOKENS, new_tokens=16,
                    expect_kernel=True, logits_tol=0.05,
                    bytes_limit=limit)
        gc.collect()
        phase_train(train_cfg, args.seed, batch=TRAIN_BATCH,
                    seq=TRAIN_SEQ, steps=4, k_fused=2, expect_flash=True,
                    bytes_limit=limit)
    say(f"[done] {time.perf_counter() - t0:.1f}s in all")
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
