"""Benchmark suite over the framework path (BASELINE.md configs 1/2/3/5).

Prints ONE JSON line.  Headline metric stays LLaMA pretrain tokens/sec/chip;
the other configs ride in the ``suite`` list of the same object:

    {"metric": ..., "value": N, "unit": ...,
     "device": {"platform": "tpu", "kind": ..., "count": N}, "suite": [...]}

Every config runs through the framework's own training path —
``jit.TrainStep`` (whole-step compilation: forward + loss + backward +
fused optimizer update in one donated-buffer XLA program) with
``paddle_tpu.optimizer`` and bf16/AMP — not hand-rolled jax.

It measures the chip and nothing else: without a TPU it exits non-zero,
a config that raises ends the run non-zero, the configuration is what
this file says (no record file is read), and a device kind whose peak is
not in the table is an error.  What it measures is redefined by the
benchmark PR (ROADMAP S1); ``chip_smoke.py`` is the proof that the
program starts on the chip.
"""
import json
import sys
import time

import numpy as np

# Peak bf16 matmul throughput per chip (TFLOP/s), by device_kind prefix —
# public spec-sheet numbers (cloud.google.com/tpu/docs/system-architecture).
# Longest-prefix match; an unknown kind is an error.
PEAK_BF16_TFLOPS = {
    "TPU v2": 46, "TPU v3": 123,
    "TPU v4 lite": 137, "TPU v4": 275,
    "TPU v5 lite": 197, "TPU v5e": 197,
    "TPU v5p": 459, "TPU v5": 459,
    "TPU v6 lite": 918, "TPU v6e": 918, "TPU v6": 918,
    "TPU7x": 2308, "TPU v7": 2308,
}


def _peak_tflops():
    import jax
    from paddle_tpu.analysis.cost import by_device_kind
    kind = jax.devices()[0].device_kind
    return kind, by_device_kind(PEAK_BF16_TFLOPS, kind, "bf16 peak")


def _mfu_fields(step, x, y, per_sec, units_per_step, compute_dtype="bf16"):
    """MFU = XLA-counted FLOPs/step x steps/sec / chip peak (bf16).

    BASELINE config 5 asks for MFU explicitly; reporting it for every
    config makes single-chip numbers comparable across rounds/hardware.
    ``mfu_dtype`` labels what precision the FLOPs actually ran in — an
    fp32/mixed config's MFU against the bf16 peak is a lower bound, not
    directly comparable with a pure-bf16 config.  Uses the memoized
    memory_analysis (one extra AOT compile per config).
    """
    flops = step.memory_analysis(x, y).get("flops_per_step", 0.0)
    if flops <= 0:      # some cost models report -1 for "can't count"
        return {}
    steps_per_sec = per_sec / units_per_step
    kind, peak = _peak_tflops()
    return {"flops_per_step": flops, "device_kind": kind,
            "peak_tflops_bf16": peak,
            "mfu": round(flops * steps_per_sec / (peak * 1e12), 4),
            "mfu_dtype": compute_dtype}


# The memory gate: a planned peak beyond this share of the device's own
# bytes_limit is refused before the first execution (planned bytes
# exclude runtime fragmentation).
HBM_SAFETY_FRACTION = 0.80


def hbm_bytes_limit(device=None):
    """``bytes_limit`` as ``device`` (default: the first) reports it."""
    import jax
    dev = device if device is not None else jax.devices()[0]
    return int(dev.memory_stats()["bytes_limit"])


def planned_peak_bytes(mem):
    """Alias-aware planned HBM peak from a TrainStep.memory_analysis()
    dict.  Donated outputs alias their arguments (TrainStep donates the
    whole param/opt-state pytree), so true peak ~ args + temps + the
    NON-aliased output slice; summing all three double-counts ~2P.  THE
    one definition every memory gate uses."""
    return (mem["argument_bytes"] + mem["temp_bytes"]
            + max(0, mem["output_bytes"] - mem.get("alias_bytes", 0)))


def _measure(step_fn, sync, units_per_step, steps, warmup=2):
    """Median-free simple wall measure: warmup (compile) then timed steps."""
    for _ in range(warmup):
        sync(step_fn())
    t0 = time.perf_counter()
    last = None
    for _ in range(steps):
        last = step_fn()
    sync(last)
    dt = time.perf_counter() - t0
    return units_per_step * steps / dt


def _sync(loss):
    import jax
    jax.block_until_ready(loss._data)
    v = float(np.asarray(loss._data))
    assert np.isfinite(v), f"non-finite loss {v}"
    return v


def _sync_vec(losses):
    """Window-boundary sync for the fused K-step path: one block for
    the whole (k,) device loss vector."""
    import jax
    jax.block_until_ready(losses._data)
    v = np.asarray(losses._data)
    assert np.all(np.isfinite(v)), f"non-finite loss {v}"
    return v


def build_llama_train_step(cfg, bf16, use_fused, opt_kind="adamw"):
    """One LLaMA pretrain TrainStep — THE definition the headline bench
    and chip_smoke.py's train phase both run.

    use_fused=True routes the loss through the chunked fused linear+CE
    (incubate.nn.functional.fused_linear_cross_entropy, logits never
    materialized); False is the classic f32-logits cross_entropy.

    opt_kind="sgd" swaps AdamW for stateless SGD."""
    import jax.numpy as jnp
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaForCausalLM

    model = LlamaForCausalLM(cfg)
    if bf16:    # bf16 params + f32 master weights in the fused optimizer
        for p in model.parameters():
            if p._data.dtype == jnp.float32:
                p._data = p._data.astype(jnp.bfloat16)
    if opt_kind == "sgd":
        opt = optim.SGD(learning_rate=1e-3, parameters=model.parameters())
    else:
        opt = optim.AdamW(learning_rate=1e-3,
                          parameters=model.parameters(),
                          multi_precision=bf16)

    if use_fused:
        from paddle_tpu.incubate.nn.functional import (
            fused_linear_cross_entropy)

        class _HiddenLM(nn.Layer):
            def __init__(self, lm):
                super().__init__()
                self.lm = lm

            def forward(self, input_ids):
                return self.lm.model(input_ids)

        def loss_fn(hidden, labels):
            return fused_linear_cross_entropy(
                hidden.reshape([-1, cfg.hidden_size]),
                model.lm_head.weight, labels.reshape([-1]),
                chunk_rows=1024)

        return TrainStep(_HiddenLM(model), loss_fn, opt), model

    def loss_fn(logits, labels):
        return F.cross_entropy(
            logits.reshape([-1, cfg.vocab_size]).astype("float32"),
            labels.reshape([-1]))

    return TrainStep(model, loss_fn, opt), model


def bench_llama():
    """Config 5 analog (single-chip): LLaMA decoder pretrain step."""
    from paddle_tpu.models.llama import LlamaConfig
    import paddle_tpu as paddle

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=768, intermediate_size=2048,
        num_hidden_layers=12, num_attention_heads=12,
        max_position_embeddings=2048, dtype="bfloat16")
    batch, seq, steps = 8, 1024, 20
    use_fused = False

    rng = np.random.default_rng(0)
    gate_note = None
    # memory gate: AOT-compile and check the alias-aware planned peak
    # against the device's own bytes_limit before the first execution;
    # step down fused -> smaller batch rather than run past the line.
    # The analysis.spmd static estimate (a trace-only lifetime walk)
    # rides next to the compiled plan.
    hbm = hbm_bytes_limit()
    candidates = list(dict.fromkeys(
        [(use_fused, batch), (True, batch), (True, batch // 2)]))
    step = _model = None
    for try_fused, try_batch in candidates:
        # drop the previous candidate's params + optimizer state BEFORE
        # building the next
        del step, _model
        step, _model = build_llama_train_step(cfg, bf16=True,
                                              use_fused=try_fused)
        ids = rng.integers(0, cfg.vocab_size,
                           (try_batch, seq + 1)).astype("int32")
        x = paddle.to_tensor(ids[:, :-1])
        y = paddle.to_tensor(ids[:, 1:])
        static_peak = step.static_peak_hbm(x, y)
        planned = planned_peak_bytes(step.memory_analysis(x, y))
        if planned <= HBM_SAFETY_FRACTION * hbm:
            use_fused, batch = try_fused, try_batch
            break
        gate_note = (f"memory gate: planned {planned/1e9:.2f}GB "
                     f"(static estimate {static_peak/1e9:.2f}GB) > "
                     f"{HBM_SAFETY_FRACTION}x{hbm/1e9:.2f}GB at "
                     f"fused={try_fused} b{try_batch}; stepped down")
    else:
        raise RuntimeError(f"no config fit under the memory gate: "
                           f"{gate_note}")

    units = batch * seq
    # K-step fused hot path: the headline dispatches ONE lax.scan program
    # per k micro-steps (lr/stepno in-program) instead of paying a Python
    # round-trip per step — the path tools/train_bench.py certifies.
    # Distinct batches per scanned step, tokens counted across all.
    k_fused = 8

    def _mk_batch():
        b = rng.integers(0, cfg.vocab_size,
                         (batch, seq + 1)).astype("int32")
        return (paddle.to_tensor(b[:, :-1]), paddle.to_tensor(b[:, 1:]))

    fused_batches = [(x, y)] + [_mk_batch() for _ in range(k_fused - 1)]
    tok_s = _measure(lambda: step.run_steps(fused_batches), _sync_vec,
                     units * k_fused, max(steps // k_fused, 2))
    out = {
        "metric": "llama_110m_pretrain_tokens_per_sec_per_chip",
        "value": round(tok_s, 1), "unit": "tokens/sec",
        "batch": batch,
        "k_steps_fused": k_fused,
        "hbm_bytes_limit": hbm,
        "path": "jit.TrainStep.run_steps(k=%d) + " % k_fused
                + "optimizer.AdamW(multi_precision) + bf16"
                + (" + fused_linear_cross_entropy" if use_fused else ""),
        **_mfu_fields(step, x, y, tok_s, units, "bf16"),
        "static_peak_hbm_bytes": int(static_peak),
    }
    if gate_note:
        out["memory_gate"] = gate_note
    return out


def bench_resnet_cifar():
    """BASELINE config 1: ResNet-50 on CIFAR-10-shaped data, images/sec."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    model, batch, steps = resnet50(num_classes=10), 256, 20
    size = 32   # CIFAR resolution

    opt = optim.Momentum(learning_rate=0.1, momentum=0.9,
                         parameters=model.parameters(), weight_decay=5e-4)
    ce = nn.CrossEntropyLoss()

    def loss_fn(logits, labels):
        return ce(logits, labels)

    step = TrainStep(model, loss_fn, opt,
                     amp_level="O1")
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.standard_normal(
        (batch, 3, size, size)).astype("float32"))
    y = paddle.to_tensor(rng.integers(0, 10, (batch,)).astype("int64"))

    units = batch
    img_s = _measure(lambda: step(x, y), _sync, units, steps)
    return {
        "metric": "resnet50_cifar10_images_per_sec",
        "value": round(img_s, 1), "unit": "images/sec",
        "path": "jit.TrainStep + optimizer.Momentum + amp O1",
        **_mfu_fields(step, x, y, img_s, units, "amp_o1_mixed"),
    }


def bench_bert_sst2():
    """BASELINE config 2: BERT-base SST-2-shaped fine-tune, tokens/sec."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    import paddle_tpu.nn.functional as F
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertConfig, BertForSequenceClassification

    cfg = BertConfig()                       # bert-base
    batch, seq, steps = 32, 128, 20

    model = BertForSequenceClassification(cfg)
    opt = optim.AdamW(learning_rate=2e-5, parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(logits, labels)

    step = TrainStep(model, loss_fn, opt,
                     amp_level="O1")
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    y = paddle.to_tensor(rng.integers(0, 2, (batch,)).astype("int64"))

    units = batch * seq
    tok_s = _measure(lambda: step(x, y), _sync, units, steps)
    return {
        "metric": "bert_base_sst2_finetune_tokens_per_sec_per_chip",
        "value": round(tok_s, 1), "unit": "tokens/sec",
        "path": "jit.TrainStep + optimizer.AdamW + amp O1",
        **_mfu_fields(step, x, y, tok_s, units, "amp_o1_mixed"),
    }


def bench_ocr_crnn():
    """BASELINE config 3 (recognition half of the OCR pipeline): CRNN + CTC
    images/sec through the framework path."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import CRNN

    n_cls, B, H, W, steps = 96, 64, 32, 320, 20
    model = CRNN(n_cls, img_height=H)

    rng = np.random.default_rng(0)
    x = paddle.to_tensor(
        rng.standard_normal((B, 1, H, W)).astype("float32"))
    y = paddle.to_tensor(
        rng.integers(1, n_cls, (B, max(W // 8, 2))).astype("int64"))
    ilen = paddle.to_tensor(np.full(B, W // 4, np.int64))
    llen = paddle.to_tensor(np.full(B, max(W // 8, 2), np.int64))
    opt = optim.Adam(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.ctc_loss(logits, labels, ilen, llen)

    step = TrainStep(model, loss_fn, opt)
    units = B
    img_s = _measure(lambda: step(x, y), _sync, units, steps)
    return {
        "metric": "crnn_ctc_ocr_rec_images_per_sec",
        "value": round(img_s, 1), "unit": "images/sec",
        "path": "jit.TrainStep + optimizer.Adam + lax.scan CTC",
        **_mfu_fields(step, x, y, img_s, units, "fp32"),
    }


def bench_paged_decode():
    """Serving decode throughput: batched autoregressive decode through
    the paged-KV path (PagedGenerator + the Pallas paged-attention
    kernel on TPU) — the reference's block_multihead_attention serving
    benchmark shape."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.paged import PagedGenerator
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=768, intermediate_size=2048,
        num_hidden_layers=12, num_attention_heads=12,
        max_position_embeddings=2048, dtype="bfloat16")
    batch, prompt, decode = 8, 128, 32
    # 8 x (128 + 32) tokens needs ~80 pages; 256 keeps headroom
    pages, page_size = 256, 16

    model = LlamaForCausalLM(cfg)
    gen = PagedGenerator(model, total_pages=pages, page_size=page_size)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, prompt)).astype("int32")

    gen.generate(ids, max_new_tokens=decode)   # warmup (compile caches)
    # phase-timed inside ONE generate call (the generator stamps prefill
    # and steady-state decode separately), so run-to-run variance of a
    # separate prefill-only run never lands in the decode figure
    out = gen.generate(ids, max_new_tokens=decode)
    decode_tokens = (out.shape[1] - prompt - 1) * batch
    dt = max(gen.last_decode_seconds, 1e-9)

    # decode throughput vs running batch size through the continuous-
    # batching engine — the serving-scaling table the serialized server
    # could not produce
    from paddle_tpu.inference.continuous import ContinuousBatchingEngine
    scaling = []
    need = -(-(prompt + decode) // page_size)   # pages per request
    for nb in (1, 2, 4, 8):
        if nb * need + 1 > pages:
            break
        with ContinuousBatchingEngine(model, total_pages=pages,
                                      page_size=page_size,
                                      max_batch=nb) as eng:
            prompts = [rng.integers(0, cfg.vocab_size, (prompt,))
                       .astype("int32") for _ in range(nb)]
            # warm pass mirrors the timed pass so every admission-ramp
            # bucket the real run hits is already compiled
            warm = [eng.submit(p, max_new_tokens=decode) for p in prompts]
            for r in warm:
                r.result(timeout=600)
            t0 = time.perf_counter()
            reqs = [eng.submit(p, max_new_tokens=decode) for p in prompts]
            for r in reqs:
                r.result(timeout=600)
            wall = time.perf_counter() - t0
        scaling.append({"running_batch": nb,
                        "tokens_per_sec": round(nb * decode / wall, 1)})

    return {
        "metric": "llama_110m_paged_decode_tokens_per_sec",
        "value": round(decode_tokens / dt, 1), "unit": "tokens/sec",
        "batch": batch, "prompt_len": prompt,
        "prefill_ms": round(gen.last_prefill_seconds * 1e3, 1),
        "continuous_batching_scaling": scaling,
        "path": "PagedGenerator fused multi-step decode (N tokens per "
                "dispatch via lax.scan) + paged-attention kernel; scaling "
                "table via ContinuousBatchingEngine",
    }


def main() -> int:
    import jax
    from paddle_tpu.framework.compile_cache import configure_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench.py measures the chip: jax found platform "
              f"{devs[0].platform!r}, no TPU", file=sys.stderr)
        return 2
    configure_compile_cache()
    # a config that raises ends the run: nothing is caught and carried on
    suite = [fn() for fn in (bench_resnet_cifar, bench_bert_sst2,
                             bench_ocr_crnn, bench_paged_decode)]
    head = bench_llama()   # headline last: largest, warm caches
    head["device"] = {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": len(devs)}
    head["suite"] = suite
    print(json.dumps(head))
    return 0


if __name__ == "__main__":
    sys.exit(main())
