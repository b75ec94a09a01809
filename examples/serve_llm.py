"""LLM serving: paged KV cache, continuous batching, speculative decoding,
int8 weight-only quantization — the serving stack in one script.

    python examples/serve_llm.py --smoke
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    args = ap.parse_args()
    if args.smoke:
        import jax
        jax.config.update("jax_platforms", "cpu")
        # every new cache length is a new shape to the eager speculative
        # generator: a handful of tokens shows each part running
        args.max_new_tokens = min(args.max_new_tokens, 4)

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import (ContinuousBatchingEngine,
                                      PagedGenerator, SpeculativeGenerator)

    cfg = LlamaConfig(vocab_size=256, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=4, num_attention_heads=4,
                      max_position_embeddings=256)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(0)
    k = args.max_new_tokens

    # 1. paged-KV batch decode (block-multi-head serving shape)
    prompts = rng.integers(0, 256, (2, 12)).astype("int32")
    gen = PagedGenerator(model, total_pages=64, page_size=8)
    out = gen.generate(prompts, max_new_tokens=k)
    print(f"paged decode: {out.shape[1] - 12} new tokens/seq, "
          f"prefill {gen.last_prefill_seconds*1e3:.1f}ms")

    # 2. continuous batching: requests admitted/retired per decode step
    with ContinuousBatchingEngine(model, total_pages=64, page_size=8,
                                  max_batch=4) as eng:
        reqs = [eng.submit(rng.integers(0, 256, (10,)).astype("int32"),
                           max_new_tokens=k) for _ in range(4)]
        outs = [r.result(timeout=600) for r in reqs]
    print(f"continuous batching: {len(outs)} concurrent requests served")

    # 3. speculative decoding: draft proposes, target verifies in one pass
    paddle.seed(1)
    draft = LlamaForCausalLM(LlamaConfig(
        vocab_size=256, hidden_size=32, intermediate_size=64,
        num_hidden_layers=1, num_attention_heads=2,
        max_position_embeddings=256))
    spec = SpeculativeGenerator(model, draft, num_speculative_tokens=4)
    prompt = paddle.to_tensor(prompts[:1])
    out = spec.generate(prompt, max_new_tokens=k)
    print(f"speculative: {spec.last_stats['acceptance_rate']:.0%} drafts "
          f"accepted, {spec.last_stats['tokens_per_round']} tokens/round "
          "(greedy output is bit-identical to target-only decoding)")

    # 4. int8 weight-only quantization of a projection (serving memory)
    from paddle_tpu.nn.quant import weight_quantize, weight_only_linear
    w = model.lm_head.weight
    q, s = weight_quantize(w, algo="weight_only_int8")
    x = paddle.to_tensor(rng.standard_normal(
        (4, cfg.hidden_size)).astype("float32"))
    yq = weight_only_linear(x, q, weight_scale=s)
    yd = paddle.matmul(x, w)
    err = float(np.max(np.abs(np.asarray(yq._data) - np.asarray(yd._data))))
    print(f"int8 weight-only lm_head: max |err| {err:.4f} "
          "(int8 kernel streams weights at half bf16's HBM bytes on TPU)")


if __name__ == "__main__":
    main()
