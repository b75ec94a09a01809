"""Mixtral-style sparse-MoE pretraining with expert parallelism.

Expert weights are stacked [E, ...] and Shard(0) over the 'ep' mesh
axis; tokens route through the ragged O(T) dispatch and GSPMD lowers the
token<->expert reshard into the all_to_all the reference issues by hand
(moe_layer.py global_scatter/global_gather).  The gate's load-balancing
aux loss compiles into the same whole-step program as the LM loss.

    python examples/pretrain_moe.py --smoke
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--ep", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    n = args.dp * args.ep
    import jax
    if args.smoke:      # n virtual CPU devices; otherwise the host's chips
        jax.config.update("jax_platforms", "cpu")
        from paddle_tpu.framework.jax_compat import pin_cpu_devices
        pin_cpu_devices(n)
    if len(jax.devices()) < n:
        raise SystemExit(f"needs {n} devices, has {len(jax.devices())} "
                         "(--smoke runs on virtual CPU devices)")

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.optimizer as optim
    import paddle_tpu.distributed as dist
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import (LlamaMoeConfig, LlamaMoeForCausalLM,
                                   shard_llama_moe)

    mesh = dist.ProcessMesh(np.arange(n).reshape(args.dp, args.ep),
                            dim_names=["dp", "ep"])
    cfg = LlamaMoeConfig(vocab_size=512, hidden_size=64,
                         intermediate_size=128, num_hidden_layers=2,
                         num_attention_heads=4,
                         max_position_embeddings=128,
                         num_experts=args.ep * 2, moe_top_k=2,
                         gate_type="gshard")
    paddle.seed(0)
    model = shard_llama_moe(LlamaMoeForCausalLM(cfg), mesh)
    opt = optim.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def loss_fn(outputs, labels):
        logits, aux = outputs                   # gate aux rides the step
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1])) + aux

    step = TrainStep(model, loss_fn, opt)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (8, 33)).astype("int32")
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

    for i in range(3 if args.smoke else args.steps):
        loss = step(x, y)
        print(f"step {i}  loss {float(np.asarray(loss._data)):.4f}")
    print(f"{cfg.num_experts} experts sharded over ep={args.ep}; "
          "routing + aux loss + update in one compiled program")


if __name__ == "__main__":
    main()
