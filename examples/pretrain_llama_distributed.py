"""Multi-chip LLaMA pretraining: mesh + placements, XLA inserts the
collectives.

The recipe (the scaling-book pattern): build a ProcessMesh over the
device grid, stamp TP/FSDP placements on the weights with shard_llama,
shard the batch over dp, and jit the whole train step — GSPMD lowers the
sharding constraints into the all-reduces/all-gathers the reference
issues through NCCL by hand.

With ``--smoke`` it runs on 8 virtual CPU devices; without, on the
host's own devices (it needs dp x mp of them).

    python examples/pretrain_llama_distributed.py --smoke
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
    ap.add_argument("--mp", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()

    n = args.dp * args.mp
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
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         shard_llama)

    mesh = dist.ProcessMesh(np.arange(n).reshape(args.dp, args.mp),
                            dim_names=["dp", "mp"])

    cfg = LlamaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=4,
                      max_position_embeddings=128)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    shard_llama(model, mesh)          # TP placements: qkv/gate/up column,
    opt = optim.AdamW(learning_rate=1e-3,   # o/down row, vocab on mp
                      parameters=model.parameters())

    def loss_fn(logits, labels):
        return F.cross_entropy(logits.reshape([-1, cfg.vocab_size]),
                               labels.reshape([-1]))

    step = TrainStep(model, loss_fn, opt)

    if 4 % args.mp != 0:
        raise SystemExit(f"--mp {args.mp} must divide the demo's 4 "
                         "attention heads (TP shards the head dim)")
    rng = np.random.default_rng(0)
    rows = 4 * args.dp                 # batch rows divisible by dp
    ids = rng.integers(0, cfg.vocab_size, (rows, 33)).astype("int32")
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])
    # batch rows ride the dp axis
    x._data = jax.device_put(x._data, NamedSharding(mesh.jax_mesh,
                                                    P("dp")))
    y._data = jax.device_put(y._data, NamedSharding(mesh.jax_mesh,
                                                    P("dp")))

    for i in range(5 if args.smoke else args.steps):
        loss = step(x, y)
        print(f"step {i}  loss {float(np.asarray(loss._data)):.4f}")
    print(f"mesh {{'dp': {args.dp}, 'mp': {args.mp}}} — GSPMD inserted "
          "the collectives; no NCCL calls were written by hand")


if __name__ == "__main__":
    main()
