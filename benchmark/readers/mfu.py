"""Model-FLOP utilization of training, in %: model FLOPs a token needs
(``flops.train_flops_per_token``: 6 per matmul parameter plus causal
attention forward and backward, recomputation not counted) times the
window's tokens per second, over the chip's bf16 peak."""
import flops


def read(args, src):
    rate = src["end_to_end"].get("train.tokens_per_s")
    if rate is None or not src.get("peak"):
        return None
    per_token = flops.train_flops_per_token(src["config"],
                                            int(src["traffic"]["seq"]))
    chips = 1
    return 100.0 * per_token * rate / (chips * src["peak"]["bf16_flops_per_s"])
