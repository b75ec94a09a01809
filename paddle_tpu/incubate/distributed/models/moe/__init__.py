"""MoE / expert parallelism (reference:
python/paddle/incubate/distributed/models/moe/)."""
from .gate import (BaseGate, NaiveGate, GShardGate, SwitchGate,
                   SigmoidTopKGate, DepthAveragedMLPGate, moe_capacity)
from .moe_layer import MoELayer, ExpertFFN, SwiGLUExperts, shard_moe_layer

__all__ = ["BaseGate", "NaiveGate", "GShardGate", "SwitchGate",
           "SigmoidTopKGate", "DepthAveragedMLPGate", "MoELayer", "ExpertFFN", "SwiGLUExperts",
           "shard_moe_layer", "moe_capacity"]
