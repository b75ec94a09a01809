"""MoE / expert parallelism (reference:
python/paddle/incubate/distributed/models/moe/)."""
from .gate import (BaseGate, NaiveGate, GShardGate, SwitchGate,
                   SigmoidTopKGate, moe_capacity)
from .moe_layer import MoELayer, ExpertFFN, SwiGLUExperts, shard_moe_layer

__all__ = ["BaseGate", "NaiveGate", "GShardGate", "SwitchGate",
           "SigmoidTopKGate", "MoELayer", "ExpertFFN", "SwiGLUExperts",
           "shard_moe_layer", "moe_capacity"]
