"""Parameters, bytes and operations of Laguna-XS.2 from its shapes — kept
with the benchmark so no later PR can move the yardstick.  ``cfg`` is
``reference.laguna_plain.model_cfg`` of a configuration file."""
from __future__ import annotations

BYTES = 2               # a parameter as the engine holds it: bfloat16


def attention_params(cfg, i) -> int:
    """Layer ``i``'s attention matrices: q and o at the layer's own head
    count, k and v over the KV heads, the gate a head."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads_per_layer"][i] * d
    kv = cfg["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + (h * q // d if cfg["gating"] else 0)


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down of the expert width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_params(cfg, i) -> int:
    """Layer ``i``'s FFN: the dense SwiGLU, or every routed expert, the
    shared expert and the router."""
    h = cfg["hidden_size"]
    if cfg["mlp_layer_types"][i] == "dense":
        return 3 * h * cfg["intermediate_size"]
    return (cfg["num_experts"] * expert_params(cfg)
            + 3 * h * cfg["shared_expert_intermediate_size"]
            + h * cfg["num_experts"])


def model_params(cfg) -> int:
    """Every matrix of the layers kept, the embedding and the head (the
    norm gains and the selection bias are not counted)."""
    layers = sum(attention_params(cfg, i) + ffn_params(cfg, i)
                 for i in range(cfg["num_hidden_layers"]))
    return layers + 2 * cfg["vocab_size"] * cfg["hidden_size"]


def sparse_layers(cfg) -> int:
    return sum(t == "sparse"
               for t in cfg["mlp_layer_types"][:cfg["num_hidden_layers"]])


def expert_bytes(cfg, experts_touched: float) -> float:
    """Bytes of the routed experts' weights a step has to read when its
    tokens chose ``experts_touched`` distinct (layer, expert) pairs: the
    memory-bound floor of scope ``moe/experts`` less the shared expert."""
    return experts_touched * expert_params(cfg) * BYTES


def step_weight_bytes(cfg, experts_touched: float) -> float:
    """Bytes of weights one serving step streams: everything but the
    routed experts and the embedding (a lookup), plus the experts
    touched."""
    routed = sparse_layers(cfg) * cfg["num_experts"] * expert_params(cfg)
    fixed = (model_params(cfg) - routed
             - cfg["vocab_size"] * cfg["hidden_size"])
    return fixed * BYTES + expert_bytes(cfg, experts_touched)


def kv_bytes_per_token(cfg) -> int:
    """K and V of one position over the layers kept."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES
            * cfg["num_hidden_layers"])
