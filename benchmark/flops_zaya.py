"""Parameters and bytes of ZAYA1-8B from its shapes — kept with the
benchmark so no later PR can move the yardstick.  ``cfg`` is
``reference.zaya_plain.model_cfg`` of a configuration file."""
from __future__ import annotations

BYTES = 2               # a parameter as the engine holds it: bfloat16


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down of the expert width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg, experts_touched: float) -> float:
    """Bytes of the experts' weights a step has to read when its tokens
    chose ``experts_touched`` distinct (layer, expert) pairs, whatever
    implements the product: 25.17 MB an expert of 2,048 x 2,048."""
    return experts_touched * expert_params(cfg) * BYTES


def cca_params(cfg) -> int:
    """A layer's attention sublayer: [W_Q; W_K; W_V1; W_V2] and W_O, the
    two convolutions with their biases, tau."""
    e, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lz = (hq + hk) * d
    return (e * (lz + hk * d) + hq * d * e
            + 2 * lz + lz + 2 * (hq + hk) * d * d + lz + hk)


def router_params(cfg) -> int:
    """A layer's router: W_d and its bias, gamma (absent in layer 0: not
    told apart here), the norm's gain, the three-layer MLP, beta."""
    e, r, n = (cfg["hidden_size"], cfg["router_hidden_size"],
               cfg["num_experts"])
    return e * r + r + r + r + 2 * (r * r + r) + r * n + n


def model_params(cfg) -> int:
    """Every leaf of the layers kept and the embedding (which is the
    head): layer 0 lacks gamma."""
    e = cfg["hidden_size"]
    layer = (cca_params(cfg) + router_params(cfg)
             + cfg["num_experts"] * expert_params(cfg) + 2 * e + 8 * e)
    return (cfg["num_hidden_layers"] * layer - cfg["router_hidden_size"]
            + cfg["vocab_size"] * e + e)


def kv_bytes_per_token(cfg) -> int:
    """K and V a token a layer as the pages hold them."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES
