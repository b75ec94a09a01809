"""Parameters and bytes of MiMo-V2-Flash from its shapes — kept with the
benchmark so no later PR can move the yardstick.  ``cfg`` is
``reference.mimo_v2_flash_plain.model_cfg`` of a configuration file."""
from __future__ import annotations

BYTES = 2               # a parameter, a K or a V element as held: bfloat16


def kv_heads(cfg, i) -> int:
    return (cfg["swa_num_key_value_heads"] if cfg["hybrid_layer_pattern"][i]
            else cfg["num_key_value_heads"])


def attention_params(cfg, i) -> int:
    """Layer ``i``'s attention matrices: q over the query heads at K's
    width, k and v over the layer's own KV heads at their own widths, o
    from the query heads at V's width; the sinks (a scalar a head) are not
    counted."""
    h, d, dv = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"]
    heads, kv = cfg["num_attention_heads"], kv_heads(cfg, i)
    return h * heads * d + h * kv * (d + dv) + heads * dv * h


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down of the expert width."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_bytes(cfg, experts_touched: float) -> float:
    """Bytes of the experts' weights a step has to read when its tokens
    chose ``experts_touched`` distinct HELD (layer, expert) pairs, whatever
    implements the product: 50.33 MB an expert of 4,096 x 2,048."""
    return experts_touched * expert_params(cfg) * BYTES


def ffn_params(cfg, i) -> int:
    """Layer ``i``'s FFN: the dense SwiGLU, or the HELD experts and the
    router over the whole expert set."""
    h = cfg["hidden_size"]
    if not cfg["moe_layer_freq"][i]:
        return 3 * h * cfg["intermediate_size"]
    return (cfg["held_experts"][1] * expert_params(cfg)
            + h * cfg["n_routed_experts"])


def model_params(cfg) -> int:
    """Every matrix of the layers kept, the embedding and the head (the
    norm gains, the sinks and the selection bias are not counted)."""
    layers = sum(attention_params(cfg, i) + ffn_params(cfg, i)
                 for i in range(cfg["num_hidden_layers"]))
    return layers + 2 * cfg["vocab_size"] * cfg["hidden_size"]


def kv_bytes_per_token(cfg, i) -> int:
    """K and V of one position in layer ``i``'s pool: 2,560 B in a full
    layer's (4 heads of 192 + 128), 5,120 B in a sliding one's (8)."""
    return kv_heads(cfg, i) * (cfg["head_dim"] + cfg["v_head_dim"]) * BYTES


def kv_bytes_per_token_all(cfg) -> int:
    """What one position pins over the layers kept: 30,720 B at 2 full and
    5 sliding layers."""
    return sum(kv_bytes_per_token(cfg, i)
               for i in range(cfg["num_hidden_layers"]))
