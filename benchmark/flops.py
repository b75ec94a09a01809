"""Operations and bytes the algorithms need, from their shapes — kept
with the benchmark so no later PR can move the yardstick."""
from __future__ import annotations


def layer_params(cfg) -> int:
    """Parameters of one decoder layer (matrices only; the two norm
    gains are not multiplied)."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + 3 * h * i


def matmul_params(cfg) -> int:
    """Parameters every token is multiplied by: the layers and the
    output head.  The embedding is a lookup and does not count."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_flops(cfg, batch: int, seq: int, backward: bool) -> float:
    """Causal self-attention of ``batch`` sequences of ``seq`` tokens in
    ONE layer: QK^T and PV are 2*s*s*d each per head, half of it under
    the causal mask; the backward pass recomputes nothing that counts
    and needs 2.5x the forward's matmuls (dQ, dK, dV, dP and the
    scores), the usual flash-attention count."""
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    fwd = 4.0 * batch * cfg["num_attention_heads"] * seq * seq * d * 0.5
    return fwd * (3.5 if backward else 1.0)


def train_flops_per_token(cfg, seq: int) -> float:
    """Model FLOPs a token of a ``seq``-token sequence needs in one
    training step: 6 per matmul parameter (2 forward, 4 backward) plus
    causal attention forward and backward.  Recomputation is not
    counted."""
    attn = cfg["num_hidden_layers"] * attention_flops(cfg, 1, seq, True) / seq
    return 6.0 * matmul_params(cfg) + attn
