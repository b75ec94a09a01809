"""Operations of ONE training step of the Kimi-Linear cell, from its
shapes — of what THIS chip computes: its share of the routed experts by
the slots counted (not by ``num_experts_per_token``), its slice of the
vocabulary.  Kept with the benchmark so no later PR can move the
yardstick.  ``cfg`` is ``reference.kimi_linear_plain.model_cfg`` of the
configuration file.  Recomputation is not counted."""
from __future__ import annotations

KDA_CHUNK = 64


def _mixer_kind(cfg, i):
    return "kda" if i + 1 in cfg["linear_attn_config"]["kda_layers"] else "mla"


def kda_matrix_params(cfg) -> int:
    """Matrix parameters a token meets in one KDA mixer: q, k, v, o, the
    two low-rank pairs W_f and W_g, W_beta, and the three width-4
    depthwise convolutions (a multiply-add a tap a channel)."""
    la, h = cfg["linear_attn_config"], cfg["hidden_size"]
    wide = la["num_heads"] * la["head_dim"]
    rank = cfg.get("kda_gate_rank") or la["head_dim"]
    return (4 * h * wide + 2 * (h * rank + rank * wide)
            + h * la["num_heads"] + 3 * wide * la["short_conv_kernel_size"])


def mla_matrix_params(cfg) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return (h * heads * (dn + dr) + h * (r + dr) + r * heads * (dn + dv)
            + heads * dv * h)


def ffn_matrix_params(cfg, i, held_slots_per_token: float) -> float:
    """Dense: 3 h w.  Expert layer: the router over all ``num_experts``,
    the shared expert, and one routed expert a HELD slot
    (``held_slots_per_token``: held slots / tokens of one expert layer,
    as counted by the program)."""
    h = cfg["hidden_size"]
    if i < cfg["first_k_dense_replace"]:
        return 3 * h * cfg["intermediate_size"]
    w = cfg["moe_intermediate_size"]
    return (h * cfg["num_experts"] + 3 * h * w * cfg["num_shared_experts"]
            + 3 * h * w * held_slots_per_token)


def matrix_params_per_token(cfg, held_slots_per_token: float) -> float:
    """Matrix parameters a token is multiplied by: the layers and the
    output head.  The embedding is a lookup and does not count."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for i in range(cfg["num_hidden_layers"]):
        total += (kda_matrix_params(cfg) if _mixer_kind(cfg, i) == "kda"
                  else mla_matrix_params(cfg))
        total += ffn_matrix_params(cfg, i, held_slots_per_token)
    return total


def mla_attention_flops(cfg, batch: int, seq: int, backward: bool) -> float:
    """Causal attention of ONE MLA layer, half of every product under
    the mask.  Forward: QK^T over (nope + rope)-wide heads and PV over
    v_head_dim-wide ones.  The backward adds dV and dP (v_head_dim
    wide) and the scores again, dQ and dK (nope + rope wide): the usual
    flash-attention count, with the two widths told apart."""
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = 2.0 * batch * cfg["num_attention_heads"] * seq * seq * 0.5
    return pairs * ((dqk + dv) + ((3 * dqk + 2 * dv) if backward else 0))


def kda_chunk_flops(cfg, batch: int, seq: int, backward: bool) -> float:
    """The chunkwise delta rule of ONE KDA layer, the products a chunk of
    C tokens needs per head: K K^T and Q K^T with decays (2 x 2 C C dk),
    W_k = T (beta K+) (2 C C dk), W_v = T (beta V) (2 C C dv), W_k S,
    K~^T U and Q+ S (3 x 2 C dk dv), P U (2 C C dv).  The triangular
    inverse (log2 C small products) and the 16 x 16 diagonal blocks'
    pairwise sums are left out.  Backward: twice the forward."""
    la = cfg["linear_attn_config"]
    c, dk = KDA_CHUNK, la["head_dim"]
    dv = dk
    per_chunk = 2.0 * c * (3 * c * dk + 2 * c * dv + 3 * dk * dv)
    chunks = batch * -(-seq // c)
    fwd = per_chunk * chunks * la["num_heads"]
    return fwd * (3.0 if backward else 1.0)


def train_flops_per_token(cfg, seq: int, held_slots_per_token: float) -> float:
    """Model FLOPs a token of a ``seq``-token sequence needs in one
    training step: 6 per matrix parameter it meets (2 forward, 4
    backward), causal MLA forward and backward, and the chunkwise KDA's
    products forward and backward."""
    mixers = 0.0
    for i in range(cfg["num_hidden_layers"]):
        mixers += (kda_chunk_flops(cfg, 1, seq, True)
                   if _mixer_kind(cfg, i) == "kda"
                   else mla_attention_flops(cfg, 1, seq, True))
    return 6.0 * matrix_params_per_token(cfg, held_slots_per_token) \
        + mixers / seq
