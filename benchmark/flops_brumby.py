"""Parameters, bytes and operations of Brumby-14B-Base from its shapes —
kept with the benchmark so no later PR can move the yardstick.  ``cfg``
is ``reference.brumby_plain.model_cfg`` of a configuration file.

The state is counted as the equations have it, whatever the program
stores: per KV head S in R^{D x d} and z in R^D in float32 with D =
d (d + 1) / 2 the symmetric power embedding's size (8,256 at d = 128):
a wider layout reads lower on a roofline, never higher."""
from __future__ import annotations

BYTES = 2               # a parameter as the engine holds it: bfloat16
STATE_BYTES = 4         # S and z: float32


def sym_dim(cfg) -> int:
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def layer_params(cfg) -> int:
    """One layer's matrices: q and o over the query heads, k and v over
    the KV heads, the gate a KV head, the SwiGLU."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return (2 * h * q + 2 * h * kv + h * cfg["num_key_value_heads"]
            + 3 * h * cfg["intermediate_size"])


def model_params(cfg) -> int:
    """Every matrix of the layers kept, the embedding and the head (the
    norm gains are not counted)."""
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def step_weight_bytes(cfg) -> int:
    """Bytes of weights one serving step streams: everything but the
    embedding (a lookup)."""
    return (model_params(cfg)
            - cfg["vocab_size"] * cfg["hidden_size"]) * BYTES


def state_bytes(cfg) -> int:
    """One sequence's state of ONE layer, once: 8 x 8,256 x 129 x 4 B =
    34.1 MB at the published widths."""
    return (cfg["num_key_value_heads"] * sym_dim(cfg)
            * (cfg["head_dim"] + 1) * STATE_BYTES)


def state_traffic_bytes(cfg, row_layers: float) -> float:
    """What ``row_layers`` (rows that carry a token x layers) read and
    write of state in a step: each state once in, once out."""
    return 2.0 * row_layers * state_bytes(cfg)


def one_token_flops(cfg) -> int:
    """The one-token form of one row of one layer: S <- g S + phi(k) v^T
    (a product, a product and a sum an entry of S and z) and phi(q_i)^T S
    for the group's query heads (a product and a sum an entry a head)."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return (cfg["num_key_value_heads"] * sym_dim(cfg)
            * (cfg["head_dim"] + 1) * (3 + 2 * group))


def chunk_flops(cfg, tokens: float) -> float:
    """The chunk form of one row of ``tokens`` tokens of one layer: phi(Q)
    S_prev for every query head, the update phi(K)^T V, and inside the
    chunk the scores and their product with V (the 129th column is the
    normaliser)."""
    d, kvh = cfg["head_dim"], cfg["num_key_value_heads"]
    group = cfg["num_attention_heads"] // kvh
    wide = d + 1
    before = 2.0 * tokens * group * sym_dim(cfg) * wide
    update = 2.0 * tokens * sym_dim(cfg) * wide
    inside = 2.0 * group * tokens * tokens * (d + wide)
    return kvh * (before + update + inside)
