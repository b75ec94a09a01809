"""Parameters, bytes and operations of Phi-4-mini-flash-reasoning from its
shapes — kept with the benchmark so no later PR can move the yardstick.
``cfg`` is ``reference.phi4_flash_plain.model_cfg`` of a configuration
file.

The state is counted as the equations have it, whatever the program
stores: a layer a sequence ``h`` in R^{d_inner x d_state} and the
convolution's last ``d_conv - 1`` inputs, float32: a wider layout reads
lower on a roofline, never higher."""
from __future__ import annotations

from reference import phi4_flash_plain as plain

BYTES = 2               # a parameter as the engine holds it: bfloat16
STATE_BYTES = 4         # h, the tail and the scan's operands: float32


def mamba_layers(cfg) -> int:
    return sum(plain.mixer(cfg, i) == "mamba"
               for i in range(cfg["num_hidden_layers"]))


def model_params(cfg) -> int:
    """Every leaf, the embedding once (the head is the embedding)."""
    n = 0
    for _, shape in plain.param_specs(cfg):
        size = 1
        for s in shape:
            size *= s
        n += size
    return n


def step_weight_bytes(cfg) -> int:
    """Bytes of weights one serving step streams: every leaf once (the
    embedding is the head's matrix)."""
    return model_params(cfg) * BYTES


def kv_bytes_per_token(cfg) -> int:
    """K and V of one position in the pools: the layers that OWN pages
    (the sliding ones and the full one), each KV head once."""
    d = plain.sizes(cfg)[0]
    own = sum(plain.mixer(cfg, i) in ("sliding", "full")
              for i in range(cfg["num_hidden_layers"]))
    return own * 2 * cfg["num_key_value_heads"] * d * BYTES


def state_bytes(cfg) -> int:
    """One sequence's state of ONE Mamba layer, once: 5,120 x (16 + 3) x
    4 B = 389,120 B at the published widths."""
    _, inner, _ = plain.sizes(cfg)
    return inner * (plain.D_STATE + plain.D_CONV - 1) * STATE_BYTES


def state_token_bytes(cfg) -> int:
    """What one token of one Mamba layer moves beside the state: the
    convolution's input and u, delta, B and C in, m out, float32."""
    _, inner, _ = plain.sizes(cfg)
    return (4 * inner + 2 * plain.D_STATE) * STATE_BYTES


def state_token_flops(cfg) -> int:
    """One token of one Mamba layer through the two state ops: the
    convolution (a product and a sum a tap a channel) and the scan
    (exp(delta a), the decay, delta u B, the sum into h, h C and its sum:
    seven an entry of h, and D u and the sums' ends a channel)."""
    _, inner, _ = plain.sizes(cfg)
    return inner * (2 * plain.D_CONV + 7 * plain.D_STATE + 3)
