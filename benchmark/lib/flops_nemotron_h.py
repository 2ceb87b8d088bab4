"""Operations a trained token costs in a ``nemotron_h`` stack as this
chip's share holds it, from shapes and from the routing that happened.

Counts are of the work the mathematics requires (one multiply-add is two
operations; no recomputation, no padding): per layer kind, forward,

- ``M``: in_proj and out_proj; the convolution (kernel taps per channel);
  the scan in its chunked form, per head ``Q*N`` for the C.B scores,
  ``Q*P`` for their product with the chunk's inputs, ``2*N*P`` for the
  chunk's state and its read-out: ``2*H*(Q*N + Q*P + 2*N*P)``;
- ``E``: the router over ALL experts; the shared expert; the routed
  experts by the (token, choice) pairs that LANDED on the experts held
  here — ``rows_per_token`` summed over the E layers, from the program's
  counter, never from a capacity;
- ``*``: q, k, v, o projections and causal attention over L(L+1)/2 pairs;
- the head over the vocabulary slice held.

Training is three times the forward.
"""

from __future__ import annotations

from benchmark.lib.flops import causal_pairs
from benchmark.lib.weights_nemotron_h import dims


def mamba_layer_flops(z: dict) -> float:
    d, inner, cdim, h = z["d"], z["inner"], z["conv_dim"], z["ssm_heads"]
    q, n, p = z["chunk"], z["ssm_state"], z["ssm_head_dim"]
    proj = 2.0 * d * (2 * inner + 2 * z["ssm_groups"] * n + h) + 2.0 * inner * d
    conv = 2.0 * z["conv_kernel"] * cdim
    scan = 2.0 * h * (q * n + q * p + 2 * n * p)
    return proj + conv + scan


def expert_layer_fixed_flops(z: dict) -> float:
    """Router and shared expert: what every token costs in an E layer."""
    return 2.0 * z["d"] * z["experts"] + 4.0 * z["d"] * z["shared_dim"]


def routed_pair_flops(z: dict) -> float:
    """One (token, choice) pair through one routed expert."""
    return 4.0 * z["d"] * z["expert_dim"]


def attention_layer_flops(z: dict, seq_len: int) -> float:
    hq, hkv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    proj = 2.0 * z["d"] * (2 * hq + 2 * hkv)
    pairs = 4.0 * z["heads"] * z["head_dim"] * causal_pairs(seq_len) / seq_len
    return proj + pairs


def forward_flops_per_token(cfg: dict, seq_len: int, rows_per_token: float) -> float:
    """``rows_per_token``: pairs landed on held experts, summed over the E
    layers, per token (an even routing gives n_E * k * held / experts)."""
    z = dims(cfg)
    return (
        z["n_m"] * mamba_layer_flops(z)
        + z["n_e"] * expert_layer_fixed_flops(z)
        + rows_per_token * routed_pair_flops(z)
        + z["n_a"] * attention_layer_flops(z, seq_len)
        + 2.0 * z["d"] * z["vocab"]
    )


def train_flops_per_token(cfg: dict, seq_len: int, rows_per_token: float) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len, rows_per_token)
