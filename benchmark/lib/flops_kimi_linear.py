"""Operations a trained token costs in a ``kimi_linear`` stack as this
chip's share holds it, from shapes and from the routing that happened, and
the operations and bytes of one call of the flash kernels at two head
sizes.

Counts are of the work the mathematics requires (one multiply-add is two
operations; no recomputation, no padding): per sublayer kind, forward,

- ``K``: the input projection (q, k, v, both low-rank gates' first halves
  and the write strength), the gates' second halves, the output
  projection; the three convolutions (kernel taps per channel); the delta
  rule AS ITS DEFINITION DOES IT, per head and token three products of the
  head's ``K x V`` state: ``S'^T k``, the rank-one update and ``S^T q``
  (the chunked form's triangular systems are the program's way, not the
  model's work);
- ``L``: q, the joint down- and up-projections, o, and causal attention
  over L(L+1)/2 pairs with q.k at ``nope + shared`` wide and p.v at
  ``v_dim`` wide;
- ``D``: the gated feed-forward's three matrices;
- ``E``: the router over ALL experts; the shared expert; the routed
  experts by the (token, choice) pairs that LANDED on the experts held
  here — ``rows_per_token`` summed over the E layers, from the program's
  counter, never from a capacity;
- the head over the vocabulary slice held.

Training is three times the forward.
"""

from __future__ import annotations

from benchmark.lib.flops import causal_pairs
from benchmark.lib.weights_kimi_linear import dims


def kda_layer_flops(z: dict) -> float:
    d, inner, rank, h = z["d"], z["kda_inner"], z["gate_rank"], z["kda_heads"]
    proj = (2.0 * d * (3 * inner + 2 * rank + h) + 2 * 2.0 * rank * inner
            + 2.0 * inner * d)
    conv = 2.0 * z["conv_kernel"] * 3 * inner
    rule = 3 * 2.0 * h * z["kda_head_dim"] ** 2
    return proj + conv + rule


def mla_layer_flops(z: dict, seq_len: int) -> float:
    d, h, qk = z["d"], z["heads"], z["nope"] + z["shared_k"]
    proj = (2.0 * d * h * qk + 2.0 * d * (z["kv_rank"] + z["shared_k"])
            + 2.0 * z["kv_rank"] * h * (z["nope"] + z["v_dim"])
            + 2.0 * h * z["v_dim"] * d)
    pairs = 2.0 * h * (qk + z["v_dim"]) * causal_pairs(seq_len) / seq_len
    return proj + pairs


def dense_layer_flops(z: dict) -> float:
    return 6.0 * z["d"] * z["dense_dim"]


def expert_layer_fixed_flops(z: dict) -> float:
    """Router and shared expert: what every token costs in an E layer."""
    return 2.0 * z["d"] * z["experts"] + 6.0 * z["d"] * z["shared_dim"]


def routed_pair_flops(z: dict) -> float:
    """One (token, choice) pair through one routed expert."""
    return 6.0 * z["d"] * z["expert_dim"]


def forward_flops_per_token(cfg: dict, seq_len: int, rows_per_token: float) -> float:
    """``rows_per_token``: pairs landed on held experts, summed over the E
    layers, per token (an even routing gives n_E * k * held / experts)."""
    z = dims(cfg)
    return (
        z["n_k"] * kda_layer_flops(z)
        + z["n_l"] * mla_layer_flops(z, seq_len)
        + z["n_d"] * dense_layer_flops(z)
        + z["n_e"] * expert_layer_fixed_flops(z)
        + rows_per_token * routed_pair_flops(z)
        + 2.0 * z["d"] * z["vocab"]
    )


def train_flops_per_token(cfg: dict, seq_len: int, rows_per_token: float) -> float:
    return 3.0 * forward_flops_per_token(cfg, seq_len, rows_per_token)


def flash_fwd_cost(batch_heads: int, seq_len: int, qk_dim: int, v_dim: int,
                   elem_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal flash forward call whose keys and
    values differ in width: q.k at ``qk_dim`` and p.v at ``v_dim`` over the
    causal pairs; q, k, v read and o written once."""
    ops = 2.0 * batch_heads * causal_pairs(seq_len) * (qk_dim + v_dim)
    byts = 2.0 * batch_heads * seq_len * (qk_dim + v_dim) * elem_bytes
    return ops, byts


def flash_bwd_cost(batch_heads: int, seq_len: int, qk_dim: int, v_dim: int,
                   elem_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal flash backward: five products
    over the causal pairs (s recomputed, dq, dk at ``qk_dim``; dp, dv at
    ``v_dim``); q, k, v, o, do read and dq, dk, dv written once. The
    two-kernel split computes s and dp twice: that is the program's way,
    and is not counted."""
    ops = 2.0 * batch_heads * causal_pairs(seq_len) * (3 * qk_dim + 2 * v_dim)
    byts = 4.0 * batch_heads * seq_len * (qk_dim + v_dim) * elem_bytes
    return ops, byts
