"""Operations and bytes the algorithm needs, from shapes alone.

Counts are of the work the mathematics requires: causal attention is
counted over the keys a query may see (half of L*L on a full sequence),
recomputation under remat is not counted, padding to a bucket or to the
slot bank is not counted. One multiply-add is two operations.
"""

from __future__ import annotations


def block_matmul_params(cfg: dict) -> int:
    """Parameters of the per-token matmuls of all blocks: q, k, v, out
    (4 d*d) and the two feed-forward matrices (8 d*d) per layer. The
    same arithmetic as the program's tools/lm_bench.py non-embedding
    count (12 * n_layer * d**2)."""
    return 12 * cfg["n_layer"] * cfg["n_embd"] ** 2


def head_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["n_embd"]


def attention_flops(cfg: dict, keys_seen: int) -> int:
    """Forward operations of attention over ``keys_seen`` (query, key)
    pairs summed over one layer's heads, times the layers: q.k and p.v,
    each 2 * d per pair."""
    return 4 * cfg["n_embd"] * cfg["n_layer"] * keys_seen


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward (3x forward) per trained token, every position
    through the tied head, causal attention over L(L+1)/2 pairs."""
    dense = 2 * (block_matmul_params(cfg) + head_params(cfg))
    attn = attention_flops(cfg, causal_pairs(seq_len)) / seq_len
    return 3.0 * (dense + attn)


def serve_flops(cfg: dict, tokens_run: int, keys_seen: int, logits_rows: int) -> float:
    """Forward operations of serving: ``tokens_run`` tokens through the
    blocks (prefilled and decoded), attention over ``keys_seen`` pairs in
    all, the head for the ``logits_rows`` positions whose token is
    picked."""
    return (
        2.0 * block_matmul_params(cfg) * tokens_run
        + attention_flops(cfg, keys_seen)
        + 2.0 * head_params(cfg) * logits_rows
    )


def flash_fwd_cost(batch: int, heads: int, seq_len: int, head_dim: int,
                   elem_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal flash-attention forward call:
    two matmuls over the causal pairs; q, k, v read and o written once."""
    ops = 4.0 * batch * heads * causal_pairs(seq_len) * head_dim
    byts = 4.0 * batch * heads * seq_len * head_dim * elem_bytes
    return ops, byts


def flash_bwd_cost(batch: int, heads: int, seq_len: int, head_dim: int,
                   elem_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal flash-attention backward call:
    five matmuls over the causal pairs (s recomputed, dv, dp, dq, dk);
    q, k, v, o, do read and dq, dk, dv written once."""
    ops = 10.0 * batch * heads * causal_pairs(seq_len) * head_dim
    byts = 8.0 * batch * heads * seq_len * head_dim * elem_bytes
    return ops, byts


def decode_step_min_bytes(cfg: dict, live_kv_rows: float, elem_bytes: int = 2) -> float:
    """Least bytes one decode step must read: every matmul weight once
    (blocks and tied head, in the compute type) and the live K and V
    rows of the resident requests."""
    weights = (block_matmul_params(cfg) + head_params(cfg)) * elem_bytes
    kv = 2.0 * live_kv_rows * cfg["n_layer"] * cfg["n_embd"] * elem_bytes
    return weights + kv


def roofline_seconds(ops: float, byts: float, peaks: dict) -> float:
    return max(ops / peaks["bf16_flops_per_s"], byts / peaks["hbm_bytes_per_s"])
