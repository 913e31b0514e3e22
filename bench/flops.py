"""Operations and bytes the served model needs, from shapes alone.

Counts are what the algorithm needs, not what the program pads: a
prefill of ``n`` tokens attends causally over ``n (n + 1) / 2`` query-key
pairs per head, a decode step over the valid context of each sequence,
and the output projection covers the true vocabulary.  A multiply-add is
two operations.  ``sizes`` is a configuration file's ``model`` block.

These are the counts of a dense decoder, where a decode step reads every
layer's weights; a configuration names its counts module (``"counts"``),
and one whose step reads only part of its weights names another.
"""
from __future__ import annotations


def _dims(sizes: dict):
    d, h, g, hd = (sizes["d_model"], sizes["num_heads"], sizes["num_kv_heads"],
                   sizes["head_dim"])
    mats = 3 if sizes["mlp_type"] == "swiglu" else 2
    return d, h, g, hd, mats * sizes["d_ff"]


def linear_flops_per_token(sizes: dict) -> int:
    """Projections of every layer plus the output table, for one token."""
    d, h, g, hd, ff = _dims(sizes)
    per_layer = 2 * d * (h * hd + 2 * g * hd) + 2 * h * hd * d + 2 * d * ff
    return sizes["num_layers"] * per_layer


def prefill_flops(sizes: dict, n: int) -> int:
    """One prompt of ``n`` tokens; logits for its last position only."""
    d, h, _, hd, _ = _dims(sizes)
    attn = sizes["num_layers"] * 2 * h * hd * n * (n + 1)  # QK^T and PV, causal
    return n * linear_flops_per_token(sizes) + attn + 2 * d * sizes["vocab_size"]


def decode_flops(sizes: dict, contexts) -> int:
    """One decode step; ``contexts[i]`` keys each sequence attends over
    (its valid context including the new token)."""
    d, h, _, hd, _ = _dims(sizes)
    per_seq = linear_flops_per_token(sizes) + 2 * d * sizes["vocab_size"]
    attn = sizes["num_layers"] * 4 * h * hd * sum(contexts)
    return len(contexts) * per_seq + attn


def kv_bytes_per_token(sizes: dict) -> int:
    return sizes["num_layers"] * 2 * sizes["num_kv_heads"] * sizes["head_dim"] * 2


def weight_bytes(sizes: dict) -> int:
    """bf16 bytes of every layer, the final norm and the output table."""
    d, h, g, hd, ff = _dims(sizes)
    per_layer = d * (h * hd + 2 * g * hd) + h * hd * d + d * ff + 2 * d
    return 2 * (sizes["num_layers"] * per_layer + d + sizes["vocab_size"] * d)


def decode_bytes(sizes: dict, contexts) -> int:
    """Weights once, each sequence's embedding row, the valid KV read once
    and the new token's KV written."""
    d = sizes["d_model"]
    kv = kv_bytes_per_token(sizes)
    return (weight_bytes(sizes) + len(contexts) * 2 * d
            + kv * sum(contexts) + kv * len(contexts))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flop_per_s"], nbytes / peak["hbm_bytes_per_s"])
