"""Needed work: the operations and bytes that the tokens a run really asked
for require, computed from the configuration's published widths.

The count does not depend on how the program computes a layer, so a
share built on it cannot pass 100%, and whatever the program computes
beyond it (padding rows, rows masked out of a join, tokens past the
point where the client stopped, rejected drafts) lowers the share.
``m`` is the dict of sizes from ``bench.reference.<family>.dims``.
Multiply-adds count as two operations; bytes are bfloat16 (2 each).
"""
from __future__ import annotations

BYTES = 2


def linear_flops(m: dict) -> int:
    """Projection and MLP operations of one token in one layer."""
    d, hd = m["d"], m["head_dim"]
    qkv = d * (m["heads"] + 2 * m["kv_heads"]) * hd
    out = m["heads"] * hd * d
    mlp = (3 if m["gated"] else 2) * d * m["ffn"]
    return 2 * (qkv + out + mlp)


def attn_flops(m: dict, keys: int) -> int:
    """Scores and weighted values of one query against ``keys`` keys, in
    one layer (QK^T and PV: two multiply-adds per key and head dim)."""
    return 4 * m["heads"] * m["head_dim"] * keys


def head_flops(m: dict) -> int:
    """Logits of one position over the vocabulary."""
    return 2 * m["d"] * m["vocab"]


def _key_sum(start: int, end: int) -> int:
    """Sum over positions p in [start, end) of the p + 1 keys each
    attends to under the causal mask."""
    return (end * (end + 1) - start * (start + 1)) // 2


def prefill_flops(m: dict, start: int, end: int, commit: bool) -> int:
    """A prompt piece at positions [start, end); a piece that completes
    its prompt also needs the logits of its last position."""
    per_layer = ((end - start) * linear_flops(m)
                 + 4 * m["heads"] * m["head_dim"] * _key_sum(start, end))
    return m["layers"] * per_layer + (head_flops(m) if commit else 0)


def decode_flops(m: dict, pos: int) -> int:
    """One decode step of the token at position ``pos`` (it attends to
    ``pos + 1`` keys) and the logits it yields."""
    return (m["layers"] * (linear_flops(m) + attn_flops(m, pos + 1))
            + head_flops(m))


def decode_attn_work(m: dict, pos: int) -> tuple[int, int]:
    """(operations, bytes) of the paged decode attention for one token at
    position ``pos``, over all layers: every live key and value read
    once, the query read and the output written once."""
    keys = pos + 1
    flops = attn_flops(m, keys)
    kv = 2 * m["kv_heads"] * m["head_dim"] * keys * BYTES
    qo = 2 * m["heads"] * m["head_dim"] * BYTES
    return m["layers"] * flops, m["layers"] * (kv + qo)


def prefill_attn_work(m: dict, start: int, end: int) -> tuple[int, int]:
    """(operations, bytes) of the paged prefill attention for a piece at
    positions [start, end), over all layers: keys and values of positions
    [0, end) read once, the piece's queries read and outputs written."""
    flops = 4 * m["heads"] * m["head_dim"] * _key_sum(start, end)
    kv = 2 * m["kv_heads"] * m["head_dim"] * end * BYTES
    qo = 2 * (end - start) * m["heads"] * m["head_dim"] * BYTES
    return m["layers"] * flops, m["layers"] * (kv + qo)
