"""Causal attention over a window, per call: 4 hd operations for each
(query, key) pair a query may see (scores and the weighted sum); bytes are
q, k, v read and the output written once, in bf16."""
from __future__ import annotations


def pairs(seq_len: int, window: int) -> int:
    """Pairs (i, j) with j <= i < seq_len and i - j < window."""
    w = window if window > 0 else seq_len
    full = max(seq_len - w, 0)            # queries that see a whole window
    ramp = seq_len - full                 # the first queries see i + 1 keys
    return ramp * (ramp + 1) // 2 + full * w


def cost(*, batch: int, seq_len: int, heads: int, kv_heads: int,
         head_dim: int, window: int) -> tuple[float, float]:
    flops = 4 * head_dim * heads * batch * pairs(seq_len, window)
    nbytes = 2 * batch * seq_len * head_dim * (2 * heads + 2 * kv_heads)
    return float(flops), float(nbytes)
