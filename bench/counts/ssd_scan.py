"""The chunked SSD scan (arXiv:2405.21060, section 6), per call.

Per chunk of Q steps and per head, with state N and head size P:
  C B^T             2 Q^2 N
  (C B^T * L) X     2 Q^2 P
  chunk state       2 Q N P
  C h_prev          2 Q N P
Bytes are what the call must move at least once: x and y (bf16), dt (f32),
B and C (bf16, one copy per group) and the final state (f32).
"""
from __future__ import annotations


def cost(*, batch: int, seq_len: int, heads: int, head_dim: int, state: int,
         groups: int, chunk: int) -> tuple[float, float]:
    Q = min(chunk, seq_len)
    n = seq_len // Q
    P, N = head_dim, state
    flops = batch * heads * n * (2 * Q * Q * N + 2 * Q * Q * P + 4 * Q * N * P)
    tokens = batch * seq_len
    nbytes = (2 * 2 * tokens * heads * P          # x in, y out
              + 4 * tokens * heads                # dt
              + 2 * 2 * tokens * groups * N       # B, C
              + 4 * batch * heads * P * N)        # final state
    return float(flops), float(nbytes)
