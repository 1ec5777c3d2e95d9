"""Model FLOPs of whole steps, from the configuration's sizes (the
program's names). Matrix products count 2 per multiply-add; the SSD scan
and attention use their kernels' counts; norms and elementwise work are
left out. Training counts the forward pass three times (forward and
backward); what remat recomputes is not counted.
"""
from __future__ import annotations

from bench.counts import flash_attention, ssd_scan


def _ssm(m: dict) -> tuple[int, int, int, int, int]:
    di = m["ssm_expand"] * m["d_model"]
    return di, di // m["ssm_head_dim"], m["ssm_head_dim"], m["ssm_state"], \
        m["ssm_ngroups"]


def layer_matmul_flops_per_token(m: dict) -> float:
    D = m["d_model"]
    di, H, P, N, G = _ssm(m)
    conv_ch = di + 2 * G * N
    f = 2 * D * (2 * di + 2 * G * N + H) + 2 * di * D + 2 * m["conv_kernel"] * conv_ch
    if m["family"] == "hybrid":
        q, kv = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
        f += 2 * D * (q + 2 * kv) + 2 * q * D + 3 * 2 * D * m["d_ff"]
    return float(f)


def forward_flops(m: dict, batch: int, seq_len: int, head_tokens: int
                  ) -> float:
    """Forward over ``batch`` sequences of ``seq_len`` tokens, with the
    output head applied to ``head_tokens`` positions in all."""
    di, H, P, N, G = _ssm(m)
    L, tokens = m["n_layers"], batch * seq_len
    f = L * tokens * layer_matmul_flops_per_token(m)
    f += L * ssd_scan.cost(batch=batch, seq_len=seq_len, heads=H,
                           head_dim=P, state=N, groups=G,
                           chunk=m["ssm_chunk"])[0]
    if m["family"] == "hybrid":
        f += L * flash_attention.cost(
            batch=batch, seq_len=seq_len, heads=m["n_heads"],
            kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
            window=m["hybrid_attn_window"])[0]
    return f + 2.0 * head_tokens * m["d_model"] * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    return 3.0 * forward_flops(m, 1, seq_len, seq_len) / seq_len


def decode_flops(m: dict, batch: int, position: int) -> float:
    """One decode step of ``batch`` tokens at ``position`` (tokens before)."""
    di, H, P, N, G = _ssm(m)
    per_tok = m["n_layers"] * (layer_matmul_flops_per_token(m) + 4 * H * P * N)
    if m["family"] == "hybrid":
        seen = min(position + 1, m["hybrid_attn_window"])
        per_tok += m["n_layers"] * 4 * m["head_dim"] * m["n_heads"] * seen
    return batch * (per_tok + 2.0 * m["d_model"] * m["vocab_size"])


def serve_request_flops(m: dict, batch: int, prompt_len: int,
                        decode_tokens: int) -> float:
    """A micro-batch: prefill (last position's logits) and the decode steps
    that produce the rest of its tokens."""
    f = forward_flops(m, batch, prompt_len, batch)
    f += sum(decode_flops(m, batch, prompt_len + t)
             for t in range(decode_tokens - 1))
    return f
