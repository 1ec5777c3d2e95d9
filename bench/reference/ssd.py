"""Mamba-2 block in plain float32 (arXiv:2405.21060, section 6).

The SSD recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t
is evaluated exactly in chunks: inside a chunk as the masked quadratic form,
across chunks as a scan over the carried (H, P, N) state.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference.numerics import Numerics, einsum, normal, rms_norm, uniform


def dims(m: dict) -> tuple[int, int, int, int, int, int]:
    di = m["ssm_expand"] * m["d_model"]
    P = m["ssm_head_dim"]
    return di, di // P, P, m["ssm_state"], m["ssm_ngroups"], m["conv_kernel"]


def init_block(m: dict, key, dtype) -> dict:
    D = m["d_model"]
    di, H, P, N, G, K = dims(m)
    conv_ch = di + 2 * G * N
    ks = jax.random.split(key, 5)
    bound = 1.0 / math.sqrt(K)
    dt = jnp.exp(uniform(ks[3], (H,), math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "in_proj": normal(ks[0], (D, 2 * di + 2 * G * N + H), D ** -0.5, dtype),
        "conv_w": uniform(ks[1], (K, conv_ch), -bound, bound, dtype),
        "conv_b": uniform(ks[2], (conv_ch,), -bound, bound, dtype),
        "A_log": jnp.log(uniform(ks[4], (H,), 1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "D": jnp.ones((H,), jnp.float32),
        "ssd_norm": jnp.zeros((di,), dtype),
        "out_proj": normal(jax.random.fold_in(key, 7), (di, D), di ** -0.5,
                           dtype),
    }


def ssd(x, dt, A, Bm, Cm, chunk: int):
    """x (B,S,H,P), dt (B,S,H), A (H,), B/C (B,S,G,N) -> y (B,S,H,P)."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Q = chunk
    n = S // Q
    heads = jnp.arange(H) // (H // G)
    Bh = Bm[:, :, heads, :].reshape(Bsz, n, Q, H, N)
    Ch = Cm[:, :, heads, :].reshape(Bsz, n, Q, H, N)
    xc = x.reshape(Bsz, n, Q, H, P)
    dtc = dt.reshape(Bsz, n, Q, H)
    la = dtc * A                                     # log decay per step
    cum = jnp.cumsum(la, axis=2)                     # (B,n,Q,H)
    # decay from step j to step i inside a chunk, i >= j
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # (B,n,i,j,H)
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    cb = einsum("bnihs,bnjhs->bnijh", Ch, Bh)
    y_in = einsum("bnijh,bnjh,bnjhp->bnihp", cb * decay, dtc, xc)
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)        # (B,n,Q,H)
    states = einsum("bnjh,bnjhs,bnjhp->bnhps", to_end * dtc, Bh, xc)

    def carry(h, inp):
        s, d = inp
        return h * d[:, :, None, None] + s, h

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    _, prev = jax.lax.scan(carry, h0, (states.swapaxes(0, 1),
                                       jnp.exp(cum[:, :, -1, :]).swapaxes(0, 1)))
    prev = prev.swapaxes(0, 1)                       # state entering each chunk
    y_out = einsum("bnihs,bnhps,bnih->bnihp", Ch, prev, jnp.exp(cum))
    return (y_in + y_out).reshape(Bsz, S, H, P)


def block(m: dict, p: dict, h, num: Numerics):
    """h (B,S,D) float32 -> (B,S,D): in_proj, causal conv, SSD, gated norm,
    out_proj."""
    Bsz, S, _ = h.shape
    di, H, P, N, G, K = dims(m)
    zxbcdt = num.mm(h, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    pad = jnp.concatenate([jnp.zeros((Bsz, K - 1, xbc.shape[-1])), xbc], 1)
    w = p["conv_w"].astype(jnp.float32)
    conv = sum(pad[:, i:i + S, :] * w[i] for i in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    xs = xs.reshape(Bsz, S, H, P)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y = ssd(xs, dt, A, Bm.reshape(Bsz, S, G, N), Cm.reshape(Bsz, S, G, N),
            m["ref_chunk"])
    y = y + xs * p["D"][None, None, :, None]
    y = rms_norm(y.reshape(Bsz, S, di) * jax.nn.silu(z), p["ssd_norm"],
                 m["norm_eps"])
    return num.mm(y, p["out_proj"])
