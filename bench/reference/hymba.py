"""Hymba-style hybrid layer as the program runs it, in plain float32.

Each layer: h = norm(x); x += (windowed causal GQA attention(h) + Mamba-2
block(h)) / 2; x += SwiGLU(norm(x)). Rotary positions use the split-halves
rotation. The departures from the published model are listed in the
configuration file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import ssd
from bench.reference.numerics import Numerics, einsum, normal, rms_norm


def init_params(m: dict, key) -> dict:
    dtype = jnp.dtype(m["param_dtype"])
    D, F, L = m["d_model"], m["d_ff"], m["n_layers"]
    q_dim = m["n_heads"] * m["head_dim"]
    kv_dim = m["n_kv_heads"] * m["head_dim"]

    def layer(k):
        ks = jax.random.split(k, 8)
        return {
            "attn_norm": jnp.zeros((D,), dtype),
            "mlp_norm": jnp.zeros((D,), dtype),
            "attn": {"wq": normal(ks[0], (D, q_dim), D ** -0.5, dtype),
                     "wk": normal(ks[1], (D, kv_dim), D ** -0.5, dtype),
                     "wv": normal(ks[2], (D, kv_dim), D ** -0.5, dtype),
                     "wo": normal(ks[3], (q_dim, D),
                                  (q_dim * L) ** -0.5, dtype)},
            "mlp": {"w_in": normal(ks[4], (D, F), D ** -0.5, dtype),
                    "w_out": normal(ks[5], (F, D), (F * L) ** -0.5, dtype),
                    "w_gate": normal(ks[6], (D, F), D ** -0.5, dtype)},
            "ssm": ssd.init_block(m, ks[7], dtype),
        }

    k_embed, k_layers = jax.random.split(key)
    return {"embed": normal(k_embed, (m["vocab_size"], D), 0.02, dtype),
            "final_norm": jnp.zeros((D,), dtype),
            "layers": jax.vmap(layer)(jax.random.split(k_layers, L))}


def _rope(x, theta: float):
    """x (B,S,H,hd): rotate the pair (first half, second half) by
    position * theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq     # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(m: dict, p: dict, h, num: Numerics):
    Bsz, S, _ = h.shape
    H, K, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _rope(num.mm(h, p["wq"]).reshape(Bsz, S, H, hd), m["rope_theta"])
    k = _rope(num.mm(h, p["wk"]).reshape(Bsz, S, K, hd), m["rope_theta"])
    v = num.mm(h, p["wv"]).reshape(Bsz, S, K, hd)
    q = q.reshape(Bsz, S, K, H // K, hd) / jnp.sqrt(jnp.float32(hd))
    s = einsum("bqkrd,bskd->bkrqs", q, k)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    seen = (j <= i) & (j > i - m["hybrid_attn_window"])
    s = jnp.where(seen, s, -jnp.inf)
    o = einsum("bkrqs,bskd->bqkrd", jax.nn.softmax(s, axis=-1), v)
    return num.mm(o.reshape(Bsz, S, H * hd), p["wo"])


def hidden(m: dict, params: dict, tokens, num: Numerics):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def layer(x, lp):
        h = rms_norm(x, lp["attn_norm"], m["norm_eps"])
        x = x + 0.5 * (_attention(m, lp["attn"], h, num)
                       + ssd.block(m, lp["ssm"], h, num))
        h = rms_norm(x, lp["mlp_norm"], m["norm_eps"])
        mlp = lp["mlp"]
        y = jax.nn.silu(num.mm(h, mlp["w_gate"])) * num.mm(h, mlp["w_in"])
        return x + num.mm(y, mlp["w_out"]), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], m["norm_eps"])


def unembed(params: dict):
    return params["embed"]
