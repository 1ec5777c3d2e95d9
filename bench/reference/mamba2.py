"""Mamba-2 language model (attention-free) in plain float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import ssd
from bench.reference.numerics import Numerics, normal, rms_norm


def init_params(m: dict, key) -> dict:
    dtype = jnp.dtype(m["param_dtype"])
    k_embed, k_layers = jax.random.split(key)
    layers = jax.vmap(lambda k: {
        "norm": jnp.zeros((m["d_model"],), dtype),
        "ssm": ssd.init_block(m, k, dtype),
    })(jax.random.split(k_layers, m["n_layers"]))
    return {"embed": normal(k_embed, (m["vocab_size"], m["d_model"]), 0.02,
                            dtype),
            "final_norm": jnp.zeros((m["d_model"],), dtype),
            "layers": layers}


def hidden(m: dict, params: dict, tokens, num: Numerics):
    """tokens (B,S) -> final hidden states (B,S,D), float32; one layer at a
    time, each recomputed in the backward pass."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)

    @jax.checkpoint
    def layer(x, lp):
        h = rms_norm(x, lp["norm"], m["norm_eps"])
        return x + ssd.block(m, lp["ssm"], h, num), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], m["norm_eps"])


def unembed(params: dict):
    return params["embed"]
