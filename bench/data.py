"""Training data as the program's counter-based pipeline makes it.

A copy of ``repro.data.pipeline.make_batch``'s arithmetic, kept with the
benchmark so that the reference trains on the rows the program trained on
without importing the program. A shard's rows are a pure function of
(seed, step, shard).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_batch(seed: int, step: int, shard: int, *, batch: int, seq_len: int,
               vocab_size: int) -> dict:
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed),
                                                step), shard)
    k_start, k_noise, k_mask = jax.random.split(key, 3)
    V = vocab_size
    a = 2 * jax.random.randint(k_start, (batch, 1), 0, 2) + 1
    b = jax.random.randint(k_start, (batch, 1), 0, V)
    x0 = jax.random.randint(k_start, (batch, 1), 0, V)
    t = jnp.arange(seq_len + 1)[None, :]
    tokens = (x0 * jnp.power(a, t)
              + b * (jnp.power(a, t) - 1) // jnp.maximum(a - 1, 1)) % V
    noise = jax.random.randint(k_noise, tokens.shape, 0, V)
    keep = jax.random.uniform(k_mask, tokens.shape) < 0.9
    stream = jnp.where(keep, tokens, noise).astype(jnp.int32)
    return {"tokens": stream[:, :-1], "labels": stream[:, 1:]}


def global_batch(seed: int, step: int, shards: list[int], **kw) -> dict:
    parts = [make_batch(seed, step, s, **kw) for s in shards]
    return {k: jnp.concatenate([p[k] for p in parts], 0) for k in parts[0]}
