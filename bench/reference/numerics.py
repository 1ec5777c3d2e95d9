"""How the references take matrix products and norms."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _to_f8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one absmax scale per tensor, back to f32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


@jax.custom_vjp
def _mm_f8(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(_to_f8(x), _to_f8(w),
                      precision=jax.lax.Precision.HIGHEST)


def _mm_f8_fwd(x, w):
    return _mm_f8(x, w), (x, w)


def _mm_f8_bwd(res, g):
    """The backward products take float8 operands too, each with its own
    scale, as a float8 training step does."""
    x, w = (_to_f8(a) for a in res)
    g = _to_f8(g)
    hi = jax.lax.Precision.HIGHEST
    dx = jnp.matmul(g, w.T, precision=hi)
    dw = jnp.einsum("...k,...n->kn", x, g, precision=hi)
    return dx, dw


_mm_f8.defvjp(_mm_f8_fwd, _mm_f8_bwd)


@dataclass(frozen=True)
class Numerics:
    """``f32``: every product in float32 at HIGHEST precision.
    ``fp8``: the operands of every weight product, forward and backward,
    rounded to float8 first (the control: the precision below the
    configuration's bfloat16)."""

    kind: str = "f32"

    def mm(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """x (..., k) @ w (k, n), float32 result."""
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
        if self.kind == "fp8":
            return _mm_f8(x, w)
        return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def einsum(spec: str, *xs) -> jax.Array:
    return jnp.einsum(spec, *[x.astype(jnp.float32) for x in xs],
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x: jax.Array, gain_offset: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with the gain stored as an offset from 1."""
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + gain_offset.astype(jnp.float32))


def normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def uniform(key, shape, lo, hi, dtype=jnp.float32):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi).astype(dtype)
