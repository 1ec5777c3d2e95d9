"""Pallas TPU kernel for int8 absmax quantization (the compression hop).

The cross-legion hop of a compressed all-reduce quantizes one master's
error-fed partial to int8 before it rides the slow links
(optim/compression.py). On device that is two passes over the flattened
tensor, both expressed as a Pallas grid over ``(block_rows, 128)`` tiles:

  1. ``absmax`` — a running per-lane max of |x| accumulated across grid
     steps into a (1, 128) output block, reduced to a scalar after the
     kernel. TPU cores execute the grid sequentially, so the same output
     block is a legal cross-step accumulator (the SSD scan's VMEM-state
     idiom applied to a reduction); a lane-wide block, because Mosaic
     cannot store a scalar to VMEM. Max is exact, so the layout cannot
     change the result.
  2. ``quantize`` — elementwise ``clip(round(x / scale), -127, 127)`` into
     an int8 tile, with the (1, 1) scale block broadcast to every step.

The two passes are exposed separately (:func:`absmax_pallas`,
:func:`quantize_int8_with_scale`) because the data plane computes the scale
``max(absmax, 1e-12) / 127`` on the host: under jit XLA rewrites division
by the constant 127 into multiplication by its reciprocal (1 ulp off true
division), so an in-graph scale cannot be bitwise-reproduced by the numpy
sim backend. With the scale as runtime data, every remaining op
(max / divide / round-half-even / clip) is IEEE-exact and the jax and sim
data planes produce byte-identical compression — a pinned test invariant.
:func:`quantize_int8_pallas` composes both passes in one jit for callers
that do not need cross-backend bit parity.

Tiles: f32 inputs want (8, 128) multiples, int8 outputs (32, 128) — the
default ``block_rows=256`` satisfies both; inputs are zero-padded up to a
whole grid (zeros never raise an absmax).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.optim.compression import Int8Grad

_LANES = 128


def _absmax_kernel(x_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] = jnp.maximum(
        out_ref[...], jnp.max(jnp.abs(x_ref[...]), axis=0, keepdims=True))


def _quantize_kernel(x_ref, scale_ref, q_ref):
    s = scale_ref[0, 0]
    q_ref[...] = jnp.clip(jnp.round(x_ref[...] / s), -127, 127
                          ).astype(jnp.int8)


def _padded(g: jax.Array, block_rows: int) -> jax.Array:
    """Flatten to a zero-padded (rows, 128) f32 grid, rows a multiple of
    ``block_rows``."""
    gf = g.astype(jnp.float32)
    n = gf.size
    rows = -(-max(n, 1) // _LANES)
    rows_p = -(-rows // block_rows) * block_rows
    flat = jnp.zeros((rows_p * _LANES,), jnp.float32).at[:n].set(
        gf.reshape(-1))
    return flat.reshape(rows_p, _LANES)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def absmax_pallas(g: jax.Array, *, block_rows: int = 256,
                  interpret: bool = False) -> jax.Array:
    """``max(|g|)`` as a () f32 — pass 1 of the quantization."""
    x = _padded(g, block_rows)
    out = pl.pallas_call(
        _absmax_kernel,
        grid=(x.shape[0] // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, _LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, _LANES), jnp.float32),
        interpret=interpret,
    )(x)
    return jnp.max(out)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_int8_with_scale(g: jax.Array, scale: jax.Array, *,
                             block_rows: int = 256,
                             interpret: bool = False) -> jax.Array:
    """``clip(round(g / scale), -127, 127)`` as int8, shaped like ``g`` —
    pass 2, with the scale as runtime data (see module docstring)."""
    x = _padded(g, block_rows)
    q = pl.pallas_call(
        _quantize_kernel,
        grid=(x.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], _LANES), jnp.int8),
        interpret=interpret,
    )(x, scale.astype(jnp.float32).reshape(1, 1))
    return q.reshape(-1)[:g.size].reshape(g.shape)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize_int8_pallas(g: jax.Array, *, block_rows: int = 256,
                         interpret: bool = False) -> Int8Grad:
    """Absmax-quantize ``g`` to int8: returns ``Int8Grad(q, scale)`` with
    ``q`` shaped like ``g`` and ``scale = max(absmax, 1e-12) / 127``."""
    if g.size == 0:
        return Int8Grad(q=g.astype(jnp.int8), scale=jnp.float32(1e-12) / 127.0)
    am = absmax_pallas(g, block_rows=block_rows, interpret=interpret)
    scale = jnp.maximum(am, 1e-12) / 127.0
    q = quantize_int8_with_scale(g, scale, block_rows=block_rows,
                                 interpret=interpret)
    return Int8Grad(q=q, scale=scale)
