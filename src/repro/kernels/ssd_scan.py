"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (arXiv:2405.21060 §6).

TPU-native formulation: the grid is ``(batch, heads, n_chunks)`` with the
chunk dimension innermost — TPU cores execute the grid sequentially, so the
inter-chunk recurrent state lives in a VMEM scratch buffer ``(P, N)`` that
persists across the chunk sweep (initialized from ``h0`` at chunk 0, written
to the ``final_state`` output at the last chunk). Within a chunk the SSD is
evaluated in its quadratic "attention-like" form, which maps onto the MXU as
three matmuls per chunk:

    scores  = C  @ (B ⊙ dt)^T                (Q, Q)
    y_intra = (scores ⊙ L) @ x               (Q, P)
    y_inter = (C ⊙ exp(cum)) @ h^T           (Q, P)
    h_new   = exp(cum[-1]) · h  +  x^T @ (B ⊙ dt·decay_end)     (P, N)

with L the exponentiated segment-sum mask. All math fp32. The within-chunk
cumsum of the log-decays ``dt*A`` is taken by the wrapper and handed to the
kernel twice, as a (Q, 1) column and a (1, Q) row: TPU blocks need their
last two dims (8, 128)-aligned or equal to the array's, which rules out a
(Q,) vector per (batch, head).

GQA-style B/C groups are handled in the BlockSpec index maps (head ``h``
reads group ``h // (H // G)``) — no replication in HBM.

VMEM per grid step (defaults Q=256, P=64, N=128):
  x (Q,P) + B,C (Q,N) + cum,dt (Q,1)/(1,Q) + masks (Q,Q) f32 + state (P,N) f32
  ≈ 0.26 + 0.26 + 0.52 MB « 16 MB. Q is a multiple of 128 to align the
  (Q,Q) and (Q,P) matmuls with the 128x128 MXU systolic array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    # refs (post-BlockSpec)
    x_ref,      # (1, 1, Q, P)
    cc_ref,     # (1, 1, Q, 1)  within-chunk cumsum of log-decays, column
    cr_ref,     # (1, 1, 1, Q)  the same, row
    dt_ref,     # (1, 1, Q, 1)
    b_ref,      # (1, 1, Q, N)
    c_ref,      # (1, 1, Q, N)
    h0_ref,     # (1, 1, P, N)
    y_ref,      # out (1, 1, Q, P)
    hout_ref,   # out (1, 1, P, N)
    # scratch
    state_ref,  # VMEM (P, N) f32
    *,
    n_chunks: int,
    chunk: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    cum_c = cc_ref[0, 0]                         # (Q, 1)
    cum_r = cr_ref[0, 0]                         # (1, Q)
    dt = dt_ref[0, 0]                            # (Q, 1)
    Bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)
    h = state_ref[...]                           # (P, N)

    # L[i, j] = exp(cum[i] - cum[j]) for j <= i else 0
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    qj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(qj <= qi, jnp.exp(cum_c - cum_r), 0.0)   # (Q, Q)

    b_dt = Bm * dt                               # row j scaled by dt[j]
    scores = jax.lax.dot_general(
        Cm, b_dt, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (Q, Q)
    y_intra = jax.lax.dot_general(
        scores * L, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (Q, P)

    y_inter = jax.lax.dot_general(
        Cm * jnp.exp(cum_c), h, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # (Q, P)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # ---- state recurrence ----
    # cum[-1] as a (1, 1) reduction result: a lane slice of the row keeps
    # an offset layout that Mosaic cannot broadcast (exact: adds zeros)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    total = jnp.sum(jnp.where(last, cum_r, 0.0), axis=1, keepdims=True)
    upd = jax.lax.dot_general(
        x, b_dt * jnp.exp(total - cum_c), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)      # (P, N)
    state_ref[...] = h * jnp.exp(total) + upd

    @pl.when(ci == n_chunks - 1)
    def _finalize():
        hout_ref[0, 0] = state_ref[...]


def ssd_scan_pallas(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)  post-softplus timesteps
    A: jax.Array,      # (H,)       negative decay rates
    Bm: jax.Array,     # (B, S, G, N)
    Cm: jax.Array,     # (B, S, G, N)
    *,
    chunk: int = 256,
    initial_state: jax.Array | None = None,   # (B, H, P, N) f32
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P) in x.dtype, final_state (B,H,P,N) f32)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    assert H % G == 0, (H, G)
    rep = H // G
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    n_chunks = Sp // Q

    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    # log-decays dt*A, summed within each chunk (exact f32 scan here; the
    # kernel reads them as a column and as a row — TPU blocks keep their
    # last two dims (8, 128)-aligned or full, so each is its own operand)
    la = (dt * A[None, None, :]).astype(jnp.float32)      # (B, Sp, H)
    cum = jnp.cumsum(la.reshape(B, n_chunks, Q, H), axis=2)
    cum = cum.reshape(B, Sp, H).transpose(0, 2, 1)        # (B, H, Sp)
    cum_col = cum[..., None]                              # (B, H, Sp, 1)
    cum_row = cum[:, :, None, :]                          # (B, H, 1, Sp)
    dt_col = dt.astype(jnp.float32).transpose(0, 2, 1)[..., None]

    # kernel layout: time-major per (batch, head)
    xt = x.transpose(0, 2, 1, 3)                          # (B, H, Sp, P)
    Bt = Bm.transpose(0, 2, 1, 3)                         # (B, G, Sp, N)
    Ct = Cm.transpose(0, 2, 1, 3)

    grid = (B, H, n_chunks)
    kernel = functools.partial(_ssd_kernel, n_chunks=n_chunks, chunk=Q)
    col = pl.BlockSpec((1, 1, Q, 1), lambda b, h, c: (b, h, c, 0))
    y, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            col,
            pl.BlockSpec((1, 1, 1, Q), lambda b, h, c: (b, h, 0, c)),
            col,
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, cum_col, cum_row, dt_col, Bt, Ct, initial_state)

    y = y.transpose(0, 2, 1, 3)                           # (B, Sp, H, P)
    if pad:
        y = y[:, :S]
    return y, h_fin
