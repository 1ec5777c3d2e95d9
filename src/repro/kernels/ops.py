"""Jitted public wrappers for the Pallas kernels.

On the CPU backend (the test path) the kernels execute in
``interpret=True`` mode — the kernel body runs op-by-op, which validates
indexing, masking and the online-softmax/recurrence algebra exactly as the
TPU grid would sequence them. On TPU the same call sites lower to Mosaic.
Any other backend is an error: a kernel never silently interprets there.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import quantize_int8_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def interpret_mode() -> bool:
    """True on the CPU backend, False on TPU; raises on any other."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run on TPU (or interpreted on CPU), not on "
            f"backend {backend!r}")
    return backend == "cpu"


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_softcap", "q_offset",
                     "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    logit_softcap: float = 0.0, q_offset: int = 0,
                    block_q: int = 512, block_k: int = 1024):
    """(B,Sq,H,hd) x (B,Sk,K,hd)² -> (B,Sq,H,hd); GQA via BlockSpec reuse."""
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
        q_offset=q_offset, block_q=min(block_q, q.shape[1]),
        block_k=min(block_k, k.shape[1]), interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 256, initial_state=None):
    """Chunked SSD scan; returns (y (B,S,H,P), final_state (B,H,P,N) f32)."""
    return ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk,
                           initial_state=initial_state,
                           interpret=interpret_mode())


@jax.jit
def quantize_int8(g):
    """Int8 absmax quantization (the compression hop); returns Int8Grad."""
    return quantize_int8_pallas(g, interpret=interpret_mode())
