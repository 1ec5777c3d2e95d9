"""Served tokens against the reference's logits.

For each sampled request the reference runs once over the prompt followed
by the served tokens (but the last). At each served position it reads the
gap by which the served token's logit lies below the reference's best; the
reading is the widest gap. Greedy decoding that agrees with the reference
reads 0, and a near tie read the other way reads a little above it. The
control reads, at the same positions, the gap of the token that a float8
forward puts first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.numerics import Numerics


def ref_chunk(seq_len: int) -> int:
    for q in (256, 128, 64, 32, 16, 8):
        if seq_len % q == 0:
            return q
    raise ValueError(f"no SSD chunk divides {seq_len}")


def served_gaps(model, m: dict, params, prompts: np.ndarray,
                served: np.ndarray, controls: tuple[str, ...] = ()) -> dict:
    """prompts (k, P), served (k, T) -> {"program": gap, <control>: gap}."""
    k, P = prompts.shape
    T = served.shape[1]
    seq = np.concatenate([prompts, served], axis=1)   # the last token is
    # never an input to a compared position: causal, so it only pads
    m = dict(m, ref_chunk=ref_chunk(seq.shape[1]))

    def logits_at(num):
        @jax.jit
        def f(params, seq):
            h = model.hidden(m, params, seq, num)[:, P - 1:P - 1 + T]
            return num.mm(h, model.unembed(params).T)
        return f

    out = {}
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(logits_at(Numerics("f32"))(params, jnp.asarray(seq)))
        best = ref.max(-1)
        took = np.take_along_axis(ref, served[..., None], -1)[..., 0]
        out["program"] = float(np.max(best - took))
        for c in controls:
            alt = np.asarray(logits_at(Numerics(c))(params, jnp.asarray(seq)))
            pick = alt.argmax(-1)
            took = np.take_along_axis(ref, pick[..., None], -1)[..., 0]
            out[c] = float(np.max(best - took))
    return out
