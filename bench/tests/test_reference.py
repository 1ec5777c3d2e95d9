"""The references against the program's own models, at a small size on the
CPU: the same weights give the same logits, so the reference means what the
program computes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import hymba, mamba2
from bench.reference.numerics import Numerics
from bench.tests import tiny


@pytest.mark.parametrize("arch,ref", [("mamba2-130m", mamba2),
                                      ("hymba-1.5b", hymba)])
def test_reference_matches_program_forward(arch, ref):
    from repro.configs.registry import get_smoke_config
    from repro.models import api
    cfg = get_smoke_config(arch).replace(dtype="float32")
    m = dict(tiny.model_of(arch), ref_chunk=16)
    params = jax.jit(functools.partial(ref.init_params, m))(
        jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 48), 0,
                                m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(m, params, tokens, Numerics("f32"))
        want = np.asarray(h[:, -1] @ params["embed"].T.astype(jnp.float32))
        got, _ = api.prefill(cfg, params, tokens, 48)
    got = np.asarray(got[:, -1])
    assert np.max(np.abs(got - want)) <= 1e-3 * np.max(np.abs(want))


def test_reference_window_matches_program_decode():
    """Past the attention window, the program's ring-buffer decode and the
    reference's windowed mask agree."""
    from repro.configs.registry import get_smoke_config
    from repro.models import api
    arch = "hymba-1.5b"
    cfg = get_smoke_config(arch).replace(dtype="float32")
    m = dict(tiny.model_of(arch), ref_chunk=8)
    params = jax.jit(functools.partial(hymba.init_params, m))(
        jax.random.PRNGKey(5))
    W = m["hybrid_attn_window"]
    seq = jax.random.randint(jax.random.PRNGKey(6), (1, W + 8), 0,
                             m["vocab_size"])
    with jax.default_matmul_precision("highest"):
        logits, cache = api.prefill(cfg, params, seq[:, :W], W + 8)
        for t in range(W, W + 8):
            logits, cache = api.decode_step(cfg, params, cache, seq[:, t:t + 1])
        h = hymba.hidden(m, params, seq, Numerics("f32"))
        want = np.asarray(h[:, -1] @ params["embed"].T.astype(jnp.float32))
    got = np.asarray(logits[:, -1])
    assert np.max(np.abs(got - want)) <= 1e-3 * np.max(np.abs(want))
