"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs here: each case lowers and compiles one kernel for a chip of a
described (not attached) ``v5e:2x2`` topology with the TPU compiler that is
installed beside JAX, and checks that the kernel reached the program as a
Mosaic custom call. Interpret-mode tests (test_kernels.py) cannot see what
this catches: block shapes the TPU tiling refuses, scalar stores to VMEM,
more fast memory than a kernel may use.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import absmax_pallas, quantize_int8_with_scale
from repro.kernels.ssd_scan import ssd_scan_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plugin on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A device of the described topology, with the persistent compile
    cache off: a compile for a chip that is not attached is written to the
    cache but cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _ssd_scan_mamba2_130m(sds):
    cfg = get_config("mamba2-130m")
    B, S = 1, 1024
    H, P = cfg.ssm_nheads, cfg.ssm_head_dim          # 24, 64
    G, N = cfg.ssm_ngroups, cfg.ssm_state            # 1, 128
    fn = lambda x, dt, A, Bm, Cm: ssd_scan_pallas(  # noqa: E731
        x, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    args = (sds((B, S, H, P), jnp.bfloat16), sds((B, S, H), jnp.float32),
            sds((H,), jnp.float32), sds((B, S, G, N), jnp.bfloat16),
            sds((B, S, G, N), jnp.bfloat16))
    return fn, args


def _flash_attention_hymba(sds):
    cfg = get_config("hymba-1.5b")
    B, S = 1, 2048
    fn = lambda q, k, v: flash_attention_pallas(  # noqa: E731
        q, k, v, causal=True, window=cfg.hybrid_attn_window)
    args = (sds((B, S, cfg.n_heads, cfg.head_dim), jnp.bfloat16),
            sds((B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16),
            sds((B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16))
    return fn, args


def _absmax(sds):
    return absmax_pallas, (sds((1 << 20,), jnp.float32),)


def _quantize(sds):
    return quantize_int8_with_scale, (sds((1 << 20,), jnp.float32),
                                      sds((), jnp.float32))


@pytest.mark.parametrize("case", [_ssd_scan_mamba2_130m,
                                  _flash_attention_hymba, _absmax, _quantize],
                         ids=["ssd_scan", "flash_attention", "absmax",
                              "quantize"])
def test_kernel_compiles_for_v5e(case, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = case(sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
