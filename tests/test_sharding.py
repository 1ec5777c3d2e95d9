"""Sharding rules and the loop-aware HLO cost analyzer."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import get_smoke_config
from repro.dist.sharding import (
    _batch_dim_axes,
    batch_specs,
    param_specs,
)
from repro.launch import hlo_stats
from repro.launch.mesh import make_named_mesh
from repro.models import api


def mesh_1():
    return make_named_mesh((1, 1), ("data", "model"))


def test_param_spec_rules(key):
    cfg = get_smoke_config("mixtral-8x22b")
    params = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, params, mesh_1())
    layers = specs["layers"]
    assert layers["attn"]["wq"] == P(None, "data", "model")
    assert layers["attn"]["wo"] == P(None, "model", "data")
    assert layers["moe"]["we_in"] == P(None, None, "data", "model")
    assert layers["moe"]["we_out"] == P(None, None, "model", "data")
    assert layers["attn_norm"] == P()                  # replicated (norms)
    assert specs["embed"] == P("model")                # vocab-parallel
    assert specs["final_norm"] == P()


def test_ssm_param_specs(key):
    cfg = get_smoke_config("mamba2-130m")
    params = jax.eval_shape(lambda: api.init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, params, mesh_1())
    ssm = specs["layers"]["ssm"]
    assert ssm["in_proj"] == P(None, "data", "model")
    assert ssm["out_proj"] == P(None, "model", "data")
    assert ssm["conv_w"] == P(None, None, "model")


def test_sanitize_spec_drops_nondivisible():
    """jit argument shardings need exact divisibility (constraints pad)."""
    from repro.dist.sharding import sanitize_spec
    mesh = AbstractMesh((16, 16), ("data", "model"))
    # kv-head dim 8 can't shard over model=16 -> dropped; batch 128 can
    s = sanitize_spec(P(None, "data", None, "model", None),
                      (56, 128, 4096, 8, 128), mesh)
    assert s == P(None, "data")          # trailing Nones trimmed
    # odd vocab (mamba2): model axis dropped on dim 0
    s2 = sanitize_spec(P("model", None), (50280, 768), mesh)
    assert s2 == P()
    # divisible: untouched
    s3 = sanitize_spec(P("model", None), (32768, 768), mesh)
    assert s3 == P("model")
    # tuple axes: product must divide
    mp = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    s4 = sanitize_spec(P(("pod", "data"), None), (64, 8), mp)
    assert s4 == P(("pod", "data"))
    s5 = sanitize_spec(P(("pod", "data"), None), (16, 8), mp)
    assert s5 == P()


def test_sanitize_spec_warns_once_per_replicated_dim():
    """Silently replicating a non-dividing dimension is a real capacity
    surprise: the first drop warns (naming param, dim and mesh axes); the
    same (param, dim, axes) never warns again."""
    import warnings
    from repro.dist import sharding
    from repro.dist.sharding import sanitize_spec
    mesh = AbstractMesh((4, 2), ("data", "model"))
    sharding._replication_warned.clear()
    with pytest.warns(UserWarning, match=r"dim 0 of blk\.wq.*'data'"):
        s = sanitize_spec(P("data", "model"), (7, 6), mesh, param="blk.wq")
    assert s == P(None, "model")
    # one-shot: an identical drop is silent
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sanitize_spec(P("data", "model"), (7, 6), mesh, param="blk.wq")
    # a different param still warns
    with pytest.warns(UserWarning, match="blk.wk"):
        sanitize_spec(P("data", "model"), (7, 6), mesh, param="blk.wk")
    # the anonymous path (in-model constraints) names "array"
    sharding._replication_warned.clear()
    with pytest.warns(UserWarning, match="array"):
        sanitize_spec(P("data"), (9,), mesh)
    sharding._replication_warned.clear()


def test_param_specs_warning_names_the_leaf():
    """The warning carries the dotted tree path of the offending leaf."""
    import warnings
    from repro.dist import sharding
    mesh = AbstractMesh((4, 2), ("data", "model"))
    params = {"layers": {"attn": {"wq": jnp.zeros((7, 6))}}}
    sharding._replication_warned.clear()
    with pytest.warns(UserWarning, match=r"layers\.attn\.wq"):
        specs = param_specs(None, params, mesh)
    assert specs["layers"]["attn"]["wq"] == P(None, "model")
    sharding._replication_warned.clear()


def test_batch_axes_divisibility():
    # AbstractMesh carries shape/axis_names without needing 2 real devices
    mesh = AbstractMesh((2, 1), ("data", "model"))
    assert _batch_dim_axes(mesh, 4) == "data"
    assert _batch_dim_axes(mesh, 1) is None            # long_500k: replicated
    assert _batch_dim_axes(mesh, 3) is None
    mp = AbstractMesh((2, 4, 1), ("pod", "data", "model"))
    assert _batch_dim_axes(mp, 16) == ("pod", "data")
    assert _batch_dim_axes(mp, 4) == "data"            # pod dropped first


def test_batch_specs_shapes():
    cfg = get_smoke_config("llama3.2-3b")
    mesh = mesh_1()
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    specs = batch_specs(cfg, mesh, batch, 8)
    assert specs["tokens"] == P("data", None)


# ---------------------------------------------------------------------------
# HLO cost analyzer
# ---------------------------------------------------------------------------

def test_analyzer_counts_scan_trips():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=9)
        return y

    x = jnp.ones((32, 32))
    c = jax.jit(f).lower(x, x).compile()
    cost = hlo_stats.analyze(c.as_text(), 1)
    assert cost.flops == pytest.approx(9 * 2 * 32 ** 3)


def test_analyzer_nested_and_unrolled_agree():
    def nested(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=4)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    def unrolled(x, w):
        for _ in range(12):
            x = x @ w
        return x

    x = jnp.ones((16, 16))
    cn = hlo_stats.analyze(jax.jit(nested).lower(x, x).compile().as_text(), 1)
    cu = hlo_stats.analyze(jax.jit(unrolled).lower(x, x).compile().as_text(), 1)
    assert cn.flops == pytest.approx(cu.flops)
    # XLA's own analysis undercounts the scan version 12x
    xla = jax.jit(nested).lower(x, x).compile().cost_analysis()
    if isinstance(xla, list):        # pre-0.5 jax returns one dict per device
        xla = xla[0]
    assert xla["flops"] * 11 < cn.flops


def test_analyzer_collective_wire_model():
    text = """
ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %ag = f32[1024]{0} all-gather(%p0), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={0}
  %ar = f32[64]{0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %out = f32[64]{0} add(%p0, %p0)
}
"""
    cost = hlo_stats.analyze(text, 16)
    # all-gather: operand 256B, wire = 15 * 256
    assert cost.coll.operand_bytes["all-gather"] == 256
    assert cost.coll.wire_bytes["all-gather"] == pytest.approx(15 * 256)
    # all-reduce over groups of 4: 2*(3/4) * 256
    assert cost.coll.wire_bytes["all-reduce"] == pytest.approx(2 * 0.75 * 256)


def test_roofline_terms_dominance():
    t = hlo_stats.roofline_terms(197e12, 0.0, 0.0)     # 1s of pure compute
    assert t["dominant"] == "compute_s"
    assert t["roofline_fraction"] == pytest.approx(1.0)
    t2 = hlo_stats.roofline_terms(197e11, 819e9, 0.0)  # memory-bound
    assert t2["dominant"] == "memory_s"
    assert t2["roofline_fraction"] == pytest.approx(0.1)


def test_model_flops_moe_uses_active():
    from repro.configs.base import SHAPES
    from repro.configs.registry import get_config
    moe = get_config("mixtral-8x22b")
    train = SHAPES["train_4k"]
    mf = hlo_stats.model_flops(moe, train)
    assert mf == pytest.approx(
        6.0 * moe.active_params() * train.global_batch * train.seq_len)
    assert moe.active_params() < 0.45 * moe.total_params()
