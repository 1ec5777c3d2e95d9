"""Sharding rules: name-based parameter specs + activation constraints.

Axis conventions (shared with ``repro.launch.mesh``): meshes carry a
``model`` axis (tensor parallelism) plus one or more batch-parallel axes
(``data``, optionally a leading ``pod``). The rules here are *name-based*:
every weight matrix in the model trees follows the Megatron pattern —
input-side projections are column-parallel (``(..., D, F)`` sharded
``("data", "model")``: FSDP over the reduction dim, tensor-parallel over
the output dim), output-side projections are row-parallel
(``(..., F, D)`` sharded ``("model", "data")``), embeddings are
vocab-parallel, and norms/biases/SSM scalars stay replicated.

Every public helper degrades to a no-op outside a mesh context (the CPU
test/trainer path runs unsharded; only the dry-run and real launches open a
``with mesh:`` scope), and every spec is passed through
:func:`sanitize_spec` so a dimension that does not divide its mesh axes is
silently replicated instead of failing to lower — jit argument shardings
need exact divisibility (constraints would pad).
"""
from __future__ import annotations

import math
import warnings
from typing import Any

import jax
from jax.interpreters import pxla
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any

# Megatron-style classification by leaf name (see module docstring).
_IN_MATS = frozenset({"wq", "wk", "wv", "w_in", "w_gate", "in_proj",
                      "we_in", "we_gate"})
_OUT_MATS = frozenset({"wo", "w_out", "out_proj", "we_out"})
_EMBEDS = frozenset({"embed", "unembed"})


# ---------------------------------------------------------------------------
# mesh context
# ---------------------------------------------------------------------------

def current_mesh():
    """The ambient physical mesh (from ``with mesh:``), or None."""
    m = pxla.thread_resources.env.physical_mesh
    return None if m.empty else m


def _axis_product(mesh, entry) -> int:
    sizes = dict(mesh.shape)
    axes = entry if isinstance(entry, tuple) else (entry,)
    return math.prod(sizes[a] for a in axes)


# ---------------------------------------------------------------------------
# spec construction
# ---------------------------------------------------------------------------

# (param, dim, mesh axes) triples already warned about — replication is
# silent after the first occurrence so sweeps over many layers of the same
# shape do not flood the log
_replication_warned: set[tuple] = set()


def sanitize_spec(spec: P, shape: tuple[int, ...], mesh,
                  *, param: str | None = None) -> P:
    """Drop spec axes whose dim is not divisible by the mesh axes' product
    (jit argument shardings need exact divisibility); trim trailing Nones.

    Each dropped axis is reported once per (param, dim, axes) via
    ``warnings.warn`` — a silently replicated weight is a real capacity/
    throughput surprise and should be visible the first time it happens.
    """
    out: list = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(None)
            continue
        prod = _axis_product(mesh, entry)
        if shape[i] % prod == 0:
            out.append(entry)
        else:
            key = (param, i, entry)
            if key not in _replication_warned:
                _replication_warned.add(key)
                warnings.warn(
                    f"sanitize_spec: dim {i} of {param or 'array'} "
                    f"(size {shape[i]}) does not divide mesh axes "
                    f"{entry!r} (product {prod}); replicating that "
                    f"dimension instead of sharding it",
                    UserWarning, stacklevel=2)
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _batch_dim_axes(mesh, global_batch: int):
    """Mesh axes the batch dimension shards over: all non-model axes if the
    batch divides their product, dropping the leading (pod) axis first;
    None (replicated) when nothing divides."""
    names = [n for n in mesh.axis_names if n != "model"]
    sizes = dict(mesh.shape)
    while names:
        prod = math.prod(sizes[n] for n in names)
        if global_batch % prod == 0:
            return tuple(names) if len(names) > 1 else names[0]
        names.pop(0)
    return None


def _key_name(entry) -> str:
    if isinstance(entry, jax.tree_util.DictKey):
        return str(entry.key)
    if isinstance(entry, jax.tree_util.GetAttrKey):
        return entry.name
    if isinstance(entry, jax.tree_util.SequenceKey):
        return str(entry.idx)
    return str(entry)


def _param_rule(name: str, ndim: int) -> tuple:
    if name in _IN_MATS and ndim >= 2:
        return (None,) * (ndim - 2) + ("data", "model")
    if name in _OUT_MATS and ndim >= 2:
        return (None,) * (ndim - 2) + ("model", "data")
    if name in _EMBEDS:
        return ("model",)
    if name == "conv_w" and ndim >= 1:
        return (None,) * (ndim - 1) + ("model",)
    return ()


def param_specs(cfg, params: PyTree, mesh) -> PyTree:
    """PartitionSpec tree for a parameter (or optimizer-moment) tree."""
    del cfg  # rules are name-based; cfg kept for signature stability

    def leaf_spec(path, leaf):
        # a bare array (empty path) follows no rule: replicated
        rule = _param_rule(_key_name(path[-1]), len(leaf.shape)) if path else ()
        spec = P(*rule)
        name = ".".join(_key_name(e) for e in path)
        return sanitize_spec(spec, leaf.shape, mesh, param=name)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def batch_specs(cfg, mesh, batch: PyTree, global_batch: int) -> PyTree:
    """Batch arrays shard dim 0 over the non-model axes, rest replicated."""
    del cfg
    b = _batch_dim_axes(mesh, global_batch)

    def leaf_spec(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        return P(b, *((None,) * (nd - 1)))

    return jax.tree.map(leaf_spec, batch)


def cache_specs(cfg, mesh, cache: PyTree, global_batch: int) -> PyTree:
    """Decode-cache specs: (L, B, ...) leaves shard batch on dim 1; the KV
    head dim (3) is tensor-parallel — see models/attention.py docstring."""
    del cfg
    b = _batch_dim_axes(mesh, global_batch)

    def leaf_spec(path, leaf):
        nd = len(leaf.shape)
        if nd < 2:
            return P()
        name = _key_name(path[-1])
        if name in ("k", "v") and nd == 5:
            spec = P(None, b, None, "model", None)
        else:
            spec = P(None, b, *((None,) * (nd - 2)))
        name = ".".join(_key_name(e) for e in path)
        return sanitize_spec(spec, leaf.shape, mesh, param=name)

    return jax.tree_util.tree_map_with_path(leaf_spec, cache)


# ---------------------------------------------------------------------------
# in-model constraints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------

def _constrain(x: jax.Array, spec: P, mesh) -> jax.Array:
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, sanitize_spec(spec, x.shape, mesh)))


def shard_activations(x: jax.Array, mode: str = "batch") -> jax.Array:
    """Constrain an activation: dim 0 batch-parallel; under ``batch_seq``
    (sequence parallelism) dim 1 additionally shards over ``model``."""
    mesh = current_mesh()
    if mesh is None or mode == "none":
        return x
    b = _batch_dim_axes(mesh, x.shape[0])
    seq = "model" if (mode == "batch_seq" and x.ndim >= 3) else None
    return _constrain(x, P(b, seq, *((None,) * (x.ndim - 2))), mesh)


def shard_heads(x: jax.Array, mode: str = "batch", head_axis: int = 2) -> jax.Array:
    """Constrain a heads-major (or FFN-intermediate) tensor: dim 0
    batch-parallel, ``head_axis`` tensor-parallel over ``model``."""
    mesh = current_mesh()
    if mesh is None or mode == "none":
        return x
    spec: list = [None] * x.ndim
    spec[0] = _batch_dim_axes(mesh, x.shape[0])
    spec[head_axis] = "model"
    return _constrain(x, P(*spec), mesh)


def gather_fsdp(tree: PyTree, mode: str = "batch") -> PyTree:
    """Re-constrain a weight tree with the FSDP (``data``) axis removed —
    GSPMD emits the all-gather; tensor-parallel (``model``) axes stay."""
    mesh = current_mesh()
    if mesh is None or mode == "none":
        return tree

    def gather(path, leaf):
        rule = _param_rule(_key_name(path[-1]), leaf.ndim)
        spec = P(*[None if e == "data" else e for e in rule])
        return _constrain(leaf, spec, mesh)

    return jax.tree_util.tree_map_with_path(gather, tree)
