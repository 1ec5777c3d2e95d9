"""Data plane: what moves the payload bytes behind the scheduled collectives.

The control plane (``repro.core``) decides who talks to whom and what each
hop costs; this module decides *how* the bytes of each hop move. Two
backends implement the same four payload operations (see docs/dataplane.md):

  ``reduce(parts, op)``          the fold behind a reduce stage
  ``bcast_payload(p)``           the root payload's hop
  ``gather_arrays(vals)``        the result gather
  ``compress(g, scheme, f)``     the cross-legion compression round-trip

:class:`SimDataPlane` is the numpy simulator — bit-for-bit the sequential
fold the schedules ran before the seam existed, and the tests' reference.
:class:`JaxDataPlane` runs the same operations as device collectives over a
1-D mesh of ``jax.devices()`` (``shard_map`` with psum/pmax/pmin, a
binomial-tree ``ppermute`` sweep, a tiled ``all_gather``) and the
compression hop as Pallas kernels. Cases it cannot run exactly — an op
outside add/max/min, a dtype the backend would canonicalize (float64/int64
without x64), ragged or non-array payloads — fall back to the sim plane per
call, and every such call is counted in :attr:`JaxDataPlane.fallbacks`.

Live state registered with :meth:`DataPlane.register_state` is placed on the
survivors' ``("data", "model")`` mesh: on the jax plane at registration and
again after every repair (:meth:`JaxDataPlane.reshard_registered`, one
measured ``device_put`` pass reported as a :class:`ReshardReport`); on the
sim plane placement is virtual and both are no-ops.

This module never imports ``repro.core`` (the cluster imports it lazily).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import spans
from repro.dist.sharding import param_specs
from repro.kernels.ops import interpret_mode
from repro.kernels.quantize import absmax_pallas, quantize_int8_with_scale
from repro.optim import compression as C

PyTree = Any
Getter = Callable[[], PyTree]
Setter = Callable[[PyTree], None]

_AXIS = "nodes"

# numpy reductions the jax plane runs on device: (local fold, collective)
_REDUCERS: dict[Callable, tuple[Callable, Callable]] = {
    np.add: (lambda x: jnp.sum(x, axis=0, dtype=x.dtype), jax.lax.psum),
    np.maximum: (lambda x: jnp.max(x, axis=0), jax.lax.pmax),
    np.minimum: (lambda x: jnp.min(x, axis=0), jax.lax.pmin),
}


@dataclass(frozen=True)
class ReshardReport:
    """One post-repair redistribution pass on the jax plane."""
    names: tuple[str, ...]           # registered trees moved
    leaves: int
    n_devices: int                   # devices in the survivors' mesh
    moved_bytes: int
    wall_seconds: float              # legio.reshard: device_put + block_until_ready
    mesh_shape: tuple[int, int]      # ("data", "model")


class DataPlane:
    """Shared registry of live-state trees; backends add the payload ops."""

    name = "base"

    def __init__(self) -> None:
        self._state: dict[str, tuple[Getter, Setter | None]] = {}

    def register_state(self, name: str, getter: Getter,
                       setter: Setter | None = None, view=None) -> None:
        """Register a live-state tree for post-repair redistribution.
        ``view`` (the current topology) places it right away where the
        backend has real placement."""
        self._state[name] = (getter, setter)

    def reshard_registered(self, view) -> ReshardReport | None:
        return None


class SimDataPlane(DataPlane):
    """Numpy simulator: the sequential fold, identity hops, numpy twins."""

    name = "sim"

    def reduce(self, parts: list, op: Callable) -> Any:
        acc = parts[0]
        for p in parts[1:]:
            acc = op(acc, p)
        return acc

    def bcast_payload(self, payload: Any) -> Any:
        return payload

    def gather_arrays(self, vals: list) -> list:
        return list(vals)

    def compress(self, g: np.ndarray, scheme: str,
                 fraction: float) -> np.ndarray:
        if scheme == "int8":
            return C.decompress_int8_np(C.compress_int8_np(g))
        if scheme == "topk":
            return C.decompress_topk_np(C.compress_topk_np(g, fraction),
                                        np.shape(g))
        raise ValueError(f"unknown compression scheme {scheme!r}")


def _device_ready(vals: list) -> bool:
    """Uniform ndarrays of a dtype the backend keeps as it is."""
    if not vals or not all(isinstance(v, np.ndarray) for v in vals):
        return False
    dt, shape = vals[0].dtype, vals[0].shape
    if dt.kind not in "fiu" or jax.dtypes.canonicalize_dtype(dt) != dt:
        return False
    return all(v.dtype == dt and v.shape == shape for v in vals)


@functools.cache
def _fold_fn(mesh: Mesh, op: Callable):
    local, collective = _REDUCERS[op]

    def body(x):                        # (1, per_device, *shape)
        return collective(local(x[0]), _AXIS)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(_AXIS),
                                 out_specs=P()))


@functools.cache
def _bcast_fn(mesh: Mesh):
    ndev = mesh.size

    def body(x):                        # (1, *shape); row 0 holds the root
        idx = jax.lax.axis_index(_AXIS)
        span = 1
        while span < ndev:              # binomial tree: 1 -> 2 -> 4 ...
            perm = [(i, i + span) for i in range(span) if i + span < ndev]
            recv = jax.lax.ppermute(x, _AXIS, perm)
            x = jnp.where((idx >= span) & (idx < 2 * span), recv, x)
            span *= 2
        return x

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(_AXIS),
                                 out_specs=P(_AXIS)))


@functools.cache
def _gather_fn(mesh: Mesh):
    def body(x):
        return jax.lax.all_gather(x, _AXIS, tiled=True)

    # all_gather's result is the same on every device, which the
    # replication check cannot infer
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(_AXIS),
                                 out_specs=P(), check_vma=False))


@jax.jit
def _dequantize(q, scale):
    return q.astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnums=1)
def _topk_roundtrip(g, fraction: float):
    return C.decompress_topk(C.compress_topk(g, fraction), g.shape)


class JaxDataPlane(DataPlane):
    """Real device collectives over a 1-D mesh of ``devices``; logical node
    ``n`` lives on ``devices[n % len(devices)]``."""

    name = "jax"

    def __init__(self, devices: list | None = None) -> None:
        super().__init__()
        self.devices = list(devices if devices is not None else jax.devices())
        self.mesh = Mesh(np.array(self.devices), (_AXIS,))
        self.fallbacks = 0              # calls the sim plane had to run
        self._sim = SimDataPlane()

    def _fallback(self) -> SimDataPlane:
        self.fallbacks += 1
        return self._sim

    def _stacked(self, vals: list, fill: np.ndarray) -> jax.Array:
        """Spread ``vals`` over the mesh as (ndev, per_device, *shape),
        padding the last devices with ``fill``."""
        ndev = self.mesh.size
        per = -(-len(vals) // ndev)
        rows = list(vals) + [fill] * (per * ndev - len(vals))
        host = np.stack(rows).reshape(ndev, per, *fill.shape)
        return jax.device_put(host, NamedSharding(self.mesh, P(_AXIS)))

    # -- payload operations ---------------------------------------------------

    def reduce(self, parts: list, op: Callable) -> Any:
        if op not in _REDUCERS or not _device_ready(parts):
            return self._fallback().reduce(parts, op)
        # add pads with zeros; max/min with a copy (idempotent)
        fill = np.zeros_like(parts[0]) if op is np.add else parts[0]
        out = _fold_fn(self.mesh, op)(self._stacked(parts, fill))
        return np.asarray(out)

    def bcast_payload(self, payload: Any) -> Any:
        if not _device_ready([payload]):
            return self._fallback().bcast_payload(payload)
        host = np.zeros((self.mesh.size, *payload.shape), payload.dtype)
        host[0] = payload
        x = jax.device_put(host, NamedSharding(self.mesh, P(_AXIS)))
        out = _bcast_fn(self.mesh)(x)
        # the copy that reached the last device of the tree
        return np.asarray(out.addressable_shards[-1].data)[0]

    def gather_arrays(self, vals: list) -> list:
        if not _device_ready(list(vals)):
            return self._fallback().gather_arrays(vals)
        fill = np.zeros_like(vals[0])
        out = np.asarray(_gather_fn(self.mesh)(self._stacked(vals, fill)))
        flat = out.reshape(-1, *fill.shape)
        return [flat[i] for i in range(len(vals))]

    def compress(self, g: np.ndarray, scheme: str,
                 fraction: float) -> np.ndarray:
        gf = np.asarray(g, np.float32)
        if scheme == "int8":
            interpret = interpret_mode()
            am = np.float32(np.asarray(absmax_pallas(gf, interpret=interpret)))
            # the scale is computed on the host: see kernels/quantize.py
            scale = np.float32(np.maximum(am, np.float32(1e-12))
                               / np.float32(127.0))
            q = quantize_int8_with_scale(gf, scale, interpret=interpret)
            return np.asarray(_dequantize(q, scale))
        if scheme == "topk":
            return np.asarray(_topk_roundtrip(gf, fraction))
        raise ValueError(f"unknown compression scheme {scheme!r}")

    # -- placement --------------------------------------------------------------

    def mesh_for(self, view) -> Mesh:
        """``("data", "model")`` mesh over the devices of ``view``'s nodes,
        deduplicated under the wrap-around mapping."""
        devs: list = []
        for n in view.nodes:
            d = self.devices[n % len(self.devices)]
            if d not in devs:
                devs.append(d)
        return Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))

    def register_state(self, name: str, getter: Getter,
                       setter: Setter | None = None, view=None) -> None:
        super().register_state(name, getter, setter)
        if view is not None and setter is not None:
            self._place({name: (getter, setter)}, self.mesh_for(view))

    def _place(self, entries: dict, mesh: Mesh) -> tuple[int, int]:
        """device_put every tree per ``param_specs`` on ``mesh``; returns
        (leaves, bytes) moved."""
        moved: dict[str, PyTree] = {}
        for name, (getter, _) in entries.items():
            tree = getter()
            specs = param_specs(None, tree, mesh)
            shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                     is_leaf=lambda s: isinstance(s, P))
            moved[name] = jax.device_put(tree, shardings)
        jax.block_until_ready(moved)
        for name, tree in moved.items():
            entries[name][1](tree)
        leaves = jax.tree.leaves(moved)
        return len(leaves), sum(int(x.nbytes) for x in leaves)

    def reshard_registered(self, view) -> ReshardReport | None:
        entries = {n: e for n, e in self._state.items() if e[1] is not None}
        if not entries:
            return None
        mesh = self.mesh_for(view)
        with spans.span("legio.reshard", devices=mesh.size) as sp:
            leaves, nbytes = self._place(entries, mesh)
            sp.set(leaves=leaves, bytes=nbytes)
        return ReshardReport(names=tuple(entries), leaves=leaves,
                             n_devices=mesh.size, moved_bytes=nbytes,
                             wall_seconds=sp.seconds,
                             mesh_shape=tuple(mesh.devices.shape))


_DEFAULT = SimDataPlane()


def default_dataplane() -> SimDataPlane:
    """The shared sim plane used by collectives built without a cluster."""
    return _DEFAULT


def make_dataplane(policy) -> DataPlane:
    """Resolve ``policy.data_plane`` (sim | jax | auto) to a backend."""
    choice = policy.data_plane
    if choice == "sim":
        return SimDataPlane()
    if choice == "jax":
        return JaxDataPlane()
    if choice == "auto":
        return JaxDataPlane() if len(jax.devices()) > 1 else SimDataPlane()
    raise ValueError(f"unknown data_plane {choice!r}")
