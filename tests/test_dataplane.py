"""Data-plane seam: sim/jax backend parity under the simulated control plane.

The pinned contract (src/repro/dist/dataplane.py): schedules, stage lists
and clock charges never depend on the backend, and for integer-exact
payloads the *results* are byte-identical too — across an entire seeded
fault campaign (shrink, substitute, background overlap). The reshard test
runs in a subprocess with 8 forced host devices (the XLA flag must be set
before jax imports; conftest already imported jax), so placement is
exercised on a real multi-device mesh regardless of the host. The CI
data-plane step additionally runs this whole file under 8 forced devices.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import FaultInjector
from repro.core.executor import VirtualCluster
from repro.core.policy import LegioPolicy
from repro.dist.dataplane import (
    JaxDataPlane,
    SimDataPlane,
    default_dataplane,
    make_dataplane,
)
from repro.mpi import Session

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# selection — policy knob -> backend
# ---------------------------------------------------------------------------

def test_policy_validates_data_plane():
    with pytest.raises(ValueError, match="data_plane"):
        LegioPolicy(data_plane="cuda")
    assert LegioPolicy().data_plane == "sim"


def test_make_dataplane_resolution():
    import jax
    assert isinstance(make_dataplane(LegioPolicy(data_plane="sim")),
                      SimDataPlane)
    # explicit "jax" is honored at any device count
    assert isinstance(make_dataplane(LegioPolicy(data_plane="jax")),
                      JaxDataPlane)
    auto = make_dataplane(LegioPolicy(data_plane="auto"))
    expect = JaxDataPlane if len(jax.devices()) > 1 else SimDataPlane
    assert isinstance(auto, expect)
    # default plane is the shared sim singleton (collectives built without
    # a cluster behave exactly as before the seam existed)
    assert default_dataplane() is default_dataplane()
    assert default_dataplane().name == "sim"


def test_session_surfaces_data_plane_name():
    sess = Session(4, policy=LegioPolicy(data_plane="sim"))
    assert sess.data_plane == "sim"


# ---------------------------------------------------------------------------
# plane-level parity (any device count; real motion under the CI 8-dev step)
# ---------------------------------------------------------------------------

def _integer_exact(shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(-50, 50, size=shape).astype(np.float32)


def test_reduce_parity_integer_exact():
    sim, jx = SimDataPlane(), JaxDataPlane()
    parts = [_integer_exact((33,), s) for s in range(5)]
    for op in (np.add, np.maximum, np.minimum):
        a = sim.reduce([p.copy() for p in parts], op)
        b = jx.reduce([p.copy() for p in parts], op)
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_reduce_unsupported_falls_back_to_sim():
    jx = JaxDataPlane()
    parts = [np.arange(4, dtype=np.float64), np.ones(4)]  # x64: canonicalized
    out = jx.reduce(parts, np.add)
    assert out.dtype == np.float64
    np.testing.assert_array_equal(out, np.arange(4) + 1.0)
    # unknown op: sim fold
    out2 = jx.reduce([np.ones(3, np.float32)] * 2, np.subtract)
    np.testing.assert_array_equal(out2, np.zeros(3))
    # each fallback is counted; a call that runs on device is not
    assert jx.fallbacks == 2
    jx.reduce([np.ones(3, np.float32)] * 2, np.add)
    assert jx.fallbacks == 2


def test_bcast_and_gather_bit_roundtrip():
    jx = JaxDataPlane()
    payload = np.random.default_rng(1).normal(size=17).astype(np.float32)
    out = jx.bcast_payload(payload)
    assert out.tobytes() == payload.tobytes()
    vals = [_integer_exact((6,), s) for s in range(3)]
    back = jx.gather_arrays(vals)
    assert len(back) == 3
    for a, b in zip(vals, back):
        assert a.tobytes() == np.asarray(b).tobytes()


@pytest.mark.parametrize("scheme", ["int8", "topk"])
def test_compress_parity_bitwise(scheme):
    """Arbitrary (non-integer) f32: the compression hop is byte-identical
    across backends — host-computed scale, IEEE-exact elementwise ops,
    stable top-k tie-breaking (see kernels/quantize.py)."""
    sim, jx = SimDataPlane(), JaxDataPlane()
    for shape, seed in [((4,), 0), ((130,), 1), ((64, 257), 2), ((1000,), 3)]:
        g = np.random.default_rng(seed).normal(size=shape).astype(np.float32)
        a = sim.compress(g, scheme, 0.05)
        b = jx.compress(g, scheme, 0.05)
        assert a.shape == b.shape
        assert a.tobytes() == b.tobytes(), f"{scheme} {shape}"


# ---------------------------------------------------------------------------
# campaign parity: the full facade loop, faults and all
# ---------------------------------------------------------------------------

def _campaign_pair(policy_kwargs, faults, n=16):
    def mk(plane):
        return Session(
            n, policy=LegioPolicy(legion_size=4, data_plane=plane,
                                  **policy_kwargs),
            injector=FaultInjector.at(list(faults)))
    return mk("sim"), mk("jax")


def _assert_result_parity(res_s, res_j, ctx):
    assert res_s.stages == res_j.stages, f"{ctx}: stage lists diverged"
    assert res_s.sim_seconds == res_j.sim_seconds, f"{ctx}: clock diverged"
    assert set(res_s.data) == set(res_j.data), f"{ctx}: membership diverged"
    for node in res_s.data:
        a, b = np.asarray(res_s.data[node]), np.asarray(res_j.data[node])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), \
            f"{ctx}: node {node} diverged"


@pytest.mark.parametrize("mode_kwargs", [
    {"recovery_mode": "shrink"},
    {"recovery_mode": "substitute", "spare_nodes": 2},
    {"recovery_mode": "shrink", "repair_overlap": True},
], ids=["shrink", "substitute", "overlap"])
def test_fault_campaign_parity(mode_kwargs):
    """Byte-identical allreduce/bcast/reduce results and identical stage
    lists between backends at every step of a seeded campaign that kills a
    legion master and a member mid-flight."""
    faults = [(2, 9), (4, 0)]
    sess_s, sess_j = _campaign_pair(mode_kwargs, faults)
    for step in range(7):
        sess_s.advance(step)
        sess_j.advance(step)
        assert sess_s.cluster.topo.nodes == sess_j.cluster.topo.nodes, \
            f"step {step}: topologies diverged"
        comm_s, comm_j = sess_s.world, sess_j.world
        def contrib(sess):
            return {m: (np.arange(8, dtype=np.float32) % 5.0) * (m + 1)
                    for m in sess.world.members
                    if m not in sess.cluster.failed}
        _assert_result_parity(comm_s.allreduce(contrib(sess_s)),
                              comm_j.allreduce(contrib(sess_j)),
                              f"step {step} allreduce")
        root = sorted(comm_s.members)[0]
        payload = np.arange(16, dtype=np.float32) - 3.0
        _assert_result_parity(comm_s.bcast(payload, root=root),
                              comm_j.bcast(payload, root=root),
                              f"step {step} bcast")
        _assert_result_parity(comm_s.reduce(contrib(sess_s), root=root),
                              comm_j.reduce(contrib(sess_j), root=root),
                              f"step {step} reduce")
    # the campaign actually exercised repair on both sides
    assert sess_s.world.stats.repair_rounds >= 2
    assert sess_j.world.stats.repair_rounds >= 2


def test_compressed_campaign_parity_topk():
    """The top-k cross hop stays byte-identical across a fault campaign:
    decompressed top-k values are the original (integer-exact) partials, so
    every downstream sum stays exact too. Equal stage lists => the wire-byte
    accounting (control plane) is identical by construction."""
    sess_s, sess_j = _campaign_pair(
        {"grad_compression": "topk"}, [(2, 5)])
    g = (np.arange(32, dtype=np.float32) % 11.0) - 5.0
    for step in range(5):
        sess_s.advance(step)
        sess_j.advance(step)
        def contrib(sess):
            return {m: g * np.float32(m % 3 + 1)
                    for m in sess.world.members
                    if m not in sess.cluster.failed}
        _assert_result_parity(sess_s.world.allreduce(contrib(sess_s)),
                              sess_j.world.allreduce(contrib(sess_j)),
                              f"step {step} topk allreduce")


def test_compressed_campaign_int8_accounting_parity():
    """int8: the hop itself is bitwise across backends (pinned above), but
    summing the *decompressed* (non-integer) partials may legally differ by
    1 ulp between a vectorized and a sequential fold — so the campaign pins
    identical stage lists/clock charges (the accounting) plus tight
    numerical agreement, not payload bytes."""
    sess_s, sess_j = _campaign_pair(
        {"grad_compression": "int8"}, [(2, 5)])
    g = np.random.default_rng(7).normal(size=32).astype(np.float32)
    for step in range(5):
        sess_s.advance(step)
        sess_j.advance(step)
        def contrib(sess):
            return {m: g * np.float32(m % 3 + 1)
                    for m in sess.world.members
                    if m not in sess.cluster.failed}
        res_s = sess_s.world.allreduce(contrib(sess_s))
        res_j = sess_j.world.allreduce(contrib(sess_j))
        assert res_s.stages == res_j.stages
        assert res_s.sim_seconds == res_j.sim_seconds
        assert set(res_s.data) == set(res_j.data)
        for node in res_s.data:
            np.testing.assert_allclose(res_s.data[node], res_j.data[node],
                                       rtol=1e-6, atol=1e-5)


def test_gather_rides_the_dataplane():
    sess = Session(4, policy=LegioPolicy(data_plane="jax"))
    sess.advance(0)
    vals = {m: _integer_exact((5,), m) for m in sess.world.members}
    out = sess.world.gather(vals)
    assert set(out) == set(vals)
    for m, v in vals.items():
        assert np.asarray(out[m]).tobytes() == v.tobytes()
    # mixed payloads stay host-side untouched
    mixed = {0: np.ones(2), 1: "text", 2: np.ones(3)}
    out2 = sess.world.gather(mixed)
    assert out2[1] == "text"


# ---------------------------------------------------------------------------
# fault-driven resharding: mesh shrink + param_specs placement
# ---------------------------------------------------------------------------

_RESHARD_SCRIPT = r"""
import numpy as np
import jax
assert len(jax.devices()) == 8, jax.devices()
from jax.sharding import NamedSharding
from repro.core import FaultInjector
from repro.core.policy import LegioPolicy
from repro.dist.sharding import param_specs
from repro.mpi import Session

sess = Session(8, policy=LegioPolicy(legion_size=4, data_plane="jax"),
               injector=FaultInjector.at([(1, 3)]))
cl = sess.cluster
state = {
    "wq": jax.numpy.ones((8, 16), jax.numpy.float32),    # ("data","model")
    "bias": jax.numpy.zeros((16,), jax.numpy.float32),   # replicated
}
holder = {"state": state}
sess.register_sharded_state("params", lambda: holder["state"],
                            lambda s: holder.update(state=s))
t0 = cl.clock.sim_seconds
for step in range(3):
    sess.advance(step)
    sess.world.allreduce({m: np.ones(4, np.float32)
                          for m in sess.world.members
                          if m not in cl.failed})
assert 3 not in cl.topo.nodes                       # the shrink landed
assert cl.reshards, "no ReshardReport logged after repair"
rep = cl.reshards[-1]
assert rep.n_devices == 7, rep                      # 8 devices - 1 dead
assert rep.mesh_shape == (7, 1), rep
assert rep.wall_seconds > 0.0
assert cl.clock.sim_seconds > t0                    # measured charge landed
# every surviving leaf sits exactly where param_specs places it
mesh = cl.dataplane.mesh_for(cl.topo.view())
specs = param_specs(None, holder["state"], mesh)
for name, leaf in holder["state"].items():
    want = NamedSharding(mesh, specs[name])
    assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (
        name, leaf.sharding, want)
print("RESHARD_OK")
"""


def test_reshard_after_shrink_places_leaves_on_survivors():
    """Subprocess with 8 forced host devices: a mid-campaign node death
    rebuilds the mesh from the 7 survivors, re-places every registered leaf
    per param_specs, and charges the measured wall time to the clock."""
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"))
    proc = subprocess.run([sys.executable, "-c", _RESHARD_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=str(REPO))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RESHARD_OK" in proc.stdout


def test_sim_plane_reshard_is_free():
    cl = VirtualCluster(4, policy=LegioPolicy(data_plane="sim"))
    cl.register_sharded_state("x", lambda: {"a": np.ones(3)})
    assert cl.dataplane.reshard_registered(cl.topo.view()) is None
    assert cl.reshards == []


# ---------------------------------------------------------------------------
# transparency: no consumer reaches around the seam
# ---------------------------------------------------------------------------

def test_consumers_never_import_dataplane_directly():
    """serve/, launch/ and examples/ select backends only via
    LegioPolicy.data_plane — grep-clean transparency."""
    roots = [REPO / "src" / "repro" / "serve",
             REPO / "src" / "repro" / "launch",
             REPO / "examples"]
    offenders = []
    for root in roots:
        for path in root.rglob("*.py"):
            text = path.read_text()
            if "dist.dataplane" in text or "DataPlane" in text:
                offenders.append(str(path.relative_to(REPO)))
    assert not offenders, f"consumers import the data plane: {offenders}"
