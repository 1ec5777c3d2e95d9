"""Fault agreement: the BNP fix (paper §IV) and the in-program bitmap reduce."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, strategies as st

from repro.core.agreement import (
    agree_bitmap_inprogram,
    agree_fault,
    agreement_rounds,
)
from repro.launch.mesh import make_named_mesh


@given(data=st.data())
def test_agreement_union_properties(data):
    n = data.draw(st.integers(2, 32))
    nodes = list(range(n))
    failed = set(data.draw(st.lists(st.sampled_from(nodes), max_size=n // 2)))
    live = [x for x in nodes if x not in failed]
    # each live observer sees an arbitrary subset of the failures
    observations = {
        obs: set(data.draw(st.lists(st.sampled_from(sorted(failed)))))
        if failed else set()
        for obs in live
    }
    verdict = agree_fault(observations, live)
    # verdict == union of live observations
    expected = set()
    for obs in live:
        expected |= observations[obs]
    assert verdict == expected
    # dead observers' claims are ignored
    observations[sorted(failed)[0] if failed else -1] = {0}
    assert agree_fault(observations, live) == expected


def test_agreement_resolves_bnp():
    """Partial noticing (some observers saw nothing) -> identical verdict."""
    live = [0, 1, 2, 3]
    obs = {0: {7}, 1: set(), 2: set(), 3: {7}}
    v = agree_fault(obs, live)
    assert v == {7}                              # everyone adopts {7}


def test_agreement_rounds_log():
    assert agreement_rounds(1) == 1
    assert agreement_rounds(2) == 1
    assert agreement_rounds(256) == 8


def test_liveness_psum_single_axis():
    mesh = make_named_mesh((1,), ("data",))
    bitmaps = jnp.array([[1, 0, 1, 1]], jnp.int32)
    out = agree_bitmap_inprogram(mesh, bitmaps)
    np.testing.assert_array_equal(out, [1, 0, 1, 1])


def test_bitmap_and_reduce_host():
    """Multiple shards, host fallback path: AND of all rows."""
    mesh = make_named_mesh((1,), ("x",))
    bitmaps = jnp.array([[1, 1, 0], [1, 0, 1]], jnp.int32)
    out = agree_bitmap_inprogram(mesh, bitmaps)
    np.testing.assert_array_equal(out, [1, 0, 0])
