"""Fault agreement — the BNP fix (paper §IV) and ULFM ``MPIX_Comm_agree``.

After a collective on a faulty communicator only *some* survivors hold a
PROC_FAILED verdict (the Broadcast Notification Problem, P.3). Legio runs an
agreement that "combines the results obtained by all the processes into a
single one equal for all". Two implementations:

  * :func:`agree_fault` — runtime-level: union of per-observer suspicion
    sets; all survivors adopt the union (what the repair path consumes).
  * :func:`liveness_psum` / :func:`agree_bitmap_inprogram` — in-program:
    a liveness bitmap AND-reduce expressed as a ``shard_map`` ``psum`` so the
    verdict is computed *inside* the jitted step with zero extra host round
    trips (one (n_nodes,) int32 all-reduce riding the gradient reduction).

The agreement itself must tolerate faults (ULFM guarantees this); here the
union over live observers is trivially fault-tolerant because dead observers
simply contribute nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



def agree_fault(observations: dict[int, set[int]], live: list[int]) -> set[int]:
    """Union of suspicion sets across live observers -> single verdict.

    ``observations[i]`` is the set of nodes that observer ``i`` noticed as
    failed; observers not in ``live`` are ignored (they may be dead).
    The result is what every survivor adopts — identical everywhere,
    resolving the BNP.
    """
    verdict: set[int] = set()
    for obs, seen in observations.items():
        if obs in live:
            verdict |= seen
    return verdict


def agreement_rounds(n_participants: int) -> int:
    """Tree-agreement depth — used by the repair cost model (log2 rounds)."""
    return max(1, int(np.ceil(np.log2(max(n_participants, 2)))))


# ---------------------------------------------------------------------------
# In-program liveness bitmap (shard_map)
# ---------------------------------------------------------------------------

def liveness_psum(local_bitmap: jax.Array, axis_name: str | tuple[str, ...]) -> jax.Array:
    """AND-reduce liveness bitmaps: each shard holds (n_nodes,) int32 with 1
    for nodes *it* believes alive; the product-reduce (min via multiply on
    0/1) yields the agreed bitmap. Runs inside shard_map/jit."""
    # 0/1 bitmap: AND == min == product. psum of log would be fancy; for 0/1
    # use psum of (1 - x) and threshold: agreed_alive = (sum of dead-votes == 0)
    dead_votes = jax.lax.psum(1 - local_bitmap, axis_name)
    return (dead_votes == 0).astype(jnp.int32)


def agree_bitmap_inprogram(mesh: Mesh, bitmaps: jax.Array) -> np.ndarray:
    """Run the liveness AND-reduce over the mesh's data axes.

    bitmaps: (n_shards, n_nodes) int32 — row i is shard i's local view.
    Returns the agreed (n_nodes,) bitmap (identical for all shards).
    """
    axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not axes:
        return np.asarray(jnp.min(bitmaps, axis=0))

    shard_axes = axes if len(axes) > 1 else axes[0]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=P(shard_axes, None),
        out_specs=P(None),
    )
    def run(bm):
        local = jnp.min(bm, axis=0)          # AND within this shard's rows
        return liveness_psum(local, shard_axes)

    return np.asarray(run(bitmaps))
