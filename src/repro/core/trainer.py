"""ResilientTrainer — SPMD data-parallel training under the Legio runtime.

This is the production integration of the paper's technique: a jitted
``train_step`` over a mesh, wrapped so node failures are survived by
*discard-and-continue* rather than global restart:

  * the virtual cluster's nodes each own one data-parallel batch shard;
  * a failure (injected here; heartbeat-detected in production) triggers the
    Legio repair path — agreement, hierarchical shrink, master re-election —
    and the trainer then (a) rebuilds its mesh from survivors, (b) reshards
    params/optimizer state, (c) recompiles the step for the new batch shape
    (jit's own cache, and JAX's persistent cache where it is enabled);
  * the global batch shrinks (DROP) or redistributes (REBALANCE); gradient
    means renormalize over the shards actually computed, so the SGD
    estimator stays unbiased — the paper's Monte-Carlo argument, applied to
    stochastic gradients;
  * per-legion checkpoints (cr.py) bound the loss of a *non-recoverable*
    event, and restart-only-failed brings replacements back without touching
    survivors.

On the CPU container meshes are virtual (1 device); on real TPUs the same
code path shrinks physical meshes — the dry-run proves those lower/compile.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.base import ModelConfig, TrainConfig
from repro.core.cr import LegionCheckpointer
from repro.core.executor import VirtualCluster
from repro.core.mesh_manager import CompileCache, DevicePool, MeshManager
from repro.core.types import RepairReport
from repro.data.pipeline import make_batch
from repro.models import api
from repro.optim import (
    OptState,
    adamw_init,
    adamw_update,
    apply_updates,
    clip_by_global_norm,
    cosine_schedule,
)

PyTree = Any


@dataclass
class TrainerReport:
    step: int
    loss: float
    grad_norm: float
    active_shards: int
    grad_scale: float
    repair: RepairReport | None = None
    recompiled: bool = False
    step_seconds: float = 0.0
    metrics: dict = field(default_factory=dict)


def make_train_step(cfg: ModelConfig, tc: TrainConfig):
    """(params, opt, batch, grad_scale) -> (params, opt, metrics); pure."""
    lr_fn = cosine_schedule(tc)

    @partial(jax.jit, static_argnums=(), donate_argnums=(0, 1))
    def train_step(params, opt: OptState, batch, grad_scale):
        def loss_fn(p):
            loss, metrics = api.train_loss(cfg, p, batch)
            return loss * grad_scale, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
        updates, opt = adamw_update(grads, opt, params, tc, lr_fn(opt.step))
        params = apply_updates(params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        return params, opt, metrics

    return train_step


class ResilientTrainer:
    """Data-parallel training loop with Legio fault resiliency."""

    def __init__(
        self,
        cfg: ModelConfig,
        tc: TrainConfig,
        cluster: VirtualCluster,
        *,
        per_shard_batch: int = 4,
        seq_len: int = 128,
        checkpointer: LegionCheckpointer | None = None,
    ):
        self.cfg, self.tc = cfg, tc
        self.cluster = cluster
        self.per_shard_batch = per_shard_batch
        self.seq_len = seq_len
        self.checkpointer = checkpointer
        if checkpointer is not None and cluster.checkpointer is None:
            # substituted ranks restore from the same per-legion store
            cluster.checkpointer = checkpointer
        # all fault plumbing rides the MPI facade: the session owns the
        # step boundary (spare delivery, splice re-expansion, ground-truth
        # injection) and the INJECTED-channel drain
        from repro.mpi import Session

        self.session = Session.adopt(cluster)
        self.pool = DevicePool(n_nodes=cluster.n_initial,
                               n_spares=cluster.spare_pool.capacity)
        self.mesh_manager = MeshManager(self.pool)
        self.compile_cache = CompileCache()
        self.train_step = make_train_step(cfg, tc)
        key = jax.random.PRNGKey(tc.seed)
        self.params = api.init_params(cfg, key)
        self.opt = adamw_init(self.params)
        self.step = 0
        self.history: list[TrainerReport] = []
        # live state rides the data plane's mesh: after every shrink or
        # regrow the surviving devices re-place params/opt in one measured
        # device_put pass (a no-op on the sim plane). Every argument of
        # train_step that carries state is registered, the step counter
        # too, so all of them always sit on the same device set.
        self.session.register_sharded_state(
            "trainer.opt.step", lambda: self.opt.step,
            lambda s: setattr(self, "opt", self.opt._replace(step=s)))
        self.session.register_sharded_state(
            "trainer.params", lambda: self.params,
            lambda p: setattr(self, "params", p))
        self.session.register_sharded_state(
            "trainer.opt.mu", lambda: self.opt.mu,
            lambda mu: setattr(self, "opt", self.opt._replace(mu=mu)))
        self.session.register_sharded_state(
            "trainer.opt.nu", lambda: self.opt.nu,
            lambda nu: setattr(self, "opt", self.opt._replace(nu=nu)))

    # -- batch assembly under the current plan --------------------------------------

    def _global_batch(self, step: int) -> tuple[dict, float]:
        cl = self.cluster
        shards: list[int] = sorted(
            s for a in cl.plan.assignments for s in a.shards)
        if not shards:
            raise RuntimeError("no surviving shards — cluster exhausted")
        with spans.span("legio.train.batch", shards=len(shards)):
            parts = [
                make_batch(self.tc.seed, step, s, batch=self.per_shard_batch,
                           seq_len=self.seq_len,
                           vocab_size=self.cfg.vocab_size)
                for s in shards
            ]
            batch = {k: jnp.concatenate([p[k] for p in parts], axis=0)
                     for k in parts[0]}
        # mean-over-present-shards is already the renormalized estimator;
        # grad_scale stays 1.0 for DROP (the mean denominator shrank with
        # the batch). It differs from 1 only for weighted schemes.
        return batch, 1.0

    # -- one resilient step -----------------------------------------------------------

    def run_step(self) -> TrainerReport:
        cl = self.cluster
        step = self.step
        with spans.span("legio.train.step", step_trace=True, step=step,
                        shards=cl.plan.active_shards) as sp:
            # step boundary through the facade: the provisioner delivers
            # re-spawned spares and warmed-up non-blocking substitutes
            # rejoin before new shards are handed out (re-expansion = mesh
            # change too); ground-truth faults land and drain through the
            # pipeline's INJECTED channel — detect → notice → agree → plan
            # → apply — so the trainer repairs through the registered
            # RecoveryStrategy, not a side door. (charge=False: the
            # trainer's clock is wall time.)
            with spans.span("legio.train.boundary", step=step):
                boundary = self.session.boundary(
                    step, observe_injected=True, charge=False)
            repair = None
            recompiled = bool(boundary.expansions)
            if boundary.actions:
                repair = boundary.actions[0].report
                recompiled = True  # mesh change forces re-lower unless cached
            sp.set(shards=cl.plan.active_shards)

            batch, grad_scale = self._global_batch(step)
            # a fault step's recompile (or persistent-cache fetch) lands here
            with spans.span("legio.train.dispatch", step=step):
                params, opt, metrics = self.train_step(
                    self.params, self.opt, batch,
                    jnp.asarray(grad_scale, jnp.float32))
            self.params, self.opt = params, opt

            with spans.span("legio.train.sync", step=step):
                loss = float(metrics["loss"])
            if not np.isfinite(loss):
                raise FloatingPointError(
                    f"non-finite loss at step {step}: {loss}")

            if self.checkpointer is not None and self.tc.checkpoint_every > 0 \
                    and step > 0 and step % self.tc.checkpoint_every == 0:
                self.checkpointer.save(step, cl.topo, self._state_of,
                                       sync=False)
            grad_norm = float(metrics.get("grad_norm", 0.0))

        report = TrainerReport(
            step=step,
            loss=loss,
            grad_norm=grad_norm,
            active_shards=cl.plan.active_shards,
            grad_scale=grad_scale,
            repair=repair,
            recompiled=recompiled,
            step_seconds=sp.seconds,
            metrics={k: float(v) for k, v in metrics.items()
                     if np.ndim(v) == 0},
        )
        self.history.append(report)
        self.step += 1
        return report

    def _state_of(self, node: int) -> PyTree:
        """Member state shard for checkpointing.

        Data-parallel state is replicated, so every member's shard is the
        (params, opt, step) triple plus its shard assignment — a replacement
        node needs nothing from survivors beyond its own file (§VII).
        """
        return {
            "params": self.params,
            "opt": {"step": self.opt.step, "mu": self.opt.mu, "nu": self.opt.nu},
            "meta": {
                "step": jnp.asarray(self.step, jnp.int32),
                "shards": jnp.asarray(list(self.cluster.plan.shards_of(node))
                                      or [-1], jnp.int32),
            },
        }

    def run(self, n_steps: int) -> list[TrainerReport]:
        return [self.run_step() for _ in range(n_steps)]

    # -- restart-only-failed (used by tests/examples) -----------------------------------

    def restore_from(self, checkpointer: LegionCheckpointer,
                     legion: int, node: int) -> None:
        state = checkpointer.restore_failed_member(
            legion, node, template=None)
        self.params = _retree(self.params, state["params"])
        self.opt = OptState(
            step=jnp.asarray(state["opt"]["step"]),
            mu=_retree(self.opt.mu, state["opt"]["mu"]),
            nu=_retree(self.opt.nu, state["opt"]["nu"]),
        )
        self.step = int(np.asarray(state["meta"]["step"]))


def _walk(tree: PyTree, prefix: str = ""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _walk(v, f"{prefix}{k}/")
    else:
        yield prefix[:-1], tree


def _pstr(entry) -> str:
    if isinstance(entry, jax.tree_util.DictKey):
        return str(entry.key)
    if isinstance(entry, jax.tree_util.SequenceKey):
        return str(entry.idx)
    return str(entry)


def _retree(template: PyTree, loaded: PyTree) -> PyTree:
    flat = {k: v for k, v in _walk(loaded)}
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.asarray(
            flat["/".join(_pstr(p) for p in path)], dtype=leaf.dtype
        ).reshape(leaf.shape),
        template,
    )
