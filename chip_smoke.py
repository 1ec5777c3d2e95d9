#!/usr/bin/env python3
"""Smoke run of Legio's resilient training and serving on TPU.

    python chip_smoke.py              # one chip: the train and serve phases
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Drives the main paths through their entry points at the published width of
mamba2-130m (24 layers, d_model 768, SSD state 128, vocab 50280), random
weights from a seed, on the jax data plane:

  train  ``repro.launch.train``'s ResilientTrainer: 4 logical nodes,
         sequence 1024, node 1 killed at step 2 (shrink). Every loss is
         finite, the repair lands, and the later steps run on the
         survivors' shards.
  serve  ``repro.launch.serve``'s ResilientServer with the Pallas SSD
         kernel: 16 requests over 4 nodes, node 1 killed in round 1. No
         request is lost or completed twice; the prefill carries the
         kernel, and its logits agree with the pure-jnp path.
  mesh   (``--chips 4``) 4 logical nodes on 4 chips, node 3 killed at
         step 1: allreduce/bcast/reduce byte-equal to the numpy simulator
         at every step, and a trainer that steps before and after its
         state is resharded from 4 chips to 3.

The script refuses to run without a TPU and never continues on the CPU.
It prints per-phase compile seconds, steady step or request wall time, the
compile-cache directory, the data-plane fallback count and peak device
memory. Any failed check raises, so the exit code is non-zero; the last
line of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

ARCH = ["--arch", "mamba2-130m", "--full"]
# Prefill logits, Pallas SSD kernel vs the pure-jnp chunked scan, as
# max|a - b| / max|b|. With bf16 activations the comparison says nothing at
# 24 layers: two equivalent orders of the same jnp scan (chunk 256 vs 128)
# already differ by 0.14 on the CPU, since every 1-ulp bf16 rounding flip is
# amplified layer by layer. So both programs run with f32 activations (the
# bf16 weights unchanged) and f32 matmuls at full precision; on the CPU they
# then differ by 9e-5. The bound is 2.5 bf16 ulps (eps 2^-7) of the largest
# logit, the room a bf16 MXU pass inside the kernel may take; a real
# indexing or recurrence bug moves the logits by O(1).
LOGITS_RTOL = 2e-2


class SmokeFailure(RuntimeError):
    """A phase's result is wrong."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileMeter:
    """Sums JAX's compile events (backend compile or persistent-cache
    fetch) between calls to :meth:`take`."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.BACKEND:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_seconds": self.seconds, "compiles": self.compiles,
               "cache_hits": self.hits}
        self.seconds, self.compiles, self.hits = 0.0, 0, 0
        return out


def run_steps(trainer, steps: int, meter: CompileMeter) -> list:
    """Run ``steps`` trainer steps; returns (report, compiled) pairs, where
    ``compiled`` says the step compiled or fetched a program from the
    persistent cache, so its time is not a steady step."""
    out = []
    for _ in range(steps):
        before = (meter.compiles, meter.hits)
        r = trainer.run_step()
        out.append((r, (meter.compiles, meter.hits) != before))
    return out


def steady_after(ran: list, first: int, what: str) -> list:
    """Reports from step ``first`` on whose step compiled nothing."""
    steady = [r for r, compiled in ran[first:] if not compiled]
    check(bool(steady), f"{what}: no step after the repair ran without "
                        "compiling")
    return steady


def peak_bytes(devices) -> list[int]:
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices]


def report(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


# ---------------------------------------------------------------------------
# phases — each returns nothing and raises SmokeFailure on a wrong result
# ---------------------------------------------------------------------------

def phase_train(arch: list[str], meter: CompileMeter, *, seq_len: int = 1024,
                per_shard_batch: int = 2, steps: int = 5) -> None:
    from repro.launch.train import build_trainer
    fault_step, victim = 2, 1
    trainer, _ = build_trainer([
        *arch, "--nodes", "4", "--seq-len", str(seq_len),
        "--per-shard-batch", str(per_shard_batch), "--steps", str(steps),
        "--fail", f"{fault_step}:{victim}", "--data-plane", "jax"])
    cl = trainer.cluster
    check(cl.dataplane.name == "jax", "trainer is not on the jax data plane")
    ran = run_steps(trainer, steps, meter)
    reports = [r for r, _ in ran]
    for r, compiled in ran:
        report("train", step=r.step, loss=r.loss, shards=r.active_shards,
               seconds=r.step_seconds, repair=r.repair is not None,
               compiled=compiled)
    check(all(np.isfinite(r.loss) for r in reports), "non-finite loss")
    check(reports[fault_step].repair is not None,
          f"no repair landed at step {fault_step}")
    check(victim not in cl.live_nodes and not cl.plan.shards_of(victim),
          f"node {victim} still holds shards after the repair")
    check([r.active_shards for r in reports]
          == [4] * fault_step + [3] * (steps - fault_step),
          "steps after the repair did not run on the survivors' shards")
    check(bool(cl.reshards), "no ReshardReport after the repair")
    steady = statistics.median(
        r.step_seconds for r in steady_after(ran, fault_step + 1, "train"))
    tokens = reports[-1].active_shards * per_shard_batch * seq_len
    report("train", **meter.take(), steady_step_seconds=steady,
           steady_tokens_per_second=tokens / steady,
           reshard_seconds=cl.reshards[-1].wall_seconds,
           reshard_devices=cl.reshards[-1].n_devices,
           dataplane_fallbacks=cl.dataplane.fallbacks)
    check(cl.dataplane.fallbacks == 0, "the jax data plane fell back to sim")


def phase_serve(cfg, meter: CompileMeter, *, nodes: int = 4,
                requests: int = 16, prompt_len: int = 1024,
                decode_tokens: int = 8, batch_per_node: int = 2) -> None:
    import jax
    from repro.core import FaultInjector, LegioPolicy
    from repro.launch.serve import ResilientServer
    from repro.models import api
    from repro.mpi import Session
    from repro.serve import recovery_preset

    session = Session(nodes, policy=LegioPolicy(**recovery_preset("shrink"),
                                                data_plane="jax"),
                      injector=FaultInjector.at([(1, 1)]))
    server = ResilientServer(cfg, session, prompt_len=prompt_len,
                             decode_tokens=decode_tokens,
                             batch_per_node=batch_per_node)
    tokens = jax.random.randint(jax.random.PRNGKey(7),
                                (batch_per_node, prompt_len), 0,
                                cfg.vocab_size, jax.numpy.int32)
    hlo = server._prefill.lower(server.params, tokens).compile().as_text()
    check("tpu_custom_call" in hlo, "the prefill holds no Pallas kernel")
    with jax.default_matmul_precision("highest"):
        logits, ref = (np.asarray(jax.jit(lambda p, t, c=c: api.prefill(
            c, p, t, prompt_len)[0])(server.params, tokens))
            for c in (cfg.replace(dtype="float32"),
                      cfg.replace(dtype="float32", use_pallas=False)))
    check(bool(np.all(np.isfinite(logits))), "non-finite prefill logits")
    rel = float(np.max(np.abs(logits - ref)) / np.max(np.abs(ref)))
    report("serve", prefill_vs_reference_rel_err=rel, tolerance=LOGITS_RTOL,
           argmax_agree=float(np.mean(logits.argmax(-1) == ref.argmax(-1))))
    check(rel <= LOGITS_RTOL, f"prefill logits off the reference by {rel}")
    server._work_batch(list(range(batch_per_node)))   # warm decode too
    report("serve", stage="warmup", **meter.take())

    rep = server.run(requests)
    compile_in_run = meter.take()
    report("serve", **{k: rep[k] for k in (
        "completed", "abandoned", "shed", "unserved", "rounds", "requeues",
        "repairs", "survivors", "wall_seconds")}, **compile_in_run)
    check(rep["completed"] + rep["abandoned"] + rep["shed"] == requests,
          "requests unaccounted for")
    check(rep["unserved"] == 0 and rep["completed"] == requests,
          "requests left unserved")
    check(sorted(server.completed) == list(range(requests)),
          "completed request ids are not each request exactly once")
    check(rep["repairs"] >= 1 and 1 not in session.cluster.topo.nodes,
          "the injected fault was not repaired")
    fallbacks = session.cluster.dataplane.fallbacks
    steady = (rep["wall_seconds"] - compile_in_run["compile_seconds"]) \
        / rep["completed"]
    report("serve", steady_request_seconds=steady,
           dataplane_fallbacks=fallbacks)
    check(fallbacks == 0, "the jax data plane fell back to sim")


def phase_mesh(arch: list[str], meter: CompileMeter, devices, *,
               payload_elems: int = 1 << 20, seq_len: int = 1024,
               steps: int = 5) -> None:
    """4 logical nodes on 4 chips; node 3 dies at step 1."""
    import jax
    from repro.core import FaultInjector, LegioPolicy
    from repro.launch.train import build_trainer
    from repro.mpi import Session

    fault = (1, 3)

    def session(plane):
        return Session(4, policy=LegioPolicy(data_plane=plane),
                       injector=FaultInjector.at([fault]))

    sim, jx = session("sim"), session("jax")
    check(len(jx.cluster.dataplane.devices) == 4,
          "the jax plane does not see 4 devices")
    base = (np.arange(payload_elems, dtype=np.float32) % 13.0) - 6.0

    def same(a, b, what):
        check(a.stages == b.stages and a.sim_seconds == b.sim_seconds,
              f"{what}: schedules differ between planes")
        check(set(a.data) == set(b.data), f"{what}: members differ")
        for n in a.data:
            x, y = np.asarray(a.data[n]), np.asarray(b.data[n])
            check(x.dtype == y.dtype and x.tobytes() == y.tobytes(),
                  f"{what}: node {n} not byte-equal")

    for step in range(4):
        sim.advance(step)
        jx.advance(step)

        def contrib(s):
            return {m: base * np.float32(m + 1) for m in s.world.members
                    if m not in s.cluster.failed}

        t0 = time.perf_counter()
        res_j = jx.world.allreduce(contrib(jx))
        wall_j = time.perf_counter() - t0
        same(sim.world.allreduce(contrib(sim)), res_j, f"step {step} allreduce")
        root = sorted(sim.world.members)[0]
        same(sim.world.bcast(base, root=root), jx.world.bcast(base, root=root),
             f"step {step} bcast")
        same(sim.world.reduce(contrib(sim), root=root),
             jx.world.reduce(contrib(jx), root=root), f"step {step} reduce")
        report("mesh", step=step, members=len(jx.world.members),
               allreduce_seconds=wall_j, byte_equal=True)
    check(fault[1] not in jx.cluster.topo.nodes, "node 3 was not repaired out")
    check(jx.cluster.dataplane.fallbacks == 0,
          "the jax data plane fell back to sim")
    report("mesh", stage="collectives", **meter.take())

    trainer, _ = build_trainer([
        *arch, "--nodes", "4", "--seq-len", str(seq_len),
        "--per-shard-batch", "1", "--steps", str(steps),
        "--fail", f"{fault[0]}:{fault[1]}", "--data-plane", "jax"])
    cl = trainer.cluster
    ran = run_steps(trainer, steps, meter)
    reports = [r for r, _ in ran]
    for r, compiled in ran:
        report("mesh", step=r.step, loss=r.loss, shards=r.active_shards,
               seconds=r.step_seconds, repair=r.repair is not None,
               compiled=compiled)
    check(all(np.isfinite(r.loss) for r in reports), "non-finite loss")
    check(reports[fault[0]].repair is not None, "no repair in the trainer")
    check(bool(cl.reshards), "no ReshardReport")
    rep = cl.reshards[-1]
    check(rep.n_devices == 3 and rep.mesh_shape == (3, 1),
          f"reshard did not land on 3 chips: {rep}")
    placed = {d for leaf in jax.tree.leaves(trainer.params)
              for d in leaf.sharding.device_set}
    check(len(placed) == 3, f"params live on {len(placed)} devices, not 3")
    report("mesh", **meter.take(), reshard_seconds=rep.wall_seconds,
           reshard_bytes=rep.moved_bytes, reshard_devices=rep.n_devices,
           steady_step_seconds=statistics.median(
               r.step_seconds
               for r in steady_after(ran, fault[0] + 1, "mesh")),
           dataplane_fallbacks=cl.dataplane.fallbacks)
    check(cl.dataplane.fallbacks == 0, "the jax data plane fell back to sim")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip mesh phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    meter = CompileMeter()
    report("smoke", cache_dir=cache, device_kind=devices[0].device_kind,
           devices=len(devices))
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_mesh(ARCH, meter, devices)
    else:
        phase_train(ARCH, meter)
        phase_serve(get_config("mamba2-130m").replace(use_pallas=True), meter)
    report("smoke", total_seconds=time.perf_counter() - t0,
           peak_bytes_in_use=peak_bytes(devices))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
