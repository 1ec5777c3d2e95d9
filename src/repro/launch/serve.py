"""Resilient batched-serving driver — model inference over ``repro.serve``.

The paper's target class — embarrassingly parallel work with no inter-worker
interaction until the final reduce — is exactly batched inference: every node
owns a slice of the request stream (prefill + decode), and the only
collective is the result gather. The serving subsystem (``repro.serve``)
owns routing, micro-batching, and fault recovery; this module supplies the
model-backed work function (prefill + greedy decode) and the CLI.

A fault mid-batch no longer loses the in-flight requests and no longer
blocks serving: the ServeEngine re-enqueues them through the FaultPipeline
listener (at-least-once, deduped to exactly-once) while healthy legions
keep dispatching — see docs/serving.md.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \\
      --requests 64 --nodes 8 --decode-tokens 8 --fail 2:3 \\
      --recovery nonblocking
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.core import FaultInjector, LegioPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.mpi import Session
from repro.serve import RECOVERY_PRESETS, Request, ServeEngine, recovery_preset


class ResilientServer:
    """Model-backed serving: prefill + greedy decode per micro-batch, fault
    recovery delegated to :class:`repro.serve.ServeEngine` over the
    ``repro.mpi`` session facade — this driver contains zero fault code."""

    def __init__(self, cfg, cluster: "Session", *, prompt_len: int = 32,
                 decode_tokens: int = 8, batch_per_node: int = 4,
                 requeue: bool = True, window: int | None = None,
                 continuous: bool = True):
        self.cfg = cfg
        self.prompt_len = prompt_len
        self.decode_tokens = decode_tokens
        key = jax.random.PRNGKey(0)
        self.params = api.init_params(cfg, key)

        # named, so that a profile's program executions tell them apart
        def prefill(p, t):
            return api.prefill(cfg, p, t, prompt_len + decode_tokens)

        def decode(p, c, t):
            return api.decode_step(cfg, p, c, t)

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode)
        # tail batches change shape and recompile the jitted prefill/decode;
        # that wall-clock noise must not soft-fail healthy nodes as stragglers
        self.engine = ServeEngine(cluster, self._work_fn,
                                  microbatch=batch_per_node, requeue=requeue,
                                  window=window, continuous=continuous,
                                  observe_stragglers=False)

    @property
    def completed(self) -> dict[int, np.ndarray]:
        return self.engine.completed

    def _work_fn(self, node: int, batch: list[Request],
                 step: int) -> dict[int, np.ndarray]:
        rids = [r.rid for r in batch]
        result = self._work_batch(rids)
        return {rid: row for rid, row in zip(rids, result)}

    def _work_batch(self, request_ids: list[int]) -> np.ndarray:
        """Prefill + greedy-decode a batch of requests; returns token matrix."""
        B = len(request_ids)
        with spans.span("legio.serve.prefill", batch=B):
            key = jax.random.PRNGKey(1234)
            tokens = jax.random.randint(
                key, (B, self.prompt_len), 0, self.cfg.vocab_size, jnp.int32)
            # deterministic per-request prompts (request id folds into row 0)
            tokens = tokens.at[:, 0].set(
                jnp.asarray(request_ids, jnp.int32) % self.cfg.vocab_size)
            logits, cache = self._prefill(self.params, tokens)
        with spans.span("legio.serve.decode", batch=B,
                        steps=self.decode_tokens):
            out = []
            tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(
                jnp.int32)
            for _ in range(self.decode_tokens):
                out.append(tok)
                logits, cache = self._decode(self.params, cache, tok)
                tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None].astype(
                    jnp.int32)
            tokens = jnp.concatenate(out, axis=1)
        with spans.span("legio.serve.fetch", batch=B):
            return np.asarray(tokens)

    def run(self, n_requests: int) -> dict:
        self.engine.submit(n_requests)
        t0 = time.perf_counter()
        rep = self.engine.serve()
        wall = time.perf_counter() - t0
        m = rep.metrics_summary
        return {
            "completed": rep.completed,
            "abandoned": m["abandoned"],
            "shed": m["shed"],
            "unserved": self.engine.pending,
            "rounds": rep.rounds,
            "requeues": m["requeues"],
            "migrations": m["migrations"],
            "p50_latency_rounds": m["p50_latency_rounds"],
            "p99_latency_rounds": m["p99_latency_rounds"],
            "p99_latency_sim": m["p99_latency_sim"],
            "slo_attainment": m["slo_attainment"],
            "starved_rounds": m["starved_rounds"],
            "wall_seconds": wall,
            "survivors": rep.survivors,
            "repairs": rep.repairs,
            "throughput_rps": rep.completed / wall if wall > 0 else 0.0,
        }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-3b")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--nodes", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-tokens", type=int, default=8)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--fail", action="append", default=[],
                    help="round:node fault injection (repeatable)")
    ap.add_argument("--recovery", choices=sorted(RECOVERY_PRESETS),
                    default="shrink", help="recovery strategy for faults")
    ap.add_argument("--no-requeue", action="store_true",
                    help="DROP failed nodes' requests instead of re-queueing")
    ap.add_argument("--window", type=int, default=None,
                    help="in-flight micro-batches per node (continuous "
                         "batching window; default policy.serve_window)")
    ap.add_argument("--lockstep", action="store_true",
                    help="use the lock-step barrier baseline instead of "
                         "continuous batching")
    ap.add_argument("--slo", type=float, default=0.0,
                    help="per-request SLO deadline in simulated seconds "
                         "(0 = no deadlines)")
    ap.add_argument("--admission", choices=("none", "shed", "park"),
                    default="none",
                    help="SLO-feasibility admission control at submit")
    ap.add_argument("--data-plane", choices=["sim", "jax", "auto"],
                    default="sim",
                    help="what moves collective payloads: the numpy "
                         "simulator, real jax device collectives, or auto "
                         "(jax when >1 device is visible)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    pairs = []
    for s in args.fail:
        step, node = s.split(":")
        pairs.append((int(step), int(node)))
    # batch size flows through the ResilientServer constructor (the engine's
    # explicit microbatch override); the policy only carries recovery setup
    policy = LegioPolicy(**recovery_preset(args.recovery),
                         serve_slo_seconds=args.slo,
                         serve_admission=args.admission,
                         data_plane=args.data_plane)
    session = Session(
        args.nodes, policy=policy, injector=FaultInjector.at(pairs))
    server = ResilientServer(
        cfg, session, prompt_len=args.prompt_len,
        decode_tokens=args.decode_tokens, batch_per_node=args.batch_per_node,
        requeue=not args.no_requeue, window=args.window,
        continuous=not args.lockstep)
    print(f"[serve] arch={cfg.name} nodes={args.nodes} "
          f"requests={args.requests} recovery={args.recovery} "
          f"mode={'lockstep' if args.lockstep else 'continuous'}")
    rep = server.run(args.requests)
    for k, v in rep.items():
        print(f"  {k}: {v if not isinstance(v, float) else round(v, 3)}")
    ok = rep["completed"] + rep["abandoned"] + rep["shed"] == args.requests
    print(f"[serve] {'OK' if ok else 'INCOMPLETE'}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
