"""Serving through a node loss: ``ResilientServer`` under open-loop load.

Set-up builds the server (``repro.launch.serve.ResilientServer``) over a
``Session`` of the traffic's logical nodes, gives it weights made from the
seed, and runs its work function once at every micro-batch size the window
can form (1 to the micro-batch), so that nothing compiles in the window.
The window submits each request at its due time (``ServeEngine.submit``)
and runs ``ServeEngine.run_round`` while any are pending. The traffic
offers more than the server sustains, so the queue never empties and the
tokens served over the window read the server's capacity. The window ends
with the round that runs past ``--seconds``. A node is lost at a fixed
round; its in-flight requests are requeued by the engine. After the
window, rounds run on (nothing new is submitted) until every request due
in the window is served, for a minute at most; a request still missing
then counts as failed.

The server makes each prompt from the request id and the request's place
in its micro-batch (a fixed key, the id as the first token); the check
rebuilds the prompts of a sample of requests by that rule, one from each
place of a micro-batch, and compares the served tokens with the
reference.
"""
from __future__ import annotations

import itertools
import time

import numpy as np

from bench import arrivals, harness as h
from bench.loops import common

DRAIN_LIMIT_S = 60.0
PROMPT_KEY = 1234


def build(ctx: h.Ctx):
    from repro.configs.registry import get_config
    from repro.core import FaultInjector, LegioPolicy
    from repro.launch.serve import ResilientServer
    from repro.mpi import Session
    from repro.serve import Request, recovery_preset
    tr = ctx.cell.traffic
    cfg = get_config(ctx.cell.config["program_arch"]).replace(
        **tr["program_overrides"])
    common.check_program_config(cfg, ctx.cell.model)
    session = Session(
        tr["nodes"], policy=LegioPolicy(**recovery_preset(tr["recovery"]),
                                        data_plane=tr["data_plane"]),
        injector=FaultInjector.at([(tr["fault"]["round"],
                                    tr["fault"]["node"])]))
    server = ResilientServer(cfg, session, prompt_len=tr["prompt_len"],
                             decode_tokens=tr["decode_tokens"],
                             batch_per_node=tr["microbatch"])
    server.params = common.adopt_weights(
        common.make_weights(ctx.cell, ctx.seed), server.params)
    engine = server.engine
    work = engine.work_fn
    for b in range(1, tr["microbatch"] + 1):
        work(0, [Request(rid=i) for i in range(b)], 0)
    return server, session


def prompts_of(rids: list[int], placed: dict, prompt_len: int,
               vocab: int) -> np.ndarray:
    """The prompts the server made: row ``i`` of ``randint(key, (B, P))``
    for a request at place ``i`` of a micro-batch of ``B``, its first
    token replaced by the request id."""
    import jax
    import jax.numpy as jnp
    out = []
    for rid in rids:
        b, i = placed[rid]
        rows = jax.random.randint(jax.random.PRNGKey(PROMPT_KEY),
                                  (b, prompt_len), 0, vocab, jnp.int32)
        row = np.array(rows[i])
        row[0] = rid % vocab
        out.append(row)
    return np.stack(out)


class Loop:
    """The open loop: submits what is due, runs rounds, stamps results."""

    def __init__(self, engine, times: np.ndarray):
        self.engine, self.times = engine, times
        self.next, self.due, self.done = 0, {}, {}
        self.rounds: list[dict] = []
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def submit_due(self, upto: float | None = None) -> None:
        """Submit every request due by now (or by ``upto``)."""
        now = self.now() if upto is None else upto
        while self.next < len(self.times) and self.times[self.next] <= now:
            rid, = self.engine.submit(1)
            self.due[rid] = float(self.times[self.next])
            self.next += 1

    def round(self) -> None:
        before = len(self.engine.completed)
        rr = self.engine.run_round()
        end = self.now()
        for rid in itertools.islice(self.engine.completed, before, None):
            self.done[rid] = end
        self.rounds.append({"seconds": rr.wall_seconds, "end": end,
                            "completed": rr.completed_now,
                            "dispatched": sum(rr.dispatched.values()),
                            "repairs": len(rr.actions)})

    def run_until(self, t_end: float) -> None:
        import jax
        while (now := self.now()) < t_end:
            self.submit_due()
            if self.engine.pending:
                with jax.profiler.TraceAnnotation("bench.run_round"):
                    self.round()
                continue
            nxt = self.times[self.next] if self.next < len(self.times) \
                else t_end
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, t_end) - now))
        # a round that ran past t_end held back requests due before it
        self.submit_due(upto=t_end)

    def drain(self, rids: list[int], limit_s: float) -> None:
        t_end = self.now() + limit_s
        while any(r not in self.done for r in rids) and self.now() < t_end \
                and self.engine.pending:
            self.round()


def sample_requests(seed: int, done: list[int], placed: dict,
                    n: int) -> list[int]:
    """``n`` served requests drawn from the seed: one from each place a
    micro-batch has, in turn, until ``n`` are drawn."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    by_place: dict[int, list[int]] = {}
    for r in done:
        by_place.setdefault(placed[r][1], []).append(r)
    pools = [list(rng.permutation(by_place[p])) for p in sorted(by_place)]
    sample = []
    while len(sample) < min(n, len(done)):
        for pool in pools:
            if pool and len(sample) < n:
                sample.append(int(pool.pop()))
    return sorted(sample)


def measure(ctx: h.Ctx) -> tuple[h.Outcome, np.ndarray, np.ndarray]:
    """Set-up, window, the traced stretch and the drain; returns the outcome
    without the reference's comparison, and the sampled requests' prompts
    and served tokens for it. The program's state is freed on return."""
    tr, model = ctx.cell.traffic, ctx.cell.model
    P, T, V = tr["prompt_len"], tr["decode_tokens"], model["vocab_size"]
    server, session = build(ctx)
    engine = server.engine
    placed: dict[int, tuple[int, int]] = {}
    runs: dict[int, int] = {}
    batches: list[tuple[float, int]] = []
    work = engine.work_fn

    def recording_work(node, batch, step):
        for i, r in enumerate(batch):
            placed.setdefault(r.rid, (len(batch), i))
            runs[r.rid] = runs.get(r.rid, 0) + 1
        batches.append((time.perf_counter(), len(batch)))
        return work(node, batch, step)

    engine.work_fn = recording_work
    times = arrivals.schedule(ctx.seed, tr["rate_per_s"], ctx.seconds,
                              tr["arrival_block"])

    ctx.setup_done()
    loop = Loop(engine, times)
    loop.run_until(ctx.seconds)
    window_s = max(loop.now(), ctx.seconds)
    t_window_end = loop.t0 + window_s
    due = sorted(loop.due)
    in_window = [r for r in due if loop.done.get(r, np.inf) <= window_s]
    n_window_rounds = len(loop.rounds)
    if n_window_rounds <= tr["fault"]["round"]:
        raise h.BenchError("the node loss did not land inside the window")

    box, traced = {}, (0.0, 0.0)
    if ctx.trace:   # the load goes on, over the backlog the window left
        t_start = loop.now()
        extra = arrivals.schedule(ctx.seed, tr["rate_per_s"],
                                  tr["trace_seconds"], tr["arrival_block"],
                                  salt=1) + t_start
        loop.times = np.concatenate([loop.times[:loop.next], extra])
        with ctx.traced(kernels=tr["kernels"]) as box:
            loop.run_until(t_start + tr["trace_seconds"])
        traced = (loop.t0 + t_start, loop.t0 + loop.now())
    loop.drain(due, DRAIN_LIMIT_S)
    lost = [r for r in due if r not in loop.done]

    repaired = len(session.cluster.repairs) >= 1 and \
        tr["fault"]["node"] not in session.cluster.topo.nodes
    sample = sample_requests(ctx.seed, [r for r in due if r in loop.done],
                             placed, tr["check_requests"])
    served = np.stack([np.asarray(engine.completed[r]) for r in sample])
    prompts = prompts_of(sample, placed, P, V)
    memory = h.memory_peak(ctx.devices)
    del server, session, engine, work, loop.engine
    h.free()

    twice = sum(runs.get(r, 0) > 1 for r in due)
    compared = [h.Compared("lost_requests", float(len(lost)), 0.0),
                h.Compared("served_twice", float(twice), 0.0),
                h.Compared("fault_unrepaired", float(not repaired), 0.0)]
    completed_tokens = T * len(in_window)
    record = {"loop": "serve", "rounds": loop.rounds[:n_window_rounds],
              "window_s": window_s, "offered": len(due), "batches": [b for t, b in batches
                                                if t <= t_window_end],
              "traced_batches": [b for t, b in batches
                                 if traced[0] <= t <= traced[1]],
              "completed": len(in_window), "prompt_len": P,
              "decode_tokens": T, "chips": len(ctx.devices)}
    return h.Outcome(
        end_to_end={"serve_tokens_per_s": completed_tokens / window_s},
        record=record, compared=compared, attempted=len(due),
        failed=len(lost) + twice, memory_peak_bytes=memory,
        trace=box.get("trace")), prompts, served


def run(ctx: h.Ctx) -> h.Outcome:
    outcome, prompts, served = measure(ctx)
    gap = common.reference_gaps(ctx, prompts, served)["program"]
    limit = ctx.cell.config["limits"]["serve"]["logit_gap"]
    outcome.compared.insert(0, h.Compared("logit_gap", gap, limit))
    return outcome
