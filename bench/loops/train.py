"""Training through a node loss: ``ResilientTrainer`` under a shrink.

Set-up builds the trainer with ``repro.launch.train.build_trainer`` for the
traffic's deployment, gives it weights made from the seed, and drives it
through its first steps with ``run_step``; those compile the full-batch
step. The window then calls ``run_step`` for ``--seconds``. A node is lost
at a fixed step of the window: the cluster shrinks, the state is resharded
onto the survivors and their smaller step compiles (or comes from the
persistent cache) inside the window. That is the cost a user pays, and
``fault_stall_s`` reads it.

The reference follows every step from the first to the one after the
node loss, on the same rows: all shards before the loss, the survivors'
from the loss on. It is compared with the losses of those steps, the first
gradient, the parameters' change after set-up's steps and, after the step
that follows the loss, the change and both moments as the reshard and the
survivors' steps left them (read on the device inside the window, without
a sync, and fetched once it has closed).
"""
from __future__ import annotations

import dataclasses
import statistics
import time

from bench import harness as h
from bench.loops import common


def build(ctx: h.Ctx):
    from repro.launch.train import build_trainer
    tr, opt = ctx.cell.traffic, ctx.cell.traffic["optimizer"]
    fault_step = tr["setup_steps"] + tr["fault"]["measured_step"]
    trainer, _ = build_trainer([
        "--arch", ctx.cell.config["program_arch"], "--full",
        "--nodes", str(tr["nodes"]),
        "--per-shard-batch", str(tr["per_shard_batch"]),
        "--seq-len", str(tr["seq_len"]),
        "--steps", str(opt["schedule_steps"]), "--lr", str(opt["lr"]),
        "--fail", f"{fault_step}:{tr['fault']['node']}",
        "--recovery", tr["recovery"], "--batch-policy", tr["batch_policy"],
        "--data-plane", tr["data_plane"]])
    common.check_program_config(trainer.cfg, ctx.cell.model)
    tc = trainer.tc
    stated = {"beta1": tc.beta1, "beta2": tc.beta2, "eps": tc.eps,
              "weight_decay": tc.weight_decay, "grad_clip": tc.grad_clip,
              "warmup_steps": tc.warmup_steps, "lr": tc.learning_rate,
              "schedule_steps": tc.total_steps}
    if stated != opt:
        raise h.BenchError(f"the trainer's optimizer {stated} is not the "
                           f"traffic's {opt}")
    trainer.tc = dataclasses.replace(tc, seed=h.small_seed(ctx.seed, 1))
    trainer.params = common.adopt_weights(
        common.make_weights(ctx.cell, ctx.seed), trainer.params)
    return trainer


def first_steps(ctx: h.Ctx, trainer, read, init) -> dict:
    """Set-up's steps; returns what the program made in them. The state
    reading compiles here, so none compiles in the window."""
    import jax
    b1 = ctx.cell.traffic["optimizer"]["beta1"]
    losses, first_grad = [], None
    for i in range(ctx.cell.traffic["setup_steps"]):
        losses.append(trainer.run_step().loss)
        if i == 0:   # the first moment after one step is (1 - beta1) * grad
            first_grad = {k: float(v) / (1 - b1)
                          for k, v in read(trainer, init)["mu"].items()}
    change = jax.tree.map(float, read(trainer, init)["change"])
    return {"losses": losses, "first_grad": first_grad, "change": change}


def state_norms():
    """A jitted reading of the trainer's state: leaf norms of the
    parameters' change since ``init`` and of both moments."""
    import jax
    import jax.numpy as jnp

    def norms(tree):
        flat = jax.tree_util.tree_flatten_with_path(tree)[0]
        return {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for k, x in flat}

    @jax.jit
    def read(params, mu, nu, init):
        return {"change": norms(jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                    params, init)),
                "mu": norms(mu), "nu": norms(nu)}

    return lambda trainer, init: read(trainer.params, trainer.opt.mu,
                                      trainer.opt.nu, init)


def window(ctx: h.Ctx, trainer, read_at: int, read, init):
    """The measured steps, for ``--seconds`` and at least through step
    ``read_at``; returns their records and the state reading taken after
    that step (still on the device)."""
    tr = ctx.cell.traffic
    tokens_per_shard = tr["per_shard_batch"] * tr["seq_len"]
    recs, reading = [], None
    ctx.setup_done()
    t0 = time.perf_counter()
    while True:
        mark = ctx.meter.mark()
        r = trainer.run_step()
        end = time.perf_counter() - t0
        c = ctx.meter.since(mark)
        recs.append({"step": r.step, "seconds": r.step_seconds,
                     "shards": r.active_shards,
                     "tokens": r.active_shards * tokens_per_shard,
                     "compiled": c["compiles"] > 0,
                     "compile_s": c["compile_s"],
                     "repair": r.repair is not None, "loss": r.loss,
                     "end": end})
        if r.step == read_at:
            reading = read(trainer, init)
        if end >= ctx.seconds and reading is not None:
            return recs, reading


def fault_event(recs: list[dict]) -> tuple[int, int]:
    """[first, last) of the steps the node loss costs: from the step it
    lands in to the first later step that compiles nothing."""
    f = next((i for i, r in enumerate(recs) if r["repair"]), None)
    if f is None:
        raise h.BenchError("the node loss did not land inside the window")
    g = next((i for i in range(f + 1, len(recs))
              if not recs[i]["compiled"]), None)
    if g is None:
        raise h.BenchError("no step after the node loss ran without "
                           "compiling inside the window")
    return f, g


def plan(ctx: h.Ctx) -> tuple[list[list[int]], int]:
    """The shards each compared step trains on, by the traffic alone (one
    shard a node), and the step after which the state is read."""
    tr = ctx.cell.traffic
    fault = tr["setup_steps"] + tr["fault"]["measured_step"]
    every = list(range(tr["nodes"]))
    survivors = [s for s in every if s != tr["fault"]["node"]]
    return [every] * fault + [survivors] * 2, fault + 1


def measure(ctx: h.Ctx) -> tuple[h.Outcome, dict]:
    """Set-up, window and the traced stretch; returns the outcome without
    the reference's comparison, and what the program made in the compared
    steps. The program's state is freed on return."""
    import jax
    tr = ctx.cell.traffic
    steps, read_at = plan(ctx)
    trainer = build(ctx)
    cl = trainer.cluster
    read, init = state_norms(), common.make_weights(ctx.cell, ctx.seed)
    mine = first_steps(ctx, trainer, read, init)
    n_shards = cl.plan.active_shards
    reshards_before = len(cl.reshards)

    recs, reading = window(ctx, trainer, read_at, read, init)
    f, g = fault_event(recs)
    window_s = recs[-1]["end"]
    tokens = sum(r["tokens"] for r in recs)
    steady = [r["seconds"] for r in recs[g:] if not r["compiled"]]
    stall = sum(r["seconds"] for r in recs[f:g]) \
        - (g - f) * statistics.median(steady)
    reshards = cl.reshards[reshards_before:]
    mine["losses"] += [r["loss"] for r in recs if r["step"] <= read_at]
    mine["end"] = jax.tree.map(float, reading)

    box = {}
    if ctx.trace:
        with ctx.traced(kernels={}) as box:
            for _ in range(tr["trace_steps"]):
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    trainer.run_step()

    # what the window left: the survivors' shards and where the state lives
    survivors = sorted(s for a in cl.plan.assignments for s in a.shards)
    shards_wrong = (n_shards != len(steps[0])) \
        + len(set(survivors) ^ set(steps[-1])) + sum(
            r["shards"] != len(steps[0] if i < f else steps[-1])
            for i, r in enumerate(recs)) \
        + (recs[f]["step"] != len(steps) - 2)
    n_dev = max(len(ctx.devices) - 1, 1)
    state = [trainer.params, trainer.opt.mu, trainer.opt.nu]
    misplaced = (len(reshards) != 1) + sum(
        len(leaf.sharding.device_set) != n_dev
        for leaf in jax.tree.leaves(state))
    memory = h.memory_peak(ctx.devices)
    del trainer, cl, state, reshards, reading, init
    h.free()

    compared = [h.Compared("shards_wrong", float(shards_wrong), 0.0),
                h.Compared("state_misplaced", float(misplaced), 0.0)]
    record = {"loop": "train", "steps": recs, "window_s": window_s,
              "tokens": tokens, "event": recs[f:g],
              "chips": len(ctx.devices), "seq_len": tr["seq_len"]}
    return h.Outcome(
        end_to_end={"train_tokens_per_s": tokens / window_s,
                    "fault_stall_s": stall},
        record=record, compared=compared, attempted=len(recs), failed=0,
        memory_peak_bytes=memory, trace=box.get("trace")), mine


def reference(ctx: h.Ctx, steps: list[list[int]], kind: str = "f32") -> dict:
    """The reference's steps, step ``i`` on the rows of shards
    ``steps[i]``, with products in ``kind`` (``f32``, or ``fp8`` for the
    control)."""
    import jax
    from bench import data
    from bench.reference import train as ref_train
    from bench.reference.numerics import Numerics
    from bench.reference.serve import ref_chunk
    tr, model = ctx.cell.traffic, ctx.cell.model
    batches = [data.global_batch(
        h.small_seed(ctx.seed, 1), step, shards,
        batch=tr["per_shard_batch"], seq_len=tr["seq_len"],
        vocab_size=model["vocab_size"]) for step, shards in enumerate(steps)]
    m = dict(model, ref_chunk=ref_chunk(tr["seq_len"]))
    with jax.default_matmul_precision("highest"):
        return ref_train.run(common.reference(ctx.cell), m, tr["optimizer"],
                             common.make_weights(ctx.cell, ctx.seed), batches,
                             Numerics(kind), tr["ref_rows_per_block"],
                             tr["setup_steps"])


def gaps(mine: dict, ref: dict) -> dict[str, float]:
    """Loss of each compared step, the first gradient and the parameters'
    change after set-up's steps, and after the step that follows the node
    loss the parameters' change and both moments, each by its worst leaf."""
    from bench.reference import train as ref_train
    counted = ref_train.counted_leaves(ref["first_grad"])

    def worst(a, b):
        return ref_train.worst_leaf_gap(a, b, counted)[0]

    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(mine["losses"], ref["losses"],
                                        strict=True)),
        "grad_gap": worst(mine["first_grad"], ref["first_grad"]),
        "change_gap": worst(mine["change"], ref["change"]),
        "fault_change_gap": worst(mine["end"]["change"],
                                  ref["end"]["change"]),
        "fault_mu_gap": worst(mine["end"]["mu"], ref["end"]["mu"]),
        "fault_nu_gap": worst(mine["end"]["nu"], ref["end"]["nu"])}


def run(ctx: h.Ctx) -> h.Outcome:
    outcome, mine = measure(ctx)
    got = gaps(mine, reference(ctx, plan(ctx)[0]))
    lim = ctx.cell.config["limits"]["train"]
    outcome.compared[:0] = [h.Compared(k, v, lim[k]) for k, v in got.items()]
    return outcome
