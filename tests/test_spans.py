"""repro.spans: nesting, the bounded buffer, the profiler's host plane, the
reports that read their spans, and one work span per served request."""
import collections
import glob
import threading
import time

import jax
import pytest

from repro import spans
from repro.configs.base import TrainConfig
from repro.configs.registry import get_smoke_config
from repro.core import FaultInjector, LegioPolicy, ResilientTrainer, VirtualCluster
from repro.launch.serve import ResilientServer
from repro.mpi import Session

PIPELINE_STAGES = ("detect", "notice", "agree", "plan", "apply")


def named(name, records=None):
    return [r for r in (spans.records() if records is None else records)
            if r.name == name]


def smoke_trainer(nodes=4, faults=(), data_plane="sim"):
    tc = TrainConfig(learning_rate=1e-2, total_steps=8, warmup_steps=1)
    cl = VirtualCluster(nodes, policy=LegioPolicy(data_plane=data_plane),
                        injector=FaultInjector.at(list(faults)))
    return ResilientTrainer(get_smoke_config("llama3.2-3b"), tc, cl,
                            per_shard_batch=1, seq_len=16)


def smoke_server(nodes=4, faults=(), batch=2):
    session = Session(nodes, injector=FaultInjector.at(list(faults)))
    return ResilientServer(get_smoke_config("llama3.2-3b"), session,
                           prompt_len=8, decode_tokens=2,
                           batch_per_node=batch)


def test_nesting_parent_links_and_self_time():
    spans.clear()
    with spans.span("t.outer", k=1) as outer:
        time.sleep(0.01)
        with spans.span("t.inner") as a:
            time.sleep(0.02)
        with spans.span("t.inner") as b:
            b.set(late=2)
            time.sleep(0.005)
    inner_a, inner_b, top = spans.records()
    assert [r.name for r in (inner_a, inner_b, top)] == \
        ["t.inner", "t.inner", "t.outer"]      # in the order they closed
    assert top.parent is None and top.attrs == {"k": 1}
    assert inner_a.parent == inner_b.parent == top.id == outer.id
    assert inner_b.attrs == {"late": 2}
    for child in (inner_a, inner_b):
        assert top.start_ns <= child.start_ns <= child.end_ns <= top.end_ns
    assert (a.seconds, b.seconds, outer.seconds) == \
        (inner_a.seconds, inner_b.seconds, top.seconds)
    own = top.seconds - inner_a.seconds - inner_b.seconds
    assert 0.009 < own < top.seconds - 0.02


def test_enclosing_span_is_per_thread():
    spans.clear()
    seen = []

    def other():
        with spans.span("t.thread") as sp:
            seen.append(sp.parent)

    with spans.span("t.main"):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert seen == [None]
    with spans.span("t.after") as after:
        pass
    assert after.parent is None


def test_buffer_keeps_the_newest_and_counts_the_dropped():
    spans.clear()
    extra = 7
    for i in range(spans.MAXLEN + extra):
        with spans.span("t.fill", i=i):
            pass
    kept = spans.records()
    assert len(kept) == spans.MAXLEN
    assert spans.dropped() == extra
    assert kept[0].attrs["i"] == extra
    assert kept[-1].attrs["i"] == spans.MAXLEN + extra - 1
    spans.clear()
    assert spans.records() == [] and spans.dropped() == 0


def host_events(trace_dir):
    """(name, start, end) of the legio.* events on the CPU host plane's
    python line."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name == "python":
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("legio.")]
    return out


def inside(events, outer, inner):
    """Every ``outer`` event holds an ``inner`` one."""
    outs = [e for e in events if e[0] == outer]
    ins = [e for e in events if e[0] == inner]
    return bool(outs) and all(
        any(o[1] <= i[1] and i[2] <= o[2] for i in ins) for o in outs)


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    trainer = smoke_trainer()
    server = smoke_server()
    trainer.run_step()                       # compiles outside the trace
    server.engine.submit(2)
    server.engine.run_round()
    server.engine.submit(2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        trainer.run_step()
        server.engine.run_round()
    finally:
        jax.profiler.stop_trace()
    ev = host_events(tmp_path)
    assert inside(ev, "legio.train.step", "legio.train.sync")
    assert inside(ev, "legio.train.step", "legio.train.dispatch")
    assert inside(ev, "legio.serve.round", "legio.serve.work")
    assert inside(ev, "legio.serve.work", "legio.serve.fetch")
    assert inside(ev, "legio.serve.work", "legio.serve.decode")


def test_reports_read_their_spans():
    spans.clear()
    trainer = smoke_trainer(faults=[(2, 1)], data_plane="jax")
    reports = trainer.run(3)
    for r in reports:
        step, = [s for s in named("legio.train.step")
                 if s.attrs["step"] == r.step]
        assert r.step_seconds == step.seconds
        assert step.attrs["shards"] == r.active_shards
    assert reports[2].repair is not None
    trace = trainer.cluster.pipeline.traces[-1]
    assert trace.step == 2
    for stage in PIPELINE_STAGES:
        rec, = [s for s in named(f"legio.pipeline.{stage}")
                if s.attrs["step"] == 2]
        assert trace.stage_seconds[stage] == rec.seconds
    reshard, = named("legio.reshard")
    apply, = [s for s in named("legio.pipeline.apply")
              if s.attrs["step"] == 2]
    assert reshard.parent == apply.id
    assert trainer.cluster.reshards[-1].wall_seconds == reshard.seconds
    assert reshard.attrs["bytes"] == trainer.cluster.reshards[-1].moved_bytes

    server = smoke_server()
    server.engine.submit(4)
    rr = server.engine.run_round()
    rnd, = [s for s in named("legio.serve.round") if s.attrs["step"] == rr.step]
    assert rr.wall_seconds == rnd.seconds
    assert rnd.attrs["dispatched"] == sum(rr.dispatched.values()) == 4
    assert rr.sim_seconds == pytest.approx(
        server.engine.cluster.policy.step_sim_seconds)


def test_each_served_request_is_in_one_work_span():
    server = smoke_server(faults=[(1, 1)])
    spans.clear()
    server.engine.submit(16)
    server.engine.serve(max_rounds=50)
    eng = server.engine
    assert len(eng.completed) == 16
    assert eng.metrics.requeues > 0, "the lost node's requests were requeued"
    work = named("legio.serve.work")
    runs = collections.Counter(rid for w in work for rid in w.attrs["rids"])
    node_of = {rid: w.attrs["node"] for w in work for rid in w.attrs["rids"]}
    for rec in eng.metrics.completions:
        assert runs[rec.rid] == 1
        assert node_of[rec.rid] == rec.node
    assert sum(w.attrs["batch"] for w in work) == 16
