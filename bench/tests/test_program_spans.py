"""The readers of the program's spans, on traced runs of the train and
serve cells at a small size on the CPU: the three metrics print, and each
stays inside the span that holds it (host time of a step within the step,
the engine's own time of a round within the round). The CPU's numbers
are no device times; only their bounds are checked."""
import dataclasses
import json

import pytest

from bench import run
from bench.tests import tiny

TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.1, "change_gap": 0.1,
                "fault_change_gap": 0.1, "fault_mu_gap": 0.1,
                "fault_nu_gap": 0.1}


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    tiny.smoke_registry(monkeypatch)
    return monkeypatch


def traced(cell, capsys) -> dict:
    """The metrics of a traced run; those that read the device trace or the
    chip's peaks are left out (there is neither on the CPU)."""
    cell = dataclasses.replace(cell, per_layer=[
        m for m in cell.per_layer
        if m["source"] in ("program_span", "program_counter")])
    rc = run.main(["--workload", "tiny", "--seed", str(2 ** 35 + 9),
                   "--seconds", "5", "--trace", "1"],
                  need_chip=False, cell=cell)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    return {k: v["value"] for k, v in out["metrics"].items()}


def test_train_spans(smoke, capsys):
    c = tiny.cell("mamba2-130m.train.drop1", seq_len=32, ref_rows_per_block=4)
    c = dataclasses.replace(c, config=dict(c.config,
                                           limits={"train": TRAIN_LIMITS}))
    m = traced(c, capsys)
    assert set(m) >= {"host_s.train", "control_plane_s", "step_s.train"}
    assert 0 < m["host_s.train"] <= m["step_s.train"]
    assert 0 < m["control_plane_s"] < m["repair_compile_s"] + 1.0


def test_serve_spans(smoke, capsys):
    c = tiny.cell("mamba2-130m.serve.drop1", prompt_len=32, decode_tokens=8,
                  rate_per_s=20.0)
    c = dataclasses.replace(c, config=dict(
        c.config, limits={"serve": {"logit_gap": 0.05}}))
    m = traced(c, capsys)
    assert set(m) >= {"engine_s.serve", "round_s.serve"}
    assert 0 < m["engine_s.serve"] < m["round_s.serve"]
