"""The control at a size a test run holds: ``bench/control.py``'s readings
on the CPU. On the chip the same readings at the cells' own sizes set the
upper ends of the limits (PERF.md)."""
import jax
import pytest

from bench import control, harness as h
from bench.tests import tiny


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    tiny.smoke_registry(monkeypatch)


def ctx(cell):
    return h.Ctx(cell=cell, seed=2 ** 40 + 3, seconds=4, trace=False,
                 t_process=0.0, devices=jax.devices()[:1],
                 meter=h.CompileMeter())


def test_train_control_reads_far_above_the_program(smoke):
    cell = tiny.cell("mamba2-130m.train.drop1", seq_len=32,
                     ref_rows_per_block=4)
    got = control.readings(ctx(cell))
    # at this size the program's loss_gap reads 8e-5, the control's 5e-4
    assert got["program"]["loss_gap"] < 2e-4 < got["control_fp8"]["loss_gap"]
    assert got["half_batch"]["grad_gap"] > 0.1
    assert set(got["state_unchanged"].values()) == {1.0}


def test_serve_control_readings(smoke):
    cell = tiny.cell("mamba2-130m.serve.drop1", prompt_len=32,
                     decode_tokens=8, rate_per_s=20.0)
    got = control.readings(ctx(cell))
    assert got["failed"] == 0 and got["served_sample"] == 8 * 8
    assert got["program"] < 0.05 < got["token_altered"]
    assert got["fp8"] >= 0.0
