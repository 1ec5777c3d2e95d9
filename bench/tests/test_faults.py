"""A run with its timed path broken underneath comes out not correct.

Each test drives the whole harness (set-up, window, check) on the CPU at a
small size, past the look for a chip, with one fault planted in the
program: a step that returns its state unchanged, a step that trains on half
of its batch (the mean over the rest), moments lost in the reshard after
the node loss, survivors' steps on the wrong rows, a decode step that
leaves its state unchanged, a served token altered where it is produced. The sound run of
the same cell comes out correct. The limits here are for this small size;
the cells' own limits are set from chip runs at full size.
"""
import dataclasses
import json

import numpy as np
import pytest

from bench import run
from bench.tests import tiny

TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.1, "change_gap": 0.1,
                "fault_change_gap": 0.1, "fault_mu_gap": 0.1,
                "fault_nu_gap": 0.1}
SERVE_LIMITS = {"logit_gap": 0.05}


def train_cell():
    c = tiny.cell("mamba2-130m.train.drop1", seq_len=32, ref_rows_per_block=4)
    return dataclasses.replace(c, config=dict(
        c.config, limits={"train": TRAIN_LIMITS}))


def serve_cell():
    c = tiny.cell("mamba2-130m.serve.drop1", prompt_len=32, decode_tokens=8,
                  rate_per_s=20.0)
    return dataclasses.replace(c, config=dict(
        c.config, limits={"serve": SERVE_LIMITS}))


def result(cell, capsys, seconds="5") -> dict:
    rc = run.main(["--workload", "tiny", "--seed", str(2 ** 35 + 9),
                   "--seconds", seconds, "--trace", "0"],
                  need_chip=False, cell=cell)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    tiny.smoke_registry(monkeypatch)
    return monkeypatch


def test_train_sound_run_is_correct(smoke, capsys):
    assert result(train_cell(), capsys)["correct"] is True


def test_train_state_unchanged_is_caught(smoke, capsys):
    import jax
    from repro.core import trainer
    real = trainer.make_train_step

    def broken(cfg, tc):
        step = real(cfg, tc)
        return jax.jit(lambda p, o, b, s: (p, o, step(p, o, b, s)[2]))

    smoke.setattr(trainer, "make_train_step", broken)
    out = result(train_cell(), capsys)
    assert out["correct"] is False
    assert out["compared"]["change_gap"]["value"] > 0.99


def test_train_half_batch_is_caught(smoke, capsys):
    from repro.core import trainer
    real = trainer.ResilientTrainer._global_batch

    def half(self, step):
        batch, scale = real(self, step)
        n = batch["tokens"].shape[0] // 2
        return {k: v[:n] for k, v in batch.items()}, scale

    smoke.setattr(trainer.ResilientTrainer, "_global_batch", half)
    assert result(train_cell(), capsys)["correct"] is False


def test_train_moments_lost_in_reshard_are_caught(smoke, capsys):
    import jax
    import jax.numpy as jnp
    from repro.dist import dataplane
    real = dataplane.JaxDataPlane.reshard_registered

    def lossy(self, view):
        report = real(self, view)
        for name in ("trainer.opt.mu", "trainer.opt.nu"):
            get, put = self._state[name][:2]
            put(jax.tree.map(jnp.zeros_like, get()))
        return report

    smoke.setattr(dataplane.JaxDataPlane, "reshard_registered", lossy)
    out = result(train_cell(), capsys)
    assert out["correct"] is False
    assert out["compared"]["fault_mu_gap"]["value"] > 0.1
    assert out["compared"]["change_gap"]["value"] < 0.1   # before the loss


def test_train_survivors_on_one_shards_rows_are_caught(smoke, capsys):
    """After the loss every survivor trains on the first survivor's rows:
    the same number of rows, the wrong ones."""
    from repro.core import trainer
    real = trainer.ResilientTrainer._global_batch

    def wrong(self, step):
        batch, scale = real(self, step)
        if self.cluster.plan.active_shards == self.cluster.n_initial:
            return batch, scale
        n = self.per_shard_batch
        return {k: v[:n].repeat(v.shape[0] // n, 0)
                for k, v in batch.items()}, scale

    smoke.setattr(trainer.ResilientTrainer, "_global_batch", wrong)
    out = result(train_cell(), capsys)
    assert out["correct"] is False
    assert out["compared"]["grad_gap"]["value"] < 0.1     # before the loss


def test_serve_sound_run_is_correct(smoke, capsys):
    assert result(serve_cell(), capsys)["correct"] is True


def test_serve_token_altered_is_caught(smoke, capsys):
    from repro.launch import serve
    real = serve.ResilientServer._work_batch

    def altered(self, rids):
        out = np.array(real(self, rids))
        out[:, out.shape[1] // 2] = (out[:, out.shape[1] // 2] + 1) \
            % self.cfg.vocab_size
        return out

    smoke.setattr(serve.ResilientServer, "_work_batch", altered)
    assert result(serve_cell(), capsys)["correct"] is False


def test_serve_decode_state_unchanged_is_caught(smoke, capsys):
    from repro.models import api
    real = api.decode_step

    def frozen(cfg, params, cache, tokens):
        logits, _ = real(cfg, params, cache, tokens)
        return logits, cache

    smoke.setattr(api, "decode_step", frozen)
    assert result(serve_cell(), capsys)["correct"] is False
