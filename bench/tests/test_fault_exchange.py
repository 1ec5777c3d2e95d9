"""The four-chip training mix with the exchange between chips left out:
the repair's reshard does not move the state, which stays on the lost
chip too, and the run comes out not correct. Four virtual CPU devices, so
the test runs in a process of its own (XLA_FLAGS before JAX starts)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SCRIPT = r'''
import sys, dataclasses
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
import pytest
from bench import run
from bench.tests import tiny
mp = pytest.MonkeyPatch()
tiny.smoke_registry(mp)
if sys.argv[2] == "broken":
    from repro.dist import dataplane
    mp.setattr(dataplane.JaxDataPlane, "reshard_registered",
               lambda self, view: None)
cell = tiny.cell("mamba2-130m.train.drop1", "train4.drop1", seq_len=32,
                 ref_rows_per_block=4)
cell = dataclasses.replace(cell, config=dict(cell.config, limits={"train": {
    "loss_gap": 1e-3, "grad_gap": 0.1, "change_gap": 0.1,
    "fault_change_gap": 0.1, "fault_mu_gap": 0.1, "fault_nu_gap": 0.1}}))
sys.exit(run.main(["--workload", "tiny", "--seed", "77", "--seconds", "5",
                   "--trace", "0"], need_chip=False, cell=cell))
'''


@pytest.mark.parametrize("variant,correct", [("sound", True),
                                             ("broken", False)])
def test_four_chip_reshard_left_out_is_caught(variant, correct, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT), variant],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["device"]["count"] == 4
    assert out["correct"] is correct
    if not correct:
        assert out["compared"]["state_misplaced"]["value"] > 0
