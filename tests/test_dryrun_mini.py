"""Integration test of the full dry-run path on a miniature mesh.

Runs in a subprocess so ``--xla_force_host_platform_device_count`` never
leaks into the main test session (smoke tests must see 1 device). Covers:
input_specs -> cell_shardings -> jit(in/out shardings, donation) -> lower
-> compile -> loop-aware HLO analysis, for one train and one decode cell
on a (2,2,2) pod/data/model mesh with a reduced-but-multi-layer config.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax

    from repro.configs.base import ShapeSpec, TrainConfig
    from repro.configs.registry import get_smoke_config
    from repro.launch import hlo_stats
    from repro.launch.mesh import make_named_mesh
    from repro.launch.steps import cell_shardings, input_specs, step_fn_for

    mesh = make_named_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke_config("llama3.2-3b").replace(n_layers=4)
    out = {}
    for shape in (ShapeSpec("mini_train", 64, 8, "train"),
                  ShapeSpec("mini_decode", 64, 8, "decode")):
        specs = input_specs(cfg, shape)
        in_sh, out_sh = cell_shardings(cfg, shape, mesh, specs)
        fn = step_fn_for(cfg, shape, TrainConfig())
        with jax.sharding.set_mesh(mesh):
            jitted = jax.jit(fn, in_shardings=tuple(in_sh[k] for k in specs),
                             out_shardings=out_sh)
            compiled = jitted.lower(*specs.values()).compile()
        cost = hlo_stats.analyze(compiled.as_text(), 8)
        mem = compiled.memory_analysis()
        out[shape.name] = {
            "flops": cost.flops,
            "wire": cost.coll.total_wire_bytes,
            "arg_bytes": mem.argument_size_in_bytes,
        }
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def mini_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # 8 fake CPU devices only: on a host with libtpu the child must not
    # try the chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_mini_train_cell_compiles(mini_result):
    r = mini_result["mini_train"]
    assert r["flops"] > 1e6            # fwd+bwd+opt actually lowered
    assert r["wire"] > 0               # gradient reduction present
    # params sharded: per-device arg bytes well under the full model
    assert r["arg_bytes"] > 0


def test_mini_decode_cell_compiles(mini_result):
    r = mini_result["mini_decode"]
    assert r["flops"] > 0
    # decode step is one token: orders less compute than the train step
    assert r["flops"] < mini_result["mini_train"]["flops"] / 10
