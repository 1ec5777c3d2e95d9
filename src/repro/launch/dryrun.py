import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder CPU devices stand in for 2 pods × 256 chips, the
full-size model is lowered from ShapeDtypeStructs (no allocation), and the
compiled artifact yields the roofline terms (memory_analysis / cost_analysis
/ parsed collective bytes).

  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES, TrainConfig, shape_applicable
from repro.configs.registry import ARCH_IDS, get_config, get_shape
from repro.launch import hlo_stats
from repro.launch.mesh import describe, make_production_mesh
from repro.launch.steps import cell_shardings, input_specs, step_fn_for


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             overrides: dict | None = None, verbose: bool = True) -> dict:
    """Lower+compile one cell; returns the JSON-able artifact record."""
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    specs = input_specs(cfg, shape)
    in_sh, out_sh = cell_shardings(cfg, shape, mesh, specs)
    fn = step_fn_for(cfg, shape, TrainConfig())

    donate = (0, 1) if shape.kind == "train" else \
             (1,) if shape.kind == "decode" else ()

    # jit+lower positionally: pjit rejects kwargs when in_shardings is given.
    args = tuple(specs.values())
    in_sh_tuple = tuple(in_sh[k] for k in specs)

    t0 = time.perf_counter()
    with jax.sharding.set_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=in_sh_tuple, out_shardings=out_sh,
                         donate_argnums=donate or None)
        lowered = jitted.lower(*args)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()

    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # loop-aware re-analysis: XLA's cost_analysis counts while bodies once
    cost = hlo_stats.analyze(hlo, n_dev)
    coll = cost.coll

    flops = cost.flops
    bytes_accessed = cost.bytes
    terms = hlo_stats.roofline_terms(
        flops, bytes_accessed, coll.total_wire_bytes)
    mflops = hlo_stats.model_flops(cfg, shape)

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": describe(mesh),
        "multi_pod": multi_pod,
        "n_devices": n_dev,
        "step_kind": shape.kind,
        "skipped": False,
        "overrides": overrides or {},
        "lower_s": round(t1 - t0, 3),
        "compile_s": round(t2 - t1, 3),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes_per_device": (
                mem.argument_size_in_bytes + mem.temp_size_in_bytes
                + mem.output_size_in_bytes - mem.alias_size_in_bytes),
        },
        "cost_analysis": {
            "flops_per_device": flops,
            "bytes_accessed_per_device": bytes_accessed,
            "xla_flops_unscaled": float(xla_cost.get("flops", 0.0)),
            "xla_bytes_unscaled": float(xla_cost.get("bytes accessed", 0.0)),
        },
        "collectives": coll.to_json(),
        "model_flops_global": mflops,
        "model_flops_per_device": mflops / n_dev,
        "useful_flops_ratio": (mflops / n_dev) / flops if flops else 0.0,
        "roofline": terms,
    }
    if verbose:
        ma = record["memory_analysis"]
        print(f"  lower {record['lower_s']:.1f}s compile {record['compile_s']:.1f}s | "
              f"args {ma['argument_bytes']/2**30:.2f} GiB temp {ma['temp_bytes']/2**30:.2f} GiB "
              f"peak {ma['peak_bytes_per_device']/2**30:.2f} GiB/dev")
        print(f"  flops/dev {flops:.3e}  bytes/dev {bytes_accessed:.3e}  "
              f"wire/dev {coll.total_wire_bytes:.3e}  "
              f"counts {coll.counts}")
        print(f"  roofline: compute {terms['compute_s']*1e3:.2f} ms | "
              f"memory {terms['memory_s']*1e3:.2f} ms | "
              f"collective {terms['collective_s']*1e3:.2f} ms  "
              f"-> {terms['dominant']}-bound, "
              f"useful-FLOP ratio {record['useful_flops_ratio']:.2f}")
    return record


def cell_list(args) -> list[tuple[str, str]]:
    if args.all:
        cells = []
        for arch in ARCH_IDS:
            for shape_name in SHAPES:
                cells.append((arch, shape_name))
        return cells
    if not args.arch or not args.shape:
        print("need --arch and --shape (or --all)", file=sys.stderr)
        sys.exit(2)
    return [(args.arch, args.shape)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true", help="every (arch x shape) cell")
    ap.add_argument("--multi-pod", action="store_true",
                    help="(2,16,16) pod/data/model mesh instead of (16,16)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun",
                    help="artifact directory (JSON per cell)")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg override key=value (repeatable), e.g. act_shard=batch_seq")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    args = ap.parse_args()

    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        overrides[k] = v

    meshes = [True, False] if args.both_meshes else [args.multi_pod]
    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    failures, n_ok, n_skip = [], 0, 0
    for arch, shape_name in cell_list(args):
        for mp in meshes:
            mesh_tag = "pod2" if mp else "pod1"
            name = f"{arch}_{shape_name}_{mesh_tag}"
            if args.tag:
                name += f"_{args.tag}"
            print(f"[dryrun] {name}")
            try:
                rec = run_cell(arch, shape_name, multi_pod=mp,
                               overrides=overrides or None)
            except Exception:
                traceback.print_exc()
                failures.append(name)
                continue
            (outdir / f"{name}.json").write_text(json.dumps(rec, indent=1))
            if rec.get("skipped"):
                n_skip += 1
                print(f"  SKIP: {rec['reason']}")
            else:
                n_ok += 1

    print(f"\n[dryrun] ok={n_ok} skipped={n_skip} failed={len(failures)}")
    for f in failures:
        print(f"  FAILED: {f}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
