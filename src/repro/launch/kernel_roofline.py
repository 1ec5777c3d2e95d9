import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Kernel-adjusted roofline: what the Pallas flash-attention kernel buys.

The dry-run lowers the blocked-XLA attention (Pallas only lowers on TPU), so
its memory term includes the (block_q, block_k) score tensors round-tripping
HBM between the two attention matmuls. On TPU the flash kernel keeps those
blocks in VMEM (see kernels/flash_attention.py — ~1.4 MB working set), so
the honest TPU roofline subtracts the attention-interior traffic and keeps
only q/k/v/o.

This tool attributes per-instruction HBM bytes (loop-scaled) to the
attention interior via op_name metadata (the einsum labels 'bhqs'/'bqhd'
and the online-softmax ops between them) and reports both terms.

  PYTHONPATH=src python -m repro.launch.kernel_roofline --arch llama3.2-3b \
      --shape train_4k
"""
import argparse
import re
import sys

import jax

from repro.configs.base import TrainConfig
from repro.configs.registry import ARCH_IDS, get_config, get_shape
from repro.launch import hlo_stats
from repro.launch.hw import DEFAULT_CHIP
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import cell_shardings, input_specs, step_fn_for

def attention_interior_bytes(hlo: str, n_dev: int, block_q: int,
                             block_k: int) -> float:
    """HBM bytes of score-block-shaped tensors inside the attention scans.

    The flash kernel's VMEM residency removes exactly these: every
    (.., block_q, block_k)-shaped intermediate (scores, masks, exp, probs)
    between the two attention matmuls. q/k/v block streaming stays — the
    kernel re-reads KV per query block just like the XLA path.
    """
    mod = hlo_stats.HloModule(hlo)
    # computations that belong to the blocked-attention kv sweep
    attn_comps = {
        name for name, instrs in mod.computations.items()
        if any("bhqs" in i.line or "bhqd" in i.line for i in instrs)
    }
    sig = re.compile(rf"\[[\d,]*{block_q},{block_k}\]")
    total = 0.0
    for r in hlo_stats.contributors(hlo, n_dev, top=10 ** 6):
        if r["comp"] in attn_comps and sig.search(r["shape"]):
            total += r["bytes"]
    return total


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3.2-3b")
    ap.add_argument("--shape", default="train_4k")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    mesh = make_production_mesh()
    specs = input_specs(cfg, shape)
    in_sh, out_sh = cell_shardings(cfg, shape, mesh, specs)
    fn = step_fn_for(cfg, shape, TrainConfig())
    with jax.sharding.set_mesh(mesh):
        compiled = jax.jit(
            fn, in_shardings=tuple(in_sh[k] for k in specs),
            out_shardings=out_sh,
            donate_argnums=(0, 1) if shape.kind == "train" else None,
        ).lower(*specs.values()).compile()
    hlo = compiled.as_text()
    n_dev = mesh.devices.size
    cost = hlo_stats.analyze(hlo, n_dev)

    bq = min(cfg.attn_block_q, shape.seq_len)
    bk = min(cfg.attn_block_k, shape.seq_len)
    attn_bytes = attention_interior_bytes(hlo, n_dev, bq, bk)
    chip = DEFAULT_CHIP
    mem = cost.bytes / chip.hbm_bw
    mem_adj = (cost.bytes - attn_bytes) / chip.hbm_bw
    comp = cost.flops / chip.peak_flops_bf16
    coll = cost.coll.total_wire_bytes / chip.ici_bw

    print(f"[kernel-roofline] {args.arch} x {args.shape} (single-pod, per-device)")
    print(f"  attention-interior HBM traffic: {attn_bytes:.3e} B "
          f"({100 * attn_bytes / cost.bytes:.1f}% of all bytes)")
    print(f"  memory term   blocked-XLA : {mem * 1e3:10.1f} ms")
    print(f"  memory term   Pallas-flash: {mem_adj * 1e3:10.1f} ms "
          f"({mem / mem_adj:.2f}x)")
    print(f"  compute {comp * 1e3:.1f} ms | collective {coll * 1e3:.1f} ms")
    bound = max(comp, mem, coll)
    bound_adj = max(comp, mem_adj, coll)
    print(f"  step bound: {bound * 1e3:.1f} -> {bound_adj * 1e3:.1f} ms "
          f"({bound / bound_adj:.2f}x); roofline fraction "
          f"{comp / bound:.3f} -> {comp / bound_adj:.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
