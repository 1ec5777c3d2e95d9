"""flash_attention's share of its roofline (bench/counts/flash_attention.py),
one call per layer per prefill."""
from bench import kernel_roofline
from bench.counts import flash_attention


def read(run):
    m = run["cell"].model

    def cost(batch):
        f, n = flash_attention.cost(
            batch=batch, seq_len=run["prompt_len"], heads=m["n_heads"],
            kv_heads=m["n_kv_heads"], head_dim=m["head_dim"],
            window=m["hybrid_attn_window"])
        return m["n_layers"] * f, m["n_layers"] * n

    return kernel_roofline.share(run, "flash_attention", cost)
