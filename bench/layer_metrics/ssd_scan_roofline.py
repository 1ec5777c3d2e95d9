"""ssd_scan's share of its roofline (bench/counts/ssd_scan.py), one call per
layer per prefill."""
from bench import kernel_roofline
from bench.counts import ssd_scan


def read(run):
    m = run["cell"].model
    di = m["ssm_expand"] * m["d_model"]

    def cost(batch):
        f, n = ssd_scan.cost(batch=batch, seq_len=run["prompt_len"],
                             heads=di // m["ssm_head_dim"],
                             head_dim=m["ssm_head_dim"], state=m["ssm_state"],
                             groups=m["ssm_ngroups"], chunk=m["ssm_chunk"])
        return m["n_layers"] * f, m["n_layers"] * n

    return kernel_roofline.share(run, "ssd_scan", cost)
