"""Model FLOP/s of the window's training over the chips' bf16 peak, in %:
forward and backward FLOPs per token (bench/counts/model.py, recompute not
counted) times tokens per second."""
from bench import harness
from bench.counts import model


def read(run):
    m = run["cell"].model
    peak = harness.peaks(run["ctx"].devices[0].device_kind)["bf16_flops_per_s"]
    flops = model.train_flops_per_token(m, run["seq_len"]) * run["tokens"]
    return 100.0 * flops / run["window_s"] / (run["chips"] * peak)
