"""Model FLOP/s of the window's serving over the chips' bf16 peak, in %:
prefill and decode FLOPs of every micro-batch the window ran
(bench/counts/model.py) over the window."""
from bench import harness
from bench.counts import model


def read(run):
    m = run["cell"].model
    peak = harness.peaks(run["ctx"].devices[0].device_kind)["bf16_flops_per_s"]
    flops = sum(model.serve_request_flops(m, b, run["prompt_len"],
                                          run["decode_tokens"])
                for b in run["batches"])
    return 100.0 * flops / run["window_s"] / (run["chips"] * peak)
