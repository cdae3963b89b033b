"""Model FLOPs utilization of serving: 2·N FLOPs for every token delivered in
the traced window, over the window's host-clock seconds and the chips' bf16
peak."""

from harness import counters


def read(run):
    c = run.counts
    flops = counters.decode_flops_per_token(c["params"]) * c["tokens"]
    return 100.0 * flops / c["seconds"] / (run.peaks["bf16_flops"] * run.chips)
