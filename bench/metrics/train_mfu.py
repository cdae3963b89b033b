"""Model FLOPs utilization of the round program: 6·N FLOPs per frame of every
client sample trained in the traced window, over the window's host-clock
seconds and the chips' bf16 peak."""

from harness import counters


def read(run):
    c = run.counts
    flops = counters.train_flops_per_sample(c["params"], c["frames"]) * c["samples"]
    return 100.0 * flops / c["seconds"] / (run.peaks["bf16_flops"] * run.chips)
