"""Roofline share of the fused aggregation kernel (``kernels/agg``): the least
HBM bytes of one fused server round, times the round programs in the traced
window whose kernel calls the trace kept in full, over the chip's HBM
bandwidth, divided by the kernel's summed device time in those programs.
The kernel moves bytes and does next to no arithmetic, so bandwidth bounds
it."""

from harness import counters, trace

KERNEL = r"^fused_aggregate[\w.-]* custom-call$"


def read(run):
    rounds, seconds = trace.kernel_runs(run.trace, KERNEL)
    if rounds <= 0 or seconds <= 0:
        return None
    c = run.counts
    per_round = sum(counters.fused_aggregate_bound_bytes(c["cohort"], n, c["container_bytes"])
                    for n in c["selected_sizes"])
    return 100.0 * per_round * rounds / run.peaks["hbm_bytes_per_s"] / seconds
