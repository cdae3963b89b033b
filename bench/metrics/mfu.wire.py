"""The whole wire round trip's share of the chip's peak: the least HBM bytes
of packing and unpacking every compressed leaf of every round trip in the
traced window, over the window's host-clock seconds and the chips' HBM
bandwidth.  By bytes, since the codec does next to no arithmetic; it bounds
what a faster bitpack kernel can show end to end."""

from harness import counters


def read(run):
    c = run.counts
    one_way = sum(counters.packbits_bound_bytes(n, w) for n, w in c["leaves"])
    return 100.0 * 2 * one_way * c["trips"] / c["seconds"] / (
        run.peaks["hbm_bytes_per_s"] * run.chips)
