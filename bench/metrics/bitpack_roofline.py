"""Roofline share of the bitpack kernels (``kernels/bitpack`` pack and
unpack): the least HBM bytes to pack and to unpack every compressed leaf of
every round trip in the traced window, over the chip's HBM bandwidth,
divided by the summed device time of the compiled pack and unpack programs
(``kernels/ops.pack_bits`` / ``unpack_bits``).  Their time, not the Pallas
custom call's alone: XLA stages each chunk into VMEM around the call, so the
call itself never touches HBM."""

from harness import counters, trace

PROGRAMS = r"^jit_(un)?pack_bits\("


def read(run):
    seconds = trace.module_s(run.trace, PROGRAMS)
    if seconds <= 0:
        return None
    c = run.counts
    one_way = sum(counters.packbits_bound_bytes(n, w) for n, w in c["leaves"])
    return 100.0 * 2 * one_way * c["trips"] / run.peaks["hbm_bytes_per_s"] / seconds
