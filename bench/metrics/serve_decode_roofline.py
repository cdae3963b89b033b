"""Roofline share of the decode step (``models/deepseek_v2.decode_step``
through ``ServeSession``): the least HBM bytes of the traced window's decode
steps (``harness/latent_moe.decode_window_bytes``: the stored leaves once at
their containers' bytes, the latent cache to its current length), over the
chip's HBM bandwidth, divided by the summed device time of the compiled
decode programs.  Steps and cache lengths follow from the window's counts
and the traffic's fixed order.  None when the trace holds no such program."""

from harness import latent_moe, trace

PROGRAMS = r"^jit_decode_fn\("


def read(run):
    seconds = trace.module_s(run.trace, PROGRAMS)
    if seconds <= 0:
        return None
    c = run.counts
    moved = latent_moe.decode_window_bytes(run.cell.config, run.cell.traffic, c["batches"],
                                           c["tokens"])
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / seconds
