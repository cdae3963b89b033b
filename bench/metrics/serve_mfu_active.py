"""Model FLOPs utilization of serving an expert model: 2·N_active FLOPs for
every token delivered in the traced window, N_active the parameters one
token uses on this chip (``harness/latent_moe.active_params``: attention,
dense layers, router, shared experts, the routed experts' expected share of
those held here, head), over the window's host-clock seconds and the chips'
bf16 peak."""

from harness import latent_moe


def read(run):
    c = run.counts
    flops = latent_moe.decode_flops_per_token(run.cell.config) * c["tokens"]
    return 100.0 * flops / c["seconds"] / (run.peaks["bf16_flops"] * run.chips)
