"""Share of serving's device time under ``omc.mla`` (``models/deepseek_v2``):
latent attention, its projections, the rotary parts and, at decode, the
absorbed scores and context over the latent cache.  Device time of the
operations, enclosing no other, whose ``tf_op`` path holds the scope, over
all such operations in the traced window (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.share(run, "omc.mla")
