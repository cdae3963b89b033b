"""Share of serving's device time under ``omc.moe`` (``models/deepseek_v2``):
the whole expert layer, router (``omc.moe.route``), held routed experts'
grouped matmuls (``omc.moe.experts``) and shared experts (``omc.moe.shared``).
Device time of the operations, enclosing no other, whose ``tf_op`` path
holds the scope, over all such operations in the traced window
(``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.share(run, "omc.moe")
