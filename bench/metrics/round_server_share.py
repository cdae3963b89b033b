"""Share of the round program's device time under ``omc.server_step``
(``engine.make_round_fn``): the server step: the fused aggregation kernel or
the float32 mean, interpolation, requantization, the loss reduction and
the error-feedback scatter.  Device time of the operations, enclosing
no other, whose ``tf_op`` path holds the scope, over all such operations in
the traced window (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.share(run, "omc.server_step")
