"""Share of the round program's device time under ``omc.client``
(``engine.make_round_fn``): the clients: data drawn in the program, the vmapped
local steps, and the stacking of client models and losses.  Device time of the operations, enclosing
no other, whose ``tf_op`` path holds the scope, over all such operations in
the traced window (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.share(run, "omc.client")
