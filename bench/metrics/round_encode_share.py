"""Share of the round program's device time under ``omc.transport_encode``
(``engine.make_round_fn``): the transport encode of the client uploads
(``transport_encode_stacked``, fused path).  Device time of the operations, enclosing
no other, whose ``tf_op`` path holds the scope, over all such operations in
the traced window (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.share(run, "omc.transport_encode")
