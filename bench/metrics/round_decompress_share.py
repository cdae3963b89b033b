"""Share of the round program's device time under ``omc.decompress``
(``engine.make_round_fn``): the server's decompress of its storage to float32
(``decompress_tree``).  Device time of the operations, enclosing
no other, whose ``tf_op`` path holds the scope, over all such operations in
the traced window (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.share(run, "omc.decompress")
