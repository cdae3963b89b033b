"""Share of serving's device time under ``omc.materialize``
(``OMCMaterializer``): the decode of the weight codes, the PVT affine and
the cast, as far as XLA kept them in fusions of their own.  Device time of
the operations, enclosing no other, whose ``tf_op`` path holds the scope,
over all such operations in the traced window (``harness/scopes.py``)."""

from harness import scopes


def read(run):
    return scopes.share(run, "omc.materialize")
