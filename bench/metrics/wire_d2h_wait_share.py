"""Share of the traced window the wire codec's host spends waiting on
device-to-host reads: the union of the ``omc.codec.d2h`` host spans
(``api/codecs``) inside the window, over the window.  None when the program
writes no such span."""

from harness import trace

SPAN = "omc.codec.d2h"


def read(run):
    lo, hi = run.trace.window
    spans = [(s, e) for n, s, e in run.trace.host if n == SPAN]
    if not spans:
        return None
    waited = sum(e - s for s, e in trace.union(spans, lo, hi))
    return 100.0 * waited / (hi - lo)
