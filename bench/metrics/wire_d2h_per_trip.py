"""Device-to-host reads of one wire round trip: the ``omc.codec.d2h`` host
spans (``api/codecs``: each compressed leaf's codes, scale and bias, each raw
leaf, each packed or unpacked chunk) that start in the traced window, over
the window's round trips.  None when the program writes no such span."""

SPAN = "omc.codec.d2h"


def read(run):
    lo, hi = run.trace.window
    reads = sum(1 for n, s, _ in run.trace.host if n == SPAN and lo <= s < hi)
    if reads == 0 or run.counts["trips"] <= 0:
        return None
    return reads / run.counts["trips"]
