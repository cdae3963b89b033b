"""Share of the traced window in which nothing ran on the device (no
operation and no compiled program), while the cell's host loop drives it:
the round driver, the wire codec or the serving loop.  One reader for
``device_idle_share.<cell group>``: each name moves its own cells'
end-to-end metric."""

from harness import trace


def read(run):
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace.window_s)
