"""Device idle share over the traced window, in %: 1 - the union of the
device's operations over the window (flushes fill it under a backlog).
Moves qps."""
from harness import layers


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    if run.trace_window is None:
        return None
    return layers.idle_share_in(run, [run.trace_window])
