"""Device idle share inside flushes charged to the host, in %, under a
backlog: the idle instants inside the client calls that answered queries
whose innermost program span (repro.obs spans mirrored into the profiler
trace) neither waits for the device nor copies from it, or that no
program span covers, averaged over the device planes that ran
operations. Moves qps (the backlog cells)."""
from harness import spans


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    split = spans.idle_split(run)
    return None if split is None else split["host"]
