"""Device idle share inside flushes, in %: 1 - device-busy time inside the
client calls that answered queries, over their length (profiler trace,
on the clock of the benchmark's own annotations). Idle time while no
query is due says nothing at a fixed offered rate and is left out. Moves
latency_p50_ms."""
from harness import layers


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return layers.idle_share_in(run, layers.flush_windows(run))
