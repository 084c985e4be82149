"""The retrieve program's share of its roofline, in %: the benchmark's work
model's least time for each launch (harness/workmodel.py) over the
launch's device time in the profiler trace. Moves latency_p50_ms."""
from harness import layers


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    share = layers.roofline_share(run)
    return None if share is None else share[0]
