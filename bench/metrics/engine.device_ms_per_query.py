"""Device time of the engine's retrieve program per answered query, in ms:
its launches' durations in the profiler trace over the queries they
served. Moves latency_p50_ms."""
from harness import layers


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return layers.device_ms_per_query(run)
