"""Device time of the sharded retrieve program per answered query, in ms:
the launches of the jitted shard_map program (_shardmap_retrieve) in the
profiler trace's window, summed per chip, averaged over the chips that ran
operations, over the queries answered. Moves latency_p50_ms."""
from harness import shardmap


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return shardmap.device_ms_per_query(run)
