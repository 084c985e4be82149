"""Device idle share inside flushes on several chips, in %: 1 - device-busy
time inside the client calls that answered queries, over their length,
averaged over the device planes that ran operations (the chips; a TPU
host's empty profiler plane is left out), in a run that launched the
shard_map program. Moves latency_p50_ms."""
from harness import shardmap


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return shardmap.idle_share_in_flush(run)
