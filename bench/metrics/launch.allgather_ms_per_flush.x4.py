"""Device time of the all-gather that merges the shards' top k, per flush,
in ms: the all-gather operations (all-gather, all-gather-start/-done)
inside the shard_map program's launches in the profiler trace, per launch
(one a flush), on the chip where it is largest. It includes the wait for
the slowest shard. Moves latency_p50_ms."""
from harness import shardmap


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return shardmap.allgather_ms_per_launch(run)
