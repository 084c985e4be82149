"""The sharded retrieve program's share of its roofline, in %: the
benchmark's work model's least time for each launch's batch over one
chip's shard (harness/workmodel.py; batches from the service.execute
spans) over the program's device time in the profiler trace, averaged
over the chips. Moves latency_p50_ms."""
from harness import shardmap


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    share = shardmap.roofline_share(run)
    return None if share is None else share[0]
