"""Batcher wait, 95th percentile, in ms: every drained query's wait from
its submit to the drain that took it (the waits_s attribute of the
program's batcher.queue_wait records), the service's own share of the
wait that serving.queue_wait_p95_ms times from the client. Moves
latency_p95_ms."""
from harness import spans


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return spans.batcher_wait_ms(run, 95)
