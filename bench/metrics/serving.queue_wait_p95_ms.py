"""Queue wait, 95th percentile, in ms: from a query's due time to the start
of the client call whose flush answered it (host clock). Moves
latency_p95_ms."""
from harness import layers


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return layers.queue_wait_ms(run, 95)
