"""Device idle share inside flushes while the host is blocked on the
device, in %: the idle instants inside the client calls that answered
queries whose innermost program span (repro.obs spans mirrored into the
profiler trace) is service.device_wait or service.fetch, averaged over
the device planes that ran operations. Moves latency_p50_ms."""
from harness import spans


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    split = spans.idle_split(run)
    return None if split is None else split["blocked"]
