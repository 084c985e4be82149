"""Host time of the service per answered query, in ms: ``service.execute``
spans minus their ``service.miss_execute`` children (repro.obs spans of a
traced run). Moves latency_p50_ms."""
from harness import layers


def read(run):
    """The metric's value for one run, or None when it has nothing to read."""
    return layers.host_ms_per_query(run)
