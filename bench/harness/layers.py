"""Per-layer quantities the metric readers in ``bench/metrics/`` share.

Each function takes a :class:`harness.runner.RunRecord` and returns a
number, or ``None`` when the run holds nothing to read it from (an
untraced run, no launches, or a trace whose host annotations do not line
up with the client's calls). A share of a roofline or of a peak is never
returned as 0 for want of data.
"""
from __future__ import annotations

import numpy as np

from . import tracing, workmodel

RETRIEVE_PROGRAM = "_retrieve_jit"    # the engine's jitted entry point
CALL_ANNOTATIONS = ("bench.submit", "bench.poll", "bench.flush")


def _answered(run) -> int:
    return int(np.sum(run.answered))


def queue_wait_ms(run, pct: float):
    """Percentile of due time -> start of the call whose flush answered
    the query, in ms (host clock)."""
    ok = run.answered
    if not np.any(ok):
        return None
    return float(np.percentile(1e3 * (run.start[ok] - run.due[ok]), pct))


def host_ms_per_query(run):
    """``service.execute`` span time outside its ``service.miss_execute``
    descendants (cache lookups, fingerprints, merges, restacks), per
    answered query, in ms."""
    by_id = {s["span_id"]: s for s in run.spans}
    execs = [s for s in run.spans if s["name"] == "service.execute"]
    n = _answered(run)
    if not execs or not n:
        return None

    def under_execute(span) -> bool:
        pid = span["parent_id"]
        while pid is not None and pid in by_id:
            if by_id[pid]["name"] == "service.execute":
                return True
            pid = by_id[pid]["parent_id"]
        return False

    inner = sum(s["duration_s"] for s in run.spans
                if s["name"] == "service.miss_execute" and under_execute(s))
    return 1e3 * (sum(s["duration_s"] for s in execs) - inner) / n


def retrieve_launches(run) -> list:
    """Per traced device, the retrieve program's launch events that start
    inside the measured window."""
    if run.trace is None or run.trace_window is None:
        return []
    lo, hi = run.trace_window
    return [[e for e in dev["modules"]
             if RETRIEVE_PROGRAM in e[0] and lo <= e[1] < hi]
            for dev in run.trace["devices"].values()]


def device_ms_per_query(run):
    """Device time of the retrieve program's launches over the queries
    they served, averaged over the traced devices, in ms."""
    launches = retrieve_launches(run)
    n = _answered(run)
    if not launches or not any(launches) or not n:
        return None
    per_dev = [sum(e[2] - e[1] for e in ls) for ls in launches]
    return 1e3 * float(np.mean(per_dev)) / n


def launch_batches(run) -> list:
    """The batch size of each retrieve launch, from the service's
    ``service.execute`` spans in order."""
    return [int(s["attrs"]["batch"]) for s in
            sorted((s for s in run.spans if s["name"] == "service.execute"),
                   key=lambda s: s["start"])]


def roofline_share(run):
    """-> (percent, binding bound): the work model's least time over the
    measured device time of the retrieve launches, or None."""
    launches = retrieve_launches(run)
    if not launches or not launches[0]:
        return None
    batches = launch_batches(run)
    ls = sorted(launches[0], key=lambda e: e[1])
    if len(batches) != len(ls):
        return None
    least, bounds = 0.0, set()
    for b in batches:
        t, bound = workmodel.least_time(run.cfg, b, run.peaks)
        least += t
        bounds.add(bound)
    spent = sum(e[2] - e[1] for e in ls)
    return 100.0 * least / spent, "/".join(sorted(bounds))


def flush_windows(run):
    """Profiler-clock intervals of the client calls that answered queries
    (their flushes), or None when the annotations do not match the calls."""
    if run.trace is None:
        return None
    anns = tracing.annotations(run.trace["host"], set(CALL_ANNOTATIONS))
    if len(anns) != len(run.calls):
        return None
    return [(a[1], a[2]) for a, c in zip(anns, run.calls) if c[3] > 0]


def idle_share_in(run, windows):
    """100 * (1 - device busy inside ``windows`` / their length), averaged
    over the traced devices."""
    if not windows or run.trace is None or not run.trace["devices"]:
        return None
    span = tracing.total(windows)
    if span <= 0:
        return None
    busy = [tracing.busy_in(dev, windows)
            for dev in run.trace["devices"].values()]
    return 100.0 * (1.0 - float(np.mean(busy)) / span)
