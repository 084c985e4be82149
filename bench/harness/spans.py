"""The program's own spans in a traced run, and what they say.

While the program's ``repro.obs`` tracer is enabled, each of its spans is
also a profiler annotation, so the reduced trace (:mod:`harness.tracing`)
holds the program's ``service.*`` and ``engine.*`` events on the host
thread of the benchmark's ``bench.*`` annotations, on the device's clock.
A program that does not mirror its spans leaves none there, and every
function here then returns ``None``.

Each device-idle instant inside a flush is charged to the innermost
program event covering it: to ``blocked`` when that event waits for the
device or copies from it (:data:`BLOCKED`), otherwise, with no program
event covering it as well, to ``host``. The two shares add up to the
flush's idle share over the devices that ran operations. The profiler
also writes device planes that hold no operation at all (a TPU host's
``/device:CUSTOM:Megascale Trace``); they are no device, and counting
one as a device idle all the time would add its whole length to every
idle share, so they are left out here.
"""
from __future__ import annotations

import bisect

import numpy as np

from . import layers, tracing

PROGRAM_PREFIXES = ("service.", "engine.")
BLOCKED = frozenset({"service.device_wait", "service.fetch"})


def devices(trace: dict) -> list:
    """The reduced trace's device planes that ran operations."""
    return [dev for dev in trace["devices"].values() if dev["ops"]]


def program_events(host: list) -> list:
    """The program's events on the threads of the ``bench.*`` annotations."""
    threads = {h[3] for h in host if h[0].startswith("bench.")}
    return [h for h in host
            if h[3] in threads and h[0].startswith(PROGRAM_PREFIXES)]


def blocked_intervals(events: list) -> list[tuple[float, float]]:
    """Merged stretches in which the innermost covering event (the one
    that started last) is in :data:`BLOCKED`."""
    bounds = sorted({t for e in events for t in (e[1], e[2])})
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    active: list = []
    out = []
    i = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while i < len(order) and order[i][1] <= lo:
            active.append(order[i])
            i += 1
        active = [e for e in active if e[2] > lo]
        if active and max(active, key=lambda e: (e[1], -e[2]))[0] in BLOCKED:
            out.append((lo, hi))
    return tracing.merge(out)


def idle_intervals(dev: dict, windows) -> list[tuple[float, float]]:
    """Stretches of ``windows`` in which no operation runs on ``dev``."""
    busy = tracing.merge(dev["ops"])
    ends = [e for _, e in busy]
    out = []
    for lo, hi in tracing.merge(windows):
        t = lo
        j = bisect.bisect_right(ends, lo)
        while j < len(busy) and busy[j][0] < hi:
            if busy[j][0] > t:
                out.append((t, busy[j][0]))
            t = max(t, busy[j][1])
            j += 1
        if t < hi:
            out.append((t, hi))
    return out


def idle_split(run):
    """-> {"host": %, "blocked": %} of the flush windows' length, averaged
    over the devices that ran operations, or None with nothing to read."""
    windows = layers.flush_windows(run)
    if not windows or not devices(run.trace):
        return None
    events = program_events(run.trace["host"])
    span = tracing.total(windows)
    if not events or span <= 0:
        return None
    blocked = blocked_intervals(events)
    host_s, blocked_s = [], []
    for dev in devices(run.trace):
        idle = idle_intervals(dev, windows)
        b = tracing.overlap(idle, blocked)
        blocked_s.append(b)
        host_s.append(tracing.total(idle) - b)
    return {"host": 100.0 * float(np.mean(host_s)) / span,
            "blocked": 100.0 * float(np.mean(blocked_s)) / span}


def batcher_wait_ms(run, pct: float):
    """Percentile of every drained query's submit -> drain wait in the
    batcher (the ``waits_s`` of the ``batcher.queue_wait`` records), in
    ms."""
    waits = [w for s in run.spans if s["name"] == "batcher.queue_wait"
             for w in s["attrs"].get("waits_s", ())]
    if not waits:
        return None
    return float(np.percentile(1e3 * np.asarray(waits), pct))
