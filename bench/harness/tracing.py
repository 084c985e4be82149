"""From a profiler trace to intervals, and from intervals to numbers.

A trace is reduced once to a plain structure, kept as JSON-able lists:

    {"devices": {"<plane>": {"ops": [[name, start_s, end_s], ...],
                             "modules": [[name, start_s, end_s], ...]}},
     "host": [[name, start_s, end_s, thread], ...]}

``ops`` are the device's operations (the ``XLA Ops`` line), ``modules`` its
program launches (``XLA Modules``), and ``host`` every host event, the
benchmark's own ``bench.*`` annotations among them. All times are seconds
on the profiler's one clock. The reductions below work on that structure
only, so a small recorded trace can check them.
"""
from __future__ import annotations

import glob
import json
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load_xplane(trace_dir: str) -> dict:
    """Read the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"ops": [], "modules": []}
            names = [line.name for line in plane.lines]
            ops = OPS_LINE if OPS_LINE in names else next(
                (n for n in names if "Ops" in n), None)
            modules = MODULES_LINE if MODULES_LINE in names else next(
                (n for n in names if "Module" in n), None)
            for line in plane.lines:
                key = ("ops" if line.name == ops else
                       "modules" if line.name == modules else None)
                if key is None:
                    continue
                for e in line.events:
                    s = e.start_ns * 1e-9
                    dev[key].append([e.name, s, s + e.duration_ns * 1e-9])
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    s = e.start_ns * 1e-9
                    out["host"].append([e.name, s, s + e.duration_ns * 1e-9,
                                        line.name])
    return out


def save(trace: dict, path: str) -> None:
    """Write a reduced trace as JSON."""
    with open(path, "w") as f:
        json.dump(trace, f)


def load(path: str) -> dict:
    """Read a reduced trace written by :func:`save`."""
    with open(path) as f:
        return json.load(f)


def _pairs(intervals) -> list[tuple[float, float]]:
    """``[name, start, end, ...]`` rows or ``(start, end)`` pairs -> pairs."""
    out = []
    for i in intervals:
        if isinstance(i[0], str):
            out.append((float(i[1]), float(i[2])))
        else:
            out.append((float(i[0]), float(i[1])))
    return out


def merge(intervals) -> list[tuple[float, float]]:
    """Disjoint sorted union of intervals given as rows or pairs."""
    out: list[list[float]] = []
    for s, e in sorted(_pairs(intervals)):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a, b) -> float:
    """Seconds in both of two interval sets (each merged first)."""
    a, b = merge(a), merge(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def total(intervals) -> float:
    """Seconds covered by a set of intervals."""
    return sum(e - s for s, e in merge(intervals))


def busy_in(dev: dict, windows) -> float:
    """Device-busy seconds (union of its operations) inside ``windows``."""
    return overlap(dev["ops"], windows)


def module_time(dev: dict, substring: str) -> tuple[float, int]:
    """-> (seconds, launches) of the programs whose name has ``substring``."""
    hits = [e for e in dev["modules"] if substring in e[0]]
    return sum(e[2] - e[1] for e in hits), len(hits)


def top_ops(dev: dict, n: int = 10) -> list[list]:
    """The ``n`` operation names that took most device time."""
    acc: dict = {}
    for name, s, e in dev["ops"]:
        acc[name] = acc.get(name, 0.0) + (e - s)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(dev: dict, host: list, window, n: int = 10) -> list[list]:
    """The ``n`` longest stretches of ``window`` with no device operation,
    each named by the innermost host event running at its middle on the
    thread that holds the benchmark's annotations (``bench.*``)."""
    lo, hi = window
    busy = [(max(s, lo), min(e, hi)) for s, e in merge(dev["ops"])
            if e > lo and s < hi]
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    threads = {h[3] for h in host if h[0].startswith("bench.")}
    events = [h for h in host if h[3] in threads]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = 0.5 * (s + e)
        covering = [h for h in events if h[1] <= mid <= h[2]]
        name = (min(covering, key=lambda h: h[2] - h[1])[0] if covering
                else "no host event")
        out.append([name, e - s])
    return out


def annotations(host: list, names) -> list[list]:
    """Host events whose name is in ``names``, in start order."""
    return sorted((h for h in host if h[0] in names), key=lambda h: h[1])
