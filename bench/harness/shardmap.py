"""What a traced run says of the program's sharded path.

A cell on several chips is served by ``repro.launch.serve``: every flush
launches the jitted ``shard_map`` program, ``_shardmap_retrieve``, on each
chip; inside it each chip runs the engine over its own shard, and an
all-gather of every shard's top k, with a top k over the gathered ones,
merges them (``jax.named_scope("launch.merge")``). The functions here read
those launches and those all-gathers from the reduced trace
(:mod:`harness.tracing`), on the device planes that ran operations
(:func:`harness.spans.devices`), and return ``None`` when the trace holds
no launch of that program: an untraced run, one chip, or a program that
does not give its sharded program that name.
"""
from __future__ import annotations

import bisect

import numpy as np

from . import layers, spans, tracing, workmodel

PROGRAM = "_shardmap_retrieve"      # the shard_map program's jitted name
ALL_GATHER = "all-gather"           # all-gather, all-gather-start/-done


def launches(run) -> list:
    """-> [(device plane, its program launches that start inside the
    measured window)] over the planes that ran operations; empty when no
    plane launched the program."""
    if run.trace is None or run.trace_window is None:
        return []
    lo, hi = run.trace_window
    out = [(dev, sorted((e for e in dev["modules"]
                         if PROGRAM in e[0] and lo <= e[1] < hi),
                        key=lambda e: e[1]))
           for dev in spans.devices(run.trace)]
    return out if any(ls for _, ls in out) else []


def _own_name(op_name: str) -> str:
    """An operation's own name. A TPU trace names an operation by its
    instruction's text, ``%all-gather.2 = f32[4,1,100]... all-gather(...)``,
    whose operands name other operations (a ``reduce`` of the gathered
    scores names ``%all-gather.2``); a plainer trace by ``all-gather.2``."""
    return op_name.split(" = ", 1)[0]


def _seconds(events) -> float:
    return float(sum(e[2] - e[1] for e in events))


def device_ms_per_query(run):
    """The program's device time in the window, averaged over the chips,
    per answered query, in ms."""
    per = launches(run)
    n = int(np.sum(run.answered))
    if not per or not n:
        return None
    return 1e3 * float(np.mean([_seconds(ls) for _, ls in per])) / n


def _inside(ops, launched) -> list:
    """The operations that start inside one of the sorted launches."""
    starts = [e[1] for e in launched]
    out = []
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[1] < launched[i][2]:
            out.append(op)
    return out


def allgather_ms_per_launch(run):
    """Device time of the all-gather operations inside the program's
    launches, per launch, on the chip where it is largest, in ms. The
    time includes the wait for the slowest shard; None where no all-gather
    operation ran inside a launch."""
    per = []
    for dev, ls in launches(run):
        if not ls:
            continue
        gathers = _inside((op for op in dev["ops"]
                           if ALL_GATHER in _own_name(op[0])), ls)
        if gathers:
            per.append(_seconds(gathers) / len(ls))
    return 1e3 * max(per) if per else None


def roofline_share(run):
    """-> (percent, binding bound): the work model's least time of each
    launch's batch over the program's device time, averaged over the
    chips, or None. The work model counts one chip's shard
    (``n_passages / chips``), the work each chip does in a launch."""
    per = launches(run)
    if not per:
        return None
    batches = layers.launch_batches(run)
    if not batches or any(len(ls) != len(batches) for _, ls in per):
        return None
    least, bounds = 0.0, set()
    for b in batches:
        t, bound = workmodel.least_time(run.cfg, b, run.peaks)
        least += t
        bounds.add(bound)
    spent = float(np.mean([_seconds(ls) for _, ls in per]))
    if spent <= 0:
        return None
    return 100.0 * least / spent, "/".join(sorted(bounds))


def idle_share_in_flush(run):
    """100 * (1 - device-busy inside the client calls that answered
    queries / their length), averaged over the chips that ran operations,
    in a run that launched the program."""
    if not launches(run):
        return None
    windows = layers.flush_windows(run)
    if not windows:
        return None
    span = tracing.total(windows)
    if span <= 0:
        return None
    busy = [tracing.busy_in(dev, windows) for dev in spans.devices(run.trace)]
    return 100.0 * (1.0 - float(np.mean(busy)) / span)
