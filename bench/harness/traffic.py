"""Arrival schedules from a traffic mix and a seed.

Two arrival processes, named by the mix's ``arrival`` key:

* ``poisson``: an open loop at ``rate_qps``. The inter-arrival gaps are
  exponential draws from the mix's ``shape_seed``, rescaled so that
  ``round(rate_qps * seconds)`` arrivals fill the window exactly. The
  schedule is the same for every run seed: the order of the gaps sets the
  bursts, and with them the latency tail (on a TPU v5e, with the gaps
  permuted per seed, the 95th percentile of six seeds spread by 18-20%
  while two runs of one seed differed by 0.3-7%), so the seed draws the
  corpus and the queries and leaves the arrivals alone.
* ``backlog``: ``backlog`` queries, all due at t=0, more than the window
  can drain; the client submits them as fast as the service takes them.
"""
from __future__ import annotations

import numpy as np

from .indexgen import host_rng


def schedule(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in seconds from the window's start, ascending."""
    kind = traffic["arrival"]
    if kind == "poisson":
        n = max(1, int(round(traffic["rate_qps"] * seconds)))
        gaps = host_rng(traffic["shape_seed"], 3).exponential(1.0, n)
        gaps = gaps * (seconds / gaps.sum())
        return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if kind == "backlog":
        return np.zeros(int(traffic["backlog"]))
    raise ValueError(f"unknown arrival process {kind!r}")


def warm_batch_sizes(traffic: dict) -> list[int]:
    """The flush sizes the window can send: every size up to ``max_batch``
    under an open loop (deadline flushes), only full batches under a
    backlog."""
    if traffic["arrival"] == "backlog":
        return [int(traffic["max_batch"])]
    return list(range(1, int(traffic["max_batch"]) + 1))
