"""The least work any EMVB implementation does per launch of B queries.

What a retrieve launch must do at the least, whatever its implementation:

* read the centroid table once: n_c * d * 4 bytes;
* compute the centroid scores: 2 * B * n_q * d * n_c operations (at the
  bf16 peak, the precision a float32 matmul runs at on the chip);
* per query, read the centroid ids of the ``n_filter`` pre-filter
  survivors (n_filter * cap * 4 bytes) and the PQ codes of the ``n_docs``
  late-interaction passages (n_docs * cap * m bytes).

Phase 2's scan is left out: its least work depends on how many candidates
a query has, so a PR that narrows it could push the share over 100%. The
least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s.
"""
from __future__ import annotations


def least_work(cfg: dict, batch: int) -> tuple[float, float]:
    """-> (operations, bytes) of one launch of ``batch`` queries."""
    eng = cfg["engine"]
    n_c, d, cap, m = cfg["n_centroids"], cfg["d"], cfg["cap"], cfg["m"]
    n_docs = cfg["n_passages"] // cfg.get("chips", 1)
    n_filter = min(eng["n_filter"], n_docs)
    n_late = min(eng["n_docs"], n_filter)
    ops = 2.0 * batch * eng["n_q"] * d * n_c
    nbytes = n_c * d * 4.0 + batch * (n_filter * cap * 4.0
                                      + n_late * cap * m * 1.0)
    return ops, nbytes


def least_time(cfg: dict, batch: int, peaks: dict) -> tuple[float, str]:
    """-> (seconds, "compute" or "memory": the bound that binds)."""
    ops, nbytes = least_work(cfg, batch)
    t_ops = ops / peaks["bf16_flops"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
