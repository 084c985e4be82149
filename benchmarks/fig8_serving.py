"""Serving-layer benchmark: throughput + cache hit-rate on a Zipf-repeated
query stream, cold vs warm, against the uncached ``retrieve_timeline``
baseline.

Real query traffic is heavily repeated (head queries dominate — modeled
here as Zipf(s=1.1) draws from the query pool), and on a ``ShardedTimeline``
every generation but the newest is immutable — so the serving cache
(``repro.serving``) should converge to serving G-1 of G generations from
host memory and computing only the newest. Rows:

    fig8,serving,uncached,docs=<n>,gens=<G>,<us_per_query>
    fig8,serving,cold,<us_per_query>,hit_rate=<r>
    fig8,serving,warm,<us_per_query>,hit_rate=<r>,speedup=x<s>,p50_ms=...
    fig8,serving,footprint,0.0,cache_kb=<c>,timeline_mb=<t>,bpe=<b>

``speedup`` is uncached/warm per-query time on the SAME stream — the
acceptance signal (>1x: the cache pays for itself on repeated traffic).
The footprint row carries the byte
accounting (cache occupancy + timeline footprint incl. manifest overhead)
that capacity planning needs.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (EngineConfig, ShardedTimeline, build_index,
                        new_generation, retrieve_timeline, timeline_footprint)
from repro.serving import RetrievalService

from .common import TH, TH_R, bench_corpus, row

N_GENS = 4
PER_GEN = 512
BATCH = 8
N_BATCHES = 12
ZIPF_S = 1.1


def _zipf_stream(n_queries: int, seed: int = 0) -> np.ndarray:
    """(N_BATCHES, BATCH) query indices, Zipf-weighted over the pool."""
    ranks = np.arange(1, n_queries + 1, dtype=np.float64)
    p = ranks ** -ZIPF_S
    p /= p.sum()
    rng = np.random.default_rng(seed)
    return rng.choice(n_queries, size=(N_BATCHES, BATCH), p=p)


def _time_stream(fn, batches) -> float:
    """Seconds per query for fn(batch) over the whole stream (min of 3)."""
    totals = []
    for _ in range(3):
        t0 = time.perf_counter()
        for b in batches:
            jax.block_until_ready(fn(b))
        totals.append(time.perf_counter() - t0)
    return min(totals) / (len(batches) * batches[0].shape[0])


def run() -> list[str]:
    corpus = bench_corpus("msmarco")
    queries = np.asarray(corpus.queries)
    cfg = EngineConfig(k=10, n_filter=256, n_docs=64, th=TH, th_r=TH_R)

    gen0, meta0 = build_index(
        jax.random.PRNGKey(1), corpus.doc_embs[:PER_GEN],
        corpus.doc_lens[:PER_GEN], n_centroids=512, m=16, nbits=8,
        plaid_b=2, kmeans_iters=4)
    timeline = ShardedTimeline.of((gen0, meta0))
    for g in range(1, N_GENS):
        lo = g * PER_GEN
        timeline = timeline.append(*new_generation(
            gen0, meta0, corpus.doc_embs[lo:lo + PER_GEN],
            corpus.doc_lens[lo:lo + PER_GEN]))

    stream = _zipf_stream(queries.shape[0])
    batches = [queries[idx] for idx in stream]

    # uncached baseline: the one-shot merge path on every batch
    t_base = _time_stream(
        lambda b: retrieve_timeline(timeline, jnp.asarray(b), cfg), batches)
    rows = [row(f"fig8,serving,uncached,docs={timeline.n_docs},"
                f"gens={len(timeline)}", t_base * 1e6)]

    # cold pass: empty cache fills as the stream arrives (single pass — a
    # cold cache is a one-time event, min-of-3 would measure a warm one)
    svc = RetrievalService(timeline, cfg)
    t0 = time.perf_counter()
    for b in batches:
        jax.block_until_ready(svc.query(b))
    t_cold = (time.perf_counter() - t0) / (len(batches) * BATCH)
    cold_hit = svc.cache.stats()["hit_rate"]
    rows.append(row("fig8,serving,cold", t_cold * 1e6,
                    f"hit_rate={cold_hit:.2f}"))

    # warm pass: same stream again — immutable generations now cached
    t_warm = _time_stream(lambda b: svc.query(b), batches)
    stats = svc.stats()
    rows.append(row(
        "fig8,serving,warm", t_warm * 1e6,
        f"hit_rate={stats['cache']['hit_rate']:.2f},"
        f"speedup=x{t_base / t_warm:.2f},"
        f"p50_ms={stats['warm_latency']['p50_ms']:.2f},"
        f"p99_ms={stats['warm_latency']['p99_ms']:.2f}"))

    fp = timeline_footprint(timeline)
    rows.append(row(
        "fig8,serving,footprint", 0.0,
        f"cache_kb={stats['cache']['bytes'] / 1024:.1f},"
        f"timeline_mb={fp['total_bytes'] / 2**20:.1f},"
        f"bpe={fp['bytes_per_embedding']:.1f},"
        f"bpe_actual={fp['bytes_per_embedding_actual']:.1f}"))
    return rows


def main() -> None:
    print("\n".join(run()))


if __name__ == "__main__":
    main()
