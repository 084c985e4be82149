"""Serving subsystem contract (repro.serving):

* ``RetrievalService(timeline, cfg).query(q)`` is BIT-exact (ids AND score
  bits) to the uncached ``retrieve_timeline(timeline, q, cfg)`` — cold and
  warm, across both candidate modes, both megakernels, masked/pruned
  queries, partial-warm (mixed hit/miss lane) batches, and across
  ``add_passages``/``new_generation`` mutations;
* cache correctness under mutation: a warm cache never serves stale results
  after ``add_passages`` on the newest generation, and ``new_generation``
  keeps old-generation entries live (hit/miss counters asserted);
* LRU eviction under the byte budget, fingerprint key semantics, batcher
  pad/deadline behavior, and the metrics/footprint accounting.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EngineConfig, ShardedTimeline, build_index,
                        bytes_per_embedding, generation_footprint,
                        new_generation, prune_queries, retrieve_timeline,
                        timeline_footprint)
from repro.data.synthetic import make_corpus
from repro.serving import (LatencyStats, MicroBatcher, ResultCache,
                           RetrievalService, ServiceMetrics,
                           config_fingerprint, pad_query, query_fingerprint)

# Same constants as tests/test_store.py so the jit cache is shared.
CFG = EngineConfig(nprobe=8, th=0.2, th_r=0.4, n_filter=128, n_docs=48, k=10)

RETRIEVAL_CFGS = {
    "ref-score_all": CFG,
    "ref-compact": dataclasses.replace(CFG, candidate_mode="compact",
                                       cand_cap=600),
    "prefilter-megakernel": dataclasses.replace(
        CFG, use_kernels=True, fused_late_interaction=False),
    "pqinter-megakernel": dataclasses.replace(
        CFG, use_kernels=True, fused_prefilter=False),
    "fused-score_all": dataclasses.replace(CFG, use_kernels=True),
    "fused-compact": dataclasses.replace(CFG, use_kernels=True,
                                         candidate_mode="compact",
                                         cand_cap=600),
}


@pytest.fixture(scope="module")
def serve_corpus():
    # 800 docs: 500 in the initial timeline, 100 for add_passages, 200 for
    # new_generation; queries plant ground truth across the whole range.
    return make_corpus(3, n_docs=800, cap=24, min_len=8, n_queries=32,
                       n_topics=32)


@pytest.fixture(scope="module")
def base_timeline(serve_corpus):
    """Generations of 200/200/100 docs (the last one deliberately small and
    still growing — the add_passages target)."""
    c = serve_corpus
    idx0, m0 = build_index(jax.random.PRNGKey(0), c.doc_embs[:200],
                           c.doc_lens[:200], n_centroids=128, m=8, nbits=4,
                           kmeans_iters=3)
    tl = ShardedTimeline.of((idx0, m0))
    tl = tl.append(*new_generation(idx0, m0, c.doc_embs[200:400],
                                   c.doc_lens[200:400]))
    return tl.append(*new_generation(idx0, m0, c.doc_embs[400:500],
                                     c.doc_lens[400:500]))


# ---------------------------------------------------------------------------
# The acceptance contract: service == uncached retrieve_timeline, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(RETRIEVAL_CFGS))
def test_service_matches_timeline_cold_and_warm(serve_corpus, base_timeline,
                                                name):
    """Cold (all-miss) AND warm (all immutable generations cached) service
    results equal the uncached merge path, ids AND score bits, for both
    candidate modes and both megakernels."""
    cfg = RETRIEVAL_CFGS[name]
    q = jnp.asarray(serve_corpus.queries[:8])
    ref = retrieve_timeline(base_timeline, q, cfg)
    svc = RetrievalService(base_timeline, cfg)
    cold = svc.query(np.asarray(q))
    warm = svc.query(np.asarray(q))
    for res in (cold, warm):
        np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                      np.asarray(res.doc_ids))
        np.testing.assert_array_equal(np.asarray(ref.scores),
                                      np.asarray(res.scores))
    # the warm pass hit every immutable generation for every query
    assert svc.cache.hits == (len(base_timeline) - 1) * 8
    assert svc.metrics.warm_queries == 8


def test_service_masked_pruned_queries(serve_corpus, base_timeline):
    """The PR 3 masking contract threads through the cache: pruned queries
    + masks retrieve bit-identically, cold and warm."""
    qp, qm = prune_queries(jnp.asarray(serve_corpus.queries[:8]), keep=16)
    ref = retrieve_timeline(base_timeline, qp, CFG, qm)
    svc = RetrievalService(base_timeline, CFG)
    for _ in range(2):  # cold, then warm
        res = svc.query(np.asarray(qp), np.asarray(qm))
        np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                      np.asarray(res.doc_ids))
        np.testing.assert_array_equal(np.asarray(ref.scores),
                                      np.asarray(res.scores))
    assert svc.cache.hits > 0


@pytest.mark.parametrize("pad_miss_lane", [True, False],
                         ids=["padded-miss-lane", "tight-miss-lane"])
def test_service_partial_warm_batch(serve_corpus, base_timeline,
                                    pad_miss_lane):
    """A batch mixing cached and novel queries (hit lane + miss lane inside
    ONE generation) still merges bit-exactly — the engine is bit-invariant
    to batch composition, padded or tight miss lane alike."""
    c = serve_corpus
    svc = RetrievalService(base_timeline, CFG, pad_miss_lane=pad_miss_lane)
    svc.query(np.asarray(c.queries[:8]))                      # cache 0..7
    mix = np.concatenate([c.queries[4:8], c.queries[8:12]])   # half warm
    ref = retrieve_timeline(base_timeline, jnp.asarray(mix), CFG)
    res = svc.query(mix)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(res.doc_ids))
    np.testing.assert_array_equal(np.asarray(ref.scores),
                                  np.asarray(res.scores))
    # the warm half hit, the novel half missed (per immutable generation)
    assert svc.metrics.warm_queries == 4


# ---------------------------------------------------------------------------
# Cache correctness under mutation (the satellite the counters pin down)
# ---------------------------------------------------------------------------

def test_warm_cache_add_passages_not_stale(serve_corpus, base_timeline):
    """add_passages on the newest generation bumps its fingerprint: the
    very next query sees the new docs, while the old generations' cache
    entries keep serving (hit counters prove both)."""
    c = serve_corpus
    q = jnp.asarray(c.queries[:8])
    svc = RetrievalService(base_timeline, CFG)
    svc.query(np.asarray(q))                                  # cold fill
    svc.query(np.asarray(q))                                  # warm
    hits_before = svc.cache.hits
    assert hits_before == 16                                  # 2 gens x 8

    svc.add_passages(c.doc_embs[500:600], c.doc_lens[500:600])
    res = svc.query(np.asarray(q))
    # bit-exact vs the uncached path over the GROWN timeline
    ref = retrieve_timeline(svc.timeline, q, CFG)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(res.doc_ids))
    np.testing.assert_array_equal(np.asarray(ref.scores),
                                  np.asarray(res.scores))
    # old generations still served from cache; only the grown one recomputed
    assert svc.cache.hits - hits_before == 16
    # not stale: queries planted in the appended range retrieve their doc
    new_q = np.nonzero((c.gt_doc >= 500) & (c.gt_doc < 600))[0][:4]
    assert new_q.size >= 2
    got = svc.query(np.asarray(c.queries[new_q]))
    ids = np.asarray(got.doc_ids)
    hits = [g in ids[i] for i, g in enumerate(c.gt_doc[new_q])]
    assert np.mean(hits) >= 0.5, (hits, ids, c.gt_doc[new_q])


def test_warm_cache_new_generation_reuses_old_entries(serve_corpus,
                                                      base_timeline):
    """new_generation freezes the previously-newest generation: old entries
    keep hitting, the frozen generation starts caching (miss once, then
    hit), and results stay bit-exact vs the uncached path."""
    c = serve_corpus
    q = jnp.asarray(c.queries[:8])
    svc = RetrievalService(base_timeline, CFG)
    svc.query(np.asarray(q))                                  # cold fill
    svc.new_generation(c.doc_embs[600:800], c.doc_lens[600:800])
    assert len(svc.timeline) == 4

    h0, m0 = svc.cache.hits, svc.cache.misses
    res = svc.query(np.asarray(q))
    # gens 0-1 hit from the pre-mutation fill; gen 2 (newly frozen) misses
    assert svc.cache.hits - h0 == 16
    assert svc.cache.misses - m0 == 8
    ref = retrieve_timeline(svc.timeline, q, CFG)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(res.doc_ids))
    np.testing.assert_array_equal(np.asarray(ref.scores),
                                  np.asarray(res.scores))

    h1 = svc.cache.hits
    svc.query(np.asarray(q))
    # now all three immutable generations hit
    assert svc.cache.hits - h1 == 24


# ---------------------------------------------------------------------------
# Cache unit behavior: keys, LRU under the byte budget
# ---------------------------------------------------------------------------

def test_query_fingerprint_semantics():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(8, 16)).astype(np.float32)
    assert query_fingerprint(q) == query_fingerprint(q, np.ones(8, bool))
    mask = np.ones(8, bool)
    mask[3] = False
    assert query_fingerprint(q, mask) != query_fingerprint(q)
    q2 = q.copy()
    q2[0, 0] += 1e-7
    assert query_fingerprint(q2) != query_fingerprint(q)
    # a prefix and its zero-padded form are distinct keys
    padded = np.zeros((12, 16), np.float32)
    padded[:8] = q
    pm = np.arange(12) < 8
    assert query_fingerprint(padded, pm) != query_fingerprint(q)


def test_config_fingerprint_covers_every_field():
    base = config_fingerprint(CFG)
    for change in ({"k": 5}, {"th": 0.3}, {"use_kernels": True},
                   {"candidate_mode": "compact"}, {"cs_dtype": "bfloat16"}):
        assert config_fingerprint(dataclasses.replace(CFG, **change)) != base
    assert config_fingerprint(dataclasses.replace(CFG)) == base


def test_cache_lru_eviction_under_byte_budget():
    entry = (np.zeros(10, np.float32), np.zeros(10, np.int32))  # 80 B
    cache = ResultCache(max_bytes=3 * 80)
    for i in range(4):
        cache.put((f"q{i}", "g", "c"), *entry)
    assert len(cache) == 3 and cache.bytes == 3 * 80
    assert cache.evictions == 1
    assert cache.get(("q0", "g", "c")) is None          # LRU'd out
    assert cache.get(("q3", "g", "c")) is not None
    # recency refresh: touch q1, insert another -> q2 (now LRU) evicts
    assert cache.get(("q1", "g", "c")) is not None
    cache.put(("q4", "g", "c"), *entry)
    assert cache.get(("q2", "g", "c")) is None
    assert cache.get(("q1", "g", "c")) is not None
    # an entry larger than the whole budget is not cached at all
    big = (np.zeros(1000, np.float32), np.zeros(1000, np.int32))
    cache.put(("huge", "g", "c"), *big)
    assert cache.get(("huge", "g", "c")) is None
    assert cache.bytes <= cache.max_bytes


# ---------------------------------------------------------------------------
# Batcher: padding, tickets, size/deadline semantics
# ---------------------------------------------------------------------------

def test_pad_query_validation():
    q16 = np.ones((16, 8), np.float32)
    padded, mask = pad_query(q16, 32)
    assert padded.shape == (32, 8) and mask.sum() == 16
    np.testing.assert_array_equal(padded[16:], 0.0)
    with pytest.raises(ValueError, match="prune it first"):
        pad_query(np.ones((40, 8), np.float32), 32)
    with pytest.raises(ValueError, match="one bool per"):
        pad_query(q16, 32, np.ones(9, bool))
    # caller's mask survives under the padding mask
    m = np.ones(16, bool)
    m[2] = False
    _, full = pad_query(q16, 32, m)
    assert not full[2] and full[:16].sum() == 15


def test_submit_flush_tickets(serve_corpus, base_timeline):
    """Heterogeneous-length queries batch through submit/flush and each
    ticket equals the uncached retrieval of ITS unpadded prefix."""
    c = serve_corpus
    svc = RetrievalService(base_timeline, CFG, max_batch=4)
    t_short = svc.submit(c.queries[0][:16])                   # 16 terms
    t_full = svc.submit(c.queries[1])                         # all 32
    with pytest.raises(RuntimeError, match="still pending"):
        t_short.result()
    svc.flush()
    assert t_short.done and t_full.done
    ref_short = retrieve_timeline(base_timeline,
                                  jnp.asarray(c.queries[0:1, :16]), CFG)
    np.testing.assert_array_equal(t_short.result()[1],
                                  np.asarray(ref_short.doc_ids)[0])
    # Ids are exact; scores agree to rounding only: XLA may reassociate the
    # padded query's per-term sum (32 slots, 16 masked) differently from
    # the 16-term prefix's, which moves the last float32 bit.
    np.testing.assert_allclose(t_short.result()[0],
                               np.asarray(ref_short.scores)[0], rtol=1e-6)
    ref_full = retrieve_timeline(base_timeline,
                                 jnp.asarray(c.queries[1:2]), CFG)
    np.testing.assert_array_equal(t_full.result()[1],
                                  np.asarray(ref_full.doc_ids)[0])


def test_batcher_size_and_deadline_triggers(serve_corpus, base_timeline):
    c = serve_corpus
    now = [0.0]
    svc = RetrievalService(base_timeline, CFG, max_batch=2,
                           max_delay_s=0.01, clock=lambda: now[0])
    # deadline: a lone query flushes only once max_delay_s has passed
    t1 = svc.submit(c.queries[0])
    svc.poll()
    assert not t1.done
    now[0] += 0.02
    svc.poll()
    assert t1.done
    # size: the max_batch-th submit flushes immediately, no poll needed
    t2 = svc.submit(c.queries[1])
    t3 = svc.submit(c.queries[2])
    assert t2.done and t3.done
    # the queue deadline re-anchors per batch
    mb = MicroBatcher(n_q=32, max_batch=2, max_delay_s=0.01,
                      clock=lambda: now[0])
    mb.submit(c.queries[0])
    assert not mb.due()
    now[0] += 0.02
    assert mb.due()


def test_batcher_overflow_keeps_original_deadline(serve_corpus):
    """A query left behind when a full max_batch drains keeps its ORIGINAL
    submit time: the deadline is a per-query promise, so it must come due
    max_delay_s after ITS submit — not max_delay_s after the drain (which
    would let an overflow query wait up to twice the promise)."""
    c = serve_corpus
    now = [0.0]
    mb = MicroBatcher(n_q=32, max_batch=2, max_delay_s=0.01,
                      clock=lambda: now[0])
    for i in range(3):                       # all submitted at t=0
        mb.submit(c.queries[i])
    now[0] = 0.008
    qb, _, _ = mb.drain()                    # full batch of 2 leaves at t=8ms
    assert qb.q.shape[0] == 2 and len(mb) == 1
    now[0] = 0.012                           # 12ms after the overflow submit
    assert mb.due()                          # NOT re-anchored to the drain
    # and the deadline was not due early either
    mb.drain()
    mb.submit(c.queries[0])
    now[0] = 0.0215
    assert not mb.due()
    now[0] = 0.023
    assert mb.due()


def test_query_empty_batch_raises_actionable(base_timeline):
    """A zero-length batch fails at the service entry point with an
    actionable message, not numpy's bare 'need at least one array to
    stack' from deep inside the pad loop."""
    svc = RetrievalService(base_timeline, CFG)
    with pytest.raises(ValueError, match="empty query batch"):
        svc.query(np.zeros((0, 32, 128), np.float32))
    with pytest.raises(ValueError, match="empty query batch"):
        svc._execute(np.zeros((0, 32, 128), np.float32),
                     np.zeros((0, 32), bool))
    with pytest.raises(ValueError, match="expected"):
        svc.query(np.zeros((32, 128), np.float32))   # missing batch dim


# ---------------------------------------------------------------------------
# Metrics + footprint accounting
# ---------------------------------------------------------------------------

def test_latency_stats_percentiles():
    ls = LatencyStats(window=100)
    for v in range(1, 101):                                   # 1..100 ms
        ls.record(v / 1e3)
    snap = ls.snapshot()
    assert snap["count"] == 100
    assert abs(snap["p50_ms"] - 50.5) < 1.0
    assert snap["p99_ms"] > 98.0
    # ring buffer: old samples age out of the window
    for _ in range(100):
        ls.record(0.2)
    assert abs(ls.snapshot()["p50_ms"] - 200.0) < 1e-6
    assert ls.count == 200


def test_service_metrics_warm_cold_split():
    m = ServiceMetrics()
    m.record_batch(8, 8, 0.001)                               # fully warm
    m.record_batch(8, 4, 0.010)                               # mixed = cold
    snap = m.snapshot()
    assert snap["queries"] == 16 and snap["warm_queries"] == 12
    assert snap["warm_latency"]["count"] == 1
    assert snap["cold_latency"]["count"] == 1
    assert snap["warm_fraction"] == 0.75


def test_footprint_accounting(base_timeline):
    tl = base_timeline
    fp = timeline_footprint(tl)
    gens = [generation_footprint(g, m) for g, m, _ in tl]
    assert fp["n_generations"] == len(tl) and fp["n_docs"] == tl.n_docs
    assert fp["index_bytes"] == sum(g["index_bytes"] for g in gens)
    assert fp["manifest_bytes"] > sum(g["manifest_bytes"] for g in gens)
    assert fp["total_bytes"] == fp["index_bytes"] + fp["manifest_bytes"]
    assert fp["n_tokens"] == int(sum(np.asarray(g.doc_lens).sum()
                                     for g in tl.generations))
    # paper-formula constant vs actual packed bytes: the fixed-shape layout
    # (padding, 4-byte ids, PLAID codes alongside PQ) costs strictly more
    assert fp["bytes_per_embedding"] == bytes_per_embedding(tl.metas[0],
                                                            "emvb")
    assert fp["bytes_per_embedding_actual"] > fp["bytes_per_embedding"]
    per_gen = gens[0]
    assert per_gen["index_bytes"] == sum(per_gen["array_bytes"].values())


def test_stats_snapshot_shape(serve_corpus, base_timeline):
    svc = RetrievalService(base_timeline, CFG)
    svc.query(np.asarray(serve_corpus.queries[:4]))
    snap = svc.stats()
    assert snap["cache"]["entries"] == 8                      # 2 gens x 4
    assert snap["timeline"]["n_generations"] == 3
    assert snap["timeline"]["total_bytes"] > 0
    assert snap["latency"]["count"] == 1
    assert snap["queries"] == 4


def test_latency_stats_ring_wrap_window():
    """Once count > window the quantiles and max see exactly the most
    recent `window` samples; count/mean stay cumulative over all."""
    ls = LatencyStats(window=8)
    for v in range(1, 21):                                    # 1..20 ms
        ls.record(v / 1e3)
    snap = ls.snapshot()
    assert snap["count"] == 20
    # window holds 13..20 ms only — the early cheap samples aged out
    assert snap["max_ms"] == pytest.approx(20.0)
    assert snap["p50_ms"] == pytest.approx(16.5)
    assert snap["p95_ms"] == pytest.approx(np.percentile(
        np.arange(13, 21), 95))
    assert ls.max() == pytest.approx(0.020)
    # mean is all-history: (1+..+20)/20 = 10.5 ms
    assert snap["mean_ms"] == pytest.approx(10.5)
    assert set(snap) == {"count", "mean_ms", "p50_ms", "p95_ms", "p99_ms",
                         "max_ms"}


def test_service_metrics_mixed_filtered_accounting():
    """n_filtered need not be 0 or n_queries: direct callers may report a
    mixed batch and the filtered/unfiltered split must still add up."""
    m = ServiceMetrics()
    m.record_batch(8, 8, 0.001, n_filtered=3)
    snap = m.snapshot()
    assert snap["filtered_queries"] == 3
    assert snap["unfiltered_queries"] == 5
    assert m.filtered_queries + m.unfiltered_queries == m.queries


def test_service_metrics_rejects_unknown_maintenance_kind():
    m = ServiceMetrics()
    m.record_maintenance("merge")
    m.record_maintenance("reepoch")
    with pytest.raises(ValueError, match="unknown maintenance action kind"):
        m.record_maintenance("compact")
    assert m.merges == 1 and m.reepochs == 1


def test_service_metrics_warm_reservoir_routing():
    """Only fully-warm batches land in the warm latency reservoir; any
    miss makes the batch's latency cold-path by accounting."""
    m = ServiceMetrics()
    m.record_batch(4, 4, 0.001)                               # fully warm
    m.record_batch(4, 3, 0.010)                               # one miss
    m.record_batch(4, 0, 0.020)                               # fully cold
    assert m.warm_latency.count == 1
    assert m.cold_latency.count == 2
    assert m.batch_latency.count == 3
    assert m.warm_latency.max() == pytest.approx(0.001)
    assert m.cold_latency.max() == pytest.approx(0.020)


def test_service_metrics_registry_equivalence():
    """The registry-backed snapshot keeps the historical dict shape: every
    counter field equals its property read, and the new batcher /
    generations sections ride along."""
    m = ServiceMetrics()
    m.record_batch(8, 8, 0.001)
    m.record_batch(8, 4, 0.010, n_filtered=8)
    m.record_swap()
    m.record_swap(deferred=True)
    m.record_maintenance("merge")
    m.record_deadline_misses(2)
    m.set_queue_depth(3)
    m.record_generation_lookups("abcdef0123456789", hits=6, misses=2)
    snap = m.snapshot()
    assert snap["batches"] == m.batches == 2
    assert snap["queries"] == m.queries == 16
    assert snap["warm_queries"] == m.warm_queries == 12
    assert snap["cold_queries"] == m.cold_queries == 4
    assert snap["warm_fraction"] == 0.75
    assert snap["filtered_queries"] == m.filtered_queries == 8
    assert snap["maintenance"] == {"swaps": 2, "deferred_swaps": 1,
                                   "merges": 1, "reepochs": 0}
    assert snap["batcher"] == {"queue_depth": 3, "deadline_misses": 2}
    assert snap["generations"] == {
        "abcdef012345": {"hits": 6, "misses": 2, "hit_ratio": 0.75}}
    assert snap["latency"]["count"] == 2
    # counters are registry instruments: mutation by assignment is gone
    with pytest.raises(AttributeError):
        m.queries = 99


def test_snapshot_rejects_partial_footprint(base_timeline):
    """A timeline_footprint dict missing required byte-accounting keys is
    a producer bug — KeyError naming the gaps, not silent omission."""
    m = ServiceMetrics()
    with pytest.raises(KeyError, match="predicate_bytes"):
        m.snapshot(timeline_footprint={"n_generations": 1, "n_docs": 10})
    full = timeline_footprint(base_timeline)
    snap = m.snapshot(timeline_footprint=full)
    assert snap["timeline"]["n_docs"] == base_timeline.n_docs
    # optional keys pass through when the producer supplies them...
    with_opt = dict(full, n_epochs=2)
    assert m.snapshot(timeline_footprint=with_opt)["timeline"][
        "n_epochs"] == 2
    # ...and are silently absent otherwise
    assert "n_epochs" not in snap["timeline"] or "n_epochs" in full
