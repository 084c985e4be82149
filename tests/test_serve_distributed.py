"""Distributed serving: the shard_map plan equals the single-device engine,
shard_index's local IVFs are consistent with the global one, and the
multi-generation timeline plan equals the single-device merge path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, engine
from repro.launch.serve import (make_shardmap_retriever,
                                make_timeline_retriever, shard_index)

CFG = EngineConfig(nprobe=8, th=0.3, th_r=0.4, n_filter=64, n_docs=16, k=10)


def test_shardmap_matches_global_single_device(small_corpus, small_index):
    idx, _ = small_index
    q = jnp.asarray(small_corpus.queries[:8])
    ref = engine.retrieve(idx, q, CFG)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    step = make_shardmap_retriever(mesh, CFG)
    with mesh:
        out = step(shard_index(idx, 1), q)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(out.doc_ids))
    np.testing.assert_allclose(np.asarray(ref.scores),
                               np.asarray(out.scores), rtol=1e-5)


def test_shardmap_runs_fused_megakernels(small_corpus, small_index):
    """The fully fused kernel engine (prefilter + late-interaction
    megakernels) runs inside shard_map against the local shard and matches
    the jnp-reference shard_map plan bit-exactly."""
    import dataclasses

    idx, _ = small_index
    q = jnp.asarray(small_corpus.queries[:4])
    kcfg = dataclasses.replace(CFG, use_kernels=True, fused_prefilter=True,
                               fused_late_interaction=True)
    ref = engine.retrieve(idx, q, kcfg)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    step = make_shardmap_retriever(mesh, kcfg)
    with mesh:
        out = step(shard_index(idx, 1), q)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(out.doc_ids))
    np.testing.assert_array_equal(np.asarray(ref.scores),
                                  np.asarray(out.scores))


def test_shard_index_partitions_consistently(small_index):
    idx, meta = small_index
    n_shards = 4
    n_docs = idx.codes.shape[0]
    assert n_docs % n_shards == 0
    st = shard_index(idx, n_shards)
    per = n_docs // n_shards
    # codes block-partitioned
    np.testing.assert_array_equal(
        np.asarray(st.codes).reshape(n_docs, -1), np.asarray(idx.codes))
    # every global IVF entry appears in exactly one local IVF (unless the
    # local list overflowed list_cap)
    g_ivf, g_lens = np.asarray(idx.ivf), np.asarray(idx.ivf_lens)
    l_ivf, l_lens = np.asarray(st.ivf), np.asarray(st.ivf_lens)
    for c in range(meta.n_centroids):
        global_docs = set(g_ivf[c, :g_lens[c]].tolist())
        local_docs = set()
        for s in range(n_shards):
            local_docs |= {int(x) + s * per
                           for x in l_ivf[s, c, :l_lens[s, c]]}
        assert local_docs <= global_docs
        if sum(l_lens[s, c] for s in range(n_shards)) == len(global_docs):
            assert local_docs == global_docs


def test_shard_index_places_shards_on_the_mesh(small_index):
    """With a mesh, every stacked leaf carries the plan's NamedSharding
    (shard s on device s), so the plan moves no index bytes per call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    idx, _ = small_index
    mesh = jax.make_mesh((1,), ("shard",))
    want = NamedSharding(mesh, P(("shard",)))
    for leaf in shard_index(idx, 1, mesh=mesh):
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
    with pytest.raises(ValueError, match="one shard per device"):
        shard_index(idx, 2, mesh=mesh)


def test_timeline_retriever_matches_single_device(small_corpus, small_index):
    """The sharded multi-generation plan (shard_map per generation + merge
    by score with doc-id offsets) returns the same ids as the single-device
    ``engine.retrieve_timeline`` over the same ShardedTimeline."""
    from repro.core import ShardedTimeline, new_generation, retrieve_timeline

    idx, meta = small_index
    gen1 = new_generation(idx, meta, np.asarray(small_corpus.doc_embs[:300]),
                          np.asarray(small_corpus.doc_lens[:300]))
    tl = ShardedTimeline.of((idx, meta), gen1)
    q = jnp.asarray(small_corpus.queries[:8])
    ref = retrieve_timeline(tl, q, CFG)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    run = make_timeline_retriever(mesh, CFG, tl)
    with mesh:
        out = run(q)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(out.doc_ids))
    np.testing.assert_allclose(np.asarray(ref.scores),
                               np.asarray(out.scores), rtol=1e-5)


def test_make_service_shardmap_miss_lane(small_corpus, small_index):
    """launch.serve.make_service: a RetrievalService whose miss lane runs
    the per-generation shard_map plans. Cold and warm results equal the
    sharded uncached retriever (the caching layer is plan-agnostic: it
    stores whatever partials the plan produced)."""
    from repro.core import ShardedTimeline, new_generation
    from repro.launch.serve import make_service

    idx, meta = small_index
    gen1 = new_generation(idx, meta, np.asarray(small_corpus.doc_embs[:300]),
                          np.asarray(small_corpus.doc_lens[:300]))
    tl = ShardedTimeline.of((idx, meta), gen1)
    q = jnp.asarray(small_corpus.queries[:8])
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ref = make_timeline_retriever(mesh, CFG, tl)(q)
    svc = make_service(mesh, CFG, tl)
    cold = svc.query(np.asarray(q))
    warm = svc.query(np.asarray(q))
    for out in (cold, warm):
        np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                      np.asarray(out.doc_ids))
        np.testing.assert_array_equal(np.asarray(ref.scores),
                                      np.asarray(out.scores))
    assert svc.cache.hits == 8          # warm pass, 1 immutable generation


def test_per_shard_topk_merge_recovers_global(small_corpus, small_index):
    """Two-level top-k invariant: with EXHAUSTIVE per-shard budgets (every
    local doc late-interacted), the merged union must equal the brute-force
    Eq. 5/6 top-k over the whole corpus exactly — this isolates the merge
    logic + shard score equivalence from filter-recall effects (with probe-
    limited budgets, global and sharded candidate sets legitimately differ;
    quality parity for that regime is covered by the serving example)."""
    import dataclasses

    from repro.core.interaction import late_interaction_pq
    from repro.core.pq import build_lut

    idx, _ = small_index
    q = jnp.asarray(small_corpus.queries[:4])
    n_shards = 4
    n_docs = idx.codes.shape[0]
    per = n_docs // n_shards
    ecfg = dataclasses.replace(CFG, n_filter=per, n_docs=per, th=-1.0)
    st = shard_index(idx, n_shards)
    merged_scores, merged_ids = [], []
    for s in range(n_shards):
        local = jax.tree.map(lambda x: x[s], st)
        res = engine.retrieve(local, q, ecfg)
        merged_scores.append(np.asarray(res.scores))
        merged_ids.append(np.asarray(res.doc_ids) + s * per)
    sc = np.concatenate(merged_scores, axis=1)
    ids = np.concatenate(merged_ids, axis=1)
    order = np.argsort(-sc, axis=1)[:, :CFG.k]
    top_ids = np.take_along_axis(ids, order, axis=1)
    top_sc = np.take_along_axis(sc, order, axis=1)

    token_mask = idx.token_mask()
    for b in range(q.shape[0]):
        lut = build_lut(jnp.asarray(q[b]) @ idx.opq_rotation, idx.pq)
        cs_t = (jnp.asarray(q[b]) @ idx.centroids.T).T
        exact = np.asarray(late_interaction_pq(
            cs_t, lut, idx.codes, idx.res_codes, token_mask, CFG.th_r))
        want = np.argsort(-exact)[:CFG.k]
        assert set(top_ids[b].tolist()) == set(want.tolist())
        np.testing.assert_allclose(np.sort(top_sc[b]),
                                   np.sort(exact[want]), rtol=1e-4)
