"""Faceted/filtered retrieval over the predicate plane (docs/FILTERING.md).

The load-bearing contract: under lossless budgets, filtered retrieval is
BIT-EXACT to retrieve-then-post-filter — ids AND score bits — across the
jnp reference, the unfused kernels, both megakernels, batched and vmap
dispatch, both candidate modes, masked queries, reduced-precision CS, and
the timeline merge path (merged and unmerged). Plus: the FilterExpr →
FilterPlan compiler semantics, schema-v3 persistence with its corruption
modes, and the serving layer's filter-aware cache keys and micro-batching.
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitvector as bv
from repro.core import engine, store
from repro.core.engine import EngineConfig
from repro.core.index import build_index

N_DOCS, CAP, D = 96, 12, 16
EXPR = bv.Pred("recent") & ~bv.Pred("lang_en")


@pytest.fixture(scope="module")
def fcorpus():
    key = jax.random.PRNGKey(0)
    embs = np.asarray(jax.random.normal(key, (N_DOCS, CAP, D)))
    lens = np.full((N_DOCS,), CAP, np.int32)
    rng = np.random.default_rng(0)
    preds = {"lang_en": rng.random(N_DOCS) < 0.7,
             "recent": rng.random(N_DOCS) < 0.5}
    queries = np.asarray(
        jax.random.normal(jax.random.PRNGKey(2), (3, 8, D)), np.float32)
    return embs, lens, preds, queries


@pytest.fixture(scope="module")
def findex(fcorpus):
    embs, lens, preds, _ = fcorpus
    return build_index(jax.random.PRNGKey(0), embs, lens, n_centroids=32,
                       predicates=preds)


# lossless budgets: every phase keeps the whole corpus, so the filtered and
# post-filtered rankings must agree bit for bit
BASE = dict(n_q=8, nprobe=4, th=0.2, th_r=0.3, n_filter=N_DOCS,
            n_docs=N_DOCS, k=8, cand_cap=N_DOCS)

MODES = {
    "ref-score_all": {},
    "ref-compact": dict(candidate_mode="compact"),
    "unfused-score_all": dict(use_kernels=True, fused_prefilter=False,
                              fused_late_interaction=False),
    "unfused-compact": dict(use_kernels=True, fused_prefilter=False,
                            fused_late_interaction=False,
                            candidate_mode="compact"),
    "fused-score_all": dict(use_kernels=True, batched_kernels=False),
    "fused-compact": dict(use_kernels=True, batched_kernels=False,
                          candidate_mode="compact"),
    "fused-batched-score_all": dict(use_kernels=True, batched_kernels=True),
    "fused-batched-compact": dict(use_kernels=True, batched_kernels=True,
                                  candidate_mode="compact"),
}


def post_filter(res, pass_np, k):
    """The oracle: cut a FULL unfiltered ranking down to its passing docs."""
    out_s, out_i = [], []
    for b in range(res.doc_ids.shape[0]):
        ids = np.asarray(res.doc_ids[b])
        sc = np.asarray(res.scores[b])
        keep = pass_np[ids]
        out_s.append(sc[keep][:k])
        out_i.append(ids[keep][:k])
    return np.stack(out_s), np.stack(out_i)


def assert_filtered_equals_postfilter(idx, meta, queries, cfg, q_masks=None):
    plan = bv.compile_filter(EXPR, meta.pred_names)
    pass_np = np.asarray(bv.apply_filter_plan(plan, idx.pred_words))
    assert cfg.k <= pass_np.sum(), "oracle needs >= k passing docs"
    full = dataclasses.replace(cfg, k=N_DOCS)
    want_s, want_i = post_filter(
        engine.retrieve(idx, queries, full, q_masks), pass_np, cfg.k)
    got = engine.retrieve(idx, queries, cfg, q_masks, doc_filter=plan)
    np.testing.assert_array_equal(np.asarray(got.doc_ids), want_i)
    np.testing.assert_array_equal(np.asarray(got.scores), want_s)


# ---------------------------------------------------------------------------
# PredicateSet packing + FilterExpr compilation
# ---------------------------------------------------------------------------

def test_predicateset_pack_roundtrip(fcorpus):
    _, _, preds, _ = fcorpus
    ps = bv.PredicateSet.pack(preds)
    assert ps.names == tuple(preds)
    for name, col in preds.items():
        np.testing.assert_array_equal(np.asarray(ps.mask(name)), col)
    with pytest.raises(ValueError, match="unknown predicate"):
        ps.mask("nope")


def test_predicateset_pack_errors():
    with pytest.raises(ValueError, match="empty mapping"):
        bv.PredicateSet.pack({})
    with pytest.raises(ValueError, match="> 32"):
        bv.PredicateSet.pack(
            {f"p{i}": np.ones(4, bool) for i in range(33)})
    with pytest.raises(ValueError, match="expected a 1-D"):
        bv.PredicateSet.pack({"p": np.ones((4, 2), bool)})
    with pytest.raises(ValueError, match="must cover the same corpus"):
        bv.PredicateSet.pack({"p": np.ones(4, bool), "q": np.ones(5, bool)})


def test_compile_unknown_name():
    with pytest.raises(ValueError, match="nope"):
        bv.compile_filter(bv.Pred("nope"), ("a", "b"))


def test_compile_demorgan():
    """~(a & b) and ~a | ~b compile to semantically equal plans."""
    names = ("a", "b")
    words = jnp.arange(4, dtype=jnp.uint32)   # 00, 01, 10, 11
    lhs = bv.compile_filter(~(bv.Pred("a") & bv.Pred("b")), names)
    rhs = bv.compile_filter(~bv.Pred("a") | ~bv.Pred("b"), names)
    np.testing.assert_array_equal(
        np.asarray(bv.apply_filter_plan(lhs, words)),
        np.asarray(bv.apply_filter_plan(rhs, words)))
    assert np.asarray(bv.apply_filter_plan(lhs, words)).tolist() == \
        [True, True, True, False]


def test_compile_contradiction_passes_nothing():
    plan = bv.compile_filter(bv.Pred("a") & ~bv.Pred("a"), ("a",))
    words = jnp.arange(2, dtype=jnp.uint32)
    assert not np.asarray(bv.apply_filter_plan(plan, words)).any()


def test_plan_matches_python_oracle(fcorpus, findex):
    _, _, preds, _ = fcorpus
    idx, meta = findex
    en, rec = preds["lang_en"], preds["recent"]
    cases = [
        (bv.Pred("lang_en"), en),
        (~bv.Pred("recent"), ~rec),
        (bv.Pred("lang_en") & bv.Pred("recent"), en & rec),
        (bv.Pred("lang_en") | ~bv.Pred("recent"), en | ~rec),
        (~(bv.Pred("lang_en") | bv.Pred("recent")), ~(en | rec)),
        (EXPR, rec & ~en),
    ]
    for expr, want in cases:
        plan = bv.compile_filter(expr, meta.pred_names)
        got = np.asarray(bv.apply_filter_plan(plan, idx.pred_words))
        np.testing.assert_array_equal(got, want, err_msg=repr(expr))


def test_engine_config_rejects_uncompiled_expr():
    with pytest.raises(ValueError, match="compile your FilterExpr"):
        EngineConfig(doc_filter=bv.Pred("a"))


def test_generation_rejects_mismatched_plan(findex, fcorpus):
    idx, meta = findex
    _, _, _, queries = fcorpus
    plan = bv.compile_filter(bv.Pred("x"), ("x",))
    cfg = EngineConfig(**BASE)
    with pytest.raises(ValueError, match="recompile the FilterExpr"):
        engine.retrieve_generation_topk(idx, meta, 0, jnp.asarray(queries),
                                        cfg, doc_filter=plan)


# ---------------------------------------------------------------------------
# The equivalence matrix: filtered == retrieve-then-post-filter, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", sorted(MODES))
def test_filtered_equals_postfilter(findex, fcorpus, mode):
    idx, meta = findex
    _, _, _, queries = fcorpus
    cfg = EngineConfig(**BASE, **MODES[mode])
    assert_filtered_equals_postfilter(idx, meta, jnp.asarray(queries), cfg)


def test_filtered_masked_queries(findex, fcorpus):
    """The filter composes with per-term query masks (the micro-batcher's
    padding contract) on the batched megakernel path."""
    idx, meta = findex
    _, _, _, queries = fcorpus
    cfg = EngineConfig(**BASE, **MODES["fused-batched-score_all"])
    masks = np.ones((queries.shape[0], BASE["n_q"]), bool)
    masks[:, 5:] = False
    q = np.array(queries)
    q[:, 5:] = 0.0
    assert_filtered_equals_postfilter(idx, meta, jnp.asarray(q), cfg,
                                      jnp.asarray(masks))


def test_filtered_bf16_cs(findex, fcorpus):
    idx, meta = findex
    _, _, _, queries = fcorpus
    cfg = EngineConfig(**BASE, **MODES["fused-batched-score_all"],
                       cs_dtype="bfloat16")
    assert_filtered_equals_postfilter(idx, meta, jnp.asarray(queries), cfg)


# ---------------------------------------------------------------------------
# Timeline: filtered retrieval across generations, merged and unmerged
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ftimeline(findex):
    idx, meta = findex
    rng = np.random.default_rng(5)
    embs = np.asarray(jax.random.normal(jax.random.PRNGKey(9),
                                        (64, CAP, D)))
    lens = np.full((64,), CAP, np.int32)
    preds = {"lang_en": rng.random(64) < 0.7, "recent": rng.random(64) < 0.5}
    gen, gmeta = store.new_generation(idx, meta, embs, lens,
                                      predicates=preds)
    return store.ShardedTimeline.of((idx, meta), (gen, gmeta))


def test_timeline_filtered_merged_equals_unmerged(ftimeline, fcorpus):
    _, _, _, queries = fcorpus
    q = jnp.asarray(queries)
    cfg = EngineConfig(**{**BASE, "n_filter": 160, "n_docs": 160,
                          "cand_cap": 160})
    merged = store.merge_generations(ftimeline, 0, 2)
    a = engine.retrieve_timeline(ftimeline, q, cfg, doc_filter=EXPR)
    b = engine.retrieve_timeline(merged, q, cfg, doc_filter=EXPR)
    np.testing.assert_array_equal(np.asarray(a.doc_ids),
                                  np.asarray(b.doc_ids))
    np.testing.assert_array_equal(np.asarray(a.scores),
                                  np.asarray(b.scores))
    # the merged plane is the concatenation, docs keep their global ids
    np.testing.assert_array_equal(
        np.asarray(merged.generations[0].pred_words),
        np.concatenate([np.asarray(g.pred_words)
                        for g in ftimeline.generations]))


def test_timeline_filtered_equals_postfilter(ftimeline, fcorpus):
    _, _, _, queries = fcorpus
    q = jnp.asarray(queries)
    cfg = EngineConfig(**{**BASE, "n_filter": 160, "n_docs": 160,
                          "cand_cap": 160})
    plan = bv.compile_filter(EXPR, ftimeline.metas[0].pred_names)
    pass_np = np.concatenate(
        [np.asarray(bv.apply_filter_plan(plan, g.pred_words))
         for g in ftimeline.generations])
    # the full-depth (k = all docs) oracle run needs one generation holding
    # every doc — per-generation top-k caps k at the generation size — and
    # merge_generations preserves retrieval bit-exactly (tested above)
    full = dataclasses.replace(cfg, k=160)
    merged = store.merge_generations(ftimeline, 0, 2)
    want_s, want_i = post_filter(
        engine.retrieve_timeline(merged, q, full), pass_np, cfg.k)
    got = engine.retrieve_timeline(ftimeline, q, cfg, doc_filter=EXPR)
    np.testing.assert_array_equal(np.asarray(got.doc_ids), want_i)
    np.testing.assert_array_equal(np.asarray(got.scores), want_s)


def test_timeline_rejects_mismatched_plane(findex):
    idx, meta = findex
    other = dataclasses.replace(meta, pred_names=("a", "b"))
    with pytest.raises(ValueError, match="predicate plane"):
        store.ShardedTimeline.of((idx, meta), (idx, other))


# ---------------------------------------------------------------------------
# Schema v3 persistence: round trip + corruption modes
# ---------------------------------------------------------------------------

def _resave(src, dst, mutate_manifest=None, mutate_arrays=None):
    with open(os.path.join(src, "manifest.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(src, "arrays.npz")) as npz:
        arrays = {k: npz[k] for k in npz.files}
    if mutate_manifest:
        mutate_manifest(manifest)
    if mutate_arrays:
        mutate_arrays(arrays)
    os.makedirs(dst, exist_ok=True)
    np.savez(os.path.join(dst, "arrays.npz"), **arrays)
    with open(os.path.join(dst, "manifest.json"), "w") as f:
        json.dump(manifest, f)


@pytest.fixture(scope="module")
def fsaved(findex, tmp_path_factory):
    idx, meta = findex
    path = str(tmp_path_factory.mktemp("filtering") / "idx")
    store.save_index(path, idx, meta)
    return path


def test_v3_round_trip_preserves_plane(findex, fcorpus, fsaved):
    idx, meta = findex
    _, _, _, queries = fcorpus
    loaded, lmeta = store.load_index(fsaved)
    assert lmeta.pred_names == meta.pred_names
    np.testing.assert_array_equal(np.asarray(loaded.pred_words),
                                  np.asarray(idx.pred_words))
    cfg = EngineConfig(**BASE, **MODES["fused-batched-score_all"])
    plan = bv.compile_filter(EXPR, lmeta.pred_names)
    q = jnp.asarray(queries)
    a = engine.retrieve(idx, q, cfg, doc_filter=plan)
    b = engine.retrieve(loaded, q, cfg, doc_filter=plan)
    np.testing.assert_array_equal(np.asarray(a.doc_ids),
                                  np.asarray(b.doc_ids))
    np.testing.assert_array_equal(np.asarray(a.scores),
                                  np.asarray(b.scores))


def test_load_wrong_plane_word_count(tmp_path, fsaved):
    dst = str(tmp_path / "badcount")

    def shrink(arrays):
        arrays["pred_words"] = arrays["pred_words"][:-3]

    def fix_decl(m):
        m["arrays"]["pred_words"]["shape"] = [N_DOCS - 3]

    _resave(fsaved, dst, mutate_manifest=fix_decl, mutate_arrays=shrink)
    with pytest.raises(ValueError, match="one uint32 word per doc"):
        store.load_index(dst)


def test_load_plane_bits_beyond_names(tmp_path, fsaved):
    dst = str(tmp_path / "badbits")

    def set_high_bit(arrays):
        pw = arrays["pred_words"].copy()
        pw[0] |= np.uint32(1 << 7)       # the meta declares 2 names
        arrays["pred_words"] = pw

    def refinger(m):
        # keep the content fingerprint consistent so the NAMES check (not
        # the byte-level one) is what fires
        m["fingerprint"] = "recomputed-below"

    _resave(fsaved, dst, mutate_manifest=refinger,
            mutate_arrays=set_high_bit)
    with open(os.path.join(dst, "manifest.json")) as f:
        manifest = json.load(f)
    with np.load(os.path.join(dst, "arrays.npz")) as npz:
        from repro.core.index import PackedIndex
        idx = PackedIndex(**{k: jnp.asarray(npz[k]) for k in npz.files})
    manifest["fingerprint"] = store.index_fingerprint(idx)
    with open(os.path.join(dst, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    with pytest.raises(ValueError, match="bits set beyond"):
        store.load_index(dst)


def test_load_v2_without_plane(tmp_path, findex, fsaved):
    """A v2 save (no pred_words array, no pred_names meta, fingerprint over
    the v2 field set) loads as an index with an empty plane."""
    idx, meta = findex
    dst = str(tmp_path / "v2")

    def downgrade(m):
        m["schema_version"] = 2
        del m["meta"]["pred_names"]
        del m["arrays"]["pred_words"]
        m["fingerprint"] = store.index_fingerprint(
            idx, fields=store._V2_FIELDS)

    def drop_plane(arrays):
        del arrays["pred_words"]

    _resave(fsaved, dst, mutate_manifest=downgrade,
            mutate_arrays=drop_plane)
    loaded, lmeta = store.load_index(dst)
    assert lmeta.pred_names == ()
    np.testing.assert_array_equal(np.asarray(loaded.pred_words),
                                  np.zeros(N_DOCS, np.uint32))
    # filtering such an index fails loudly at compile: no names exist
    with pytest.raises(ValueError, match="recent"):
        bv.compile_filter(EXPR, lmeta.pred_names)


# ---------------------------------------------------------------------------
# Serving: filter-aware cache keys, micro-batch homogeneity, metrics
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fservice_cfg():
    return EngineConfig(**{**BASE, "n_filter": 64, "n_docs": 64,
                           "cand_cap": 64})


def test_service_filtered_cold_warm_and_no_collision(ftimeline, fcorpus,
                                                     fservice_cfg):
    from repro.serving import RetrievalService

    _, _, _, queries = fcorpus
    q = jnp.asarray(queries)
    svc = RetrievalService(ftimeline, fservice_cfg)
    want_u = engine.retrieve_timeline(ftimeline, q, fservice_cfg)
    want_f = engine.retrieve_timeline(ftimeline, q, fservice_cfg,
                                      doc_filter=EXPR)
    # unfiltered first — its partials populate the cache under the base
    # config fingerprint; the filtered queries that follow must NOT hit them
    got_u = svc.query(q)
    got_f_cold = svc.query(q, doc_filter=EXPR)
    got_f_warm = svc.query(q, doc_filter=EXPR)
    for got, want in ((got_u, want_u), (got_f_cold, want_f),
                      (got_f_warm, want_f)):
        np.testing.assert_array_equal(np.asarray(got.doc_ids),
                                      np.asarray(want.doc_ids))
        np.testing.assert_array_equal(np.asarray(got.scores),
                                      np.asarray(want.scores))
    s = svc.stats()
    assert s["filtered_queries"] == 2 * q.shape[0]
    assert s["unfiltered_queries"] == q.shape[0]
    assert "predicate_bytes" in s["timeline"]
    # the warm filtered pass hit the cache (its partials were cached by the
    # cold filtered pass, NOT poisoned by the unfiltered ones)
    assert s["warm_queries"] >= q.shape[0]


def test_service_submit_groups_by_filter(ftimeline, fcorpus, fservice_cfg):
    from repro.serving import RetrievalService

    _, _, _, queries = fcorpus
    q = jnp.asarray(queries)
    svc = RetrievalService(ftimeline, fservice_cfg, max_batch=16)
    want_u = engine.retrieve_timeline(ftimeline, q, fservice_cfg)
    want_f = engine.retrieve_timeline(ftimeline, q, fservice_cfg,
                                      doc_filter=EXPR)
    t0 = svc.submit(queries[0], doc_filter=EXPR)
    t1 = svc.submit(queries[1])
    t2 = svc.submit(queries[2], doc_filter=EXPR)
    svc.flush()
    for t, want, b in ((t0, want_f, 0), (t1, want_u, 1), (t2, want_f, 2)):
        np.testing.assert_array_equal(t.result()[1],
                                      np.asarray(want.doc_ids)[b])
        np.testing.assert_array_equal(t.result()[0],
                                      np.asarray(want.scores)[b])


def test_batcher_drains_longest_same_filter_prefix():
    from repro.serving.batcher import MicroBatcher

    mb = MicroBatcher(n_q=4, max_batch=8)
    q = np.zeros((2, 3), np.float32)
    for f in ("A", "A", "B", "A"):          # batcher compares filters by ==
        mb.submit(q, doc_filter=f)
    qb, tickets, f = mb.drain()
    assert (qb.q.shape[0], f) == (2, "A")
    qb, tickets, f = mb.drain()
    assert (qb.q.shape[0], f) == (1, "B")
    qb, tickets, f = mb.drain()
    assert (qb.q.shape[0], f) == (1, "A")
    assert mb.drain() is None


def test_metrics_filtered_split():
    from repro.serving.metrics import ServiceMetrics

    m = ServiceMetrics()
    m.record_batch(4, 0, 0.01)
    m.record_batch(3, 3, 0.01, n_filtered=3)
    snap = m.snapshot()
    assert snap["filtered_queries"] == 3
    assert snap["unfiltered_queries"] == 4


# ---------------------------------------------------------------------------
# shard_map: the filter evaluates per shard against the local plane slice
# ---------------------------------------------------------------------------

def test_shardmap_filtered_matches_engine(findex, fcorpus):
    from repro.launch.serve import make_shardmap_retriever, shard_index

    idx, meta = findex
    _, _, _, queries = fcorpus
    q = jnp.asarray(queries)
    cfg = EngineConfig(**BASE, **MODES["fused-batched-score_all"])
    plan = bv.compile_filter(EXPR, meta.pred_names)
    ref = engine.retrieve(idx, q, cfg, doc_filter=plan)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    run = make_shardmap_retriever(mesh, cfg)
    with mesh:
        stacked = shard_index(idx, 1)
        out = run(stacked, q, doc_filter=plan)
        out_u = run(stacked, q)
    ref_u = engine.retrieve(idx, q, cfg)
    np.testing.assert_array_equal(np.asarray(ref.doc_ids),
                                  np.asarray(out.doc_ids))
    np.testing.assert_array_equal(np.asarray(ref.scores),
                                  np.asarray(out.scores))
    # the same retriever still serves unfiltered traffic (separate trace)
    np.testing.assert_array_equal(np.asarray(ref_u.doc_ids),
                                  np.asarray(out_u.doc_ids))
