"""Distributed serving: the shard_map plan equals the single-device engine,
shard_index's local IVFs are consistent with the global one, and the
multi-generation timeline plan equals the single-device merge path. The
plan's program is named ``_shardmap_retrieve`` and its set-up and dispatch
spans carry their attributes; on four devices its answers are pinned bit
for bit.

Run as a script (``python test_serve_distributed.py``) this file is the
four-device subprocess: it prints one JSON line per case with digests of
the sharded answer's ids and scores.
"""
import hashlib
import json
import os
import subprocess
import sys

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


def test_shardmap_program_carries_its_name(small_corpus, small_index):
    """A device trace finds the plan's launches by the program's name, apart
    from the single-device ``_retrieve_jit``; its merge is named too."""
    idx, _ = small_index
    q = jnp.asarray(small_corpus.queries[:2])
    mesh = jax.make_mesh((1,), ("shard",))
    run = make_shardmap_retriever(mesh, CFG)
    stacked = shard_index(idx, 1, mesh=mesh)
    names = [e.params["name"] for e in jax.make_jaxpr(run)(stacked, q).eqns
             if e.primitive.name in ("pjit", "jit")]
    text = jax.jit(run).lower(stacked, q).as_text(debug_info=True)
    assert names == ["_shardmap_retrieve"]
    assert "_retrieve_jit" not in text
    merge = [ln for ln in text.splitlines() if "launch.merge" in ln]
    assert any("all_gather" in ln for ln in merge), merge[:5]
    assert any("top_k" in ln or "topk" in ln for ln in merge), merge[:5]


def test_shard_plan_span_records_batch(small_corpus, small_index):
    from repro.core import ShardedTimeline
    from repro.launch.serve import make_timeline_partial_plans
    from repro.obs import trace

    idx, meta = small_index
    mesh = jax.make_mesh((1,), ("shard",))
    with trace.tracing() as tracer:
        (plan,) = make_timeline_partial_plans(
            mesh, CFG, ShardedTimeline.of((idx, meta)))
        plan(jnp.asarray(small_corpus.queries[:3]))
    (sp,) = [s for s in tracer.finished() if s["name"] == "launch.shard_plan"]
    assert sp["attrs"] == {"generation": 0, "shards": 1, "batch": 3}


def test_shard_index_span_records_its_lists(small_index):
    from repro.obs import trace

    idx, _ = small_index
    with trace.tracing() as tracer:
        st = shard_index(idx, 4)
    (sp,) = [s for s in tracer.finished() if s["name"] == "launch.shard_index"]
    lens = np.asarray(st.ivf_lens)
    assert sp["attrs"] == {"shards": 4, "list_cap": int(idx.ivf.shape[1]),
                           "longest_local_list": int(lens.max()),
                           "dropped": 0}
    assert 0 < lens.max() <= idx.ivf.shape[1]
    # every global IVF entry lands in one local list: nothing dropped
    assert lens.sum() == np.asarray(idx.ivf_lens).sum()


# sha256 of the ids' and the scores' bytes of the four-device answer, per
# case, as the plan gave them before its program was named and its merge
# scoped (computed in this file's subprocess, whose XLA flags are fixed).
PINNED = {
    "plain": (
        "5699db52bef22890c9f5db1093cb44f568c26ae4aa6711bf9466ae9c2b8886aa",
        "2415618fda8046c972b15feaec4792087e94a0d03735b185ac291d907854ce8d"),
    "masked": (
        "2a491db5ba0824dda9ba4d2bcdd054dfcdd7de083445adf1a96d7479116ca341",
        "897ef45a8431ca49c05e00fa6eb4c4bfd53d64cadc06ce65384c83d9b4e826bc"),
    "filtered": (
        "78c10ed11eac322b45b97046862ea5a96e4b5fca8ea8b115ff632b3a7f583fee",
        "6f1ddbf79e868fba1e5f98519615541bd60cca4e66b13e0b46fa53d32d201784"),
    "masked+filtered": (
        "3e1497a88db938c50f5f6c6b6ff50c2ec3a4a87f8093a44eeff906ac9a6f03f1",
        "f122b4e626ecd79ab49f7b4386c3b1955a18f6baa6c967f65390c205561a6c0c"),
}


def main() -> int:
    """The four-device subprocess: one JSON line per case."""
    from repro.core import bitvector as bv
    from repro.core.index import build_index

    assert len(jax.devices()) == 4, jax.devices()
    n_docs, cap, d = 256, 12, 16
    embs = np.asarray(jax.random.normal(jax.random.PRNGKey(0),
                                        (n_docs, cap, d)))
    lens = np.random.default_rng(1).integers(4, cap + 1, n_docs)
    lens = lens.astype(np.int32)
    rng = np.random.default_rng(0)
    preds = {"lang_en": rng.random(n_docs) < 0.7,
             "recent": rng.random(n_docs) < 0.5}
    idx, meta = build_index(jax.random.PRNGKey(0), embs, lens,
                            n_centroids=32, predicates=preds)
    q = jnp.asarray(jax.random.normal(jax.random.PRNGKey(2), (3, 8, d)))
    mask = jnp.asarray(np.arange(8)[None, :] < np.array([[8], [5], [2]]))
    plan = bv.compile_filter(bv.Pred("recent") & ~bv.Pred("lang_en"),
                             meta.pred_names)
    cfg = EngineConfig(n_q=8, nprobe=4, th=0.2, th_r=0.3, n_filter=32,
                       n_docs=16, k=8)
    mesh = jax.make_mesh((4,), ("shard",))
    run = make_shardmap_retriever(mesh, cfg)
    stacked = shard_index(idx, 4, mesh=mesh)
    cases = {"plain": (None, None), "masked": (mask, None),
             "filtered": (None, plan), "masked+filtered": (mask, plan)}
    for case, (m, f) in cases.items():
        out = run(stacked, q, m, doc_filter=f)
        ids, scores = np.asarray(out.doc_ids), np.asarray(out.scores)
        print(json.dumps({
            "case": case, "ids": hashlib.sha256(ids.tobytes()).hexdigest(),
            "scores": hashlib.sha256(scores.tobytes()).hexdigest(),
            "found": int((ids >= 0).sum())}), flush=True)
    return 0


@pytest.fixture(scope="module")
def four_device_answers():
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
                         "--xla_backend_optimization_level=0")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + env.get("PYTHONPATH", "").split(os.pathsep))
    p = subprocess.run([sys.executable, os.path.abspath(__file__)], env=env,
                       capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-4000:]
    return {x["case"]: x for x in map(json.loads, p.stdout.splitlines())}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_four_device_answers_equal_the_unnamed_plan(four_device_answers,
                                                    case):
    got = four_device_answers[case]
    assert got["found"] > 0
    assert (got["ids"], got["scores"]) == PINNED[case]


if __name__ == "__main__":
    sys.exit(main())
