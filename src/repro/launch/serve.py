"""Distributed EMVB serving: the shard_map plan.

Each device owns a doc shard with a *local* IVF, runs the full four-phase
pipeline locally for the whole query batch, and the per-shard top-k are
merged with one all-gather + re-top-k (two-level top-k): collective traffic
is O(B * k) instead of O(corpus gathers). Runs on any mesh size.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import (EngineConfig, RetrievalResult,
                               _as_query_batch, _retrieve_batch,
                               _with_filter)
from repro.core.index import PackedIndex
from repro.obs import trace


# ---------------------------------------------------------------------------
# shard_map plan
# ---------------------------------------------------------------------------

def _local_retrieve(index_local: PackedIndex, queries: jax.Array,
                    q_masks: jax.Array, cfg: EngineConfig,
                    axes: Tuple[str, ...]) -> RetrievalResult:
    """Runs on ONE device's doc shard; queries AND q_masks are replicated.

    Goes through the SAME batched pipeline ``retrieve`` uses, so with a
    ``batched_kernels`` config every shard runs its whole query batch as
    one batch-native megakernel launch per fused phase pair."""
    local = _retrieve_batch(index_local, queries, cfg, q_masks)

    # translate local doc ids -> global ids with the shard offset
    shard_id = jnp.int32(0)
    for ax in axes:
        shard_id = shard_id * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    n_local = index_local.codes.shape[0]
    global_ids = local.doc_ids + shard_id * n_local

    # two-level top-k: all-gather each shard's k, rerank
    sc = jax.lax.all_gather(local.scores, axes, axis=0, tiled=False)
    gi = jax.lax.all_gather(global_ids, axes, axis=0, tiled=False)
    sc = jnp.moveaxis(sc, 0, 1).reshape(queries.shape[0], -1)   # (B, S*k)
    gi = jnp.moveaxis(gi, 0, 1).reshape(queries.shape[0], -1)
    top_sc, pos = jax.lax.top_k(sc, cfg.k)
    return RetrievalResult(top_sc, jnp.take_along_axis(gi, pos, axis=1))


def make_shardmap_retriever(mesh: Mesh, cfg: EngineConfig):
    """Returns a fn(index_stacked, queries, q_masks=None) -> RetrievalResult.

    ``index_stacked`` leaves carry a leading shard axis (S, ...) where S =
    number of devices; leaf [s] is device s's local index (local doc ids,
    local IVF). Build with ``shard_index``.

    ``q_masks`` (optional (B, n_q) bool) is replicated across shards exactly
    like ``queries`` — every shard applies the same per-term mask to its
    local four-phase pipeline, so the two-level top-k merges shard results
    computed under identical masking. ``None`` fills in an all-True mask,
    which is the bitwise identity.

    ``doc_filter`` (optional compiled ``bitvector.FilterPlan``, keyword)
    evaluates the predicate filter per shard against the shard's local
    ``pred_words`` slice — each shard's four-phase pipeline masks its own
    non-passing docs to -inf, so the two-level top-k merge only ever sees
    passing docs. The plan is static config, so each DISTINCT plan gets
    its own traced shard_map program (memoized here; the unfiltered
    program is traced on first unfiltered call, exactly as before).
    """
    axes = tuple(mesh.axis_names)
    in_specs = (jax.tree.map(lambda _: P(axes), _index_struct()),
                P(*([None])), P(*([None])))
    out_specs = RetrievalResult(P(None), P(None))

    steps: dict = {}   # filtered config -> traced shard_map program

    def _step_for(fcfg: EngineConfig):
        if fcfg not in steps:
            @functools.partial(jax.jit)
            @functools.partial(jax.shard_map, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            def step(index_stacked, queries, q_masks):
                index_local = jax.tree.map(lambda x: x[0], index_stacked)
                return _local_retrieve(index_local, queries, q_masks,
                                       fcfg, axes)
            steps[fcfg] = step
        return steps[fcfg]

    def run(index_stacked, queries, q_masks=None, *, doc_filter=None):
        qb = _as_query_batch(queries, q_masks)
        q_masks = (jnp.ones(qb.q.shape[:2], jnp.bool_)
                   if qb.q_mask is None else qb.q_mask)
        return _step_for(_with_filter(cfg, doc_filter))(
            index_stacked, qb.q, q_masks)

    return run


def _index_struct():
    """A PackedIndex-shaped pytree of placeholders (for tree.map of specs)."""
    return PackedIndex(*([0] * len(PackedIndex._fields)))


# ---------------------------------------------------------------------------
# Multi-generation serving (PLAID SHIRTTT): one shard_map plan per immutable
# index generation, merged by score at the top.
# ---------------------------------------------------------------------------

def make_timeline_partial_plans(mesh: Mesh, cfg: EngineConfig, timeline, *,
                                shard_cache: dict = None):
    """Per-generation shard_map execution plans over a
    ``repro.core.store.ShardedTimeline``.

    Reuses the existing shard_map plan PER GENERATION: each generation is
    doc-sharded across the whole mesh (``shard_index``), queried through
    ``make_shardmap_retriever`` (so the per-shard four-phase pipeline, the
    kernel choices, and the two-level top-k all apply unchanged), with the
    generation's global doc-id offset applied to the result. Selection
    budgets are clamped to each generation's PER-SHARD doc count AND token
    cap via ``engine.adapt_config_to_corpus``.

    ``shard_cache`` (optional dict the caller owns) memoizes the stacked
    shard arrays by generation CONTENT fingerprint: across timeline swaps
    (growth, compaction, re-epoching) only generations whose content
    actually changed are re-sharded — the same invalidation-by-construction
    rule the result cache uses. Pass the SAME dict on every rebuild (and
    across epochs — the service invokes the factory once per epoch with
    one dict); it is kept LRU-bounded here, so stale fingerprints age out
    without ever evicting another epoch's still-live entries first.

    Every generation's ``n_docs`` must divide the mesh size (the
    ``shard_index`` block-partition contract). Returns one
    ``plan(queries, q_masks=None) -> RetrievalResult`` (GLOBAL doc ids)
    per generation — the partials ``make_timeline_retriever`` merges and
    ``repro.serving.RetrievalService`` caches per immutable generation.
    """
    from repro.core.engine import adapt_config_to_corpus

    n_shards = mesh.size
    fps = timeline.fingerprints if shard_cache is not None else None
    # one retriever per DISTINCT clamped config: equal-size generations (the
    # steady-state stream) share a single traced/compiled shard_map program
    # instead of compiling G identical ones
    retrievers: dict = {}
    plans = []
    for g, (gen, meta, off) in enumerate(timeline):
        gcfg = adapt_config_to_corpus(cfg, meta.n_docs // n_shards, meta.cap)
        if gcfg not in retrievers:
            retrievers[gcfg] = make_shardmap_retriever(mesh, gcfg)
        if shard_cache is None:
            stacked = shard_index(gen, n_shards, mesh=mesh)
        else:
            ckey = (fps[g], n_shards)
            stacked = shard_cache.pop(ckey, None)
            if stacked is None:
                stacked = shard_index(gen, n_shards, mesh=mesh)
            shard_cache[ckey] = stacked   # (re)insert at LRU tail

        def plan(queries, q_masks=None, doc_filter=None, *, _stacked=stacked,
                 _retriever=retrievers[gcfg], _off=off, _g=g):
            """queries: (B, n_q, d) array or QueryBatch; ``doc_filter`` an
            optional compiled FilterPlan applied on every shard."""
            # dispatch-only span (jax is async); generation attr is the
            # plan's position in the timeline it was built from
            with trace.span("launch.shard_plan", generation=_g,
                            shards=n_shards):
                r = _retriever(_stacked, queries, q_masks,
                               doc_filter=doc_filter)
                return RetrievalResult(r.scores, r.doc_ids + jnp.int32(_off))

        plans.append(plan)
    if shard_cache is not None:
        # LRU bound (insertion order = recency after the pop/reinsert
        # above): stale fingerprints from superseded timelines age out;
        # never evicts this timeline's own entries (they were just
        # refreshed) as long as the bound exceeds one epoch's generations
        while len(shard_cache) > max(32, 2 * len(plans)):
            del shard_cache[next(iter(shard_cache))]
    return plans


def make_timeline_retriever(mesh: Mesh, cfg: EngineConfig, timeline):
    """Sharded serving over a timeline: the per-generation shard_map plans
    (``make_timeline_partial_plans``) merged by score — a third top-k level
    on top of the per-shard merge. Returns
    ``run(queries, q_masks=None) -> RetrievalResult`` over global doc ids.
    """
    from repro.core.engine import merge_partial_topk

    plans = make_timeline_partial_plans(mesh, cfg, timeline)

    def run(queries, q_masks=None, *, doc_filter=None) -> RetrievalResult:
        qb = _as_query_batch(queries, q_masks)
        q_masks = (jnp.ones(qb.q.shape[:2], jnp.bool_)
                   if qb.q_mask is None else qb.q_mask)
        return merge_partial_topk(
            [p(qb.q, q_masks, doc_filter) for p in plans], cfg.k)

    return run


def make_service(mesh: Mesh, cfg: EngineConfig, timeline, **service_kwargs):
    """A ``repro.serving.RetrievalService`` whose cache-MISS lane runs the
    sharded plans: hits are served from host memory, and only the miss
    lane's sub-batch ever reaches the mesh. The plan factory is re-invoked
    on every timeline swap (``add_passages``/``new_generation``/
    maintenance), so changed generations get freshly sharded plans while
    unchanged generations reuse their stacked shard arrays (memoized by
    content fingerprint in a cache this factory owns) AND keep their
    result-cache entries. ``service_kwargs`` pass through to
    ``RetrievalService`` (cache budget, batching knobs, ...).
    """
    from repro.serving import RetrievalService

    shard_cache: dict = {}
    return RetrievalService(
        timeline, cfg,
        plan_factory=lambda tl: make_timeline_partial_plans(
            mesh, cfg, tl, shard_cache=shard_cache),
        **service_kwargs)


def shard_index(index: PackedIndex, n_shards: int, *,
                mesh: Mesh | None = None) -> PackedIndex:
    """Split a global index into per-shard local indices, stacked on a new
    leading axis. Docs are block-partitioned; each shard's IVF is rebuilt
    with local doc ids. (Host-side, numpy.) If a rebuilt local list exceeds
    the global list_cap a warning reports how many doc-id entries were
    dropped (those docs become unreachable through that centroid on that
    shard).

    With ``mesh`` (of ``n_shards`` devices) every stacked leaf is placed
    with the shard_map plan's ``NamedSharding``, so device s holds shard s
    and the plan moves no index bytes; without it the leaves go to the
    default device."""
    import warnings

    import numpy as np

    n_docs = int(index.codes.shape[0])
    assert n_docs % n_shards == 0, "pad docs to a shard multiple first"
    per = n_docs // n_shards
    n_c, list_cap = index.ivf.shape

    codes = np.asarray(index.codes).reshape(n_shards, per, -1)
    doc_lens = np.asarray(index.doc_lens).reshape(n_shards, per)
    pred_words = np.asarray(index.pred_words).reshape(n_shards, per)
    res_codes = np.asarray(index.res_codes).reshape(
        n_shards, per, *index.res_codes.shape[1:])
    plaid_res = np.asarray(index.plaid_res)
    if plaid_res.shape[0] == n_docs:
        plaid_res = plaid_res.reshape(n_shards, per, *plaid_res.shape[1:])
    else:  # dummy
        plaid_res = np.broadcast_to(plaid_res, (n_shards, *plaid_res.shape))

    # local IVFs
    ivf = np.asarray(index.ivf)
    ivf_lens_g = np.asarray(index.ivf_lens)
    local_ivf = np.full((n_shards, n_c, list_cap), per, dtype=np.int32)
    local_lens = np.zeros((n_shards, n_c), dtype=np.int32)
    n_dropped = 0
    n_overflowed = 0
    for c in range(n_c):
        docs = ivf[c, :ivf_lens_g[c]]
        for s in range(n_shards):
            mine = docs[(docs >= s * per) & (docs < (s + 1) * per)] - s * per
            ln = min(len(mine), list_cap)
            if len(mine) > ln:
                n_dropped += len(mine) - ln
                n_overflowed += 1
            local_ivf[s, c, :ln] = mine[:ln]
            local_lens[s, c] = ln
    if n_dropped:
        warnings.warn(
            f"shard_index: {n_overflowed} local IVF list(s) overflowed "
            f"list_cap={list_cap}; {n_dropped} doc-id entries dropped — "
            "those docs are unreachable through the overflowed centroid on "
            "their shard. Rebuild with a larger list_cap.",
            stacklevel=2)

    def rep(x):
        return np.broadcast_to(np.asarray(x), (n_shards, *np.shape(x))).copy()

    if mesh is None:
        place = jnp.asarray
    else:
        if mesh.size != n_shards:
            raise ValueError(f"mesh has {mesh.size} devices but n_shards="
                             f"{n_shards}: one shard per device")
        sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))

        def place(x):
            return jax.device_put(x, sharding)

    return PackedIndex(
        centroids=place(rep(index.centroids)),
        codes=place(codes),
        doc_lens=place(doc_lens),
        res_codes=place(res_codes),
        pq_codebooks=place(rep(index.pq_codebooks)),
        ivf=place(local_ivf),
        ivf_lens=place(local_lens),
        plaid_res=place(plaid_res),
        plaid_cutoffs=place(rep(index.plaid_cutoffs)),
        plaid_weights=place(rep(index.plaid_weights)),
        opq_rotation=place(rep(index.opq_rotation)),
        pred_words=place(pred_words),
    )
