"""EMVB retrieval engine — the paper's full four-phase pipeline, jit-able.

Phases (single query; batched via vmap):
  1. centroid scoring + candidate generation  (CS matmul, masked top-nprobe,
     IVF gather -> candidate bitmap)                              [paper §4.1]
  2. bit-vector pre-filter F(P,q), select top-n_filter docs       [paper §4.2]
  3. centroid interaction S̄ on survivors, select top-n_docs      [paper §4.3]
  4. PQ late interaction w/ dynamic term filter, final top-k      [paper §4.4]

Every phase has fixed shapes. ``EngineConfig`` is hashable and passed as a
static jit argument. The same functions run single-device (benchmarks/tests)
and under shard_map with per-shard local indices (launch/serve.py).

Query-term masking: every entry point takes an optional per-term mask
(``q_masks (B, n_q)`` / ``q_mask (n_q,)`` bool, True = live). Masked
(zero-padded or pruned) terms are excluded end-to-end — no bit in the
Eq. 4 bit vectors, no IVF probes, no row in S̄, no MaxSim term in Eq. 5/6 —
so retrieval of a padded query with its mask is bit-exact to retrieval of
the unpadded prefix (tests/test_query_masking.py), and ``prune_queries``
turns the mask into a latency knob (smaller static n_q).

The public phase-split entry points (``phase1_candidates`` …
``phase4_late_interaction``, plus the fused ``phase12_prefilter`` and
``phase34_late_interaction``) and ``retrieve`` share the SAME internal
``_phaseN`` helpers, so composing the split phases reproduces ``retrieve``
exactly by construction — the invariant tests/test_engine_phases.py asserts.

Kernel dispatch: ``use_kernels`` selects the Pallas kernels over the jnp
reference math; ``fused_prefilter`` additionally replaces the four-launch
phase 1b-2 sequence (bitpack -> bitfilter -> mask -> top_k, with full-corpus
intermediates) by the single ``kernels/prefilter.py`` megakernel;
``fused_late_interaction`` does the same for phases 3-4 (cinter -> top_k ->
gather -> pqscore -> top_k becomes the single ``kernels/pqinter.py``
megakernel). Whether the kernels run in the Pallas interpreter or compile
with Mosaic follows the platform (``repro.kernels.ops``), not the config.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import TYPE_CHECKING, NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import bitvector, interaction
from .index import PackedIndex
from .pq import build_lut
from repro.obs import trace

if TYPE_CHECKING:  # avoid a runtime engine <-> store import cycle
    from .store import ShardedTimeline


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static retrieval configuration — hashable, passed as a jit-static arg.

    Field groups: the paper's knobs (``th`` for the Eq. 4 bit vectors,
    ``th_r`` for the Eq. 6 term filter, ``nprobe``/``n_filter``/``n_docs``/
    ``k`` for the per-phase selection budgets) and the implementation knobs
    (kernel dispatch, candidate layout, CS precision). ``__post_init__``
    rejects inconsistent combinations with actionable errors.
    """

    n_q: int = 32            # query terms (<= 32: one uint32 bit per term)
    nprobe: int = 4          # centroid lists unioned per query term
    th: float = 0.4          # bit-vector threshold (paper Fig. 2: 0.4)
    th_r: Optional[float] = 0.5   # Eq. 6 term filter; None -> Eq. 5
    n_filter: int = 512      # docs surviving the bit-vector pre-filter
    n_docs: int = 64         # docs entering PQ late interaction
    k: int = 10              # final results
    use_kernels: bool = False  # Pallas kernels vs jnp ref
    # With use_kernels: run phases 1b-2 as the single fused megakernel
    # (kernels/prefilter.py) instead of bitpack -> bitfilter -> mask -> top_k
    # with full-corpus intermediates. False keeps the four separate kernels
    # (the benchmarks time both).
    fused_prefilter: bool = True
    # With use_kernels: run phases 3-4 as the single fused megakernel
    # (kernels/pqinter.py: centroid interaction + phase-3 top-n_docs + PQ
    # late interaction + final top-k in one launch) instead of
    # cinter -> top_k -> gather -> pqscore -> top_k with per-survivor
    # intermediates. False keeps the two separate kernels.
    fused_late_interaction: bool = True
    # With use_kernels + a fused megakernel: run each micro-batch as ONE
    # batch-native kernel launch (kernels/prefilter.py::prefilter_batched,
    # kernels/pqinter.py::pqinter_batched) that loads the index-resident
    # operands into VMEM once and iterates queries in-kernel, instead of
    # ``jax.vmap`` over single-query launches. Bit-exact to the vmap path
    # (ids AND score bits, tie order); B = 1 and non-kernel configs always
    # take the vmap path.
    batched_kernels: bool = True
    # 'score_all' evaluates F on every (local) doc masked by the candidate
    # bitmap (TPU-friendly); 'compact' gathers candidates into a fixed buffer
    # of size cand_cap first (closer to the paper's CPU loop).
    candidate_mode: str = "score_all"
    cand_cap: int = 4096
    # Per-token compaction for phase 4 (DESIGN.md §2 mode (b)): tokens whose
    # centroid is close to NO query term are compacted away before the
    # centroid/LUT gathers, shrinking them cap -> compact_cap. Requires th_r.
    compact_cap: Optional[int] = None
    # Reduced-precision centroid scores (paper §6: "the centroid interaction
    # is carried out with reduced precision"): "bfloat16" halves the CS
    # matrix HBM traffic — the memory bound of the sharded serving plan.
    cs_dtype: str = "float32"
    # Metadata filter: a compiled bitvector.FilterPlan over the index's
    # predicate plane (docs/FILTERING.md), or None for unfiltered. The plan
    # is a static tuple of word-mask clauses, so the kernel signatures stay
    # shape-stable (one jit trace per distinct plan) and it folds into
    # config_fingerprint — filtered and unfiltered cache entries can never
    # collide. Filtered retrieval enforces the filter at EVERY selection:
    # phase 2 ANDs it into the candidate bitmap (in-kernel for the fused
    # score_all megakernel), phases 3-4 mask non-passing survivors' scores
    # to -inf, so the contract `filtered == retrieve-then-post-filter` holds
    # bit-exactly under lossless budgets.
    doc_filter: Optional[bitvector.FilterPlan] = None

    def __post_init__(self):
        """Fail fast with actionable messages on the configs that otherwise
        die deep inside ``top_k``/the bit pack (or worse, run silently
        wrong)."""
        if self.n_q > 32:
            raise ValueError(
                f"n_q={self.n_q} > 32: the stacked bit vector packs one "
                "query term per bit of a uint32 word (paper Fig. 3); split "
                "the query or widen the word type first")
        if self.k > self.n_docs:
            raise ValueError(
                f"k={self.k} > n_docs={self.n_docs}: phase 4 can only rank "
                "the n_docs survivors of phase 3; raise n_docs (paper uses "
                "n_docs >= 4*k) or lower k")
        if self.n_docs > self.n_filter:
            raise ValueError(
                f"n_docs={self.n_docs} > n_filter={self.n_filter}: phase 3 "
                "selects from the n_filter bit-vector survivors; raise "
                "n_filter or lower n_docs")
        if self.candidate_mode not in ("score_all", "compact"):
            raise ValueError(
                f"unknown candidate_mode={self.candidate_mode!r}: expected "
                "'score_all' (mask the whole corpus by the candidate "
                "bitmap) or 'compact' (gather candidates into a cand_cap "
                "buffer)")
        # cand_cap only bounds the compact-mode candidate buffer; score_all
        # configs never touch it, so don't reject them over its default.
        if self.candidate_mode == "compact" and self.cand_cap < self.n_filter:
            raise ValueError(
                f"cand_cap={self.cand_cap} < n_filter={self.n_filter}: in "
                "candidate_mode='compact' the top-n_filter selection runs "
                "over the cand_cap candidate buffer; raise cand_cap to at "
                "least n_filter")
        if self.compact_cap is not None and self.th_r is None:
            raise ValueError(
                f"compact_cap={self.compact_cap} requires th_r: per-token "
                "compaction keeps tokens whose centroid beats the Eq. 6 "
                "threshold — set th_r or drop compact_cap")
        if self.cs_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown cs_dtype={self.cs_dtype!r}: expected 'float32' or "
                "'bfloat16'")
        if self.doc_filter is not None and \
                not isinstance(self.doc_filter, bitvector.FilterPlan):
            raise ValueError(
                f"doc_filter is a {type(self.doc_filter).__name__}: expected "
                "a compiled FilterPlan (or None) — compile your FilterExpr "
                "against the index's predicate names first with "
                "bitvector.compile_filter(expr, meta.pred_names)")


class RetrievalResult(NamedTuple):
    """Top-k retrieval output: scores sorted descending + global doc ids."""

    scores: jax.Array   # (B, k)
    doc_ids: jax.Array  # (B, k) int32


class QueryBatch(NamedTuple):
    """A batch of queries with its optional per-term mask — the one value
    that travels everywhere ``q`` + ``q_mask`` used to travel as parallel
    loose arrays (engine entry points, the serving batcher, the launch/serve
    plan factories).

    ``q`` is (B, n_q, d); ``q_mask`` is (B, n_q) bool (True = live term) or
    None for all-live. A plain array still works wherever a QueryBatch is
    accepted — ``QueryBatch(q)`` and ``q`` are interchangeable inputs.
    """

    q: jax.Array                       # (B, n_q, d)
    q_mask: Optional[jax.Array] = None  # (B, n_q) bool, None = all live


def _as_query_batch(queries, q_masks=None) -> QueryBatch:
    """Normalize ``queries`` (array or QueryBatch) + optional loose
    ``q_masks`` into one QueryBatch; reject conflicting masks."""
    if isinstance(queries, QueryBatch):
        if q_masks is not None and queries.q_mask is not None:
            raise ValueError(
                "got a q_mask both inside the QueryBatch and as a separate "
                "argument — pass exactly one")
        return QueryBatch(queries.q,
                          queries.q_mask if q_masks is None else q_masks)
    return QueryBatch(queries, q_masks)


def _kops(cfg: EngineConfig):
    """The Pallas kernel dispatch module, or None for the jnp reference."""
    if not cfg.use_kernels:
        return None
    from repro.kernels import ops as kops
    return kops


def _with_filter(cfg: EngineConfig, doc_filter) -> EngineConfig:
    """Fold a per-call ``doc_filter`` into the static config (kwarg wins
    over any filter already on ``cfg``); ``EngineConfig.__post_init__``
    rejects uncompiled FilterExprs with the compile hint."""
    if doc_filter is None:
        return cfg
    return dataclasses.replace(cfg, doc_filter=doc_filter)


# ---------------------------------------------------------------------------
# Phase 1 — centroid scores, bitvector, probes, candidate bitmap
# ---------------------------------------------------------------------------

def centroid_scores(q: jax.Array, centroids: jax.Array,
                    dtype: str = "float32") -> jax.Array:
    """q (n_q, d), centroids (n_c, d) -> CS (n_q, n_c)."""
    if dtype == "bfloat16":
        return (q.astype(jnp.bfloat16) @ centroids.T.astype(jnp.bfloat16))
    return q @ centroids.T


def candidate_bitmap(ivf: jax.Array, ivf_lens: jax.Array, probe_ids: jax.Array,
                     n_docs: int) -> jax.Array:
    """Union of the IVF lists of the probed centroids -> (n_docs,) bool.

    Probe ids >= n_c (the one-past-end sentinel ``masked_topk_centroids``
    emits for masked query terms) contribute NOTHING: their list length is
    forced to 0, so a padded/pruned term cannot add candidates."""
    n_c = ivf.shape[0]
    flat = probe_ids.reshape(-1)
    safe = jnp.clip(flat, 0, n_c - 1)
    lists = jnp.take(ivf, safe, axis=0)                          # (P, list_cap)
    lens = jnp.where(flat < n_c, jnp.take(ivf_lens, safe), 0)    # (P,)
    valid = jnp.arange(ivf.shape[1])[None, :] < lens[:, None]
    ids = jnp.where(valid, lists, n_docs)                        # sentinel
    bitmap = jnp.zeros((n_docs,), jnp.bool_)
    return bitmap.at[ids.reshape(-1)].set(True, mode="drop")


def _doc_pass(index: PackedIndex, cfg: EngineConfig) -> Optional[jax.Array]:
    """(n_docs,) bool — docs passing ``cfg.doc_filter`` — or None when
    unfiltered. Evaluated over the index's predicate plane; constant across
    a query batch, so under vmap it lowers to one corpus-wide pass."""
    if cfg.doc_filter is None:
        return None
    return bitvector.apply_filter_plan(cfg.doc_filter, index.pred_words)


# ---------------------------------------------------------------------------
# Internal phase helpers — single source of truth for retrieve() AND the
# public phase-split entry points.
# ---------------------------------------------------------------------------

# Each phase traces under ``jax.named_scope("engine.phaseN")``: its
# operations carry the name in their metadata, so a device trace can charge
# them to the phase. The scopes change no computation.

@jax.named_scope("engine.phase1")
def _phase1(q: jax.Array, index: PackedIndex, cfg: EngineConfig,
            q_mask: Optional[jax.Array] = None):
    """-> (cs (n_q, n_c), bits (n_c,) u32, bitmap (n_docs,) bool).

    q_mask (n_q,) bool: masked terms pack a 0 bit AND probe no IVF lists."""
    kops = _kops(cfg)
    cs = centroid_scores(q, index.centroids, cfg.cs_dtype)
    if kops is not None:
        bits = kops.bitpack(cs, cfg.th, q_mask)
    else:
        bits = bitvector.build_bitvectors(cs, cfg.th, q_mask)
    probe_ids = bitvector.masked_topk_centroids(cs, cfg.th, cfg.nprobe,
                                                q_mask)
    bitmap = candidate_bitmap(index.ivf, index.ivf_lens, probe_ids,
                              index.codes.shape[0])
    doc_pass = _doc_pass(index, cfg)
    if doc_pass is not None:
        bitmap = bitmap & doc_pass     # filtered docs are never candidates
    return cs, bits, bitmap


def _compact_candidates(bitmap: jax.Array, cfg: EngineConfig):
    """Fixed-size candidate buffer (ids of bitmap==True, arbitrary order)."""
    _, cand_ids = jax.lax.top_k(bitmap.astype(jnp.int32), cfg.cand_cap)
    cand_ids = cand_ids.astype(jnp.int32)
    cand_valid = jnp.take(bitmap, cand_ids)
    return cand_ids, cand_valid


@jax.named_scope("engine.phase2")
def _phase2(index: PackedIndex, token_mask: jax.Array, bits: jax.Array,
            bitmap: jax.Array, cfg: EngineConfig) -> jax.Array:
    """Unfused bit-vector pre-filter -> sel1 (n_filter,) int32."""
    kops = _kops(cfg)
    if cfg.candidate_mode == "compact":
        cand_ids, cand_valid = _compact_candidates(bitmap, cfg)
        c_codes = jnp.take(index.codes, cand_ids, axis=0)
        c_mask = jnp.take(token_mask, cand_ids, axis=0) & cand_valid[:, None]
        if kops is not None:
            f = kops.bitfilter(bits, c_codes, c_mask)
        else:
            f = bitvector.filter_score(bits, c_codes, c_mask)
        f = jnp.where(cand_valid, f, -1)
        _, sel1_local = jax.lax.top_k(f, cfg.n_filter)
        sel1 = jnp.take(cand_ids, sel1_local)
    else:
        if kops is not None:
            f = kops.bitfilter(bits, index.codes, token_mask)
        else:
            f = bitvector.filter_score(bits, index.codes, token_mask)
        f = jnp.where(bitmap, f, -1)                             # (n_docs,)
        _, sel1 = jax.lax.top_k(f, cfg.n_filter)
    return sel1.astype(jnp.int32)


def _phase12(q: jax.Array, index: PackedIndex, token_mask: jax.Array,
             cfg: EngineConfig, q_mask: Optional[jax.Array] = None):
    """Phases 1-2 -> (cs, sel1). Dispatches to the fused megakernel when
    configured; otherwise composes _phase1 + _phase2."""
    kops = _kops(cfg)
    if kops is None or not cfg.fused_prefilter:
        cs, bits, bitmap = _phase1(q, index, cfg, q_mask)
        return cs, _phase2(index, token_mask, bits, bitmap, cfg)
    # Fused path: the bit table never leaves the kernel; no full-corpus f.
    with jax.named_scope("engine.phase1"):
        cs = centroid_scores(q, index.centroids, cfg.cs_dtype)
        probe_ids = bitvector.masked_topk_centroids(cs, cfg.th, cfg.nprobe,
                                                    q_mask)
        bitmap = candidate_bitmap(index.ivf, index.ivf_lens, probe_ids,
                                  index.codes.shape[0])
    with jax.named_scope("engine.phase2"):
        if cfg.candidate_mode == "compact":
            # Filter BEFORE compaction: non-passing docs never enter the
            # fixed-size candidate buffer, matching the unfused path's
            # pre-filtered bitmap bit for bit.
            doc_pass = _doc_pass(index, cfg)
            if doc_pass is not None:
                bitmap = bitmap & doc_pass
            cand_ids, cand_valid = _compact_candidates(bitmap, cfg)
            c_codes = jnp.take(index.codes, cand_ids, axis=0)
            c_mask = jnp.take(token_mask, cand_ids, axis=0)
            _, sel1_local, _ = kops.prefilter(cs, cfg.th, c_codes, c_mask,
                                              cand_valid, cfg.n_filter, q_mask)
            sel1 = jnp.take(cand_ids, sel1_local)
        else:
            # score_all: the predicate words ride into the megakernel and the
            # static word-combine plan ANDs them into the candidate bitmap
            # INSIDE the launch — no host-side full-corpus pass mask.
            plan = None if cfg.doc_filter is None else cfg.doc_filter.clauses
            _, sel1, _ = kops.prefilter(cs, cfg.th, index.codes, token_mask,
                                        bitmap, cfg.n_filter, q_mask,
                                        pred_words=index.pred_words, plan=plan)
    return cs, sel1.astype(jnp.int32)


@jax.named_scope("engine.phase3")
def _phase3(index: PackedIndex, token_mask: jax.Array, cs: jax.Array,
            sel1: jax.Array, cfg: EngineConfig,
            q_mask: Optional[jax.Array] = None) -> jax.Array:
    """Centroid interaction on survivors -> sel2 (n_docs,) int32."""
    kops = _kops(cfg)
    cs_t = cs.T                                                  # (n_c, n_q)
    s1_codes = jnp.take(index.codes, sel1, axis=0)               # (nf, cap)
    s1_mask = jnp.take(token_mask, sel1, axis=0)
    if kops is not None:
        sbar = kops.cinter(cs_t, s1_codes, s1_mask, q_mask)
    else:
        sbar = interaction.centroid_interaction(cs_t, s1_codes, s1_mask,
                                                q_mask)
    doc_pass = _doc_pass(index, cfg)
    if doc_pass is not None:
        # Under tight budgets phase 2's fixed n_filter slots can still admit
        # non-passing fillers; mask their S̄ to -inf so they cannot displace
        # passing docs from the phase-3 cut.
        sbar = jnp.where(jnp.take(doc_pass, sel1), sbar, -jnp.inf)
    _, sel2_local = jax.lax.top_k(sbar, cfg.n_docs)
    return jnp.take(sel1, sel2_local)                            # (nd,)


@jax.named_scope("engine.phase4")
def _phase4(index: PackedIndex, token_mask: jax.Array, q: jax.Array,
            cs: jax.Array, sel2: jax.Array, cfg: EngineConfig,
            q_mask: Optional[jax.Array] = None):
    """PQ late interaction (+ Eq. 6 term filter) -> (scores, ids), (k,)."""
    kops = _kops(cfg)
    n_c = index.centroids.shape[0]
    cs_t = cs.T
    pq = index.pq
    q_rot = q @ index.opq_rotation
    lut = build_lut(q_rot, pq)                                   # (n_q, m, K)
    s2_codes = jnp.take(index.codes, sel2, axis=0)
    s2_res = jnp.take(index.res_codes, sel2, axis=0)
    s2_mask = jnp.take(token_mask, sel2, axis=0)
    if kops is not None:
        scores = kops.pqscore(cs_t, lut, s2_codes, s2_res, s2_mask, cfg.th_r,
                              q_mask)
    elif cfg.compact_cap is not None and cfg.th_r is not None:
        scores = interaction.late_interaction_pq_compact(
            cs_t, lut, s2_codes, s2_res, s2_mask, cfg.th_r, cfg.compact_cap,
            q_mask=q_mask)
    else:
        centroid = None
        if cfg.cs_dtype != "float32":
            # exact f32 centroid term for the FINAL scores: gather the few
            # selected docs' centroid vectors (small) instead of trusting
            # the reduced-precision CS used by phases 1-3
            cent_vecs = jnp.take(index.centroids,
                                 jnp.clip(s2_codes, 0, n_c - 1), axis=0)
            centroid = jnp.einsum("ntd,qd->ntq", cent_vecs, q)
        scores = interaction.late_interaction_pq(
            cs_t, lut, s2_codes, s2_res, s2_mask, cfg.th_r, centroid=centroid,
            q_mask=q_mask)
    doc_pass = _doc_pass(index, cfg)
    if doc_pass is not None:
        # Final guard: a non-passing doc that slipped through the fixed
        # phase-2/3 slots must not appear in the top-k.
        scores = jnp.where(jnp.take(doc_pass, sel2), scores, -jnp.inf)
    top_scores, top_local = jax.lax.top_k(scores, cfg.k)
    return top_scores, jnp.take(sel2, top_local)


def _phase34(index: PackedIndex, token_mask: jax.Array, q: jax.Array,
             cs: jax.Array, sel1: jax.Array, cfg: EngineConfig,
             q_mask: Optional[jax.Array] = None):
    """Phases 3-4 -> (scores, ids), both (k,). Dispatches to the fused
    megakernel when configured; otherwise composes _phase3 + _phase4."""
    kops = _kops(cfg)
    if kops is None or not cfg.fused_late_interaction:
        sel2 = _phase3(index, token_mask, cs, sel1, cfg, q_mask)
        return _phase4(index, token_mask, q, cs, sel2, cfg, q_mask)
    # Fused path: S̄, the phase-3 selection, the Eq. 5/6 PQ scores and the
    # final top-k never leave the kernel; codes/residuals are gathered ONCE
    # for the phase-2 survivors instead of once per phase. The gathers are
    # phase 3's; the launch, phase-3 cut included, is named phase 4.
    with jax.named_scope("engine.phase3"):
        s1_codes = jnp.take(index.codes, sel1, axis=0)           # (nf, cap)
        s1_res = jnp.take(index.res_codes, sel1, axis=0)
        s1_mask = jnp.take(token_mask, sel1, axis=0)
        doc_pass = _doc_pass(index, cfg)
        s1_pass = None if doc_pass is None else jnp.take(doc_pass, sel1)
    with jax.named_scope("engine.phase4"):
        q_rot = q @ index.opq_rotation
        lut = build_lut(q_rot, index.pq)                         # (n_q, m, K)
        top_scores, top_pos, _, _ = kops.pqinter(
            cs.T, lut, s1_codes, s1_res, s1_mask, cfg.th_r, cfg.n_docs,
            cfg.k, q_mask, doc_pass=s1_pass)
        return top_scores, jnp.take(sel1, top_pos)


# ---------------------------------------------------------------------------
# Full pipeline (single query)
# ---------------------------------------------------------------------------

def _retrieve_one(q: jax.Array, index: PackedIndex, token_mask: jax.Array,
                  cfg: EngineConfig,
                  q_mask: Optional[jax.Array] = None) -> RetrievalResult:
    cs, sel1 = _phase12(q, index, token_mask, cfg, q_mask)
    top_scores, top_ids = _phase34(index, token_mask, q, cs, sel1, cfg,
                                   q_mask)
    return RetrievalResult(top_scores, top_ids)


# ---------------------------------------------------------------------------
# Batched phase helpers — ONE launch per micro-batch on the batch-native
# megakernels when ``cfg.batched_kernels`` applies, ``jax.vmap`` over the
# single-query helpers otherwise. The pre-kernel math (centroid scores,
# probes, bitmaps, gathers, LUTs) is vmapped over the SAME single-query
# functions in both branches, so the two paths are bit-identical by
# construction everywhere but the (bit-exact) kernel swap.
# ---------------------------------------------------------------------------

def _vmap1(fn, queries, q_masks):
    """vmap ``fn(q, q_mask)`` over the batch, eliding a ``None`` mask."""
    if q_masks is None:
        return jax.vmap(lambda q: fn(q, None))(queries)
    return jax.vmap(fn)(queries, q_masks)


def _phase12_batch(index: PackedIndex, token_mask: jax.Array,
                   queries: jax.Array, cfg: EngineConfig,
                   q_masks: Optional[jax.Array] = None):
    """Batched phases 1-2 -> (cs (B, n_q, n_c), sel1 (B, n_filter))."""
    kops = _kops(cfg)
    nb = queries.shape[0]
    if (kops is None or not cfg.fused_prefilter or not cfg.batched_kernels
            or nb <= 1):
        return _vmap1(
            lambda q, m: _phase12(q, index, token_mask, cfg, m),
            queries, q_masks)
    with jax.named_scope("engine.phase1"):
        cs = jax.vmap(
            lambda q: centroid_scores(q, index.centroids, cfg.cs_dtype))(queries)
        probe_ids = _vmap1(
            lambda c, m: bitvector.masked_topk_centroids(c, cfg.th, cfg.nprobe,
                                                         m), cs, q_masks)
        bitmap = jax.vmap(
            lambda p: candidate_bitmap(index.ivf, index.ivf_lens, p,
                                       index.codes.shape[0]))(probe_ids)
    with jax.named_scope("engine.phase2"):
        if cfg.candidate_mode == "compact":
            # Same pre-compaction filter as the single-query fused path, shared
            # across the batch (the pass mask is query-independent).
            doc_pass = _doc_pass(index, cfg)
            if doc_pass is not None:
                bitmap = bitmap & doc_pass[None, :]
            cand_ids, cand_valid = jax.vmap(
                lambda b: _compact_candidates(b, cfg))(bitmap)
            c_codes = jnp.take(index.codes, cand_ids, axis=0)  # (B, cand_cap, cap)
            c_mask = jnp.take(token_mask, cand_ids, axis=0)
            _, sel1_local, _ = kops.prefilter_batched(
                cs, cfg.th, c_codes, c_mask, cand_valid, cfg.n_filter, q_masks)
            sel1 = jnp.take_along_axis(cand_ids, sel1_local, axis=1)
        else:
            plan = None if cfg.doc_filter is None else cfg.doc_filter.clauses
            _, sel1, _ = kops.prefilter_batched(
                cs, cfg.th, index.codes, token_mask, bitmap, cfg.n_filter,
                q_masks, pred_words=index.pred_words, plan=plan)
    return cs, sel1.astype(jnp.int32)


def _phase34_batch(index: PackedIndex, token_mask: jax.Array,
                   queries: jax.Array, cs: jax.Array, sel1: jax.Array,
                   cfg: EngineConfig,
                   q_masks: Optional[jax.Array] = None) -> RetrievalResult:
    """Batched phases 3-4 -> RetrievalResult with (B, k) scores/ids."""
    kops = _kops(cfg)
    nb = queries.shape[0]
    if (kops is None or not cfg.fused_late_interaction
            or not cfg.batched_kernels or nb <= 1):
        if q_masks is None:
            scores, ids = jax.vmap(
                lambda q, c, s: _phase34(index, token_mask, q, c, s, cfg)
            )(queries, cs, sel1)
        else:
            scores, ids = jax.vmap(
                lambda q, c, s, m: _phase34(index, token_mask, q, c, s, cfg,
                                            m))(queries, cs, sel1, q_masks)
        return RetrievalResult(scores, ids)
    # named as in the single-query fused path (_phase34)
    with jax.named_scope("engine.phase3"):
        s1_codes = jnp.take(index.codes, sel1, axis=0)       # (B, nf, cap)
        s1_res = jnp.take(index.res_codes, sel1, axis=0)
        s1_mask = jnp.take(token_mask, sel1, axis=0)
        doc_pass = _doc_pass(index, cfg)
        s1_pass = None if doc_pass is None else \
            jnp.take(doc_pass, sel1)                         # (B, nf)
    with jax.named_scope("engine.phase4"):
        q_rot = jax.vmap(lambda q: q @ index.opq_rotation)(queries)
        lut = jax.vmap(lambda qr: build_lut(qr, index.pq))(q_rot)
        top_scores, top_pos, _, _ = kops.pqinter_batched(
            jnp.swapaxes(cs, -1, -2), lut, s1_codes, s1_res, s1_mask,
            cfg.th_r, cfg.n_docs, cfg.k, q_masks, doc_pass=s1_pass)
        return RetrievalResult(top_scores,
                               jnp.take_along_axis(sel1, top_pos, axis=1))


def _retrieve_batch(index: PackedIndex, queries: jax.Array,
                    cfg: EngineConfig,
                    q_masks: Optional[jax.Array] = None) -> RetrievalResult:
    """The full batched pipeline — shared by ``retrieve`` and the shard_map
    plan in launch/serve.py (so sharded serving rides the batched kernels
    too)."""
    token_mask = index.token_mask()
    cs, sel1 = _phase12_batch(index, token_mask, queries, cfg, q_masks)
    return _phase34_batch(index, token_mask, queries, cs, sel1, cfg, q_masks)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _retrieve_jit(index: PackedIndex, queries: jax.Array, cfg: EngineConfig,
                  q_masks: Optional[jax.Array] = None) -> RetrievalResult:
    return _retrieve_batch(index, queries, cfg, q_masks)


def retrieve(index: PackedIndex, queries, cfg: EngineConfig,
             q_masks: Optional[jax.Array] = None, *,
             doc_filter: Optional[bitvector.FilterPlan] = None
             ) -> RetrievalResult:
    """queries (B, n_q, d) or QueryBatch -> RetrievalResult, (B, k) each.

    doc_filter : optional compiled :class:`~repro.core.bitvector.FilterPlan`
    restricting results to documents whose predicate-plane bits satisfy the
    filter (docs/FILTERING.md); equivalent to setting ``cfg.doc_filter``
    (which it overrides for this call). Filtered retrieval equals
    retrieve-then-post-filter bit for bit under lossless budgets, in every
    dispatch mode.

    q_masks : optional (B, n_q) bool — True for live query terms (or carry
    it inside a :class:`QueryBatch`). Masked (zero-padded / pruned) terms
    are excluded from every phase: they pack no bit into the Eq. 4 bit
    vectors, probe no IVF lists, contribute no row to S̄ and no MaxSim term
    to Eq. 5/6. Retrieval of a padded query with its mask is bit-exact to
    retrieval of the unpadded prefix; omitting the mask (or passing
    all-True) reproduces the unmasked pipeline bit for bit.

    With ``cfg.use_kernels`` + fused megakernels + ``cfg.batched_kernels``
    and B > 1, the batch runs as ONE batch-native kernel launch per fused
    phase pair; otherwise each query runs under ``jax.vmap``. The two paths
    are bit-identical — ids AND score bits, including tie order.
    """
    qb = _as_query_batch(queries, q_masks)
    # spans time DISPATCH, not device compute: jax returns futures, so
    # unless the caller blocks inside the span this measures enqueue cost
    with trace.span("engine.retrieve.dispatch", batch=qb.q.shape[0],
                    filtered=(doc_filter or cfg.doc_filter) is not None):
        return _retrieve_jit(index, qb.q, _with_filter(cfg, doc_filter),
                             qb.q_mask)


# ---------------------------------------------------------------------------
# Phase-split entry points (benchmarks: paper Fig. 1-style breakdown).
#
# ONE convention: ``phaseN(index, queries, cfg, *, q_mask=None, ...)`` on
# BATCHED queries ((B, n_q, d) array or QueryBatch), intermediates riding as
# keyword-only arguments with a leading batch axis, results batched. Every
# entry point also takes ``doc_filter=`` (a compiled FilterPlan), folded
# into the static config exactly as ``retrieve`` does. Each is
# a plain-Python normalizer over a jit'd batched internal that composes the
# SAME _phaseN helpers retrieve() uses, so composing the split phases
# reproduces ``retrieve`` exactly by construction.
#
# The pre-PR-7 single-query signatures (mixed index-first/array-first orders,
# loose positional q/q_mask) still work through deprecation shims for one
# release: they warn ``DeprecationWarning``, lift to B=1 and squeeze the
# result. scripts/check_legacy_signatures.py keeps new in-tree callers out.
# ---------------------------------------------------------------------------

def _warn_legacy(name: str, hint: str) -> None:
    warnings.warn(
        f"{name} with the pre-batch single-query signature is deprecated "
        f"and will be removed; call {name}({hint}) on batched queries "
        "(a (B, n_q, d) array or a QueryBatch) instead",
        DeprecationWarning, stacklevel=3)


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _phase1_entry(index, queries, cfg, q_masks=None):
    return _vmap1(lambda q, m: _phase1(q, index, cfg, m), queries, q_masks)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _phase2_entry(index, cfg, bits, bitmap):
    token_mask = index.token_mask()
    return jax.vmap(
        lambda b, bm: _phase2(index, token_mask, b, bm, cfg))(bits, bitmap)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _phase12_entry(index, queries, cfg, q_masks=None):
    return _phase12_batch(index, index.token_mask(), queries, cfg, q_masks)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _phase3_entry(index, cfg, cs, sel1, q_masks=None):
    token_mask = index.token_mask()
    if q_masks is None:
        return jax.vmap(
            lambda c, s: _phase3(index, token_mask, c, s, cfg))(cs, sel1)
    return jax.vmap(
        lambda c, s, m: _phase3(index, token_mask, c, s, cfg, m)
    )(cs, sel1, q_masks)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _phase4_entry(index, queries, cfg, cs, sel2, q_masks=None):
    token_mask = index.token_mask()
    if q_masks is None:
        scores, ids = jax.vmap(
            lambda q, c, s: _phase4(index, token_mask, q, c, s, cfg)
        )(queries, cs, sel2)
    else:
        scores, ids = jax.vmap(
            lambda q, c, s, m: _phase4(index, token_mask, q, c, s, cfg, m)
        )(queries, cs, sel2, q_masks)
    return RetrievalResult(scores, ids)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _phase34_entry(index, queries, cfg, cs, sel1, q_masks=None):
    return _phase34_batch(index, index.token_mask(), queries, cs, sel1, cfg,
                          q_masks)


def _legacy_call(args, kwargs, cfg_pos: int):
    """Detect a legacy positional call: EngineConfig sitting at the OLD
    position (``cfg_pos``) in the post-``index`` positional args."""
    if len(args) > cfg_pos:
        return isinstance(args[cfg_pos], EngineConfig)
    return False


def phase1_candidates(index: PackedIndex, *args, **kwargs):
    """Phase 1 (paper §4.1) — ``(index, queries, cfg, *, q_mask=None)`` ->
    (cs (B, n_q, n_c), bits (B, n_c) u32, bitmap (B, n_docs) bool): centroid
    scores, the stacked Eq. 4 bit vectors, and the IVF candidate bitmap."""
    queries, cfg = args[0], args[1]
    cfg = _with_filter(cfg, kwargs.get("doc_filter"))
    legacy = (not isinstance(queries, QueryBatch)
              and getattr(queries, "ndim", 3) == 2) or len(args) > 2
    if legacy:
        _warn_legacy("phase1_candidates", "index, queries, cfg")
        q_mask = args[2] if len(args) > 2 else kwargs.get("q_mask")
        qm = None if q_mask is None else q_mask[None]
        return _squeeze0(_phase1_entry(index, queries[None], cfg, qm))
    qb = _as_query_batch(queries, kwargs.get("q_mask"))
    return _phase1_entry(index, qb.q, cfg, qb.q_mask)


def phase2_prefilter(index: PackedIndex, *args, **kwargs):
    """Phase 2 (paper §4.2) — ``(index, queries, cfg, *, bits, bitmap)`` ->
    sel1 (B, n_filter) int32: the bit-vector pre-filter — score F(P, q)
    (paper Eq. 4) for every candidate, select the top-n_filter doc ids.

    ``bits``/``bitmap`` are phase 1's batched outputs; omitted, phase 1
    runs internally. Takes no q_mask: masked terms are already 0 bits in
    ``bits``, so Eq. 4's popcount structurally cannot count them (the
    ``queries`` mask only feeds the internal phase-1 run)."""
    if _legacy_call(args, kwargs, 2):
        _warn_legacy("phase2_prefilter",
                     "index, queries, cfg, bits=..., bitmap=...")
        bits, bitmap, cfg = args
        return _squeeze0(
            _phase2_entry(index, cfg, bits[None], bitmap[None]))
    queries, cfg = args[0], args[1]
    cfg = _with_filter(cfg, kwargs.get("doc_filter"))
    bits, bitmap = kwargs.get("bits"), kwargs.get("bitmap")
    if bits is None or bitmap is None:
        qb = _as_query_batch(queries, kwargs.get("q_mask"))
        _, bits, bitmap = _phase1_entry(index, qb.q, cfg, qb.q_mask)
    return _phase2_entry(index, cfg, bits, bitmap)


def phase12_prefilter(index: PackedIndex, *args, **kwargs):
    """Fused phases 1-2 — ``(index, queries, cfg, *, q_mask=None)`` ->
    (cs (B, n_q, n_c), sel1 (B, n_filter)); with a fused-prefilter config
    this is the megakernel launch (ONE batch-native launch when
    ``cfg.batched_kernels`` applies) the breakdown benchmark times against
    the phase1_candidates + phase2_prefilter pair."""
    queries, cfg = args[0], args[1]
    cfg = _with_filter(cfg, kwargs.get("doc_filter"))
    legacy = (not isinstance(queries, QueryBatch)
              and getattr(queries, "ndim", 3) == 2) or len(args) > 2
    if legacy:
        _warn_legacy("phase12_prefilter", "index, queries, cfg")
        q_mask = args[2] if len(args) > 2 else kwargs.get("q_mask")
        qm = None if q_mask is None else q_mask[None]
        return _squeeze0(_phase12_entry(index, queries[None], cfg, qm))
    qb = _as_query_batch(queries, kwargs.get("q_mask"))
    return _phase12_entry(index, qb.q, cfg, qb.q_mask)


def phase3_centroid_interaction(index: PackedIndex, *args, **kwargs):
    """Phase 3 (paper §4.3) — ``(index, queries, cfg, *, q_mask=None, cs,
    sel1)`` -> sel2 (B, n_docs) int32: centroid interaction S̄ (the Eq. 2
    proxy) on the phase-2 survivors; select the top-n_docs for late
    interaction. ``cs``/``sel1`` are phase 1-2's batched outputs; omitted,
    phases 1-2 run internally."""
    if _legacy_call(args, kwargs, 2):
        _warn_legacy("phase3_centroid_interaction",
                     "index, queries, cfg, cs=..., sel1=...")
        cs, sel1 = args[0], args[1]
        cfg = args[2]
        q_mask = args[3] if len(args) > 3 else kwargs.get("q_mask")
        qm = None if q_mask is None else q_mask[None]
        return _phase3_entry(index, cfg, cs[None], sel1[None], qm)[0]
    queries, cfg = args[0], args[1]
    cfg = _with_filter(cfg, kwargs.get("doc_filter"))
    qb = _as_query_batch(queries, kwargs.get("q_mask"))
    cs, sel1 = kwargs.get("cs"), kwargs.get("sel1")
    if cs is None or sel1 is None:
        cs_c, sel1_c = _phase12_entry(index, qb.q, cfg, qb.q_mask)
        cs = cs_c if cs is None else cs
        sel1 = sel1_c if sel1 is None else sel1
    return _phase3_entry(index, cfg, cs, sel1, qb.q_mask)


def phase4_late_interaction(index: PackedIndex, *args, **kwargs):
    """Phase 4 (paper §4.4) — ``(index, queries, cfg, *, q_mask=None, cs,
    sel2)`` -> RetrievalResult ((B, k) scores/ids): PQ late interaction on
    the phase-3 survivors — paper Eq. 5, or Eq. 6 with the dynamic per-term
    filter when ``cfg.th_r`` is set — and the final top-k selection.
    ``cs``/``sel2`` are phase 1-3's batched outputs; omitted, phases 1-3
    run internally."""
    if _legacy_call(args, kwargs, 3):
        _warn_legacy("phase4_late_interaction",
                     "index, queries, cfg, cs=..., sel2=...")
        q, cs, sel2, cfg = args[0], args[1], args[2], args[3]
        q_mask = args[4] if len(args) > 4 else kwargs.get("q_mask")
        qm = None if q_mask is None else q_mask[None]
        return _squeeze0(
            _phase4_entry(index, q[None], cfg, cs[None], sel2[None], qm))
    queries, cfg = args[0], args[1]
    cfg = _with_filter(cfg, kwargs.get("doc_filter"))
    qb = _as_query_batch(queries, kwargs.get("q_mask"))
    cs, sel2 = kwargs.get("cs"), kwargs.get("sel2")
    if cs is None or sel2 is None:
        cs_c, sel1 = _phase12_entry(index, qb.q, cfg, qb.q_mask)
        cs = cs_c if cs is None else cs
        if sel2 is None:
            sel2 = _phase3_entry(index, cfg, cs, sel1, qb.q_mask)
    return _phase4_entry(index, qb.q, cfg, cs, sel2, qb.q_mask)


def phase34_late_interaction(index: PackedIndex, *args, **kwargs):
    """Fused phases 3-4 — ``(index, queries, cfg, *, q_mask=None, cs,
    sel1)`` -> RetrievalResult ((B, k) scores/ids); with a
    fused-late-interaction config this is the megakernel launch (ONE
    batch-native launch when ``cfg.batched_kernels`` applies) the breakdown
    benchmark times against the phase3_centroid_interaction +
    phase4_late_interaction pair (which keep their unfused behavior,
    mirroring how phase1/phase2 relate to phase12_prefilter). ``cs``/
    ``sel1`` are phase 1-2's batched outputs; omitted, phases 1-2 run
    internally."""
    if _legacy_call(args, kwargs, 3):
        _warn_legacy("phase34_late_interaction",
                     "index, queries, cfg, cs=..., sel1=...")
        q, cs, sel1, cfg = args[0], args[1], args[2], args[3]
        q_mask = args[4] if len(args) > 4 else kwargs.get("q_mask")
        qm = None if q_mask is None else q_mask[None]
        return _squeeze0(
            _phase34_entry(index, q[None], cfg, cs[None], sel1[None], qm))
    queries, cfg = args[0], args[1]
    cfg = _with_filter(cfg, kwargs.get("doc_filter"))
    qb = _as_query_batch(queries, kwargs.get("q_mask"))
    cs, sel1 = kwargs.get("cs"), kwargs.get("sel1")
    if cs is None or sel1 is None:
        cs_c, sel1_c = _phase12_entry(index, qb.q, cfg, qb.q_mask)
        cs = cs_c if cs is None else cs
        sel1 = sel1_c if sel1 is None else sel1
    return _phase34_entry(index, qb.q, cfg, cs, sel1, qb.q_mask)


# ---------------------------------------------------------------------------
# Multi-generation serving (PLAID SHIRTTT): run the fused pipeline per
# immutable index generation, merge per-generation top-k by score.
# ---------------------------------------------------------------------------

def adapt_config_to_corpus(cfg: EngineConfig, n_docs: int,
                           cap: Optional[int] = None) -> EngineConfig:
    """Clamp a config's selection budgets to a (small) corpus of ``n_docs``.

    Timeline generations can be smaller than ``n_filter``/``n_docs``/
    ``cand_cap`` (a freshly opened generation might hold a few hundred
    docs); ``lax.top_k`` cannot select more entries than exist, so the
    budgets are clamped to the generation size. Clamping is lossless: a
    top-min(n_filter, n_docs) cut over n_docs docs keeps everything the
    unclamped cut would. ``k`` is NOT clamped — a generation smaller than
    ``k`` cannot fill a top-k and raises an actionable error instead.

    ``cap`` (the index's per-doc token capacity, ``meta.cap``) additionally
    clamps ``compact_cap``: the per-token compaction buffer selects
    ``compact_cap`` tokens per doc out of ``cap``, so a ``compact_cap``
    above ``cap`` dies in ``lax.top_k`` over the token axis. The clamp is
    lossless too — a buffer covering every token reproduces Eq. 6 exactly
    (tests/test_interaction.py) — and preserves the
    ``compact_cap``-requires-``th_r`` invariant (``None`` stays ``None``,
    a clamped value keeps needing the threshold it already had).
    """
    if n_docs < cfg.k:
        raise ValueError(
            f"corpus/generation has {n_docs} docs but cfg.k={cfg.k}: "
            "every generation must hold >= k docs to fill a per-generation "
            "top-k — batch tiny additions with store.add_passages instead "
            "of opening a new generation")
    nf = min(cfg.n_filter, n_docs)
    cc = cfg.compact_cap
    if cc is not None and cap is not None:
        cc = min(cc, cap)
    return dataclasses.replace(
        cfg, n_filter=nf, n_docs=min(cfg.n_docs, nf),
        cand_cap=max(min(cfg.cand_cap, n_docs), nf), compact_cap=cc)


def merge_partial_topk(parts: list[RetrievalResult],
                       k: int) -> RetrievalResult:
    """Merge per-generation partial top-k results (GLOBAL doc ids) into one
    final top-k.

    Concatenates the partials in generation (= global id) order and
    re-selects the top ``k`` by score. The SINGLE definition of the merge,
    shared by ``retrieve_timeline``, the sharded plan in ``launch/serve.py``
    and the serving cache (``repro.serving``) — so the documented tie
    contract (``lax.top_k`` prefers the earlier concatenation position =
    the lower global doc id) cannot diverge between the paths, and a merge
    of CACHED partials is bit-identical to a merge of freshly computed ones.
    """
    scores = jnp.concatenate([r.scores for r in parts], axis=1)   # (B, G*k)
    ids = jnp.concatenate([r.doc_ids for r in parts], axis=1)
    top_scores, pos = jax.lax.top_k(scores, k)
    return RetrievalResult(top_scores,
                           jnp.take_along_axis(ids, pos, axis=1))


def merge_partial_topk_by_rank(parts: list[RetrievalResult],
                               k: int) -> RetrievalResult:
    """Merge per-EPOCH top-k results whose scores are NOT comparable.

    Scores from different codebook epochs live on different quantization
    grids (each epoch's PQ/centroid codebooks define their own error
    profile), so a by-score merge across epochs would silently prefer
    whichever epoch's codebooks happen to inflate scores — ranks are the
    only calibration-free common currency. The merge interleaves by
    per-epoch rank, NEWEST epoch first at every rank (its codebooks were
    trained on the freshest slice of the distribution, so its rank-r doc is
    the best-informed rank-r claim), and truncates to ``k``:

        rank 0 of epoch E-1, rank 0 of epoch E-2, ..., rank 1 of E-1, ...

    Doc-id sets are disjoint across epochs (each owns a global id range),
    so no dedup is needed. The returned ``scores`` are each doc's OWN-epoch
    score — diagnostic only: they are not sorted and not mutually
    comparable; consumers must rank by position. A single part passes
    through unchanged (the common non-re-epoched case stays bit-exact).
    docs/MAINTENANCE.md discusses the semantics.
    """
    if len(parts) == 1:
        return parts[0]
    ids = jnp.stack([p.doc_ids for p in reversed(parts)], axis=1)  # (B, E, k)
    sc = jnp.stack([p.scores for p in reversed(parts)], axis=1)
    b = ids.shape[0]
    return RetrievalResult(
        jnp.swapaxes(sc, 1, 2).reshape(b, -1)[:, :k],
        jnp.swapaxes(ids, 1, 2).reshape(b, -1)[:, :k])


def merge_generation_topk(parts: list[RetrievalResult], offsets,
                          k: int) -> RetrievalResult:
    """Merge per-generation top-k results carrying LOCAL doc ids.

    Applies each generation's global doc-id ``offset`` then defers to
    :func:`merge_partial_topk` (the single merge definition).
    """
    return merge_partial_topk(
        [RetrievalResult(r.scores, r.doc_ids + off)
         for r, off in zip(parts, offsets)], k)


def retrieve_generation_topk(index: PackedIndex, meta, offset: int,
                             queries: jax.Array, cfg: EngineConfig,
                             q_masks: Optional[jax.Array] = None, *,
                             doc_filter: Optional[bitvector.FilterPlan] = None
                             ) -> RetrievalResult:
    """One generation's partial top-k, doc ids mapped into the GLOBAL space.

    The reusable intermediate of the timeline merge path: runs the full
    four-phase pipeline (``retrieve``, budgets clamped to the generation via
    :func:`adapt_config_to_corpus`) over ONE immutable generation and
    offsets its local doc ids by the generation's position in the timeline.
    ``retrieve_timeline`` is ``merge_partial_topk`` over these partials —
    and because a generation is immutable, a partial depends only on
    (query bytes, generation contents, config), which is exactly what makes
    it cacheable (``repro.serving.cache``): a cached partial merges
    bit-identically with freshly computed ones.

    ``doc_filter`` (or ``cfg.doc_filter``) must be compiled against THIS
    timeline's predicate names — checked against ``meta.pred_names`` here,
    where the generation's meta is in hand.
    """
    cfg = _with_filter(cfg, doc_filter)
    if cfg.doc_filter is not None and \
            tuple(cfg.doc_filter.names) != tuple(meta.pred_names):
        raise ValueError(
            f"doc_filter was compiled against predicate names "
            f"{tuple(cfg.doc_filter.names)} but this generation declares "
            f"{tuple(meta.pred_names)}: bit positions would disagree — "
            "recompile the FilterExpr with compile_filter(expr, "
            "meta.pred_names) for this timeline")
    part = retrieve(index, queries,
                    adapt_config_to_corpus(cfg, meta.n_docs, meta.cap),
                    q_masks)
    return RetrievalResult(part.scores, part.doc_ids + jnp.int32(offset))


def retrieve_timeline(timeline: "ShardedTimeline", queries: jax.Array,
                      cfg: EngineConfig,
                      q_masks: Optional[jax.Array] = None, *,
                      doc_filter=None) -> RetrievalResult:
    """Retrieve over a :class:`~repro.core.store.ShardedTimeline` — the
    PLAID-SHIRTTT merge path.

    Runs the existing fused four-phase pipeline (``retrieve``, so every
    kernel/config choice applies unchanged) once per immutable generation,
    offsets each generation's local doc ids into the global id space, and
    merges the per-generation top-k by score into one final top-k.

    Equivalence contract (tests/test_store.py): all generations share the
    frozen centroid/PQ codebooks, and every phase's SCORE (Eq. 4 filter,
    Eq. 2 proxy, Eq. 5/6 late interaction) is per-document given those
    codebooks — so a document scores bit-identically in a timeline
    generation and in one monolithic index grown over the union corpus.
    With cut-lossless budgets (``n_filter``/``n_docs`` at least the
    candidate count, e.g. the corpus size — clamped per generation
    automatically) the merged top-k therefore equals the monolithic top-k
    exactly, ids AND score bits. Under tight budgets the two legitimately
    diverge in the timeline's FAVOR: phase 2/3 keep the top-n of the
    *visible pool*, and a per-generation pool has fewer competitors — the
    same relative-selection caveat the shard_map plan documents. Score
    ties: ``lax.top_k`` breaks ties toward the lower index at every cut
    and generations are concatenated in id order, so both paths resolve
    ties toward the lower GLOBAL doc id.

    Budgets are clamped per generation via :func:`adapt_config_to_corpus`;
    generations of equal shape share one jit cache entry. The per-generation
    partials are exposed as :func:`retrieve_generation_topk` so the serving
    layer (``repro.serving``) can cache them per immutable generation and
    merge cached + fresh partials through the same
    :func:`merge_partial_topk`.

    Also accepts an :class:`~repro.core.store.EpochedTimeline` (codebook
    epochs opened by drift-triggered re-epoching —
    ``repro.serving.maintenance``): each epoch retrieves as above, its
    local doc ids shift by the epoch's global offset, and the per-epoch
    top-k merge BY RANK through :func:`merge_partial_topk_by_rank` —
    scores from different codebooks are not bit-comparable, ranks are.
    A single-epoch EpochedTimeline is bit-exact to its plain timeline.

    ``doc_filter`` accepts a compiled :class:`FilterPlan` (must match the
    timeline's predicate names) or a raw
    :class:`~repro.core.bitvector.FilterExpr`, which is compiled here
    against each (epoch's) timeline's own predicate names — the one entry
    point where per-epoch name sets can legitimately differ.
    """
    epochs = getattr(timeline, "epochs", None)
    if epochs is not None:
        parts = [
            RetrievalResult(r.scores, r.doc_ids + jnp.int32(eoff))
            for tl, eoff in timeline
            for r in (retrieve_timeline(tl, queries, cfg, q_masks,
                                        doc_filter=doc_filter),)]
        return merge_partial_topk_by_rank(parts, cfg.k)
    if isinstance(doc_filter, bitvector.FilterExpr):
        doc_filter = bitvector.compile_filter(doc_filter,
                                              timeline.metas[0].pred_names)
    cfg = _with_filter(cfg, doc_filter)
    # dispatch-only span (see retrieve): per-generation launches + merge
    # enqueue here; device compute overlaps with whatever the caller does
    # next until it blocks on the result
    with trace.span("engine.retrieve_timeline.dispatch",
                    generations=len(timeline.generations)):
        parts = [retrieve_generation_topk(gen, meta, off, queries, cfg,
                                          q_masks)
                 for gen, meta, off in timeline]
        return merge_partial_topk(parts, cfg.k)


# ---------------------------------------------------------------------------
# Query-embedding pruning (Tonellotto & Macdonald, 2021) — the speed knob
# query masking unlocks on top of EMVB's pipeline.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("keep",))
def prune_queries(q: jax.Array, keep: int,
                  importance: Optional[jax.Array] = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Keep the ``keep`` most important terms of each query.

    q          : (..., n_q, d) query term embeddings
    keep       : static number of terms to retain (keep <= n_q)
    importance : optional (..., n_q) per-term importance. Defaults to the
                 term's L2 norm — zero-padded terms rank last, so pruning
                 doubles as pad-stripping; callers with model-derived
                 importance (e.g. encoder attention mass) pass it here.
    -> (q_pruned (..., keep, d), q_mask (..., keep) bool)

    The selected terms keep their original relative order (so a keep == n_q
    prune is the identity), and ``q_mask`` is False exactly where the kept
    slot holds a zero EMBEDDING (padding) — detected from the term's norm,
    never from the sign of the caller's importance, so zero/negative
    importance scores (attention logits, IDF deltas) on real terms cannot
    silently mask them. Feed both to ``retrieve``: the smaller static n_q
    shrinks every per-term tensor in all four phases — CS rows, bit-vector
    bits, S̄ rows, LUT rows — which is where the latency saving comes from
    (masking alone keeps shapes fixed).
    """
    n_q = q.shape[-2]
    assert keep <= n_q, f"keep={keep} exceeds n_q={n_q}"
    if importance is None:
        importance = jnp.linalg.norm(q, axis=-1)
    _, sel = jax.lax.top_k(importance, keep)
    sel = jnp.sort(sel, axis=-1)                       # original term order
    q_pruned = jnp.take_along_axis(q, sel[..., None], axis=-2)
    q_mask = jnp.linalg.norm(q_pruned, axis=-1) > 0
    return q_pruned, q_mask
