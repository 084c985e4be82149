"""End-to-end query-term masking: the invariant that makes the mask
tractable is

    retrieve(zero-padded query, q_mask)  ==  retrieve(unpadded prefix)

bit-exactly — ids AND score bits — for the jnp reference, the unfused
kernels, both fused megakernels, both candidate modes, and under shard_map;
and an all-True mask (or no mask) reproduces the unmasked pipeline bit for
bit. Plus the bf16 probe-selection regression for ``masked_topk_centroids``
and the ``prune_queries`` helper contract."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EngineConfig, engine, prune_queries
from repro.core.bitvector import masked_topk_centroids

CFG = EngineConfig(nprobe=8, th=0.2, th_r=0.4, n_filter=128, n_docs=48, k=10)
N_PREFIX = 20          # live terms; terms 20..31 are zero padding


def _padded_queries(small_corpus, n=3):
    """(B, 32, d) queries with a zeroed tail + the matching (B, 32) mask."""
    q = np.asarray(small_corpus.queries[:n]).copy()
    q[:, N_PREFIX:, :] = 0.0
    mask = np.zeros(q.shape[:2], bool)
    mask[:, :N_PREFIX] = True
    return jnp.asarray(q), jnp.asarray(mask)


def _assert_results_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.doc_ids), np.asarray(b.doc_ids))
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))


# ---------------------------------------------------------------------------
# padded + mask == unpadded prefix (the tentpole invariant)
# ---------------------------------------------------------------------------

# (use_kernels, fused): jnp reference, unfused Pallas kernels, and the two
# megakernels. The unfused-kernel x compact combination shares all masked
# code paths with the cases below, so it is left out to save two compiles.
@pytest.mark.parametrize("mode,use_kernels,fused", [
    ("score_all", False, False),
    ("compact", False, False),
    ("score_all", True, False),
    ("score_all", True, True),
    ("compact", True, True),
])
def test_padded_query_equals_unpadded_prefix(small_corpus, small_index, mode,
                                             use_kernels, fused):
    idx, _ = small_index
    cfg = dataclasses.replace(CFG, candidate_mode=mode, cand_cap=600,
                              use_kernels=use_kernels, fused_prefilter=fused,
                              fused_late_interaction=fused)
    qp, mask = _padded_queries(small_corpus)
    padded = engine.retrieve(idx, qp, cfg, mask)
    prefix = engine.retrieve(idx, qp[:, :N_PREFIX], cfg)
    _assert_results_equal(padded, prefix)


@pytest.mark.parametrize("th_r", [None, 0.4])
def test_padded_equals_prefix_th_r_modes(small_corpus, small_index, th_r):
    """Eq. 5 (no term filter) and Eq. 6 both honour the mask."""
    idx, _ = small_index
    cfg = dataclasses.replace(CFG, th_r=th_r)
    qp, mask = _padded_queries(small_corpus, n=2)
    padded = engine.retrieve(idx, qp, cfg, mask)
    prefix = engine.retrieve(idx, qp[:, :N_PREFIX], cfg)
    _assert_results_equal(padded, prefix)


def test_padded_equals_prefix_compact_cap(small_corpus, small_index):
    """Per-token compaction path: masked terms must not keep tokens alive
    through the keymax criterion."""
    idx, meta = small_index
    cfg = dataclasses.replace(CFG, compact_cap=meta.cap)
    qp, mask = _padded_queries(small_corpus, n=2)
    padded = engine.retrieve(idx, qp, cfg, mask)
    prefix = engine.retrieve(idx, qp[:, :N_PREFIX], cfg)
    _assert_results_equal(padded, prefix)


def test_padded_equals_prefix_under_shard_map(small_corpus, small_index):
    """The shard_map plan replicates the mask like the queries; the merged
    two-level top-k must equal the prefix retrieval bit-exactly, and the
    masked sharded result must equal the masked single-device one."""
    from repro.launch.serve import make_shardmap_retriever, shard_index

    idx, _ = small_index
    kcfg = dataclasses.replace(CFG, use_kernels=True)
    qp, mask = _padded_queries(small_corpus, n=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    retr = make_shardmap_retriever(mesh, kcfg)
    stacked = shard_index(idx, 1)
    with mesh:
        sharded = retr(stacked, qp, mask)
        sharded_prefix = retr(stacked, qp[:, :N_PREFIX])
    _assert_results_equal(sharded, sharded_prefix)
    single = engine.retrieve(idx, qp, kcfg, mask)
    _assert_results_equal(sharded, single)


# ---------------------------------------------------------------------------
# all-True mask == no mask, bit for bit (property test)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_kernels", [False, True])
def test_all_true_mask_is_identity(small_corpus, small_index, use_kernels):
    idx, _ = small_index
    cfg = dataclasses.replace(CFG, use_kernels=use_kernels)
    q = jnp.asarray(small_corpus.queries[:3])
    unmasked = engine.retrieve(idx, q, cfg)
    masked = engine.retrieve(idx, q, cfg, jnp.ones(q.shape[:2], jnp.bool_))
    _assert_results_equal(unmasked, masked)


def test_all_true_mask_is_identity_phase_split(small_corpus, small_index):
    """The phase-split entry points honour the mask the same way."""
    idx, _ = small_index
    q = jnp.asarray(small_corpus.queries[:1])
    ones = jnp.ones(q.shape[:2], jnp.bool_)
    cs0, bits0, bm0 = engine.phase1_candidates(idx, q, CFG)
    cs1, bits1, bm1 = engine.phase1_candidates(idx, q, CFG, q_mask=ones)
    np.testing.assert_array_equal(np.asarray(bits0), np.asarray(bits1))
    np.testing.assert_array_equal(np.asarray(bm0), np.asarray(bm1))
    sel1 = jnp.arange(CFG.n_filter, dtype=jnp.int32)[None]
    sel2 = engine.phase3_centroid_interaction(idx, q, CFG, q_mask=ones,
                                              cs=cs0, sel1=sel1)
    sel2_ref = engine.phase3_centroid_interaction(idx, q, CFG, cs=cs0,
                                                  sel1=sel1)
    np.testing.assert_array_equal(np.asarray(sel2), np.asarray(sel2_ref))


# ---------------------------------------------------------------------------
# masked_topk_centroids: dtype-safe probe masking (bf16 regression) + the
# masked-terms-probe-nothing contract
# ---------------------------------------------------------------------------

def test_masked_topk_bf16_matches_f32_selection():
    """Regression: the old ``cs - 1e6`` sentinel, computed in the CS dtype,
    collapsed all non-survivor scores onto one bf16 value (ulp at 1e6 is
    2048), so the bf16 selection silently diverged from the f32 one. With
    the ranking done in f32 the selection is identical for scores exactly
    representable in bf16 — and the best-non-survivor fallback order is
    preserved (slots beyond the survivors rank by score, not index)."""
    # bf16-exact values, one survivor (> th), non-survivors NOT in index
    # order of merit — the old f32 path ranked them by score, the old bf16
    # path by index, so old code fails this equality.
    vals = np.array([[0.5, 0.125, 0.21875, 0.3125, 0.40625,
                      0.25, 0.375, 0.34375]], np.float32)
    cs32 = jnp.asarray(vals)
    cs16 = cs32.astype(jnp.bfloat16)
    th, nprobe = 0.45, 4
    idx32 = np.asarray(masked_topk_centroids(cs32, th, nprobe))
    idx16 = np.asarray(masked_topk_centroids(cs16, th, nprobe))
    np.testing.assert_array_equal(idx32, idx16)
    # survivor first, then the BEST non-survivors by score (not by index)
    np.testing.assert_array_equal(idx32[0], [0, 4, 6, 7])


def test_masked_topk_survivors_lead():
    """Every threshold survivor must outrank every non-survivor."""
    rng = np.random.default_rng(0)
    cs = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    th, nprobe = 0.8, 8
    idx = np.asarray(masked_topk_centroids(cs, th, nprobe))
    cs_np = np.asarray(cs)
    for t in range(4):
        n_surv = int((cs_np[t] > th).sum())
        lead = idx[t, :min(n_surv, nprobe)]
        assert (cs_np[t, lead] > th).all()


def test_masked_terms_probe_nothing():
    rng = np.random.default_rng(1)
    cs = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    q_mask = jnp.asarray([True, False, True, False])
    idx = np.asarray(masked_topk_centroids(cs, 0.2, 4, q_mask))
    assert (idx[1] == 64).all() and (idx[3] == 64).all()  # sentinel == n_c
    ref = np.asarray(masked_topk_centroids(cs, 0.2, 4))
    np.testing.assert_array_equal(idx[0], ref[0])
    np.testing.assert_array_equal(idx[2], ref[2])


def _topk_reference(cs, th, nprobe, q_mask=None):
    """The selection as ``jax.lax.top_k`` makes it, on the same masked scores."""
    cs32 = cs.astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.where(cs > th, cs32, cs32 - 1e6), nprobe)
    if q_mask is not None:
        idx = jnp.where(q_mask[..., :, None], idx, cs.shape[-1])
    return np.asarray(idx)


def _selection_case(name):
    """-> (cs, th, q_mask) for one exactness case."""
    rng = np.random.default_rng(7)
    q_mask = None
    th = 0.4
    if name == "random":
        cs = rng.normal(size=(2, 6, 700)).astype(np.float32)
    elif name == "constant_no_survivor":    # every slot ties at 0.1 - 1e6
        cs = np.full((3, 500), 0.1, np.float32)
    elif name == "tied_survivors":
        cs = rng.choice(np.float32([0.5, 0.75, 0.1]), size=(4, 600))
    elif name == "fewer_survivors_than_nprobe":
        cs = rng.uniform(-1.0, 0.3, size=(4, 600)).astype(np.float32)
        cs[:, [17, 300]] = [0.9, 0.6]
        cs[:, 400:420] = 0.25               # tied non-survivors
    elif name == "q_mask":
        cs = rng.normal(size=(2, 5, 300)).astype(np.float32)
        q_mask = jnp.asarray([[True, False, True, True, False],
                              [False, False, True, False, True]])
    elif name == "bf16":
        cs = jnp.asarray(rng.normal(size=(4, 800)), jnp.bfloat16)
    elif name == "float_specials":          # the float32 total order
        th = -2.0
        cs = np.tile(np.float32([0.0, -0.0, np.inf, np.nan, -np.nan, -np.inf,
                                 1.0, -0.0, 0.0, -3.0]), (2, 1))
    else:                                   # one row at MS MARCO's n_c
        cs = rng.normal(size=(1, 1 << 18)).astype(np.float32)
        cs[0, [5, 70_000, 262_143]] = cs.max()    # a tie at the top
    return jnp.asarray(cs), th, q_mask


@pytest.mark.parametrize("nprobe", [1, 4, 8])
@pytest.mark.parametrize("case", [
    "random", "constant_no_survivor", "tied_survivors",
    "fewer_survivors_than_nprobe", "q_mask", "bf16", "float_specials",
    "n_c_2_18"])
def test_masked_topk_equals_lax_top_k(case, nprobe):
    """The sort-free selection returns ``lax.top_k``'s ids in its order, ties
    to the lower index, eagerly and under jit."""
    cs, th, q_mask = _selection_case(case)
    ref = _topk_reference(cs, th, nprobe, q_mask)
    eager = np.asarray(masked_topk_centroids(cs, th, nprobe, q_mask))
    jitted = np.asarray(jax.jit(masked_topk_centroids, static_argnums=(1, 2))(
        cs, th, nprobe, q_mask))
    assert eager.dtype == np.int32
    np.testing.assert_array_equal(eager, ref)
    np.testing.assert_array_equal(jitted, ref)


def test_sentinel_probes_add_no_candidates():
    """candidate_bitmap must treat sentinel probe ids as empty lists."""
    ivf = jnp.asarray(np.arange(12, dtype=np.int32).reshape(4, 3))
    ivf_lens = jnp.asarray([3, 3, 3, 3], np.int32)
    probes = jnp.asarray([[0], [4]], np.int32)     # term 1 masked -> n_c=4
    bm = np.asarray(engine.candidate_bitmap(ivf, ivf_lens, probes, 16))
    assert set(np.nonzero(bm)[0].tolist()) == {0, 1, 2}


# ---------------------------------------------------------------------------
# prune_queries
# ---------------------------------------------------------------------------

def test_prune_queries_identity_at_full_keep(small_corpus):
    q = jnp.asarray(small_corpus.queries[:2])
    qp, qm = prune_queries(q, q.shape[1])
    np.testing.assert_array_equal(np.asarray(qp), np.asarray(q))
    assert np.asarray(qm).all()


def test_prune_queries_strips_padding_first(small_corpus):
    """Zero-padded terms rank last under the default (norm) importance, so
    pruning down to the live count recovers exactly the prefix."""
    qp_full, _ = _padded_queries(small_corpus, n=2)
    qp, qm = prune_queries(qp_full, N_PREFIX)
    np.testing.assert_array_equal(np.asarray(qp),
                                  np.asarray(qp_full[:, :N_PREFIX]))
    assert np.asarray(qm).all()


def test_prune_queries_masks_kept_padding(small_corpus):
    """keep > live count: the kept zero slots come back mask=False, so
    retrieval with the pruned pair equals the true prefix."""
    idx_keep = N_PREFIX + 4
    qp_full, _ = _padded_queries(small_corpus, n=2)
    qp, qm = prune_queries(qp_full, idx_keep)
    assert np.asarray(qm)[:, :N_PREFIX].all()
    assert not np.asarray(qm)[:, N_PREFIX:].any()


def test_pruned_retrieval_quality(small_corpus, small_index):
    """Dropping a quarter of the terms keeps MRR within a small delta on the
    planted corpus — the latency/quality trade-off the benchmark tracks."""
    from repro.data.synthetic import mrr_at_k

    idx, _ = small_index
    q = jnp.asarray(small_corpus.queries)
    full = mrr_at_k(np.asarray(engine.retrieve(idx, q, CFG).doc_ids),
                    small_corpus.gt_doc)
    qp, qm = prune_queries(q, 24)
    pruned = mrr_at_k(np.asarray(engine.retrieve(idx, qp, CFG, qm).doc_ids),
                      small_corpus.gt_doc)
    assert pruned >= full - 0.15, (pruned, full)
