"""Fused Pallas megakernel: bit-vector build + candidate masking + pre-filter
scoring + running top-n_filter selection (EMVB phases 1b-2 in ONE kernel).

The seed engine ran this as four kernels with full-corpus intermediates:

    bitpack(CS) -> bits        (n_c,)  HBM round-trip
    bitfilter(bits, codes)     (n_docs,) full-corpus f array in HBM
    where(bitmap, f, -1)       second full-corpus pass
    top_k(f, n_filter)         third full-corpus pass

This kernel streams document blocks once.  Grid step 0 packs the stacked bit
vectors from the (VMEM-resident) centroid-score matrix into an on-chip table;
every step then gathers the packed words for its (BD, cap) code block, masks
by token validity AND the candidate bitmap, popcounts (Eq. 4), and merges the
block's scores into a running top-``n_filter`` kept on chip.  Nothing of
size n_docs ever touches HBM — the only outputs are the (n_filter,) winners
and the (n_c,) bit table (a free byproduct kept for API compatibility).

Predicate filtering (docs/FILTERING.md) rides the same stream: each step
also loads its (BD,) slice of the index's packed predicate plane and — when
a static word-combine ``plan`` is given — ANDs the plan's verdict into the
candidate bitmap INSIDE the launch, so filtered docs are rejected in the
same pass that scores them (no host-side full-corpus pass mask).  The plan
is a static tuple of (required, forbidden) uint32 mask pairs, so distinct
filters trace distinct (still shape-stable) kernels; ``plan=None`` skips
the predicate load entirely and is bit-identical to the pre-predicate
kernel.

Selection is EXACTLY ``top_k(where(bitmap, F, -1), n_filter)`` including
tie-breaking: scores and doc ids are packed into one monotonic int32 key

    key = (f + 1) << ID_BITS  |  (MAX_ID - doc_id)

so "higher f, then lower doc id" is plain integer order and the running merge
is a single ``top_k`` over (n_filter + BD) keys.  f ranges over [-1, 32]
(34 values) which leaves ID_BITS = 25 id bits inside int32: up to 2^25
(~33.5M) documents per shard — far above any per-shard corpus slice here.

TPU notes: the grid is sequential, so the step-0 bit table and the running
keys live in revisited output blocks (the standard Pallas accumulator
pattern).  The merge's ``lax.top_k`` is the one op a Mosaic build would
replace with a bitonic merge over the 8x128 lanes; everything else is VPU
compare/shift/gather, same as the unfused kernels.  Mosaic refuses the
kernel as written (the uint32 ``jnp.sum`` of the bit pack, then the
in-kernel ``jnp.take`` from the bit table), so today it runs only in
interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bitvector import apply_filter_plan

DEFAULT_BD = 256
ID_BITS = 25          # (f+1) <= 33 -> 34 << 25 < 2^31: int32-safe
MAX_ID = (1 << ID_BITS) - 1
KEY_INIT = -(2 ** 31)  # python int: jnp scalars would be captured as consts


def _prefilter_kernel(th_ref, cs_ref, qm_ref, codes_ref, mask_ref, bitmap_ref,
                      pred_ref, bits_ref, keys_ref, *, n_filter: int,
                      plan=None):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cs = cs_ref[...]                                    # (n_q, n_c)
        # Compare in the CS dtype (weak-typed-scalar semantics): for bf16 CS
        # the reference rounds th to bf16 before comparing; do the same here
        # so boundary values cannot flip bits between kernel and oracle.
        # Masked (padded / pruned) query terms pack a 0 bit for every
        # centroid, so the popcount below structurally cannot count them.
        live = qm_ref[...] != 0                             # (n_q, 1)
        m = ((cs > th_ref[0].astype(cs.dtype)) & live).astype(jnp.uint32)
        shifts = jax.lax.broadcasted_iota(jnp.uint32, (cs.shape[0], 1), 0)
        # Disjoint bit positions: sum == OR (same pack as kernels/bitpack.py).
        bits_ref[...] = jnp.sum(m << shifts, axis=0, keepdims=True)
        keys_ref[...] = jnp.full((1, n_filter), KEY_INIT, jnp.int32)

    bits = bits_ref[0, :]                                   # (n_c,) u32
    codes = codes_ref[...]                                  # (BD, cap)
    valid = mask_ref[...] != 0                              # (BD, cap)
    cand = bitmap_ref[0, :] != 0                            # (BD,)
    if plan is not None:
        # Predicate filter, fused into the candidate test: evaluate the
        # static word-combine plan on this block's predicate words.
        cand = cand & apply_filter_plan(plan, pred_ref[0, :])
    bd = codes.shape[0]

    idx = jnp.clip(codes, 0, bits.shape[0] - 1)
    words = jnp.take(bits, idx, axis=0)                     # (BD, cap) u32
    words = jnp.where(valid, words, jnp.uint32(0))
    ored = jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_or, (1,))
    f = jax.lax.population_count(ored).astype(jnp.int32)    # (BD,)
    f = jnp.where(cand, f, -1)

    ids = i * bd + jax.lax.broadcasted_iota(jnp.int32, (bd, 1), 0)[:, 0]
    keys = ((f + 1) << ID_BITS) + (MAX_ID - ids)
    merged = jnp.concatenate([keys_ref[0, :], keys])
    top, _ = jax.lax.top_k(merged, n_filter)
    keys_ref[...] = top[None, :]


def _prefilter_batched_kernel(th_ref, cs_ref, qm_ref, codes_ref, mask_ref,
                              bitmap_ref, pred_ref, bits_ref, keys_ref, *,
                              n_filter: int, plan=None):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        cs = cs_ref[...]                                    # (B, n_q, n_c)
        # Same pack as the single-query kernel, vectorized over the leading
        # batch axis: per query b, bits[b] is bitwise identical to what
        # ``_prefilter_kernel`` packs from cs[b] / qm[b].
        live = qm_ref[...][..., None] != 0                  # (B, n_q, 1)
        m = ((cs > th_ref[0].astype(cs.dtype)) & live).astype(jnp.uint32)
        shifts = jax.lax.broadcasted_iota(
            jnp.uint32, (1, cs.shape[1], 1), 1)
        bits_ref[...] = jnp.sum(m << shifts, axis=1)        # (B, n_c)
        keys_ref[...] = jnp.full(
            (cs.shape[0], n_filter), KEY_INIT, jnp.int32)

    bits = bits_ref[...]                                    # (B, n_c)
    codes = codes_ref[...]                                  # (Bc, BD, cap)
    valid = mask_ref[...] != 0                              # (Bc, BD, cap)
    cand = bitmap_ref[...] != 0                             # (B, BD)
    if plan is not None:
        # The predicate plane is query-independent: ONE (BD,) word slice
        # serves every query in the batch.
        cand = cand & apply_filter_plan(plan, pred_ref[0, :])[None, :]
    nb, bd = cand.shape

    idx = jnp.clip(codes, 0, bits.shape[1] - 1)
    if codes.shape[0] == 1:
        # Shared corpus block (score_all mode): ONE codes slice serves every
        # query in the batch — the amortization the vmap path cannot do.
        words = jnp.take(bits, idx[0], axis=1)              # (B, BD, cap)
        words = jnp.where(valid[0][None], words, jnp.uint32(0))
    else:
        # Per-query candidate blocks (compact mode): row-aligned gather.
        words = jnp.take_along_axis(
            bits, idx.reshape(nb, -1), axis=1).reshape(idx.shape)
        words = jnp.where(valid, words, jnp.uint32(0))
    ored = jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_or, (2,))
    f = jax.lax.population_count(ored).astype(jnp.int32)    # (B, BD)
    f = jnp.where(cand, f, -1)

    ids = i * bd + jax.lax.broadcasted_iota(jnp.int32, (1, bd), 1)
    keys = ((f + 1) << ID_BITS) + (MAX_ID - ids)
    merged = jnp.concatenate([keys_ref[...], keys], axis=1)
    # Batched top_k reduces each row independently with the same
    # lowest-index tie-breaking as the single-query merge: row b of the
    # running keys is bitwise the single-query kernel's buffer for query b.
    top, _ = jax.lax.top_k(merged, n_filter)
    keys_ref[...] = top


@functools.partial(jax.jit,
                   static_argnames=("n_filter", "block_d", "plan",
                                    "interpret"))
def prefilter_batched(cs: jax.Array, th, codes: jax.Array,
                      token_mask: jax.Array, bitmap: jax.Array,
                      n_filter: int, q_masks: jax.Array | None = None, *,
                      pred_words: jax.Array | None = None, plan=None,
                      block_d: int = DEFAULT_BD,
                      interpret: bool) -> tuple[jax.Array, jax.Array,
                                                jax.Array]:
    """Batch-native fused phases 1b-2: one launch for a whole micro-batch.

    cs         : (B, n_q, n_c) centroid scores per query (fp32 or bf16)
    th         : scalar bit-vector threshold (shared across the batch)
    codes      : (n_docs, cap) int32 — ONE corpus shared by every query
                 (score_all mode), or (B, n_docs, cap) per-query candidate
                 blocks (compact mode)
    token_mask : bool, same leading shape as ``codes``
    bitmap     : (B, n_docs) bool candidate bitmaps
    q_masks    : optional (B, n_q) bool per-query term masks
    pred_words : optional (n_docs,) uint32 packed predicate plane, shared
                 across the batch (query-independent)
    plan       : optional STATIC tuple of (required, forbidden) uint32 mask
                 pairs (``FilterPlan.clauses``); when given, each document
                 block's predicate words are tested in-kernel and the
                 verdict ANDed into ``bitmap``. ``None`` skips the predicate
                 load, bit-identical to the unfiltered kernel.
    -> (scores (B, n_filter) i32, doc_ids (B, n_filter) i32,
        bits (B, n_c) u32)

    Row b of every output is bit-identical to
    ``prefilter(cs[b], th, codes[b or :], ..., q_mask=q_masks[b])`` — ids
    AND score bits, including tie order.  Unlike ``jax.vmap(prefilter)``
    (which lifts the batch into an outer grid axis and re-slices the codes
    block per (query, block) step), this kernel walks the document stream
    ONCE: the (B, n_q, n_c) score table stays VMEM-resident and each
    (BD, cap) codes slice is scored for all B queries before the next
    block loads.
    """
    nb, n_q, n_c = cs.shape
    shared = codes.ndim == 2
    n_docs, cap = codes.shape[-2:]
    assert n_q <= 32, "stacked bitvector packs one query term per uint32 bit"
    assert n_filter <= n_docs, \
        f"n_filter={n_filter} exceeds the {n_docs} documents scored"
    assert n_docs <= MAX_ID, "int32 packed keys support up to 2^25 docs/shard"
    assert bitmap.shape == (nb, n_docs)
    pad = (-n_docs) % block_d
    if shared:
        codesp = jnp.pad(codes, ((0, pad), (0, 0)))[None]
        maskp = jnp.pad(token_mask.astype(jnp.int8), ((0, pad), (0, 0)))[None]
    else:
        codesp = jnp.pad(codes, ((0, 0), (0, pad), (0, 0)))
        maskp = jnp.pad(token_mask.astype(jnp.int8),
                        ((0, 0), (0, pad), (0, 0)))
    bmp = jnp.pad(bitmap.astype(jnp.int8), ((0, 0), (0, pad)))
    ndp = n_docs + pad
    bc = codesp.shape[0]
    th_arr = jnp.asarray([th], jnp.float32)
    qm = (jnp.ones((nb, n_q), jnp.int8) if q_masks is None
          else q_masks.astype(jnp.int8).reshape(nb, n_q))
    # Always pass a predicate operand (zeros dummy when unfiltered) so every
    # plan shares ONE pallas_call signature; plan=None never reads it.
    pw = (jnp.zeros((n_docs,), jnp.uint32) if pred_words is None
          else pred_words)
    pwp = jnp.pad(pw, (0, pad))[None, :]
    kern = functools.partial(_prefilter_batched_kernel, n_filter=n_filter,
                             plan=plan)
    bits, keys = pl.pallas_call(
        kern,
        grid=(ndp // block_d,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),              # th
            pl.BlockSpec((nb, n_q, n_c), lambda i: (0, 0, 0)),  # CS resident
            pl.BlockSpec((nb, n_q), lambda i: (0, 0)),       # q_masks
            pl.BlockSpec((bc, block_d, cap), lambda i: (0, i, 0)),
            pl.BlockSpec((bc, block_d, cap), lambda i: (0, i, 0)),
            pl.BlockSpec((nb, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),    # predicate plane
        ],
        out_specs=[
            pl.BlockSpec((nb, n_c), lambda i: (0, 0)),       # revisited accum
            pl.BlockSpec((nb, n_filter), lambda i: (0, 0)),  # revisited accum
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, n_c), jnp.uint32),
            jax.ShapeDtypeStruct((nb, n_filter), jnp.int32),
        ],
        interpret=interpret,
    )(th_arr, cs, qm, codesp, maskp, bmp, pwp)
    scores = (keys >> ID_BITS) - 1
    doc_ids = MAX_ID - (keys & MAX_ID)
    return scores.astype(jnp.int32), doc_ids.astype(jnp.int32), bits


@functools.partial(jax.jit,
                   static_argnames=("n_filter", "block_d", "plan",
                                    "interpret"))
def prefilter(cs: jax.Array, th, codes: jax.Array, token_mask: jax.Array,
              bitmap: jax.Array, n_filter: int,
              q_mask: jax.Array | None = None, *,
              pred_words: jax.Array | None = None, plan=None,
              block_d: int = DEFAULT_BD,
              interpret: bool) -> tuple[jax.Array, jax.Array,
                                        jax.Array]:
    """Fused phases 1b-2 for one query.

    cs         : (n_q, n_c) centroid scores (fp32 or bf16), n_q <= 32
    th         : scalar bit-vector threshold
    codes      : (n_docs, cap) int32 centroid id per token (padded)
    token_mask : (n_docs, cap) bool — True for real tokens
    bitmap     : (n_docs,) bool — candidate docs (IVF union)
    q_mask     : optional (n_q,) bool — masked (padded / pruned) query terms
                 pack a 0 bit, so F(P, q) never counts them (all-ones == no
                 mask, bit for bit)
    pred_words : optional (n_docs,) uint32 packed predicate plane
    plan       : optional STATIC ``FilterPlan.clauses`` tuple — when given,
                 the plan's verdict over ``pred_words`` is ANDed into
                 ``bitmap`` in-kernel (docs/FILTERING.md); ``None`` skips
                 the predicate load, bit-identical to the unfiltered kernel
    -> (scores (n_filter,) int32, doc_ids (n_filter,) int32,
        bits (n_c,) uint32)

    (scores, doc_ids) == ``lax.top_k(where(bitmap & pass, F, -1), n_filter)``
    bit-exactly, including index-order tie-breaking.
    """
    n_q, n_c = cs.shape
    n_docs, cap = codes.shape
    assert n_q <= 32, "stacked bitvector packs one query term per uint32 bit"
    assert n_filter <= n_docs, \
        f"n_filter={n_filter} exceeds the {n_docs} documents scored " \
        f"(compact mode: cand_cap is the document count)"
    assert n_docs <= MAX_ID, "int32 packed keys support up to 2^25 docs/shard"
    pad = (-n_docs) % block_d
    codesp = jnp.pad(codes, ((0, pad), (0, 0)))
    maskp = jnp.pad(token_mask.astype(jnp.int8), ((0, pad), (0, 0)))
    bmp = jnp.pad(bitmap.astype(jnp.int8), (0, pad))[None, :]
    ndp = n_docs + pad
    th_arr = jnp.asarray([th], jnp.float32)
    qm = (jnp.ones((n_q, 1), jnp.int8) if q_mask is None
          else q_mask.astype(jnp.int8).reshape(n_q, 1))
    # Zeros dummy when unfiltered: ONE pallas_call signature per shape, and
    # plan=None statically skips the read.
    pw = (jnp.zeros((n_docs,), jnp.uint32) if pred_words is None
          else pred_words)
    pwp = jnp.pad(pw, (0, pad))[None, :]
    kern = functools.partial(_prefilter_kernel, n_filter=n_filter, plan=plan)
    bits, keys = pl.pallas_call(
        kern,
        grid=(ndp // block_d,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),              # th
            pl.BlockSpec((n_q, n_c), lambda i: (0, 0)),      # CS resident
            pl.BlockSpec((n_q, 1), lambda i: (0, 0)),        # q_mask
            pl.BlockSpec((block_d, cap), lambda i: (i, 0)),
            pl.BlockSpec((block_d, cap), lambda i: (i, 0)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, block_d), lambda i: (0, i)),    # predicate plane
        ],
        out_specs=[
            pl.BlockSpec((1, n_c), lambda i: (0, 0)),        # revisited accum
            pl.BlockSpec((1, n_filter), lambda i: (0, 0)),   # revisited accum
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n_c), jnp.uint32),
            jax.ShapeDtypeStruct((1, n_filter), jnp.int32),
        ],
        interpret=interpret,
    )(th_arr, cs, qm, codesp, maskp, bmp, pwp)
    keys = keys[0]
    scores = (keys >> ID_BITS) - 1
    doc_ids = MAX_ID - (keys & MAX_ID)
    return scores.astype(jnp.int32), doc_ids.astype(jnp.int32), bits[0]
