"""Fused Pallas megakernel: centroid interaction + phase-3 selection + PQ
late interaction with the dynamic term filter + final top-k (EMVB phases 3-4
in ONE launch).

The unfused engine runs the tail of the pipeline as two kernels with
full-survivor intermediates and two host-side selections:

    cinter(cs_t, sel1 codes)      (n_filter,) S̄ array in HBM     [§4.3]
    top_k(S̄, n_docs)             host selection -> sel2
    gather codes/res for sel2     second HBM gather
    pqscore(lut, sel2 codes)      (n_docs,) score array in HBM    [§4.4]
    top_k(scores, k)              host selection -> final

This kernel does all five steps in one ``pallas_call``, as two statically
unrolled block loops inside a single kernel invocation (the standard
"grid over independent work, inner loop over the stream" Pallas shape —
here the whole computation is one sequential stream, so the grid is 1):

  * pass 1 walks (BD1, cap) blocks of the phase-2 survivors, gathers their
    centroid columns from the VMEM-resident CS^T, max-reduces to the
    column-wise centroid interaction S̄ (Eq. 2), and merges each block into
    a running top-``n_docs`` buffer of (S̄, survivor position) pairs —
    phase 3's selection, kept on chip.
  * pass 2 walks that buffer in phase-3 rank order, gathers the winners'
    token codes and PQ residual codes, applies the dynamic term filter
    (Eq. 5 when ``th_r is None``, Eq. 6 otherwise — filtered (term, token)
    pairs are masked to -1e9 so they never win the max, i.e. only surviving
    terms contribute a LUT score), and merges each (BD2,) block of final
    scores into a running top-``k``.

Nothing of size ``(n_docs, cap, n_q)`` is ever materialized in HBM — the
centroid+residual score tensor only exists one (BD2, cap, n_q) tile at a
time, and the only outputs are the (k,) winners plus the (n_docs,) phase-3
selection (a free byproduct kept for the phase-split API). The LUT gather
mirrors the reference ``_lut_gather`` exactly — same static unroll, same
subspace accumulation order — because identical reduction order is what
keeps the final scores bitwise equal to the oracle.

Bit-exactness: both running merges are plain ``lax.top_k`` over
[buffer ++ block] concatenations. The buffer is kept sorted (score
descending, survivor position ascending within ties) and every block's
positions exceed everything already seen, so ``top_k``'s lowest-index
tie-breaking reproduces the reference ``top_k`` over the full score array
exactly — same docs, same order, including ties. The per-doc math is the
same gather/where/max/sum sequence as the jnp reference, so scores agree
bitwise and ties resolve identically (tests/test_kernels.py asserts this on
tie-heavy quantized score distributions).

Why not a multi-step grid with revisited accumulator blocks (the
``prefilter.py`` pattern)? Interpret mode — the tier-1 validation target —
lowers the grid to a ``lax.while_loop`` that re-slices EVERY input block and
writes it back into the loop carry on EVERY step; with the (n_filter, cap,
m) residual codes and the flattened LUT necessarily resident (pass 2
gathers arbitrary rows), that carry traffic alone cost more than the whole
unfused pair. A single grid step with static python-unrolled block loops
keeps the identical running-merge algorithm but slices each input exactly
once, and the merge carries are (n_docs,)/(k,) sized.

TPU notes: VMEM contract — CS^T (per-shard slice at production scale,
DESIGN.md §4), the flattened LUT, and the (n_filter, cap[, m]) survivor
arrays must all be resident, ~2.5 MiB at the paper's n_filter=512, cap=48,
m=16 shapes (uint8 residual codes). A Mosaic build would re-block the full-
array reads into (BD, cap) VMEM tiles behind double-buffered DMA and
replace the merge ``lax.top_k`` with a bitonic merge over the 8x128 lanes;
the row gather by phase-3 winner position is the one dynamic-DMA op without
an unfused analogue. Everything else is VPU gather/compare/select, same as
the unfused ``cinter``/``pqscore`` kernels. Mosaic refuses the kernel as
written: its CS^T and LUT gathers are not 2D ("Only 2D gather is
supported"), so today it runs only in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .cinter import sbar_block, sbar_block_batched
from .pqscore import eq56_block, eq56_block_batched

MAX_BD1 = 512         # pass-1 block cap (S̄ is cheap: one gather + max/sum)
MAX_BD2 = 64          # pass-2 block cap (PQ scoring is the heavy stage)
MAX_BB = 8            # batched kernel: queries per grid step (VMEM bound)
NEG_INF = float("-inf")  # buffer init / padding: below any real score


def _pqinter_kernel(thr_ref, cs_t_ref, lut2_ref, codes_ref, res_ref,
                    mask_ref, qm_ref, pass_ref, sbar_ref, pos_ref, tops_ref,
                    topp_ref, *, m: int, ksub: int, use_filter: bool,
                    n_docs: int, k: int, bd1: int, bd2: int, nf: int,
                    nd_pad: int):
    cs_t = cs_t_ref[...]                                    # (n_c, n_q)
    codes = codes_ref[...]                                  # (nfp, cap)
    valid_all = mask_ref[...] != 0                          # (nfp, cap)
    qlive = qm_ref[0, :] != 0                               # (n_q,)
    pass_all = pass_ref[0, :] != 0                          # (nfp,)
    nfp = codes.shape[0]

    # ---- pass 1: S̄ blocks + running top-n_docs (sbar, position) ----------
    # Buffer-init entries carry position -1: with a predicate filter, real
    # rows can be -inf too, and an init entry that survives the -inf ties
    # must be recognizable in pass 2 (a position-0 init would be RESCORED
    # as survivor 0, duplicating a real doc in the top-k). Unfiltered, init
    # entries only ever sit at ranks >= n_filter, where ``live`` already
    # masks them — bit-identical to the previous zeros init.
    sbar_buf = jnp.full((nd_pad,), NEG_INF, jnp.float32)
    pos_buf = jnp.full((nd_pad,), -1, jnp.int32)
    for i in range(nfp // bd1):                             # static unroll
        start = i * bd1
        c = jax.lax.slice_in_dim(codes, start, start + bd1)
        v = jax.lax.slice_in_dim(valid_all, start, start + bd1)
        sbar = sbar_block(cs_t, c, v, qlive)                # (BD1,)
        rows = start + jax.lax.broadcasted_iota(jnp.int32, (bd1, 1), 0)[:, 0]
        p = jax.lax.slice_in_dim(pass_all, start, start + bd1)
        # exact-f32 cast (bf16 CS promotes losslessly; order/ties preserved);
        # padded rows AND predicate-filtered survivors rank below every real
        # passing doc, even all-token-masked ones
        sbar = jnp.where((rows < nf) & p, sbar.astype(jnp.float32), NEG_INF)
        merged_s = jnp.concatenate([sbar_buf, sbar])
        merged_p = jnp.concatenate([pos_buf, rows])
        sbar_buf, sel = jax.lax.top_k(merged_s, nd_pad)
        pos_buf = jnp.take(merged_p, sel)
    sbar_ref[...] = sbar_buf[None, :]
    pos_ref[...] = pos_buf[None, :]

    # ---- pass 2: Eq. 5/6 PQ scores in phase-3 rank order + running top-k --
    lut2 = lut2_ref[...]                                    # (m*K, n_q)
    res_all = res_ref[...]                                  # (nfp, cap, m)
    tops_buf = jnp.full((k,), NEG_INF, jnp.float32)
    topp_buf = jnp.zeros((k,), jnp.int32)
    for j in range(nd_pad // bd2):                          # static unroll
        start = j * bd2
        pos = jax.lax.slice_in_dim(pos_buf, start, start + bd2)
        lane = start + jax.lax.broadcasted_iota(jnp.int32, (bd2, 1), 0)[:, 0]
        live = lane < n_docs                                # buffer tail is
        posc = jnp.clip(pos, 0, nfp - 1)                    # rank > n_docs
        c = jnp.take(codes, posc, axis=0)                   # (BD2, cap)
        res = jnp.take(res_all, posc, axis=0)               # (BD2, cap, m)
        valid = jnp.take(valid_all, posc, axis=0) & live[:, None]
        score = eq56_block(cs_t, lut2, c, res, valid, thr_ref[0],
                           m=m, ksub=ksub, use_filter=use_filter,
                           qlive=qlive)
        # gather the pass bit by survivor position: a filtered doc that
        # still occupies a phase-3 slot must not reach the top-k; buffer
        # fillers (pos < 0) are never rescored
        ok = live & (pos >= 0) & jnp.take(pass_all, posc)
        score = jnp.where(ok, score, NEG_INF)
        merged_s = jnp.concatenate([tops_buf, score])
        merged_p = jnp.concatenate([topp_buf, pos])
        tops_buf, sel = jax.lax.top_k(merged_s, k)
        topp_buf = jnp.take(merged_p, sel)
    tops_ref[...] = tops_buf[None, :]
    topp_ref[...] = topp_buf[None, :]


@functools.partial(jax.jit,
                   static_argnames=("th_r", "n_docs", "k", "block_d1",
                                    "block_d2", "interpret"))
def pqinter(cs_t: jax.Array, lut: jax.Array, codes: jax.Array,
            res_codes: jax.Array, token_mask: jax.Array,
            th_r: float | None, n_docs: int, k: int,
            q_mask: jax.Array | None = None, *,
            doc_pass: jax.Array | None = None,
            block_d1: int | None = None, block_d2: int | None = None,
            interpret: bool) -> tuple[jax.Array, jax.Array,
                                      jax.Array, jax.Array]:
    """Fused phases 3-4 for one query, over the phase-2 survivor set.

    cs_t       : (n_c, n_q) centroid scores, transposed (fp32 or bf16)
    lut        : (n_q, m, K) PQ inner-product LUT for this query
    codes      : (n_filter, cap) int32 token centroid ids of the survivors
    res_codes  : (n_filter, cap, m) PQ codes of the survivors' residuals
    token_mask : (n_filter, cap) bool — True for real tokens
    th_r       : None -> Eq. 5 (score every term); float -> Eq. 6 filter
    n_docs     : phase-3 selection size
    k          : final result count
    q_mask     : optional (n_q,) bool — masked (padded / pruned) terms are
                 excluded from BOTH passes: no row in S̄'s sum, no MaxSim
                 term in Eq. 5/6 (all-ones == no mask, bit for bit)
    doc_pass   : optional (n_filter,) bool — predicate-filter verdict per
                 survivor (docs/FILTERING.md). False rows are masked to -inf
                 in BOTH selections, exactly like the unfused phase-3/4
                 masking, so filtered docs can never reach the top-k
                 (all-ones == no filter, bit for bit)
    -> (scores (k,) f32, pos (k,) i32, sel2 (n_docs,) i32, sbar (n_docs,) f32)

    ``pos``/``sel2`` index the n_filter survivor axis (the caller translates
    through its sel1). (scores, pos) == the unfused
    ``top_k(pqscore(top_k(cinter(...), n_docs) docs), k)`` composition
    bit-exactly, including index-order tie-breaking at both selections;
    ``sel2``/``sbar`` are the phase-3 selection and its S̄ values.
    """
    nf, cap = codes.shape
    n_c, n_q = cs_t.shape
    _, m, ksub = lut.shape
    assert k <= n_docs <= nf, \
        f"need k <= n_docs <= n_filter, got {k}/{n_docs}/{nf}"
    # NOTE: keep this wrapper in lockstep with ``pqinter_batched`` below —
    # the batched kernel is the same two-pass algorithm vectorized over a
    # leading batch axis, and bit-exactness between them is a tested
    # contract.
    if block_d1 is None:
        block_d1 = min(MAX_BD1, nf + (-nf) % 8)
    if block_d2 is None:
        block_d2 = min(MAX_BD2, n_docs + (-n_docs) % 8)
    pad1 = (-nf) % block_d1
    nd_pad = n_docs + ((-n_docs) % block_d2)
    codesp = jnp.pad(codes, ((0, pad1), (0, 0)))
    # residual codes stay uint8 end-to-end; the int32 offset cast happens at
    # the in-kernel gather, exactly where the reference _lut_gather does it
    resp = jnp.pad(res_codes, ((0, pad1), (0, 0), (0, 0)))
    maskp = jnp.pad(token_mask.astype(jnp.int8), ((0, pad1), (0, 0)))
    nfp = nf + pad1
    lut2 = lut.transpose(1, 2, 0).reshape(m * ksub, n_q)
    thr = jnp.asarray([0.0 if th_r is None else th_r], jnp.float32)
    qm = (jnp.ones((1, n_q), jnp.int8) if q_mask is None
          else q_mask.astype(jnp.int8).reshape(1, n_q))
    # All-ones default == no filter; padded rows are already rejected by the
    # rows < nf test, so the pad value is irrelevant (ones keeps it uniform).
    dp = (jnp.ones((nf,), jnp.int8) if doc_pass is None
          else doc_pass.astype(jnp.int8))
    dpp = jnp.pad(dp, (0, pad1), constant_values=1)[None, :]
    kern = functools.partial(
        _pqinter_kernel, m=m, ksub=ksub, use_filter=th_r is not None,
        n_docs=n_docs, k=k, bd1=block_d1, bd2=block_d2, nf=nf, nd_pad=nd_pad)
    sbar, pos, tops, topp = pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),              # th_r
            pl.BlockSpec((n_c, n_q), lambda i: (0, 0)),      # CS^T
            pl.BlockSpec((m * ksub, n_q), lambda i: (0, 0)),  # LUT
            pl.BlockSpec((nfp, cap), lambda i: (0, 0)),      # codes
            pl.BlockSpec((nfp, cap, m), lambda i: (0, 0, 0)),  # residual codes
            pl.BlockSpec((nfp, cap), lambda i: (0, 0)),      # token mask
            pl.BlockSpec((1, n_q), lambda i: (0, 0)),        # q_mask
            pl.BlockSpec((1, nfp), lambda i: (0, 0)),        # doc_pass
        ],
        out_specs=[
            pl.BlockSpec((1, nd_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, nd_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, nd_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, nd_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        interpret=interpret,
    )(thr, cs_t, lut2, codesp, resp, maskp, qm, dpp)
    return tops[0], topp[0], pos[0, :n_docs], sbar[0, :n_docs]


def _pqinter_batched_kernel(thr_ref, cs_t_ref, lut2_ref, codes_ref, res_ref,
                            mask_ref, qm_ref, pass_ref, sbar_ref, pos_ref,
                            tops_ref, topp_ref, *, m: int, ksub: int,
                            use_filter: bool, n_docs: int, k: int, bd1: int,
                            bd2: int, nf: int, nd_pad: int):
    cs_t = cs_t_ref[...]                                    # (BB, n_c, n_q)
    codes = codes_ref[...]                                  # (BB, nfp, cap)
    valid_all = mask_ref[...] != 0                          # (BB, nfp, cap)
    qlive = qm_ref[...] != 0                                # (BB, n_q)
    pass_all = pass_ref[...] != 0                           # (BB, nfp)
    bb, nfp, _ = codes.shape

    # ---- pass 1: batched S̄ blocks + per-row running top-n_docs -----------
    # init position -1: see the single-query kernel's pass-1 comment
    sbar_buf = jnp.full((bb, nd_pad), NEG_INF, jnp.float32)
    pos_buf = jnp.full((bb, nd_pad), -1, jnp.int32)
    for i in range(nfp // bd1):                             # static unroll
        start = i * bd1
        c = jax.lax.slice_in_dim(codes, start, start + bd1, axis=1)
        v = jax.lax.slice_in_dim(valid_all, start, start + bd1, axis=1)
        sbar = sbar_block_batched(cs_t, c, v, qlive)        # (BB, BD1)
        rows = start + jax.lax.broadcasted_iota(jnp.int32, (1, bd1), 1)
        p = jax.lax.slice_in_dim(pass_all, start, start + bd1, axis=1)
        sbar = jnp.where((rows < nf) & p, sbar.astype(jnp.float32), NEG_INF)
        merged_s = jnp.concatenate([sbar_buf, sbar], axis=1)
        merged_p = jnp.concatenate(
            [pos_buf, jnp.broadcast_to(rows, (bb, bd1))], axis=1)
        # per-row top_k: same lowest-index tie-breaking as the single-query
        # merge, applied to each query's buffer independently
        sbar_buf, sel = jax.lax.top_k(merged_s, nd_pad)
        pos_buf = jnp.take_along_axis(merged_p, sel, axis=1)
    sbar_ref[...] = sbar_buf
    pos_ref[...] = pos_buf

    # ---- pass 2: batched Eq. 5/6 in phase-3 rank order + running top-k ----
    lut2 = lut2_ref[...]                                    # (BB, m*K, n_q)
    res_all = res_ref[...]                                  # (BB, nfp, cap, m)
    tops_buf = jnp.full((bb, k), NEG_INF, jnp.float32)
    topp_buf = jnp.zeros((bb, k), jnp.int32)
    for j in range(nd_pad // bd2):                          # static unroll
        start = j * bd2
        pos = jax.lax.slice_in_dim(pos_buf, start, start + bd2, axis=1)
        lane = start + jax.lax.broadcasted_iota(jnp.int32, (1, bd2), 1)
        live = lane < n_docs                                # (1, BD2)
        posc = jnp.clip(pos, 0, nfp - 1)
        c = jnp.take_along_axis(codes, posc[..., None], axis=1)
        res = jnp.take_along_axis(res_all, posc[..., None, None], axis=1)
        valid = (jnp.take_along_axis(valid_all, posc[..., None], axis=1)
                 & live[..., None])
        score = eq56_block_batched(cs_t, lut2, c, res, valid, thr_ref[0],
                                   m=m, ksub=ksub, use_filter=use_filter,
                                   qlive=qlive)
        # same per-row pass gather as the single-query kernel's pass 2
        pas = jnp.take_along_axis(pass_all, posc, axis=1)
        score = jnp.where(live & (pos >= 0) & pas, score, NEG_INF)
        merged_s = jnp.concatenate([tops_buf, score], axis=1)
        merged_p = jnp.concatenate([topp_buf, pos], axis=1)
        tops_buf, sel = jax.lax.top_k(merged_s, k)
        topp_buf = jnp.take_along_axis(merged_p, sel, axis=1)
    tops_ref[...] = tops_buf
    topp_ref[...] = topp_buf


@functools.partial(jax.jit,
                   static_argnames=("th_r", "n_docs", "k", "block_b",
                                    "block_d1", "block_d2", "interpret"))
def pqinter_batched(cs_t: jax.Array, lut: jax.Array, codes: jax.Array,
                    res_codes: jax.Array, token_mask: jax.Array,
                    th_r: float | None, n_docs: int, k: int,
                    q_masks: jax.Array | None = None, *,
                    doc_pass: jax.Array | None = None,
                    block_b: int | None = None, block_d1: int | None = None,
                    block_d2: int | None = None,
                    interpret: bool) -> tuple[jax.Array, jax.Array,
                                              jax.Array, jax.Array]:
    """Batch-native fused phases 3-4: one launch for a whole micro-batch.

    cs_t       : (B, n_c, n_q) per-query transposed centroid scores
    lut        : (B, n_q, m, K) per-query PQ LUTs
    codes      : (B, n_filter, cap) survivors' token centroid ids
    res_codes  : (B, n_filter, cap, m) survivors' PQ residual codes
    token_mask : (B, n_filter, cap) bool
    th_r, n_docs, k : as in ``pqinter`` (shared across the batch)
    q_masks    : optional (B, n_q) bool per-query term masks
    doc_pass   : optional (B, n_filter) bool per-survivor predicate-filter
                 verdicts (as in ``pqinter``; all-ones == no filter)
    -> (scores (B, k), pos (B, k), sel2 (B, n_docs), sbar (B, n_docs))

    Row b of every output is bit-identical to ``pqinter(cs_t[b], lut[b],
    ..., q_mask=q_masks[b])``.  The grid walks the batch in ``block_b``-query
    steps; within a step the two statically unrolled block passes run the
    SAME running-merge algorithm as the single-query kernel, vectorized over
    the step's queries (batched ``lax.top_k`` reduces each row independently
    with identical tie-breaking).  Versus ``jax.vmap(pqinter)`` — which in
    interpret mode re-slices every resident operand once per query — this
    launch slices each query's operands exactly once and amortizes the
    interpreter's per-step overhead over ``block_b`` queries of vectorized
    VPU work.  VMEM contract: ``block_b`` times the single-query residency
    (CS^T + LUT + survivor arrays), so ~``block_b`` * 2.5 MiB at paper
    shapes — the default ``MAX_BB = 8`` keeps that within a v5e core's
    16 MiB VMEM.
    """
    nb, nf, cap = codes.shape
    _, n_c, n_q = cs_t.shape
    _, _, m, ksub = lut.shape
    assert k <= n_docs <= nf, \
        f"need k <= n_docs <= n_filter, got {k}/{n_docs}/{nf}"
    if block_b is None:
        block_b = min(MAX_BB, nb)
    if block_d1 is None:
        block_d1 = min(MAX_BD1, nf + (-nf) % 8)
    if block_d2 is None:
        block_d2 = min(MAX_BD2, n_docs + (-n_docs) % 8)
    pad1 = (-nf) % block_d1
    nd_pad = n_docs + ((-n_docs) % block_d2)
    padb = (-nb) % block_b
    nbp = nb + padb
    # Pad the batch with all-zero queries (zero CS, zero LUT, all-masked
    # tokens and terms): their rows compute finite garbage that is sliced
    # off below and never mixes into real rows (all reductions are per-row).
    csp = jnp.pad(cs_t, ((0, padb), (0, 0), (0, 0)))
    lutp = jnp.pad(lut, ((0, padb), (0, 0), (0, 0), (0, 0)))
    codesp = jnp.pad(codes, ((0, padb), (0, pad1), (0, 0)))
    resp = jnp.pad(res_codes, ((0, padb), (0, pad1), (0, 0), (0, 0)))
    maskp = jnp.pad(token_mask.astype(jnp.int8),
                    ((0, padb), (0, pad1), (0, 0)))
    nfp = nf + pad1
    lut2 = lutp.transpose(0, 2, 3, 1).reshape(nbp, m * ksub, n_q)
    thr = jnp.asarray([0.0 if th_r is None else th_r], jnp.float32)
    qm = (jnp.ones((nb, n_q), jnp.int8) if q_masks is None
          else q_masks.astype(jnp.int8).reshape(nb, n_q))
    qm = jnp.pad(qm, ((0, padb), (0, 0)))
    dp = (jnp.ones((nb, nf), jnp.int8) if doc_pass is None
          else doc_pass.astype(jnp.int8))
    dpp = jnp.pad(dp, ((0, padb), (0, pad1)), constant_values=1)
    kern = functools.partial(
        _pqinter_batched_kernel, m=m, ksub=ksub, use_filter=th_r is not None,
        n_docs=n_docs, k=k, bd1=block_d1, bd2=block_d2, nf=nf, nd_pad=nd_pad)
    sbar, pos, tops, topp = pl.pallas_call(
        kern,
        grid=(nbp // block_b,),
        in_specs=[
            pl.BlockSpec((1,), lambda b: (0,)),              # th_r
            pl.BlockSpec((block_b, n_c, n_q), lambda b: (b, 0, 0)),
            pl.BlockSpec((block_b, m * ksub, n_q), lambda b: (b, 0, 0)),
            pl.BlockSpec((block_b, nfp, cap), lambda b: (b, 0, 0)),
            pl.BlockSpec((block_b, nfp, cap, m), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((block_b, nfp, cap), lambda b: (b, 0, 0)),
            pl.BlockSpec((block_b, n_q), lambda b: (b, 0)),
            pl.BlockSpec((block_b, nfp), lambda b: (b, 0)),  # doc_pass
        ],
        out_specs=[
            pl.BlockSpec((block_b, nd_pad), lambda b: (b, 0)),
            pl.BlockSpec((block_b, nd_pad), lambda b: (b, 0)),
            pl.BlockSpec((block_b, k), lambda b: (b, 0)),
            pl.BlockSpec((block_b, k), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nbp, nd_pad), jnp.float32),
            jax.ShapeDtypeStruct((nbp, nd_pad), jnp.int32),
            jax.ShapeDtypeStruct((nbp, k), jnp.float32),
            jax.ShapeDtypeStruct((nbp, k), jnp.int32),
        ],
        interpret=interpret,
    )(thr, csp, lut2, codesp, resp, maskp, qm, dpp)
    return (tops[:nb], topp[:nb], pos[:nb, :n_docs], sbar[:nb, :n_docs])
