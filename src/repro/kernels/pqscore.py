"""Pallas kernel: fused PQ late interaction with dynamic term filter
(EMVB C3+C4, Eq. 5/6).

Per document tile, entirely in VMEM:
    score[p] = sum_i max_{t in J̄_i} ( cs_t[codes[p,t], i]            (centroid)
                                     + sum_s lut[i, s, res[p,t,s]] )  (residual)
with J̄_i = {t : centroid > th_r} and the Eq. 5 fallback when J̄_i = ∅.

This is the paper's core §4.4 claim made structural: the PQ LUT
(n_q x m x 256 fp32 = 0.5–1 MiB) and the centroid-score table live in VMEM,
token codes stream HBM->VMEM once, and **no decompressed residual ever touches
HBM** — the 5x decompression cost in PLAID's Fig. 1 simply has no analogue.
The m-subspace accumulation is a static unrolled loop so the intermediate is
one (BD, cap, n_q) block rather than a 4-D tensor.

VMEM contract: same as ``cinter`` — cs_t is the per-shard slice at production
scale (DESIGN.md §4).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.interaction import term_sum

DEFAULT_BD = 32
NEG = -1e9


def eq56_block(cs_t: jax.Array, lut2: jax.Array, codes: jax.Array,
               res: jax.Array, valid: jax.Array, thr: jax.Array, *,
               m: int, ksub: int, use_filter: bool,
               qlive: jax.Array | None = None) -> jax.Array:
    """Eq. 5/6 PQ late-interaction scores for one (BD, cap) block -> (BD,).

    cs_t (n_c, n_q); lut2 (m*K, n_q) flattened LUT; res (BD, cap, m) any int
    dtype; valid (BD, cap) bool; thr scalar (ignored unless ``use_filter``).
    qlive optional (n_q,) bool: masked (padded / pruned) query terms
    contribute 0 to the final sum — no per-term max, no Eq. 6 fallback —
    mirroring the reference's zeroing (fp-exact; all-live is the identity).

    Shared by this kernel and the pass-2 stream of ``pqinter.py``. The
    subspace accumulation is the SAME static unroll, in the SAME s = 0..m-1
    order, as the jnp reference ``interaction._lut_gather`` — identical
    reduction order is what keeps kernel scores bitwise equal to the
    reference (and the unroll keeps the intermediate one (BD, cap, n_q)
    block instead of a 4-D tensor). The Eq. 6 threshold comparison happens
    in the centroid dtype, matching the reference's weak-typed-scalar
    semantics under bf16 CS. Keep the three in lockstep."""
    idx = jnp.clip(codes, 0, cs_t.shape[0] - 1)
    centroid = jnp.take(cs_t, idx, axis=0)                  # (BD, cap, n_q)
    res32 = res.astype(jnp.int32)
    residual = jnp.take(lut2, res32[..., 0], axis=0)        # (BD, cap, n_q)
    for s in range(1, m):                                   # static unroll
        residual = residual + jnp.take(lut2, res32[..., s] + s * ksub,
                                       axis=0)
    full = jnp.where(valid[..., None], centroid + residual, NEG)
    if use_filter:
        keep = (centroid > thr.astype(centroid.dtype)) & valid[..., None]
        masked_max = jnp.max(jnp.where(keep, full, NEG), axis=1)
        full_max = jnp.max(full, axis=1)
        any_keep = jnp.any(keep, axis=1)
        colmax = jnp.where(any_keep, masked_max, full_max)  # (BD, n_q)
    else:
        colmax = jnp.max(full, axis=1)
    if qlive is not None:
        colmax = jnp.where(qlive, colmax, 0.0)
    return term_sum(colmax)


def eq56_block_batched(cs_t: jax.Array, lut2: jax.Array, codes: jax.Array,
                       res: jax.Array, valid: jax.Array, thr: jax.Array, *,
                       m: int, ksub: int, use_filter: bool,
                       qlive: jax.Array) -> jax.Array:
    """Batched ``eq56_block``: cs_t (B, n_c, n_q), lut2 (B, m*K, n_q),
    codes/valid (B, BD, cap), res (B, BD, cap, m), qlive (B, n_q) -> (B, BD).

    Row b is bitwise equal to ``eq56_block(cs_t[b], lut2[b], ...)``: the
    subspace accumulation is the SAME static unroll in the SAME s = 0..m-1
    order (per-row gathers via ``take_along_axis`` fetch the rows
    ``jnp.take`` fetches per query), the Eq. 6 comparison happens in the
    centroid dtype, and the max/``term_sum`` reductions act per row.  Used
    by the pass-2 stream of the batched ``pqinter`` kernel — keep in
    lockstep with ``eq56_block`` and the jnp reference."""
    nb, bd, cap = codes.shape
    n_q = cs_t.shape[2]
    idx = jnp.clip(codes, 0, cs_t.shape[1] - 1)
    centroid = jnp.take_along_axis(
        cs_t, idx.reshape(nb, bd * cap, 1), axis=1).reshape(nb, bd, cap, n_q)
    res32 = res.astype(jnp.int32)

    def _gather(sub):
        return jnp.take_along_axis(
            lut2, sub.reshape(nb, bd * cap, 1),
            axis=1).reshape(nb, bd, cap, n_q)

    residual = _gather(res32[..., 0])
    for s in range(1, m):                                   # static unroll
        residual = residual + _gather(res32[..., s] + s * ksub)
    full = jnp.where(valid[..., None], centroid + residual, NEG)
    if use_filter:
        keep = (centroid > thr.astype(centroid.dtype)) & valid[..., None]
        masked_max = jnp.max(jnp.where(keep, full, NEG), axis=2)
        full_max = jnp.max(full, axis=2)
        any_keep = jnp.any(keep, axis=2)
        colmax = jnp.where(any_keep, masked_max, full_max)  # (B, BD, n_q)
    else:
        colmax = jnp.max(full, axis=2)
    colmax = jnp.where(qlive[:, None, :], colmax, 0.0)
    return term_sum(colmax)


def _pqscore_kernel(cs_t_ref, lut2_ref, codes_ref, res_ref, mask_ref, thr_ref,
                    qm_ref, out_ref, *, m: int, ksub: int, use_filter: bool):
    scores = eq56_block(cs_t_ref[...], lut2_ref[...], codes_ref[...],
                        res_ref[...], mask_ref[...] != 0, thr_ref[0],
                        m=m, ksub=ksub, use_filter=use_filter,
                        qlive=qm_ref[0, :] != 0)
    out_ref[...] = scores[None, :]


@functools.partial(jax.jit,
                   static_argnames=("th_r", "block_d", "interpret"))
def pqscore(cs_t: jax.Array, lut: jax.Array, codes: jax.Array,
            res_codes: jax.Array, token_mask: jax.Array,
            th_r: float | None, q_mask: jax.Array | None = None, *,
            block_d: int = DEFAULT_BD, interpret: bool) -> jax.Array:
    """cs_t (n_c, n_q); lut (n_q, m, K); codes (docs, cap);
    res_codes (docs, cap, m) uint8 -> (docs,) fp32 final scores.
    q_mask optional (n_q,) bool — masked terms contribute nothing."""
    n_docs, cap = codes.shape
    n_c, n_q = cs_t.shape
    _, m, ksub = lut.shape
    pad = (-n_docs) % block_d
    codesp = jnp.pad(codes, ((0, pad), (0, 0)))
    resp = jnp.pad(res_codes.astype(jnp.int32), ((0, pad), (0, 0), (0, 0)))
    maskp = jnp.pad(token_mask.astype(jnp.int8), ((0, pad), (0, 0)))
    ndp = n_docs + pad
    lut2 = lut.transpose(1, 2, 0).reshape(m * ksub, n_q)
    thr = jnp.asarray([0.0 if th_r is None else th_r], jnp.float32)
    qm = (jnp.ones((1, n_q), jnp.int8) if q_mask is None
          else q_mask.astype(jnp.int8).reshape(1, n_q))

    kern = functools.partial(_pqscore_kernel, m=m, ksub=ksub,
                             use_filter=th_r is not None)
    out = pl.pallas_call(
        kern,
        grid=(ndp // block_d,),
        in_specs=[
            pl.BlockSpec((n_c, n_q), lambda i: (0, 0)),          # resident
            pl.BlockSpec((m * ksub, n_q), lambda i: (0, 0)),     # resident LUT
            pl.BlockSpec((block_d, cap), lambda i: (i, 0)),
            pl.BlockSpec((block_d, cap, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_d, cap), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1, n_q), lambda i: (0, 0)),            # q_mask
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, ndp), jnp.float32),
        interpret=interpret,
    )(cs_t, lut2, codesp, resp, maskp, thr, qm)
    return out[0, :n_docs]
