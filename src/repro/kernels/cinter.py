"""Pallas kernel: column-wise centroid interaction (EMVB C2, Eq. 2).

cs_t (n_c, n_q) fp32, codes (docs, cap) int32 -> S̄ (docs,) fp32
    S̄[p] = sum_i max_t cs_t[codes[p, t], i]

TPU schedule (mirrors paper §4.3, adapted): the paper transposes CS so the
reduction walks contiguous memory and max-reduces with AVX512 compare+blend;
here rows of CS^T are gathered into a (BD*cap, n_q) VMEM block and the
token-axis max is a VPU ``maximum`` accumulation (compare+select), with the
final n_q-sum an 8x128 cross-lane reduce.

VMEM contract: cs_t must fit in VMEM. At |C|=2^18, n_q=32 this is 32 MiB fp32
— larger than a v5e core's VMEM, which is exactly why the production config
shards the centroid axis 16-way over the model axis (local table 2 MiB); see
DESIGN.md §4. The kernel is written against the local shard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.interaction import term_sum

DEFAULT_BD = 128
NEG = -1e9


def sbar_block(cs_t: jax.Array, codes: jax.Array, valid: jax.Array,
               qlive: jax.Array | None = None) -> jax.Array:
    """S̄ for one (BD, cap) block: cs_t (n_c, n_q), valid bool -> (BD,).

    qlive optional (n_q,) bool: masked (padded / pruned) query terms
    contribute 0 to the sum instead of a spurious per-term max (exactly the
    jnp reference's zeroing — adding 0.0 is fp-exact, so the all-live mask
    is the identity).

    Shared by this kernel and the pass-1 stream of ``pqinter.py`` — the
    gather/mask/max/sum order here is the SAME one the jnp reference
    (``interaction.centroid_interaction``) uses, which is what keeps kernel
    S̄ (and therefore phase-3 selection order) bitwise equal to it. Keep the
    three in lockstep."""
    idx = jnp.clip(codes, 0, cs_t.shape[0] - 1)
    pt = jnp.take(cs_t, idx, axis=0)                       # (BD, cap, n_q)
    pt = jnp.where(valid[..., None], pt, NEG)
    colmax = jnp.max(pt, axis=1)                           # (BD, n_q)
    if qlive is not None:
        colmax = jnp.where(qlive, colmax, 0.0)
    return term_sum(colmax)                                # (BD,)


def sbar_block_batched(cs_t: jax.Array, codes: jax.Array, valid: jax.Array,
                       qlive: jax.Array) -> jax.Array:
    """Batched ``sbar_block``: cs_t (B, n_c, n_q), codes/valid (B, BD, cap),
    qlive (B, n_q) -> (B, BD).

    Row b is bitwise equal to ``sbar_block(cs_t[b], codes[b], valid[b],
    qlive[b])`` — the gather/mask/max/sum sequence is the same per-query
    computation vectorized over a leading batch axis (``take_along_axis``
    gathers the same rows ``jnp.take`` does per query; the max and the
    ``term_sum`` chain reduce each row independently in the same order).
    Used by the pass-1 stream of the batched ``pqinter`` kernel — keep in
    lockstep with ``sbar_block`` and the jnp reference."""
    nb, bd, cap = codes.shape
    n_q = cs_t.shape[2]
    idx = jnp.clip(codes, 0, cs_t.shape[1] - 1)
    pt = jnp.take_along_axis(cs_t, idx.reshape(nb, bd * cap, 1), axis=1)
    pt = pt.reshape(nb, bd, cap, n_q)
    pt = jnp.where(valid[..., None], pt, NEG)
    colmax = jnp.max(pt, axis=2)                           # (B, BD, n_q)
    colmax = jnp.where(qlive[:, None, :], colmax, 0.0)
    return term_sum(colmax)                                # (B, BD)


def _cinter_kernel(cs_t_ref, codes_ref, mask_ref, qm_ref, out_ref):
    cs_t = cs_t_ref[...]                                   # (n_c, n_q)
    codes = codes_ref[...]                                 # (BD, cap)
    valid = mask_ref[...] != 0                             # (BD, cap) int8
    qlive = qm_ref[0, :] != 0                              # (n_q,)
    out_ref[...] = sbar_block(cs_t, codes, valid, qlive)[None, :]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cinter(cs_t: jax.Array, codes: jax.Array, token_mask: jax.Array,
           q_mask: jax.Array | None = None, *,
           block_d: int = DEFAULT_BD, interpret: bool) -> jax.Array:
    """cs_t (n_c, n_q); codes/token_mask (docs, cap) -> (docs,) fp32.
    q_mask optional (n_q,) bool — masked terms are excluded from S̄."""
    n_docs, cap = codes.shape
    n_c, n_q = cs_t.shape
    pad = (-n_docs) % block_d
    codesp = jnp.pad(codes, ((0, pad), (0, 0)))
    maskp = jnp.pad(token_mask.astype(jnp.int8), ((0, pad), (0, 0)))
    ndp = n_docs + pad
    qm = (jnp.ones((1, n_q), jnp.int8) if q_mask is None
          else q_mask.astype(jnp.int8).reshape(1, n_q))
    out = pl.pallas_call(
        _cinter_kernel,
        grid=(ndp // block_d,),
        in_specs=[
            pl.BlockSpec((n_c, n_q), lambda i: (0, 0)),          # resident
            pl.BlockSpec((block_d, cap), lambda i: (i, 0)),
            pl.BlockSpec((block_d, cap), lambda i: (i, 0)),
            pl.BlockSpec((1, n_q), lambda i: (0, 0)),            # q_mask
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, ndp), jnp.float32),
        interpret=interpret,
    )(cs_t, codesp, maskp, qm)
    return out[0, :n_docs]
