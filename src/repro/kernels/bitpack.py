"""Pallas kernel: threshold + bit-pack the centroid score matrix (EMVB C1a).

CS (n_q<=32, n_c) fp32  ->  bits (n_c,) uint32 with bit i = CS[i, c] > th.

TPU schedule: tile the centroid axis into (n_q, BC) VMEM blocks (BC a
multiple of 128 lanes); the pack is a VPU compare + shift + sum over the
sublane axis — branchless by construction, the TPU analogue of the paper's
"VecBranchless" AVX512 routine (no compressstore needed because we keep the
*dense* word array; see DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BC = 512


def _bitpack_kernel(th_ref, cs_ref, qm_ref, out_ref):
    cs = cs_ref[...]                                   # (n_q, BC)
    n_q = cs.shape[0]
    live = qm_ref[...] != 0                            # (n_q, 1)
    mask = ((cs > th_ref[0]) & live).astype(jnp.uint32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (n_q, 1), 0)
    # Disjoint bit positions: sum == OR. Keep the reduce in uint32.
    out_ref[...] = jnp.sum(mask << shifts, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def bitpack(cs: jax.Array, th, q_mask: jax.Array | None = None, *,
            block_c: int = DEFAULT_BC, interpret: bool) -> jax.Array:
    """cs (n_q, n_c) fp32, th scalar -> (n_c,) uint32.

    q_mask optional (n_q,) bool: masked (padded / pruned) query terms pack a
    0 bit for every centroid, so Eq. 4's popcount cannot count them. The AND
    with an all-ones mask is the bitwise identity, so omitting the mask is
    exactly today's behavior.
    """
    n_q, n_c = cs.shape
    assert n_q <= 32
    pad = (-n_c) % block_c
    csp = jnp.pad(cs, ((0, 0), (0, pad)), constant_values=-jnp.inf)
    ncp = n_c + pad
    th_arr = jnp.asarray([th], jnp.float32)
    if q_mask is None:
        qm = jnp.ones((n_q, 1), jnp.int8)
    else:
        qm = q_mask.astype(jnp.int8).reshape(n_q, 1)
    out = pl.pallas_call(
        _bitpack_kernel,
        grid=(ncp // block_c,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),                  # th (smem-ish)
            pl.BlockSpec((n_q, block_c), lambda i: (0, i)),
            pl.BlockSpec((n_q, 1), lambda i: (0, 0)),            # q_mask
        ],
        out_specs=pl.BlockSpec((1, block_c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, ncp), jnp.uint32),
        interpret=interpret,
    )(th_arr, csp, qm)
    return out[0, :n_c]
