"""ColBERTv2 / PLAID b-bit residual codec — the *baseline* compressor.

Each residual dimension is bucketized into 2^b quantile buckets (b ∈ {1, 2});
codes are bit-packed 8/b per byte. Scoring requires an explicit decompression
step (centroid + bucket value) — exactly the cost the paper's PQ replaces.
Implemented faithfully so benchmarks can reproduce the PLAID column of
Table 1/2 and the Fig. 1 phase breakdown.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ResidualCodec(NamedTuple):
    """PLAID's b-bit quantile bucket codec for residual values."""

    cutoffs: jax.Array         # (2^b - 1,) bucket boundaries
    bucket_weights: jax.Array  # (2^b,) reconstruction values
    b: int                     # static: bits per dimension


def train_residual_codec(residuals: jax.Array, b: int) -> ResidualCodec:
    """Quantile bucketization over a sample of residual values (all dims pooled,
    as in ColBERTv2)."""
    flat = residuals.reshape(-1)
    nbuckets = 1 << b
    qs = jnp.linspace(0.0, 1.0, nbuckets + 1)[1:-1]
    cutoffs = jnp.quantile(flat, qs)
    mids = jnp.linspace(0.0, 1.0, 2 * nbuckets + 1)[1::2]
    bucket_weights = jnp.quantile(flat, mids)
    return ResidualCodec(cutoffs, bucket_weights, b)


_ENCODE_ROWS = 1 << 16


def encode_residual(r: jax.Array, codec: ResidualCodec) -> jax.Array:
    """(..., d) -> (..., d * b / 8) uint8, bit-packed.

    Encodes ``_ENCODE_ROWS`` rows at a time: unchunked, ``searchsorted``
    over a whole index build's residuals (5.2M x 128 at 65,536 MS MARCO
    passages) needs ~10 GiB of TPU temporaries, more than a v5e chip has
    left once the build's other arrays are resident."""
    *lead, d = r.shape

    def _rows(x):
        codes = jnp.searchsorted(codec.cutoffs, x).astype(jnp.uint8)
        return pack_codes(codes, codec.b)

    packed = jax.lax.map(_rows, r.reshape(-1, d), batch_size=_ENCODE_ROWS)
    return packed.reshape(*lead, packed.shape[-1])


def decode_residual(packed: jax.Array, codec: ResidualCodec, d: int) -> jax.Array:
    """(..., d*b/8) uint8 -> (..., d) fp32 reconstruction."""
    codes = unpack_codes(packed, codec.b, d)
    return codec.bucket_weights[codes.astype(jnp.int32)]


def pack_codes(codes: jax.Array, b: int) -> jax.Array:
    """Pack b-bit codes (values < 2^b) along the last axis, 8/b per byte."""
    per = 8 // b
    *lead, d = codes.shape
    assert d % per == 0
    grp = codes.reshape(*lead, d // per, per).astype(jnp.uint32)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * b)
    packed = jnp.sum(grp << shifts, axis=-1)  # disjoint bit fields -> sum == OR
    return packed.astype(jnp.uint8)


def unpack_codes(packed: jax.Array, b: int, d: int) -> jax.Array:
    """Inverse of :func:`pack_codes`: (..., d*b/8) uint8 -> (..., d) codes."""
    per = 8 // b
    mask = jnp.uint32((1 << b) - 1)
    shifts = (jnp.arange(per, dtype=jnp.uint32) * b)
    grp = (packed.astype(jnp.uint32)[..., None] >> shifts) & mask
    out = grp.reshape(*packed.shape[:-1], -1)
    return out[..., :d].astype(jnp.uint8)
