"""Jit'd public wrappers around the Pallas kernels.

Interpret mode or Mosaic is decided here and nowhere else, from the
platform JAX computes on (``_interpret``): the Pallas interpreter off the
TPU, compiled Mosaic on it. There is no switch that sends a TPU run through
the interpreter, so a kernel Mosaic refuses fails with Mosaic's own error.

``q_mask`` (optional (n_q,) bool, True = live term) threads the query-term
mask through every kernel that consumes the query-term axis: ``bitpack`` and
``prefilter`` pack a 0 bit for masked terms, ``cinter``/``pqscore``/
``pqinter`` exclude masked rows from the per-term max sums. ``bitfilter``
takes no mask — it only sees the already-masked packed words.
"""
from __future__ import annotations

import jax

from . import bitpack as _bitpack
from . import bitfilter as _bitfilter
from . import cinter as _cinter
from . import pqinter as _pqinter
from . import pqscore as _pqscore
from . import prefilter as _prefilter


def _interpret() -> bool:
    """Pallas interpret mode everywhere but on a TPU backend."""
    return jax.default_backend() != "tpu"


def bitpack(cs: jax.Array, th: float,
            q_mask: jax.Array | None = None) -> jax.Array:
    """Phase-1a kernel: threshold the (n_q, n_centroids) centroid-score
    matrix at ``th`` and pack each centroid's passing query-term set into
    one uint32 word (EMVB's stacked bit vectors) -> (n_centroids,)."""
    return _bitpack.bitpack(cs, th, q_mask, interpret=_interpret())


def bitfilter(bits: jax.Array, codes: jax.Array,
              token_mask: jax.Array) -> jax.Array:
    """Phase-1b kernel: OR the packed words of each doc's token centroids
    (EMVB Eq. 4) -> (n_docs,) uint32 candidate words (0 = no query term
    close to any token; popcount = evidence strength)."""
    return _bitfilter.bitfilter(bits, codes, token_mask,
                                interpret=_interpret())


def cinter(cs_t: jax.Array, codes: jax.Array, token_mask: jax.Array,
           q_mask: jax.Array | None = None) -> jax.Array:
    """Phase-2 kernel: centroid-interaction approximate scores — per doc,
    sum over query terms of the max centroid score across its tokens ->
    (n_docs,) f32."""
    return _cinter.cinter(cs_t, codes, token_mask, q_mask,
                          interpret=_interpret())


def pqscore(cs_t: jax.Array, lut: jax.Array, codes: jax.Array,
            res_codes: jax.Array, token_mask: jax.Array,
            th_r: float | None, q_mask: jax.Array | None = None) -> jax.Array:
    """Phase-4 kernel: PQ late-interaction over the survivor block —
    centroid score + residual LUT sum per (term, token), optionally
    skipping tokens below ``th_r``, maxed over tokens and summed over live
    terms -> (n_sel,) f32."""
    return _pqscore.pqscore(cs_t, lut, codes, res_codes, token_mask, th_r,
                            q_mask, interpret=_interpret())


def prefilter(cs: jax.Array, th: float, codes: jax.Array,
              token_mask: jax.Array, bitmap: jax.Array, n_filter: int,
              q_mask: jax.Array | None = None, *,
              pred_words: jax.Array | None = None, plan=None):
    """Fused phases 1b-2 megakernel -> (scores, doc_ids, bits).

    ``pred_words`` ((n_docs,) uint32 packed predicate plane) + ``plan``
    (static ``FilterPlan.clauses``) evaluate the predicate filter in-kernel
    and AND it into ``bitmap``; ``plan=None`` is unfiltered."""
    return _prefilter.prefilter(cs, th, codes, token_mask, bitmap, n_filter,
                                q_mask, pred_words=pred_words, plan=plan,
                                interpret=_interpret())


def pqinter(cs_t: jax.Array, lut: jax.Array, codes: jax.Array,
            res_codes: jax.Array, token_mask: jax.Array,
            th_r: float | None, n_docs: int, k: int,
            q_mask: jax.Array | None = None, *,
            doc_pass: jax.Array | None = None):
    """Fused phases 3-4 megakernel -> (scores, pos, sel2, sbar).

    ``doc_pass`` ((n_filter,) bool predicate-filter verdict per survivor)
    masks non-passing rows to -inf in both selections; ``None`` == all
    passing."""
    return _pqinter.pqinter(cs_t, lut, codes, res_codes, token_mask, th_r,
                            n_docs, k, q_mask, doc_pass=doc_pass,
                            interpret=_interpret())


def prefilter_batched(cs: jax.Array, th, codes: jax.Array,
                      token_mask: jax.Array, bitmap: jax.Array,
                      n_filter: int, q_masks: jax.Array | None = None, *,
                      pred_words: jax.Array | None = None, plan=None):
    """Batch-native phases 1b-2 megakernel -> (scores, doc_ids, bits), each
    with a leading batch axis; row b bit-identical to ``prefilter`` on
    query b.  ``codes``/``token_mask`` are (n_docs, cap) shared or
    (B, n_docs, cap) per-query candidate blocks; ``pred_words``/``plan``
    (batch-shared) as in ``prefilter``."""
    return _prefilter.prefilter_batched(cs, th, codes, token_mask, bitmap,
                                        n_filter, q_masks,
                                        pred_words=pred_words, plan=plan,
                                        interpret=_interpret())


def pqinter_batched(cs_t: jax.Array, lut: jax.Array, codes: jax.Array,
                    res_codes: jax.Array, token_mask: jax.Array,
                    th_r: float | None, n_docs: int, k: int,
                    q_masks: jax.Array | None = None, *,
                    doc_pass: jax.Array | None = None):
    """Batch-native phases 3-4 megakernel -> (scores, pos, sel2, sbar),
    each with a leading batch axis; row b bit-identical to ``pqinter`` on
    query b.  ``doc_pass`` is (B, n_filter) per-survivor verdicts."""
    return _pqinter.pqinter_batched(cs_t, lut, codes, res_codes, token_mask,
                                    th_r, n_docs, k, q_masks,
                                    doc_pass=doc_pass,
                                    interpret=_interpret())
