"""EMVB contribution C1 — stacked bit-vector pre-filter (paper §4.2).

The paper stores, for each query term i, the set ``close_i^th`` of centroids
whose score exceeds ``th``, as *vertically stacked* bit vectors: one 32-bit
word per centroid whose bit i says "centroid is close to query term i"
(paper Fig. 3). A passage's filter score is then

    F(P, q) = popcount( OR_{j in P} word[code_j] )            (paper Eq. 4)

i.e. how many query terms have at least one close passage token.

TPU adaptation (see DESIGN.md §2): instead of compressstore'd index lists we
build the packed words directly as a dense (n_c,) uint32 tensor — a pure
VPU threshold+shift+or, branchless by construction. Membership testing is a
uint32 gather + OR-reduction + ``lax.population_count``. These functions are
the jnp reference; ``repro.kernels.bitpack`` / ``repro.kernels.bitfilter``
are the Pallas versions.

The same word layout generalizes beyond query-term membership: a
:class:`PredicateSet` packs up to 32 NAMED per-document boolean predicates
(language, tenant, date bucket, ...) into one uint32 word per document, and a
:class:`FilterExpr` (AND/OR/NOT over predicate names) compiles through
:func:`compile_filter` into a :class:`FilterPlan` — a static tuple of
``(required_mask, forbidden_mask)`` clause pairs that every dispatch path
(jnp reference, unfused kernels, both megakernels) evaluates with the same
two bitwise ops per clause. See docs/FILTERING.md.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np


def build_bitvectors(cs: jax.Array, th: float,
                     q_mask: Optional[jax.Array] = None) -> jax.Array:
    """Pack per-term threshold masks into stacked bit vectors.

    cs     : (..., n_q, n_c) centroid score matrix (n_q <= 32)
    q_mask : optional (..., n_q) bool — True for live query terms. Masked
             (padded / pruned) terms pack a 0 bit for EVERY centroid, so
             Eq. 4's popcount can never count them.
    -> (..., n_c) uint32 ; bit i of word c == (cs[..., i, c] > th)
    """
    n_q = cs.shape[-2]
    assert n_q <= 32, "stacked bitvector packs one query term per bit of uint32"
    mask = (cs > th)
    if q_mask is not None:
        mask = mask & q_mask[..., :, None]
    mask = mask.astype(jnp.uint32)
    shifts = jnp.arange(n_q, dtype=jnp.uint32)
    # Disjoint bit fields: sum == bitwise OR.
    return jnp.sum(mask << shifts[..., :, None], axis=-2).astype(jnp.uint32)


def or_reduce(words: jax.Array, axis: int) -> jax.Array:
    """Bitwise-OR reduction along ``axis``."""
    return jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_or,
                          (axis % words.ndim,))


def filter_score(bits: jax.Array, codes: jax.Array,
                 token_mask: jax.Array) -> jax.Array:
    """Evaluate Eq. 4 for a batch of passages.

    bits       : (n_c,) uint32 stacked bit vectors for ONE query
    codes      : (n_docs, cap) int32 centroid id per token (padded)
    token_mask : (n_docs, cap) bool — True for real tokens
    -> (n_docs,) int32 = F(P, q)
    """
    words = jnp.take(bits, jnp.clip(codes, 0, bits.shape[0] - 1), axis=0)
    words = jnp.where(token_mask, words, jnp.uint32(0))
    ored = or_reduce(words, axis=-1)              # (n_docs,)
    return jax.lax.population_count(ored).astype(jnp.int32)


def filter_score_batch(bits: jax.Array, codes: jax.Array,
                       token_mask: jax.Array) -> jax.Array:
    """Batched over queries: bits (B, n_c) -> (B, n_docs)."""
    return jax.vmap(filter_score, in_axes=(0, None, None))(bits, codes, token_mask)


def _first_of(a, b):
    """The (key, pos) pair ahead in the order greater key, then lower pos."""
    take_a = (a[0] > b[0]) | ((a[0] == b[0]) & (a[1] < b[1]))
    return jnp.where(take_a, a[0], b[0]), jnp.where(take_a, a[1], b[1])


def masked_topk_centroids(cs: jax.Array, th: float, nprobe: int,
                          q_mask: Optional[jax.Array] = None) -> jax.Array:
    """Top-nprobe centroid ids per query term, restricted to the survivors of
    the threshold (paper §4.1: the pre-filter 'tears down' the number of
    evaluated elements; the TPU-native equivalent ranks non-survivors at
    ``cs - 1e6`` so the selection never ranks them above any survivor).

    The ranking runs in f32 regardless of the CS dtype: the old code
    computed ``cs - 1e6`` in the CS dtype, and under reduced-precision CS
    (bf16 ulp at 1e6 is 2048) that offset collapsed all non-survivor scores
    onto a handful of values, so the bf16 probe selection silently diverged
    from the f32 one. Casting to f32 first is the dtype-safe fix that
    PRESERVES the fallback ordering: if a term has fewer than nprobe
    survivors the remaining slots still fall back to the best-scoring
    non-survivors (harmless: their inverted lists are unioned with
    higher-scoring ones). For f32 CS this is bit-identical to the old
    behavior.

    The selection is nprobe statically unrolled passes over the row, each a
    single reduction that picks the greatest remaining score and, among
    equal scores, the lowest index; a pass skips every entry at or ahead of
    the previous pick in that order, so nothing is written back. It is not
    ``jax.lax.top_k`` because the TPU lowers that to a full sort of the
    2^18-wide row to pick 4 entries. Scores compare as ``lax.top_k``'s
    comparator does (the float32 total order: -0.0 below +0.0, NaNs at the
    ends), so the ids and their order, ties included, equal ``lax.top_k``'s.

    q_mask : optional (..., n_q) bool — masked terms probe NOTHING: their
             rows are returned as the one-past-end sentinel ``n_c``, which
             ``candidate_bitmap`` treats as an empty list.
    cs -> (..., n_q, nprobe) int32.
    """
    n_c = cs.shape[-1]
    if nprobe > n_c:
        raise ValueError(f"nprobe={nprobe} exceeds the {n_c} centroids")
    cs32 = cs.astype(jnp.float32)
    masked = jnp.where(cs > th, cs32, cs32 - 1e6)
    # float32 -> int32 key, monotone in the float32 total order
    bits = jax.lax.bitcast_convert_type(masked, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, key.ndim - 1)
    lowest = jnp.int32(np.iinfo(np.int32).min)
    picks = []
    k_last = p_last = None
    for _ in range(nprobe):
        k_, p_ = key, pos
        if picks:
            # Picked entries become (lowest, n_c), which loses to every entry
            # still in the row, even one whose key is ``lowest`` itself.
            kl, pl = k_last[..., None], p_last[..., None]
            left = (key < kl) | ((key == kl) & (pos > pl))
            k_ = jnp.where(left, key, lowest)
            p_ = jnp.where(left, pos, n_c)
        k_last, p_last = jax.lax.reduce((k_, p_), (lowest, jnp.int32(n_c)),
                                        _first_of, (key.ndim - 1,))
        picks.append(p_last)
    idx = jnp.stack(picks, axis=-1)
    if q_mask is not None:
        idx = jnp.where(q_mask[..., :, None], idx, jnp.int32(n_c))
    return idx


# ---------------------------------------------------------------------------
# Predicate planes: the SAME u32 word layout, repurposed for named per-doc
# metadata predicates. Bit i of pred_words[d] == "predicate names[i] holds
# for document d". Built once at index/growth time, persisted per generation
# (store schema v3), and ANDed into the candidate bitmap at query time.
# ---------------------------------------------------------------------------

MAX_PREDICATES = 32  # one uint32 word per document


@dataclasses.dataclass(frozen=True)
class PredicateSet:
    """Named boolean per-document predicates packed one-bit-per-name.

    ``words[d]`` holds bit ``i`` set iff predicate ``names[i]`` is true for
    document ``d`` — the exact layout :func:`build_bitvectors` uses for
    query terms, so the kernels' gather/AND machinery applies unchanged.
    Build one with :meth:`pack`; pass it (or the raw dict) to
    ``build_index(predicates=...)``.
    """

    names: tuple[str, ...]
    words: jax.Array  # (n_docs,) uint32

    @classmethod
    def pack(cls, predicates: Mapping[str, np.ndarray]) -> "PredicateSet":
        """Pack ``{name: (n_docs,) bool array}`` into one word per doc.

        Insertion order of the mapping fixes the bit positions (and thereby
        the on-disk ``pred_names`` order every FilterPlan compiles against).
        """
        names = tuple(predicates)
        if not names:
            raise ValueError(
                "PredicateSet.pack got an empty mapping: pass at least one "
                "named predicate, or use predicates=None for no plane")
        if len(names) > MAX_PREDICATES:
            raise ValueError(
                f"{len(names)} predicates > {MAX_PREDICATES}: the plane "
                "packs one bit per predicate into a uint32 word")
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate predicate names in {names}")
        words = None
        for i, name in enumerate(names):
            col = np.asarray(predicates[name])
            if col.ndim != 1:
                raise ValueError(
                    f"predicate {name!r} has shape {col.shape}: expected a "
                    "1-D (n_docs,) boolean array")
            if words is None:
                words = np.zeros(col.shape[0], np.uint32)
            elif col.shape[0] != words.shape[0]:
                raise ValueError(
                    f"predicate {name!r} has {col.shape[0]} docs but "
                    f"{names[0]!r} has {words.shape[0]}: all predicates "
                    "must cover the same corpus")
            words |= col.astype(bool).astype(np.uint32) << np.uint32(i)
        return cls(names, jnp.asarray(words))

    def mask(self, name: str) -> jax.Array:
        """Unpack one named predicate back to a (n_docs,) bool array."""
        try:
            i = self.names.index(name)
        except ValueError:
            raise ValueError(
                f"unknown predicate {name!r}: this set has {self.names}"
            ) from None
        return (self.words >> jnp.uint32(i)) & jnp.uint32(1) != 0


class FilterExpr:
    """Base of the tiny AND/OR/NOT expression tree over predicate names.

    Compose with operators — ``Pred("en") & ~Pred("draft") | Pred("fr")`` —
    then compile against an index's ``meta.pred_names`` via
    :func:`compile_filter`. Instances are frozen and hashable, so they can
    key caches (the serving layer memoizes compiled plans by expression).
    """

    def __and__(self, other: "FilterExpr") -> "And":
        return And(self, other)

    def __or__(self, other: "FilterExpr") -> "Or":
        return Or(self, other)

    def __invert__(self) -> "Not":
        return Not(self)


@dataclasses.dataclass(frozen=True)
class Pred(FilterExpr):
    """Leaf: the named predicate must hold."""

    name: str


@dataclasses.dataclass(frozen=True)
class And(FilterExpr):
    """Both sub-expressions must hold."""

    lhs: FilterExpr
    rhs: FilterExpr


@dataclasses.dataclass(frozen=True)
class Or(FilterExpr):
    """At least one sub-expression must hold."""

    lhs: FilterExpr
    rhs: FilterExpr


@dataclasses.dataclass(frozen=True)
class Not(FilterExpr):
    """The sub-expression must NOT hold."""

    operand: FilterExpr


@dataclasses.dataclass(frozen=True)
class FilterPlan:
    """A compiled filter: static DNF clauses over one predicate word.

    ``clauses`` is a tuple of ``(required, forbidden)`` uint32 mask pairs; a
    document with word ``w`` passes iff ANY clause has
    ``(w & required) == required and (w & forbidden) == 0``. An empty tuple
    matches nothing; the ``(0, 0)`` clause matches everything. Being a flat
    tuple of Python ints, a plan is hashable — it rides on ``EngineConfig``
    as a static jit argument (one trace per distinct plan, shape-stable
    kernel signatures) and folds into ``config_fingerprint`` so filtered and
    unfiltered cache entries can never collide.

    ``names`` records the pred_names ordering the plan was compiled against;
    layers that hold an :class:`~repro.core.index.IndexMeta` use it to
    reject plans compiled for a different plane layout.
    """

    names: tuple[str, ...]
    clauses: tuple[tuple[int, int], ...]


def _dnf(expr: FilterExpr, bit_of: dict, negate: bool
         ) -> list[tuple[int, int]]:
    """Push negations to the leaves and expand to (required, forbidden)
    clause pairs; contradictory clauses (a bit both required and forbidden)
    are dropped as statically-false."""
    if isinstance(expr, Pred):
        if expr.name not in bit_of:
            raise ValueError(
                f"filter references unknown predicate {expr.name!r}: this "
                f"index has {tuple(bit_of) or '(no predicate plane)'}")
        bit = 1 << bit_of[expr.name]
        return [(0, bit)] if negate else [(bit, 0)]
    if isinstance(expr, Not):
        return _dnf(expr.operand, bit_of, not negate)
    if not isinstance(expr, (And, Or)):
        raise TypeError(
            f"expected a FilterExpr (Pred/And/Or/Not), got "
            f"{type(expr).__name__}")
    lhs = _dnf(expr.lhs, bit_of, negate)
    rhs = _dnf(expr.rhs, bit_of, negate)
    conjunction = isinstance(expr, And) != negate  # De Morgan under negate
    if not conjunction:
        return lhs + rhs
    out = []
    for p1, n1 in lhs:
        for p2, n2 in rhs:
            pos, neg = p1 | p2, n1 | n2
            if pos & neg:
                continue
            out.append((pos, neg))
    return out


def compile_filter(expr: FilterExpr,
                   names: tuple[str, ...]) -> FilterPlan:
    """Compile a :class:`FilterExpr` into a :class:`FilterPlan`.

    ``names`` is the index's predicate ordering (``meta.pred_names``) — bit
    ``i`` of every plane word is ``names[i]``, so a plan is only valid for
    indexes built with the same names in the same order.
    """
    names = tuple(names)
    if len(names) > MAX_PREDICATES:
        raise ValueError(f"{len(names)} predicate names > {MAX_PREDICATES}")
    bit_of = {n: i for i, n in enumerate(names)}
    if len(bit_of) != len(names):
        raise ValueError(f"duplicate predicate names in {names}")
    raw = _dnf(expr, bit_of, False)
    clauses, seen = [], set()
    for c in raw:
        if c not in seen:
            seen.add(c)
            clauses.append(c)
    return FilterPlan(names=names, clauses=tuple(clauses))


def apply_filter_plan(plan: Union[FilterPlan, tuple], words: jax.Array
                      ) -> jax.Array:
    """Evaluate a compiled plan against predicate words.

    ``plan`` : a :class:`FilterPlan` or its raw ``clauses`` tuple (the form
    the kernels receive as a static argument).
    ``words`` : (...,) uint32 predicate plane words.
    -> (...,) bool — True where the document passes the filter. Two bitwise
    ops per clause; every dispatch path shares this exact evaluation, which
    is what makes in-kernel filtering bit-exact against the jnp reference.
    """
    clauses = plan.clauses if isinstance(plan, FilterPlan) else tuple(plan)
    ok = jnp.zeros(words.shape, jnp.bool_)
    for pos, neg in clauses:
        c = jnp.ones(words.shape, jnp.bool_)
        if pos:
            c = c & ((words & jnp.uint32(pos)) == jnp.uint32(pos))
        if neg:
            c = c & ((words & jnp.uint32(neg)) == jnp.uint32(0))
        ok = ok | c
    return ok
