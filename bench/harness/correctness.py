"""The comparison that decides ``correct``.

After the window, a sample of the answered queries (drawn from the seed)
goes through the plain reference (:mod:`harness.reference`). Two numbers
are compared, each with its limit from the configuration's ``limits``:

* ``score_gap``: the widest gap, over every served (query, rank), between
  the score the service returned and the reference's Eq. 6 score of the
  passage it named. An altered id, an altered score, or a score computed in
  a lower precision shows here. An id outside the corpus reads infinite.
* ``selection_gap``: how far the served top k is from an answer EMVB can
  give. EMVB cuts three times: the top ``n_filter`` candidates by the
  integer pre-filter score F, the top ``n_docs`` of those by Eq. 2, the
  top k of those by Eq. 6. F ties by the hundred at its cut, Eq. 2 ties
  where passages share their best centroids, and nothing in EMVB orders
  the ties. So the served answer is judged against every order: the
  number is the least slack ``eps`` such that some choice of the tied
  passages at the F cut, with every Eq. 2 and Eq. 6 comparison loosened by
  ``eps``, selects exactly the served passages. A missed candidate, a
  passage that cannot pass a cut, or another query's answer reads the
  score by which it is wrong; an answer that no pool the reference keeps
  can judge reads infinite.

A deployment block-sharded over several chips answers with two levels:
each shard makes EMVB's three cuts over its own passages and gives its
top k, and the top k of those is served. So there the reference runs per
shard (:func:`harness.reference.reference_shards`), ``score_gap`` looks a
served passage up in its own shard, by local id, and ``selection_gap`` is
the least ``eps`` at which, in every shard at once, some order of the ties
selects the served passages that shard holds as the top of its answer,
and no passage of its top k that was not served beats the lowest served
Eq. 6 by more than ``eps``. On one shard that is the judgement above.

A run is correct when every query due in the window was answered and both
numbers are within their limits.
"""
from __future__ import annotations

import math

import numpy as np

from .indexgen import host_rng

EPS_MAX = 1e3          # slack past which an answer counts as wrong outright


def sample(answered: np.ndarray, seed: int, n: int) -> np.ndarray:
    """Indices of at most ``n`` answered queries, drawn from the seed."""
    if len(answered) <= n:
        return np.sort(answered)
    return np.sort(host_rng(seed, 4).choice(answered, n, replace=False))


class _Query:
    """One query's reference readings, arranged for :meth:`feasible`."""

    def __init__(self, ref: dict, served: np.ndarray, eng: dict, n_docs: int):
        self.reason = None
        n3 = min(eng["n_filter"], n_docs)
        self.nd = min(eng["n_docs"], n3)
        valid = ref["cand"] < n_docs
        if ref["n_cand"] > valid.sum():
            self.reason = "more candidates than the reference's pool"
            return
        if ref["n_cand"] < n3:
            self.reason = "fewer candidates than n_filter"
            return
        if len(np.unique(served)) < len(served):
            self.reason = "the answer names a passage twice"
            return
        f, ci, cand = ref["f"][valid], ref["ci"][valid], ref["cand"][valid]
        f_cut = np.sort(f)[::-1][n3 - 1]
        a1, t1 = f > f_cut, f == f_cut
        self.cnt1 = n3 - int(a1.sum())          # tied passages phase 2 takes
        keep = ref["e_rows"] < len(f)
        keep[keep] = f[ref["e_rows"][keep]] >= f_cut
        e_rows, e = ref["e_rows"][keep], ref["e"][keep]
        in_e = np.zeros(len(f), bool)
        in_e[e_rows] = True
        pos = np.searchsorted(cand, served)
        pos = np.minimum(pos, len(cand) - 1)
        if np.any(cand[pos] != served) or np.any(f[pos] < f_cut):
            self.reason = "a served passage cannot pass the pre-filter"
            return
        if not np.all(in_e[pos]):
            self.reason = "a served passage lies outside the Eq. 6 pool"
            return
        out = ~in_e & (f >= f_cut)
        self.c_floor = float(ci[out].max()) if out.any() else -math.inf
        self.t1_out = int((t1 & out).sum())     # always below the cut
        in_l = np.zeros(len(f), bool)
        in_l[pos] = True
        order = np.argsort(ci[e_rows], kind="stable")
        rows = e_rows[order]
        self.ci = ci[rows].astype(np.float64)
        self.e = np.empty(len(f))
        self.e[e_rows] = e
        self.e = self.e[rows]
        self.a1, self.t1, self.in_l = a1[rows], t1[rows], in_l[rows]
        self.min_e_l = float(self.e[self.in_l].min(initial=math.inf))
        self.min_ci_l = float(self.ci[self.in_l].min(initial=math.inf))

    def _above(self, mask: np.ndarray, t: np.ndarray) -> np.ndarray:
        """Per threshold in ``t``, how many of ``mask`` have Eq. 2 > t."""
        cum = np.concatenate([[0], np.cumsum(mask)])
        return cum[-1] - cum[np.searchsorted(self.ci, t, side="right")]

    def _at_least(self, mask, t):
        cum = np.concatenate([[0], np.cumsum(mask)])
        return cum[-1] - cum[np.searchsorted(self.ci, t, side="left")]

    def feasible(self, eps: float, bound: float | None = None) -> bool:
        """Does some order of the ties select exactly the served k, with
        Eq. 2 and Eq. 6 compared to within ``eps``?

        Phase 3 keeps the passages of the phase-2 survivors P3 whose Eq. 2
        is at least some threshold c and drops those below c - eps; the
        served k must be kept, and no kept passage beyond them may have an
        Eq. 6 over ``bound`` (default: the served k's lowest) by more than
        eps ("bad")."""
        bound = self.min_e_l if bound is None else bound
        c = np.unique(self.ci)
        c = c[(c <= self.min_ci_l) & (c > self.c_floor)]
        if not len(c):
            return False
        good = ~(~self.in_l & (self.e > bound + eps))
        hi_t = c + eps

        def hi(mask):
            return self._above(mask, hi_t)

        def band(mask):
            return self._at_least(mask, c) - self._above(mask, hi_t)

        a1, t1, in_l = self.a1, self.t1, self.in_l
        ok = hi(a1 & ~good) == 0
        a_hi = hi(a1)
        y_lo, y_hi = band(a1 & in_l), band(a1 & good)
        t1_not_hi = (t1.sum() - hi(t1)) + self.t1_out
        h_lo = np.maximum(hi(t1 & in_l), self.cnt1 - t1_not_hi)
        h_hi = hi(t1 & good)
        b_lo, b_hi = band(t1 & in_l), band(t1 & good)
        ok &= (y_lo <= y_hi) & (h_lo <= h_hi) & (b_lo <= b_hi)
        ok &= h_lo + b_lo <= self.cnt1
        ok &= a_hi + y_lo + h_lo + b_lo <= self.nd
        ok &= self.nd <= a_hi + y_hi + np.minimum(h_hi + b_hi, self.cnt1)
        return bool(ok.any())

    def gap(self, bound: float | None = None) -> float:
        """The least ``eps`` at which the answer is feasible (to 1%)."""
        if self.reason is not None:
            return math.inf
        if self.feasible(0.0, bound):
            return 0.0
        if not self.feasible(EPS_MAX, bound):
            return math.inf
        lo, hi = 1e-9, EPS_MAX
        if self.feasible(lo, bound):
            return lo
        while hi / lo > 1.01:
            mid = math.sqrt(lo * hi)
            lo, hi = (lo, mid) if self.feasible(mid, bound) else (mid, hi)
        return hi


def _query_gap(refs: list, j: int, served: np.ndarray, eng: dict,
               per: int) -> tuple[float, str | None]:
    """Query ``j``'s selection gap over shards of ``per`` passages."""
    shard = served // per
    if np.any((served < 0) | (shard >= len(refs))):
        return math.inf, "a served id lies outside the corpus"
    queries = [_Query({k: v[j] for k, v in ref.items()},
                      served[shard == s] - s * per, eng, per)
               for s, ref in enumerate(refs)]
    reason = next((q.reason for q in queries if q.reason), None)
    if reason is not None:
        return math.inf, reason
    # a passage of a shard's top k that was not served has to lie below
    # the lowest served Eq. 6; on one shard that is the prefix rule
    lowest = min(q.min_e_l for q in queries)
    return max(q.gap(lowest) for q in queries), None


def selection_gaps(refs: list, served_ids: np.ndarray, eng: dict,
                   per: int) -> tuple[np.ndarray, list]:
    """-> (gap per query, the reason for each infinite one).

    ``refs`` are the shards' readings in shard order
    (:func:`harness.reference.reference_shards`); ``served_ids`` are
    global, ``per`` the passages of a shard."""
    gaps, reasons = [], []
    for j in range(len(served_ids)):
        gap, reason = _query_gap(refs, j, np.asarray(served_ids[j]), eng,
                                 per)
        gaps.append(gap)
        if reason is not None:
            reasons.append(reason)
    return np.array(gaps), reasons


def rescored(refs: list, served_ids: np.ndarray, per: int) -> np.ndarray:
    """The reference's Eq. 6 of each served (query, rank), from the shard
    that holds the passage; NaN for an id outside the corpus."""
    shard = np.asarray(served_ids) // per
    out = np.full(np.shape(served_ids), np.nan)
    for s, ref in enumerate(refs):
        out = np.where(shard == s, ref["rescored"], out)
    return out


def numbers(served_scores: np.ndarray, served_ids: np.ndarray, refs: list,
            eng: dict, per: int) -> tuple[dict, list]:
    """-> ({"score_gap": float, "selection_gap": float}, the reasons for
    infinite selection gaps) over (S, k) arrays and the shards' readings
    in shard order (``harness.reference.reference_shards``)."""
    score_gap = np.abs(served_scores.astype(np.float64)
                       - rescored(refs, served_ids, per))
    gaps, reasons = selection_gaps(refs, served_ids, eng, per)
    return {
        "score_gap": float(np.max(np.where(np.isnan(score_gap), np.inf,
                                           score_gap))),
        "selection_gap": float(np.max(gaps)) if len(gaps) else math.inf,
    }, reasons


def recall_at(ids: np.ndarray, targets: np.ndarray, k: int) -> float:
    """Share of queries whose planted target is among the first k ids."""
    if len(ids) == 0:
        return float("nan")
    return float(np.mean([t in row[:k] for row, t in zip(ids, targets)]))


def verdict(nums: dict, limits: dict, failed: int) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}) with ``failed`` first."""
    checks = {"failed": {"value": int(failed), "limit": 0}}
    for name, value in nums.items():
        checks[name] = {"value": value, "limit": limits[name]}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
