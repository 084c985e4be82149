"""The plain EMVB reference: paper §4 (Eqs. 2, 4 and 6), one query at a time.

Written from the paper and the index layout alone; it imports nothing of
the program. Per query:

1. centroid scores ``cs = q C^T`` (matmuls at JAX's default precision, as
   the configuration states);
2. Eq. 4's stacked bit vectors (bit i of centroid c is ``cs[i, c] > th``)
   and, per live term, the ``nprobe`` best-scoring centroids;
3. the candidates: the union of the probed centroids' inverted lists, in
   ascending id order, at most ``pool`` of them;
4. the pre-filter score F = popcount of the OR of the bit vectors of a
   candidate's tokens, for every candidate, and its top ``n_filter``;
5. the centroid interaction (Eq. 2), for every candidate, and the top
   ``n_docs`` of the phase-2 survivors;
6. PQ late interaction with the per-term filter ``th_r`` (Eq. 6), top k.

Besides its own answer (ties broken toward the lower id at every cut) the
reference returns what :mod:`harness.correctness` needs to judge an answer
under any order of the ties: F and Eq. 2 for every candidate, and Eq. 6
for the ``e_pool`` candidates with the best Eq. 2 among those that can
pass phase 2.

``dtype`` is the precision of every floating array and of all arithmetic:
``float32`` for the reference, ``bfloat16`` for the control (the step
below the configuration's float32). Queries run one after another in one
compiled loop, so the reference needs the memory of one query only.

A block-sharded index is judged shard by shard (:func:`reference_shards`),
as each shard of the deployment runs EMVB alone, each shard on its own
device; :func:`merge_answers` is the deployment's merge of their answers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _or_reduce(words: jax.Array, axis: int) -> jax.Array:
    return jax.lax.reduce(words, jnp.uint32(0), jax.lax.bitwise_or, (axis,))


def _eq6(cs_t, lut, codes, res, real, live, th_r):
    """Eq. 6 scores of passages given by their codes (n, cap), PQ codes
    (n, cap, m) and real-token mask (n, cap) -> (n,)."""
    n_c = cs_t.shape[0]
    m = lut.shape[1]
    cent = cs_t[jnp.clip(codes, 0, n_c - 1)]                 # (n, cap, n_q)
    lut_t = jnp.transpose(lut, (1, 2, 0))                    # (m, K, n_q)
    resid = lut_t[jnp.arange(m)[None, None, :], res.astype(jnp.int32)]
    full = cent + jnp.sum(resid, axis=2)                     # (n, cap, n_q)
    neg = jnp.array(-jnp.inf, full.dtype)
    real = real[..., None]
    keep = (cent > th_r) & real
    kept_max = jnp.max(jnp.where(keep, full, neg), axis=1)
    all_max = jnp.max(jnp.where(real, full, neg), axis=1)
    per_term = jnp.where(jnp.any(keep, axis=1), kept_max, all_max)
    per_term = jnp.where(live[None, :], per_term, jnp.zeros((), full.dtype))
    return jnp.sum(per_term, axis=1)


def _one(index, q, live, served, *, eng, dtype, pool, e_pool):
    cent = index["centroids"].astype(dtype)
    codes, lens = index["codes"], index["doc_lens"]
    ivf, ivf_lens = index["ivf"], index["ivf_lens"]
    n_c = cent.shape[0]
    n_docs, cap = codes.shape
    n_q = q.shape[0]
    q = q.astype(dtype)
    cs = jnp.matmul(q, cent.T)                               # (n_q, n_c)
    cs_t = cs.T
    neg = jnp.array(-jnp.inf, cs.dtype)
    # Eq. 4 bit vectors
    close = (cs > eng["th"]) & live[:, None]
    words = jnp.sum(close.astype(jnp.uint32)
                    << jnp.arange(n_q, dtype=jnp.uint32)[:, None], axis=0)
    # candidates: the probed centroids' inverted lists
    _, probes = jax.lax.top_k(cs, eng["nprobe"])             # (n_q, nprobe)
    probes = jnp.where(live[:, None], probes, n_c).reshape(-1)
    safe_p = jnp.clip(probes, 0, n_c - 1)
    lens_p = jnp.where(probes < n_c, ivf_lens[safe_p], 0)
    lists = jnp.where(jnp.arange(ivf.shape[1])[None, :] < lens_p[:, None],
                      ivf[safe_p], n_docs)
    bitmap = jnp.zeros((n_docs + 1,), jnp.bool_).at[lists.reshape(-1)].set(
        True)[:n_docs]
    n_cand = jnp.sum(bitmap).astype(jnp.int32)
    cand = jnp.nonzero(bitmap, size=pool, fill_value=n_docs)[0]
    valid = cand < n_docs
    cand_c = jnp.clip(cand, 0, n_docs - 1)
    c_codes = codes[cand_c]                                  # (pool, cap)
    real = (jnp.arange(cap)[None, :] < lens[cand_c][:, None]) & valid[:, None]
    safe = jnp.clip(c_codes, 0, n_c - 1)
    # phase 2: F(P, q) of every candidate, top n_filter
    ored = _or_reduce(jnp.where(real, words[safe], jnp.uint32(0)), 1)
    f = jnp.where(valid, jax.lax.population_count(ored).astype(jnp.int32), -1)
    n_filter = min(eng["n_filter"], n_docs)
    f_top, sel1 = jax.lax.top_k(f, n_filter)
    f_cut = f_top[-1]
    # phase 3: Eq. 2 of every candidate, top n_docs of the survivors
    pt = jnp.where(real[..., None], cs_t[safe], neg)         # (pool, cap, n_q)
    colmax = jnp.where(live[None, :], jnp.max(pt, axis=1),
                       jnp.zeros((), cs.dtype))
    ci = jnp.where(valid, jnp.sum(colmax, axis=1), neg)
    n_docs3 = min(eng["n_docs"], n_filter)
    _, pos2 = jax.lax.top_k(ci[sel1], n_docs3)
    sel2 = sel1[pos2]
    # phase 4: Eq. 6, top k
    cb = index["pq_codebooks"].astype(dtype)
    m, _, dsub = cb.shape
    lut = jnp.einsum("isd,skd->isk", q.reshape(n_q, m, dsub), cb)
    th_r = eng["th_r"]

    def eq6(rows):
        ids = cand_c[rows]
        return _eq6(cs_t, lut, codes[ids], index["res_codes"][ids],
                    real[rows], live, th_r)

    top, pos = jax.lax.top_k(eq6(sel2), eng["k"])
    # Eq. 6 of the best Eq. 2 among the candidates that can pass phase 2
    _, e_rows = jax.lax.top_k(jnp.where(f >= f_cut, ci, neg), e_pool)
    e_scores = eq6(e_rows)
    # the served answer, rescored by the same Eq. 6
    ok = (served >= 0) & (served < n_docs)
    s = jnp.clip(served, 0, n_docs - 1)
    rescored = _eq6(cs_t, lut, codes[s], index["res_codes"][s],
                    jnp.arange(cap)[None, :] < lens[s][:, None], live, th_r)
    rescored = jnp.where(ok, rescored.astype(jnp.float32), jnp.nan)
    f32 = jnp.float32
    return {"top": top.astype(f32), "ids": cand[sel2[pos]].astype(jnp.int32),
            "rescored": rescored, "n_cand": n_cand, "cand": cand, "f": f,
            "ci": ci.astype(f32), "e_rows": e_rows.astype(jnp.int32),
            "e": e_scores.astype(f32)}


@functools.partial(jax.jit,
                   static_argnames=("eng", "dtype", "pool", "e_pool"))
def _reference(index, queries, live, served, *, eng, dtype, pool, e_pool):
    e = dict(eng)
    return jax.lax.map(
        lambda a: _one(index, a[0], a[1], a[2], eng=e, dtype=dtype,
                       pool=pool, e_pool=e_pool),
        (queries, live, served))


REF_FIELDS = ("centroids", "codes", "doc_lens", "res_codes", "pq_codebooks",
              "ivf", "ivf_lens")


def reference(index: dict, queries, live, served, eng: dict,
              dtype: str = "float32", pool: int = 8192,
              e_pool: int = 1024, device=None) -> dict:
    """Run the reference over a batch of queries, on ``device`` (default:
    JAX's).

    index   : the generated index (only ``REF_FIELDS`` are read)
    queries : (S, n_q, d) float32; live : (S, n_q) bool
    served  : (S, k) int32 doc ids whose Eq. 6 scores to recompute
    eng     : th, th_r, nprobe, n_filter, n_docs, k
    pool    : candidates kept per query (``n_cand`` says how many there
              were; more than ``pool`` cannot be judged)
    e_pool  : candidates given an Eq. 6 score besides the answer's
    -> per query, as device arrays with a leading (S,) axis:
       ``top``/``ids`` (k) the reference's own answer, ``rescored`` (k)
       Eq. 6 of the served ids, ``n_cand``, and over the candidate pool
       ``cand`` (ids, ascending, ``n_docs`` past the end), ``f`` (F, -1
       past the end), ``ci`` (Eq. 2), ``e_rows`` (pool rows) with ``e``
       (their Eq. 6)
    """
    keys = ("th", "th_r", "nprobe", "n_filter", "n_docs", "k")
    frozen = tuple((k, eng[k]) for k in keys)
    n_docs = index["codes"].shape[0]
    pool = min(int(pool), n_docs)
    sub = {f: index[f] for f in REF_FIELDS}
    args = (jnp.asarray(queries, jnp.float32), jnp.asarray(live, jnp.bool_),
            jnp.asarray(served, jnp.int32))
    if device is not None:
        sub, args = jax.device_put((sub, args), device)
    return _reference(sub, *args, eng=frozen, dtype=jnp.dtype(dtype).name,
                      pool=pool, e_pool=min(int(e_pool), pool))


def reference_shards(shards: list, queries, live, served, eng: dict,
                     dtype: str = "float32", pool: int = 8192,
                     e_pool: int = 1024, devices=None) -> list:
    """The reference over each block shard of an index, shard s alone, on
    ``devices[s]`` when given: EMVB as one shard of a sharded deployment
    runs it, with its own cuts over its own passages.

    shards : per shard, the ``REF_FIELDS`` with local ids; shard s holds
             global ids ``[s * n, (s + 1) * n)``, n its passage count
    served : (S, k) global ids; each shard rescores those it holds, by
             local id, and reads NaN for the rest
    -> per shard, :func:`reference`'s readings as host arrays (local ids).
    One shard is the whole index, and then this is :func:`reference`."""
    served = np.asarray(served, np.int64)
    outs = []
    for s, sub in enumerate(shards):
        per = int(sub["codes"].shape[0])
        mine = (served >= s * per) & (served < (s + 1) * per)
        outs.append(reference(sub, queries, live,
                              np.where(mine, served - s * per, -1), eng,
                              dtype, pool, e_pool,
                              None if devices is None else devices[s]))
    return [{k: np.asarray(v) for k, v in out.items()} for out in outs]


def merge_answers(outs: list, per: int, k: int) -> tuple:
    """The two-level top k of :func:`reference_shards`' readings: each
    shard's own top k, its ids made global (shard s adds ``s * per``),
    merged by score, ties to the lower global id.
    -> (scores (S, k) float32, ids (S, k) int32)."""
    scores = np.concatenate([o["top"] for o in outs], axis=1)
    ids = np.concatenate([o["ids"] + s * per for s, o in enumerate(outs)],
                         axis=1)
    pos = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(scores, pos, 1).astype(np.float32),
            np.take_along_axis(ids, pos, 1).astype(np.int32))
