"""Generate an EMVB index and its queries on the device, from a seed.

No fp32 token array is ever built and no k-means runs: the index is drawn
directly in its compressed form, the way a model benchmark draws weights.

Corpus model (the configuration's ``corpus`` block states every number):

* Centroids are unit vectors in blocks of ``block``: a block shares one
  random direction and each centroid adds ``block_spread`` times its own
  noise, so centroids of one block are close to each other and far from
  the rest.
* Every passage has one topic, which is one block. Topic sizes are a fixed
  multiset (log-normal with ``topic_size_sigma``, drawn from ``shape_seed``)
  that the run's seed permutes, so list lengths, and with them the IVF's
  ``list_cap``, are the same set on every seed.
* A token's centroid is, with probability ``topic_share``, a centroid of its
  passage's block drawn by a Zipf law of exponent ``topic_zipf`` over the
  block, and otherwise a centroid drawn uniformly from all of them.
* Passage lengths are a fixed multiset from the ``length`` law, permuted by
  the seed. PQ codes are uniform; codebook entries are Gaussian, scaled so
  a decoded residual has norm ``residual_norm``.
* The IVF has the layout of the program's index builder: per centroid the
  ascending unique ids of the passages that hold it, padded with
  ``n_docs``. ``list_cap`` is the longest list rounded up to
  ``list_cap_multiple``, so no list is cut.
* A query picks a target passage and ``n_q`` of its tokens; each term is the
  token's reconstruction (centroid plus decoded residual) plus Gaussian
  noise of norm ``query_noise``, normalized. The target is the planted
  answer.

The PLAID fields that the program's index carries are generated at their
real shapes as zeros: EMVB never reads them.

A configuration whose ``chips`` is above 1 is a deployment block-sharded
over that many chips: ``n_passages`` is the global count, and shard s holds
global ids ``[s * n / chips, (s + 1) * n / chips)``. Topics and lengths are
drawn over the global ids as above; centroids and codebooks are one table
for every shard; shard s's tokens come from its own key, and its IVF lists
its passages by local id. The global arrays, with the global IVF that the
shards' lists make, are kept on the host, and each shard's device copies
are freed before the next is drawn, so no device holds more than one
shard's set-up.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_DOCS = 32768     # passages generated per device call


def run_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``PRNGKey`` alone keeps only
    the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32),
                              (seed // 2**32) % 2**32)


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for ``(seed, stream)``."""
    return np.random.default_rng([int(seed), int(stream)])


def fixed_shapes(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """The seed-independent multisets: topic sizes (n_topics,) summing to
    n_docs, and passage lengths (n_docs,)."""
    corpus = cfg["corpus"]
    n_docs, n_c = cfg["n_passages"], cfg["n_centroids"]
    n_topics = n_c // corpus["block"]
    rng = host_rng(corpus["shape_seed"], 0)
    w = rng.lognormal(0.0, corpus["topic_size_sigma"], n_topics)
    sizes = np.floor(n_docs * w / w.sum()).astype(np.int64)
    short = n_docs - int(sizes.sum())
    sizes[np.argsort(-(n_docs * w / w.sum() - sizes))[:short]] += 1
    law = corpus["length"]
    if law["law"] == "uniform":
        lens = rng.integers(law["min"], law["max"] + 1, n_docs)
    elif law["law"] == "lognormal":
        lens = np.round(rng.lognormal(math.log(law["median"]), law["sigma"],
                                      n_docs))
        lens = np.clip(lens, law["min"], law["max"])
    else:
        raise ValueError(f"unknown length law {law['law']!r}")
    if lens.min() < cfg["engine"]["n_q"] or lens.max() > cfg["cap"]:
        raise ValueError("passage lengths must lie in [n_q, cap]")
    return sizes, lens.astype(np.int32)


@functools.partial(jax.jit, static_argnames=("n_c", "d", "block", "spread"))
def _centroids(key, *, n_c, d, block, spread):
    ka, kg = jax.random.split(key)
    a = jax.random.normal(ka, (n_c // block, d), jnp.float32)
    a = a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    g = jax.random.normal(kg, (n_c, d), jnp.float32) / math.sqrt(d)
    c = jnp.repeat(a, block, axis=0) + spread * g
    return c / jnp.linalg.norm(c, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("m", "dsub", "scale"))
def _codebooks(key, *, m, dsub, scale):
    return scale * jax.random.normal(key, (m, 256, dsub), jnp.float32)


@functools.partial(jax.jit, static_argnames=("cap", "n_c", "m", "block",
                                             "share", "zipf"))
def _chunk(key, topics, lens, *, cap, n_c, m, block, share, zipf):
    """Codes (C, cap) int32 with the ``n_c`` pad and PQ codes (C, cap, m)."""
    kt, kr, ku, kp = jax.random.split(key, 4)
    c = topics.shape[0]
    cdf = jnp.cumsum(1.0 / jnp.arange(1, block + 1, dtype=jnp.float32) ** zipf)
    cdf = cdf / cdf[-1]
    rank = jnp.searchsorted(cdf, jax.random.uniform(kr, (c, cap)))
    rank = jnp.minimum(rank, block - 1).astype(jnp.int32)
    uniform = jax.random.randint(ku, (c, cap), 0, n_c, jnp.int32)
    in_topic = jax.random.uniform(kt, (c, cap)) < share
    codes = jnp.where(in_topic, topics[:, None] * block + rank, uniform)
    real = jnp.arange(cap)[None, :] < lens[:, None]
    codes = jnp.where(real, codes, n_c).astype(jnp.int32)
    res = jax.random.bits(kp, (c, cap, m), jnp.uint8)
    return codes, res


@functools.partial(jax.jit, static_argnames=("n_c",))
def _ivf_order(codes, *, n_c):
    """Sort (centroid, passage) pairs; mark the first of each pair."""
    n_docs, cap = codes.shape
    c = codes.reshape(-1)
    d = jnp.repeat(jnp.arange(n_docs, dtype=jnp.int32), cap)
    c, d = jax.lax.sort((c, d), num_keys=2)
    prev_c = jnp.concatenate([jnp.full((1,), -1, c.dtype), c[:-1]])
    prev_d = jnp.concatenate([jnp.full((1,), -1, d.dtype), d[:-1]])
    first = (c < n_c) & ((c != prev_c) | (d != prev_d))
    lens = jax.ops.segment_sum(first.astype(jnp.int32), c,
                               num_segments=n_c + 1)[:n_c]
    starts = jnp.cumsum(lens) - lens
    pos = jnp.cumsum(first.astype(jnp.int32)) - 1 \
        - starts[jnp.clip(c, 0, n_c - 1)]
    return c, d, first, pos, lens


@functools.partial(jax.jit, static_argnames=("n_c", "n_docs", "list_cap"))
def _ivf_fill(c, d, first, pos, *, n_c, n_docs, list_cap):
    rows = jnp.where(first, c, n_c)                  # n_c: dropped
    ivf = jnp.full((n_c, list_cap), n_docs, jnp.int32)
    return ivf.at[rows, pos].set(d, mode="drop")


def build_ivf(codes: jax.Array, n_c: int, multiple: int):
    """-> (ivf (n_c, list_cap) int32 padded with n_docs, ivf_lens (n_c,)).

    Same lists as the program's builder; ``list_cap`` is the longest list
    rounded up to ``multiple`` (at least 8)."""
    n_docs = codes.shape[0]
    c, d, first, pos, lens = _ivf_order(codes, n_c=n_c)
    longest = int(jnp.max(lens))
    list_cap = max(8, -(-longest // multiple) * multiple)
    ivf = _ivf_fill(c, d, first, pos, n_c=n_c, n_docs=n_docs,
                    list_cap=list_cap)
    return ivf, lens


def _draws(cfg: dict, seed: int):
    """-> (topics (n_docs,), lengths (n_docs,), centroids, codebooks, the
    tokens' key) of the global corpus."""
    corpus = cfg["corpus"]
    n_c, d, m = cfg["n_centroids"], cfg["d"], cfg["m"]
    sizes, lens_base = fixed_shapes(cfg)
    rng = host_rng(seed, 1)
    topics = rng.permutation(np.repeat(np.arange(len(sizes)), sizes))
    lens = rng.permutation(lens_base)
    k_cent, k_cb, k_tok = jax.random.split(run_key(seed), 3)
    centroids = _centroids(k_cent, n_c=n_c, d=d, block=corpus["block"],
                           spread=float(corpus["block_spread"]))
    codebooks = _codebooks(k_cb, m=m, dsub=d // m,
                           scale=float(corpus["residual_norm"] / math.sqrt(d)))
    return topics, lens, centroids, codebooks, k_tok


def _tokens(key, topics: np.ndarray, lens: np.ndarray, cfg: dict):
    """-> (codes (n, cap) int32, PQ codes (n, cap, m) uint8, the last
    chunk's pair) of passages with these topics and lengths, chunk i drawn
    from ``fold_in(key, i)``. The one-chip build keeps the last chunk alive
    until it returns, as it always has: ``peak_hbm_gib`` reads set-up's
    high-water mark, which includes it."""
    corpus = cfg["corpus"]
    n_docs = len(lens)
    n_chunks = -(-n_docs // CHUNK_DOCS)
    pad = n_chunks * CHUNK_DOCS - n_docs
    topics = np.concatenate([topics, np.zeros(pad, topics.dtype)])
    lens_p = np.concatenate([lens, np.zeros(pad, lens.dtype)])
    codes, res = [], []
    for i in range(n_chunks):
        sl = slice(i * CHUNK_DOCS, (i + 1) * CHUNK_DOCS)
        ci, ri = _chunk(jax.random.fold_in(key, i),
                        jnp.asarray(topics[sl], jnp.int32),
                        jnp.asarray(lens_p[sl], jnp.int32),
                        cap=cfg["cap"], n_c=cfg["n_centroids"], m=cfg["m"],
                        block=corpus["block"],
                        share=float(corpus["topic_share"]),
                        zipf=float(corpus["topic_zipf"]))
        codes.append(ci)
        res.append(ri)
    codes = jnp.concatenate(codes)[:n_docs]
    res = jnp.concatenate(res)[:n_docs]
    return codes, res, (ci, ri)


def _fields(centroids, codes, lens, res, codebooks, ivf, ivf_lens, cfg,
            xp=jnp) -> dict:
    """The program's index fields, the PLAID ones as ``xp`` zeros."""
    n_docs, cap, d = len(lens), cfg["cap"], cfg["d"]
    plaid_b = int(cfg["plaid_b"])
    return {
        "centroids": centroids,
        "codes": codes,
        "doc_lens": lens,
        "res_codes": res,
        "pq_codebooks": codebooks,
        "ivf": ivf,
        "ivf_lens": ivf_lens,
        "plaid_res": xp.zeros((n_docs, cap, d * plaid_b // 8), np.uint8),
        "plaid_cutoffs": xp.zeros((2 ** plaid_b - 1,), np.float32),
        "plaid_weights": xp.zeros((2 ** plaid_b,), np.float32),
        "opq_rotation": xp.eye(d, dtype=np.float32),
        "pred_words": xp.zeros((n_docs,), np.uint32),
        "list_cap": int(ivf.shape[1]),
    }


def generate_index(cfg: dict, seed: int) -> dict:
    """The cell's index as a dict of the program's index fields, by name,
    plus ``list_cap``: device arrays on one chip; with ``chips`` above 1,
    host arrays of the global index plus ``shards``, each shard's local
    IVF (``ivf``, ``ivf_lens``, ``list_cap``)."""
    chips = int(cfg.get("chips", 1))
    if chips > 1:
        return _generate_sharded(cfg, seed, chips)
    topics, lens, centroids, codebooks, k_tok = _draws(cfg, seed)
    # last_chunk is held until this returns (see _tokens)
    codes, res, last_chunk = _tokens(k_tok, topics, lens, cfg)
    ivf, ivf_lens = build_ivf(codes, cfg["n_centroids"],
                              int(cfg["list_cap_multiple"]))
    return _fields(centroids, codes, jnp.asarray(lens, jnp.int32), res,
                   codebooks, ivf, ivf_lens, cfg)


def merge_ivfs(shards: list, per: int, multiple: int):
    """The global IVF of block shards of ``per`` passages: list c is the
    shards' lists c in shard order, local ids plus the shard's offset,
    padded with the global count. -> (ivf, ivf_lens) on the host; equal to
    :func:`build_ivf` over the global codes."""
    lens = np.sum([np.asarray(sh["ivf_lens"]) for sh in shards], axis=0,
                  dtype=np.int32)
    longest = int(lens.max())
    list_cap = max(8, -(-longest // multiple) * multiple)
    ivf = np.full((len(lens), list_cap), per * len(shards), np.int32)
    at = np.zeros(len(lens), np.int64)
    for s, sh in enumerate(shards):
        local, ln = np.asarray(sh["ivf"]), np.asarray(sh["ivf_lens"])
        rows, cols = np.nonzero(np.arange(local.shape[1])[None, :]
                                < ln[:, None])
        ivf[rows, at[rows] + cols] = local[rows, cols] + s * per
        at += ln
    return ivf, lens


def _generate_sharded(cfg: dict, seed: int, chips: int) -> dict:
    """One shard at a time on JAX's default device, each copied to the
    host and freed there before the next: the device never holds more
    than one shard's set-up, which is a one-chip cell's."""
    n_docs, n_c = cfg["n_passages"], cfg["n_centroids"]
    per, multiple = n_docs // chips, int(cfg["list_cap_multiple"])
    topics, lens, centroids, codebooks, k_tok = _draws(cfg, seed)
    codes = np.empty((n_docs, cfg["cap"]), np.int32)
    res = np.empty((n_docs, cfg["cap"], cfg["m"]), np.uint8)
    shards = []
    for s in range(chips):
        sl = slice(s * per, (s + 1) * per)
        codes_s, res_s, _ = _tokens(jax.random.fold_in(k_tok, s),
                                    topics[sl], lens[sl], cfg)
        ivf_s, lens_s = build_ivf(codes_s, n_c, multiple)
        codes[sl], res[sl] = np.asarray(codes_s), np.asarray(res_s)
        shards.append({"ivf": np.asarray(ivf_s),
                       "ivf_lens": np.asarray(lens_s),
                       "list_cap": int(ivf_s.shape[1])})
        del codes_s, res_s, ivf_s, lens_s
    ivf, ivf_lens = merge_ivfs(shards, per, multiple)
    out = _fields(np.asarray(centroids), codes, lens.astype(np.int32), res,
                  np.asarray(codebooks), ivf, ivf_lens, cfg, xp=np)
    out["shards"] = shards
    return out


def shard_fields(index: dict, s: int) -> dict:
    """Shard ``s`` of an index in the one-chip form: its passages with
    local ids and its local IVF; a one-chip index is its own shard 0."""
    shards = index.get("shards")
    if shards is None:
        return index
    per = len(index["doc_lens"]) // len(shards)
    sl = slice(s * per, (s + 1) * per)
    return dict(index, codes=index["codes"][sl],
                doc_lens=index["doc_lens"][sl],
                res_codes=index["res_codes"][sl], **shards[s])


@functools.partial(jax.jit, static_argnames=("n_q", "noise"))
def _queries(key, targets, centroids, codes, doc_lens, res_codes, codebooks,
             *, n_q, noise):
    kp, kn = jax.random.split(key)
    n, cap = targets.shape[0], codes.shape[1]
    m, _, dsub = codebooks.shape
    u = jax.random.uniform(kp, (n, cap))
    u = jnp.where(jnp.arange(cap)[None, :] < doc_lens[targets][:, None], u, 2.0)
    pos = jnp.argsort(u, axis=1)[:, :n_q]                   # distinct tokens
    code = codes[targets[:, None], pos]                     # (n, n_q)
    res = res_codes[targets[:, None], pos].astype(jnp.int32)  # (n, n_q, m)
    dec = codebooks[jnp.arange(m)[None, None, :], res]      # (n, n_q, m, dsub)
    tok = centroids[code] + dec.reshape(n, n_q, m * dsub)
    d = tok.shape[-1]
    q = tok + noise / math.sqrt(d) * jax.random.normal(kn, tok.shape)
    return q / jnp.linalg.norm(q, axis=-1, keepdims=True)


def generate_queries(index: dict, cfg: dict, seed: int, n: int
                     ) -> tuple[np.ndarray, np.ndarray]:
    """-> (queries (n, n_q, d) float32 on the host, planted target ids (n,))."""
    targets = host_rng(seed, 2).integers(0, cfg["n_passages"], n)
    rows = (index["codes"], index["doc_lens"], index["res_codes"])
    at = jnp.asarray(targets, jnp.int32)
    if "shards" in index:
        # the targets' rows of their shards, gathered on the host
        rows = tuple(a[targets] for a in rows)
        at = jnp.arange(n, dtype=jnp.int32)
    q = _queries(jax.random.fold_in(run_key(seed), 7), at,
                 index["centroids"], *rows, index["pq_codebooks"],
                 n_q=cfg["engine"]["n_q"],
                 noise=float(cfg["corpus"]["query_noise"]))
    return np.array(q, np.float32), targets.astype(np.int64)


def index_bytes(index: dict) -> int:
    """Bytes of every array of the program's index."""
    return int(sum(v.nbytes for v in index.values()
                   if hasattr(v, "nbytes")))
