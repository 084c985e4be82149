"""Serve EMVB once on a TPU at the paper's MS MARCO widths and check it.

    python chip_smoke.py              # one chip: RetrievalService -> engine
    python chip_smoke.py --chips 4    # four chips: launch.serve.make_service

The widths are those of ``configs/emvb_msmarco.py`` (d=128, cap=80,
n_c=2^18, m=16, nbits=8, n_q=32, and its EngineConfig). Only the corpus
depth is cut: 65,536 of MS MARCO's 8,841,823 passages, because
``build_index`` holds the whole fp32 token array on the host and on the
device. Every cut is printed.

One chip: 64 queries of 32, 24 and 16 live terms go through
``submit``/``flush`` of a ``RetrievalService`` over a one-generation
timeline; ``new_generation`` then adds 8,192 passages, 64 more queries are
served, and the same 64 again, now cached in the frozen first generation.
Checks, each of which fails the run:
  (a) every ticket equals ``engine.retrieve_timeline`` on its unpadded
      prefix: ids at every rank, scores within ``RTOL``;
  (b) for 8 tickets, numpy recomputes the Eq. 6 scores of the top 10 from
      the index arrays, with matmul operands rounded as the device rounds
      them (measured by ``matmul_precision``), within ``EQ6_ATOL_PER_TERM``
      per live term;
  (c) the repeated pass counts exactly one cache hit per query.

Four chips: the same corpus, 16,384 passages per chip, served through
``make_service`` on a 4-device mesh and compared with ``engine.retrieve``
on each shard merged on the host (ids at every rank, scores within
``RTOL``); every stacked index leaf must sit one shard per device.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before it. Timings printed here are smoke timings, not a
benchmark. Without ``JAX_COMPILATION_CACHE_DIR`` the compile cache lives
in ``.jax_cache`` next to this file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import ml_dtypes  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro.configs.emvb_msmarco import make_config  # noqa: E402
from repro.core import ShardedTimeline, build_index, engine  # noqa: E402
from repro.data.synthetic import make_corpus, mrr_at_k, recall_at_k  # noqa: E402
from repro.launch import use_compile_cache  # noqa: E402
from repro.launch.serve import make_service, shard_index  # noqa: E402
from repro.serving import RetrievalService  # noqa: E402

MSMARCO = make_config()
N_DOCS = 65_536          # passages in the first generation (depth cut)
N_NEW = 8_192            # passages of the generation added while serving
N_QUERIES = 64           # per serving pass
MIN_LEN = 54             # passage lengths uniform in 54..80, mean ~67
LIVE_TERMS = (32, 24, 16)
MAX_BATCH = 8
N_EQ6 = 8                # tickets whose top 10 numpy recomputes
RTOL = 1e-5              # served vs direct engine, same device
# Eq. 6 sums per live term a 128-wide centroid dot and 16 LUT entries
# (8-wide dots), accumulated in float32 after the operands are rounded as
# the device rounds them; 2^-15 bounds that term's float32 error.
EQ6_ATOL_PER_TERM = 2.0 ** -15


class CheckFailed(Exception):
    """A smoke check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def log(*parts) -> None:
    print(*parts, flush=True)


class FirstCalls:
    """Wall seconds of the first call of each labelled program."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    def run(self, label: str, fn, *args, **kwargs):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kwargs))
        if label not in self.seconds:
            self.seconds[label] = time.perf_counter() - t0
        return out


def matmul_precision() -> str:
    """How the default backend rounds float32 matmul operands: 'bfloat16'
    (one MXU pass, float32 accumulation) or 'float32', told apart by
    comparing one device matmul with numpy under both roundings."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 128)).astype(np.float32)
    b = rng.standard_normal((128, 256)).astype(np.float32)
    dev = np.asarray(jnp.asarray(a) @ jnp.asarray(b), np.float64)
    for name in ("float32", "bfloat16"):
        want = _round(a, name) @ _round(b, name)
        if np.max(np.abs(dev - want)) <= 2.0 ** -20 * np.max(np.abs(want)):
            return name
    raise CheckFailed("device float32 matmul matches neither float32 nor "
                      "bfloat16-rounded operands")


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    """x as float64 after rounding to the matmul operand precision."""
    x = np.asarray(x, np.float32)
    if precision == "bfloat16":
        x = x.astype(ml_dtypes.bfloat16)
    return x.astype(np.float64)


def host_eq6(q: np.ndarray, timeline, doc_ids, th_r: float, precision: str,
             cents: np.ndarray) -> np.ndarray:
    """Eq. 6 scores of global ``doc_ids`` for one query ``q`` (n_live, d),
    recomputed in numpy from the timeline's index arrays: per live term, the
    max over tokens whose centroid score beats ``th_r`` (all tokens if none
    does) of centroid score + PQ residual score, summed over terms.
    ``cents`` is the centroid table after ``_round``."""
    gen0 = timeline.generations[0]
    cb = np.asarray(gen0.pq_codebooks)
    m, ksub, dsub = cb.shape
    q_rot = _round(_round(q, precision) @ _round(gen0.opq_rotation, precision),
                   precision)
    lut = np.einsum("qsd,skd->qsk", q_rot.reshape(len(q), m, dsub),
                    _round(cb, precision))                     # (n_q, m, K)
    qr = _round(q, precision)
    out = []
    for gid in doc_ids:
        for gen, meta, off in timeline:
            if off <= gid < off + meta.n_docs:
                local = int(gid) - off
                break
        n_tok = int(gen.doc_lens[local])
        codes = np.asarray(gen.codes[local])[:n_tok]
        res = np.asarray(gen.res_codes[local])[:n_tok].astype(np.int64)
        centroid = qr @ cents[codes].T                         # (n_q, n_tok)
        residual = lut[:, np.arange(m)[None, :], res].sum(-1)  # (n_q, n_tok)
        full = centroid + residual
        keep = centroid > th_r
        kept = np.where(keep, full, -np.inf).max(axis=1)
        out.append(np.where(keep.any(axis=1), kept, full.max(axis=1)).sum())
    return np.asarray(out)


def make_data(n_docs: int, n_new: int, n_queries: int, seed: int):
    """Seeded corpus of ``n_docs + n_new`` passages at MS MARCO widths, and
    two query sets: the first with planted answers in the first
    ``n_docs`` passages, the second over all of them."""
    corpus = make_corpus(seed, n_docs=n_docs + n_new, cap=MSMARCO.doc_cap,
                         min_len=MIN_LEN, d=MSMARCO.d, n_q=MSMARCO.engine.n_q,
                         n_queries=4 * n_queries)
    in_base = np.flatnonzero(corpus.gt_doc < n_docs)
    check(len(in_base) >= n_queries,
          f"only {len(in_base)} planted answers in the first generation")
    first = in_base[:n_queries]
    second = np.setdiff1d(np.arange(len(corpus.gt_doc)), first)[:n_queries]
    return corpus, first, second


def build(corpus, n_docs: int, n_centroids: int):
    t0 = time.perf_counter()
    index, meta = build_index(
        jax.random.PRNGKey(0), corpus.doc_embs[:n_docs],
        corpus.doc_lens[:n_docs], n_centroids=n_centroids, m=MSMARCO.m,
        nbits=MSMARCO.nbits)
    jax.block_until_ready(index)
    log(f"build_index: {time.perf_counter() - t0:.3f} s for {n_docs} "
        f"passages, {int(np.sum(corpus.doc_lens[:n_docs]))} tokens")
    log(f"cut: list_cap auto-sized from the data to {meta.list_cap} "
        f"(the config's {MSMARCO.list_cap} is for the full corpus)")
    return index, meta


def live_terms(i: int) -> int:
    return LIVE_TERMS[i % len(LIVE_TERMS)]


def serve_pass(svc, queries: np.ndarray, timings: list) -> list:
    """Submit every query with its live-term count; one flush per
    ``MAX_BATCH`` submits. Returns the tickets, appends per-batch seconds."""
    tickets = []
    for b in range(0, len(queries), MAX_BATCH):
        t0 = time.perf_counter()
        for i in range(b, min(b + MAX_BATCH, len(queries))):
            tickets.append(svc.submit(queries[i][:live_terms(i)]))
        svc.flush()
        timings.append(time.perf_counter() - t0)
    return tickets


def compare(tickets, refs, what: str) -> int:
    """Check (a): ids equal at every rank, scores within RTOL."""
    for i, (t, (ref_scores, ref_ids)) in enumerate(zip(tickets, refs)):
        scores, ids = t.result()
        check(np.array_equal(ids, ref_ids),
              f"{what}: query {i} ({live_terms(i)} terms) ids differ from "
              f"the direct engine at ranks "
              f"{np.flatnonzero(ids != ref_ids)[:10].tolist()}")
        check(np.allclose(scores, ref_scores, rtol=RTOL, atol=0.0),
              f"{what}: query {i} scores differ by up to "
              f"{np.max(np.abs(scores - ref_scores) / np.abs(ref_scores))} "
              f"relative")
    return len(tickets)


def quality(ids: np.ndarray, gt: np.ndarray) -> str:
    return (f"MRR@10={mrr_at_k(ids, gt, 10):.4f} "
            f"Recall@100={recall_at_k(ids, gt, 100):.4f}")


def run_one_chip(corpus, first, second, index, meta, n_new: int,
                 first_calls: FirstCalls) -> dict:
    cfg = MSMARCO.engine
    precision = matmul_precision()
    log(f"matmul precision (float32 operands on the default backend): "
        f"{precision}; check (b) tolerance {EQ6_ATOL_PER_TERM:.3e} per live "
        "term")
    timeline = ShardedTimeline.of((index, meta))
    svc = RetrievalService(timeline, cfg, max_batch=MAX_BATCH)
    q1, q2 = corpus.queries[first], corpus.queries[second]

    def reference(tl, queries, label):
        out = []
        for i, q in enumerate(queries):
            r = first_calls.run(
                f"retrieve_timeline B=1 {live_terms(i)} terms, {label}",
                engine.retrieve_timeline, tl,
                jnp.asarray(q[None, :live_terms(i)]), cfg)
            out.append((np.asarray(r.scores)[0], np.asarray(r.doc_ids)[0]))
        return out

    batch_s: list = []
    t_first = time.perf_counter()
    cold = serve_pass(svc, q1, batch_s)
    first_calls.seconds.setdefault("service flush B=8, 1 generation",
                                   batch_s[0])
    ref_cold = reference(timeline, q1, "1 generation")
    n_checked = compare(cold, ref_cold, "cold pass")
    log(f"(a) cold pass: {n_checked} tickets equal the direct engine "
        f"({time.perf_counter() - t_first:.3f} s incl. compiles)")

    n_docs = meta.n_docs
    svc.new_generation(corpus.doc_embs[n_docs:n_docs + n_new],
                       corpus.doc_lens[n_docs:n_docs + n_new])
    timeline = svc.timeline
    batch_s = []
    fresh = serve_pass(svc, q2, batch_s)
    first_calls.seconds.setdefault("service flush B=8, 2 generations",
                                   batch_s[0])
    ref_fresh = reference(timeline, q2, "2 generations")
    n_checked += compare(fresh, ref_fresh, "second generation pass")
    hits0 = svc.cache.hits
    warm_s: list = []
    warm = serve_pass(svc, q2, warm_s)
    n_checked += compare(warm, ref_fresh, "repeated pass")
    hits = svc.cache.hits - hits0
    log(f"(a) {n_checked} tickets in all equal the direct engine, ids at "
        f"every rank, scores within rtol {RTOL}")
    check(hits == len(q2), f"(c) repeated pass counted {hits} cache hits, "
          f"expected {len(q2)}")
    log(f"(c) repeated pass: {hits} cache hits for {len(q2)} queries")

    worst = 0.0
    cents = _round(index.centroids, precision)
    for i in range(N_EQ6):
        scores, ids = warm[i].result()
        n_live = live_terms(i)
        want = host_eq6(q2[i][:n_live], timeline, ids[:10], cfg.th_r,
                        precision, cents)
        err = float(np.max(np.abs(scores[:10] - want)))
        worst = max(worst, err / n_live)
        check(err <= EQ6_ATOL_PER_TERM * n_live,
              f"(b) query {i}: Eq. 6 scores differ from numpy by {err} "
              f"(> {EQ6_ATOL_PER_TERM * n_live} for {n_live} live terms)")
    log(f"(b) Eq. 6 top-10 of {N_EQ6} queries equal numpy: worst error "
        f"{worst:.3e} per live term (bound {EQ6_ATOL_PER_TERM:.3e})")

    log(f"smoke timing (not a benchmark): repeated pass "
        f"{1e3 * float(np.median(warm_s)):.3f} ms per batch of {MAX_BATCH} "
        f"(median of {len(warm_s)}; 1 generation cached, 1 computed)")
    served = np.stack([t.result()[1] for t in cold + fresh])
    direct = np.stack([r[1] for r in ref_cold + ref_fresh])
    gt = np.concatenate([corpus.gt_doc[first], corpus.gt_doc[second]])
    log(f"quality served: {quality(served, gt)}")
    log(f"quality direct engine: {quality(direct, gt)}")
    return {"tickets": n_checked, "cache_hits": hits, "precision": precision,
            "eq6_worst_per_term": worst}


def _count_placed_leaves(mesh) -> int:
    """Count live arrays stacked one shard per mesh device; fail if any
    shard of such an array sits on another device than its own."""
    devices = list(mesh.devices.flat)
    n = 0
    for a in jax.live_arrays():
        s = a.sharding
        if not (isinstance(s, NamedSharding) and s.mesh == mesh
                and a.ndim and a.shape[0] == len(devices)
                and s.spec and s.spec[0] is not None):
            continue
        for shard in a.addressable_shards:
            row = shard.index[0].start or 0
            check(shard.device == devices[row],
                  f"shard {row} of a {a.shape} array is on {shard.device}, "
                  f"not {devices[row]}")
        n += 1
    return n


def run_sharded(corpus, first, index, meta, chips: int,
                first_calls: FirstCalls) -> dict:
    cfg = MSMARCO.engine
    mesh = jax.make_mesh((chips,), ("shard",), devices=jax.devices()[:chips])
    timeline = ShardedTimeline.of((index, meta))
    t0 = time.perf_counter()
    svc = make_service(mesh, cfg, timeline, max_batch=MAX_BATCH)
    log(f"make_service: {time.perf_counter() - t0:.3f} s to shard "
        f"{meta.n_docs} passages over {chips} devices")
    n_placed = _count_placed_leaves(mesh)
    check(n_placed >= 12, f"only {n_placed} index leaves are stacked one "
          "shard per device")
    log(f"placement: {n_placed} index leaves hold shard s on device s")
    for d in mesh.devices.flat:
        stats = d.memory_stats() or {}
        log(f"bytes_in_use {d}: {stats.get('bytes_in_use', 'n/a')}")

    q1 = corpus.queries[first]
    batch_s: list = []
    tickets = serve_pass(svc, q1, batch_s)
    first_calls.seconds.setdefault(f"sharded flush B=8 on {chips} devices",
                                   batch_s[0])

    per = meta.n_docs // chips
    local_cfg = engine.adapt_config_to_corpus(cfg, per, meta.cap)
    stacked = shard_index(index, chips)
    shards = [jax.tree.map(lambda x, s=s: x[s], stacked)
              for s in range(chips)]
    refs = []
    for i, q in enumerate(q1):
        sc, ids = [], []
        for s, local in enumerate(shards):
            r = first_calls.run(f"per-shard retrieve B=1 {live_terms(i)} "
                                "terms", engine.retrieve, local,
                                jnp.asarray(q[None, :live_terms(i)]),
                                local_cfg)
            sc.append(np.asarray(r.scores)[0])
            ids.append(np.asarray(r.doc_ids)[0] + s * per)
        sc, ids = np.concatenate(sc), np.concatenate(ids)
        top = np.argsort(-sc, kind="stable")[:cfg.k]
        refs.append((sc[top], ids[top]))
    n = compare(tickets, refs, f"{chips}-device service")
    log(f"(a) {n} tickets from the {chips}-device service equal the "
        f"per-shard engine merged on the host")
    log(f"smoke timing (not a benchmark): "
        f"{1e3 * float(np.median(batch_s[1:] or batch_s)):.3f} ms per batch "
        f"of {MAX_BATCH} on {chips} devices")
    gt = corpus.gt_doc[first]
    log(f"quality served: "
        f"{quality(np.stack([t.result()[1] for t in tickets]), gt)}")
    log(f"quality per-shard engine: "
        f"{quality(np.stack([r[1] for r in refs]), gt)}")
    return {"tickets": n, "placed_leaves": n_placed}


def run(n_docs: int = N_DOCS, chips: int = 1, *, n_new: int = N_NEW,
        n_centroids: int = MSMARCO.n_centroids, n_queries: int = N_QUERIES,
        seed: int = 0) -> dict:
    """Build, serve and check. ``chips == 1`` runs the one-chip service
    flow; ``chips > 1`` runs only the sharded service and its per-shard
    comparison. Raises ``CheckFailed`` on the first check that fails."""
    e = MSMARCO.engine
    log(f"widths: d={MSMARCO.d} cap={MSMARCO.doc_cap} n_c={n_centroids} "
        f"m={MSMARCO.m} nbits={MSMARCO.nbits} n_q={e.n_q} nprobe={e.nprobe} "
        f"th={e.th} th_r={e.th_r} n_filter={e.n_filter} n_docs={e.n_docs} "
        f"k={e.k}; passage lengths {MIN_LEN}..{MSMARCO.doc_cap}")
    log(f"cut: {n_docs} passages of {MSMARCO.n_docs} (build_index holds the "
        "whole fp32 token array on host and device)")
    t0 = time.perf_counter()
    corpus, first, second = make_data(n_docs, n_new if chips == 1 else 0,
                                      n_queries, seed)
    log(f"corpus: {time.perf_counter() - t0:.3f} s")
    index, meta = build(corpus, n_docs, n_centroids)
    first_calls = FirstCalls()
    if chips == 1:
        out = run_one_chip(corpus, first, second, index, meta, n_new,
                           first_calls)
    else:
        out = run_sharded(corpus, first, index, meta, chips, first_calls)
    for label, s in first_calls.seconds.items():
        log(f"first call: {label}: {s:.3f} s")
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        log(f"peak_bytes_in_use {d}: {stats.get('peak_bytes_in_use', 'n/a')}")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip service flow; 4: only the "
                         "sharded service on a 4-device mesh")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__}")
    cache = use_compile_cache(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))
    log(f"compile cache: {cache}")
    run(chips=args.chips)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
