"""A tiny benchmark tree for the CPU tests of the benchmark harness.

``tiny_bench`` writes a ``BENCHMARK.json``, one small configuration and two
traffic mixes into a temporary directory and points the harness at them;
the per-layer metric readers are the real ones under ``bench/metrics``.
The configuration keeps the widths' structure at toy sizes and takes its
correctness limits from the MS MARCO configuration, so the tests hold the
same limits the chip runs do.
"""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY_CONFIG = {
    "name": "tiny", "n_passages": 512, "d": 32, "cap": 24,
    "n_centroids": 256, "m": 8, "nbits": 8, "plaid_b": 2,
    "engine": {"n_q": 8, "nprobe": 2, "th": 0.4, "th_r": 0.5,
               "n_filter": 64, "n_docs": 32, "k": 10},
    "chips": 1, "list_cap_multiple": 8,
    "corpus": {"shape_seed": 0, "block": 16, "block_spread": 1.0,
               "topic_share": 0.6, "topic_zipf": 1.0,
               "topic_size_sigma": 0.5, "residual_norm": 0.35,
               "query_noise": 0.3,
               "length": {"law": "uniform", "min": 12, "max": 24}},
    "check": {"sample": 32, "pool": 512, "e_pool": 128},
}
TRAFFIC = {
    "tiny-poisson": {"arrival": "poisson", "rate_qps": 40.0, "shape_seed": 0,
                     "max_batch": 4, "max_delay_s": None, "generations": 1,
                     "live_terms": 8},
    "tiny-backlog": {"arrival": "backlog", "backlog": 256, "max_batch": 4,
                     "max_delay_s": None, "generations": 1,
                     "live_terms": 6},
}


def _metric(name, unit, moves, cells, source="device_trace"):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": name.split(".")[0], "moves": moves, "workloads": cells}


@pytest.fixture
def tiny_bench(tmp_path):
    """-> (root, bench_dir) of a tiny benchmark with cells ``tiny.poisson``
    and ``tiny.bulk``."""
    with open(os.path.join(BENCH, "configs", "msmarco-v1-s32.json")) as f:
        limits = json.load(f)["limits"]
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(
        json.dumps(dict(TINY_CONFIG, limits=limits)))
    bench = tmp_path / "bench"
    (bench / "traffic").mkdir(parents=True)
    for name, mix in TRAFFIC.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    shutil.copytree(os.path.join(BENCH, "metrics"), bench / "metrics")
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench / "peaks.json")
    poisson, bulk = ["tiny.poisson"], ["tiny.bulk"]
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test", "reduced": [],
                     "file": "configs/tiny.json", "why": "test"}],
        "workloads": [
            {"name": "tiny.poisson", "config": "tiny",
             "traffic": "tiny-poisson", "chips": 1, "why": "test"},
            {"name": "tiny.bulk", "config": "tiny", "traffic": "tiny-backlog",
             "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "latency_p50_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": poisson},
            {"name": "latency_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock", "workloads": poisson},
            {"name": "qps", "unit": "queries/s", "better": "higher",
             "bound": 0.1, "source": "host_clock", "workloads": bulk},
            {"name": "peak_hbm_gib", "unit": "GiB", "better": "lower",
             "bound": 0.01, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            _metric("serving.queue_wait_p95_ms", "ms", "latency_p95_ms",
                    poisson, "host_clock"),
            _metric("serving.host_ms_per_query", "ms", "latency_p50_ms",
                    poisson, "program_span"),
            _metric("engine.device_ms_per_query", "ms", "latency_p50_ms",
                    poisson),
            _metric("serving.host_ms_per_query.bulk", "ms", "qps", bulk,
                    "program_span")],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path), str(bench)
