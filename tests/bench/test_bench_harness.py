"""CPU tests of the benchmark harness under ``bench/``.

They check the pieces the chip runs rest on: the generated IVF against the
program's own builder, finding a cell's files by name, the arrival
schedules, the trace reduction on a small recorded trace, the refusal to
run without a TPU, and one whole run of each tiny cell on the CPU.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from harness import indexgen, runner, spec, tracing, traffic
from repro.core.index import _build_ivf

from conftest import BENCH, REPO, TINY_CONFIG

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_generated_ivf_equals_program_builder(seed):
    index = indexgen.generate_index(TINY_CONFIG, seed)
    codes = np.asarray(index["codes"])
    n_c = TINY_CONFIG["n_centroids"]
    ivf, lens, list_cap, dropped = _build_ivf(codes, n_c, index["list_cap"])
    assert dropped == 0
    assert list_cap == index["list_cap"]
    np.testing.assert_array_equal(np.asarray(index["ivf"]), ivf)
    np.testing.assert_array_equal(np.asarray(index["ivf_lens"]), lens)
    assert int(lens.max()) <= index["list_cap"] < int(lens.max()) + 8


def test_generated_index_shapes_and_seeds():
    a = indexgen.generate_index(TINY_CONFIG, 7)
    b = indexgen.generate_index(TINY_CONFIG, 7)
    c = indexgen.generate_index(TINY_CONFIG, 7 + 2**32)
    cfg = TINY_CONFIG
    assert a["codes"].shape == (cfg["n_passages"], cfg["cap"])
    assert a["res_codes"].shape == (cfg["n_passages"], cfg["cap"], cfg["m"])
    assert a["plaid_res"].shape == (cfg["n_passages"], cfg["cap"],
                                    cfg["d"] * cfg["plaid_b"] // 8)
    norms = np.linalg.norm(np.asarray(a["centroids"]), axis=1)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    real = np.arange(cfg["cap"])[None] < np.asarray(a["doc_lens"])[:, None]
    assert np.all((np.asarray(a["codes"]) == cfg["n_centroids"]) == ~real)
    np.testing.assert_array_equal(np.asarray(a["codes"]),
                                  np.asarray(b["codes"]))
    assert not np.array_equal(np.asarray(a["codes"]), np.asarray(c["codes"]))
    # topic sizes and lengths are one multiset on every seed
    np.testing.assert_array_equal(np.sort(np.asarray(a["doc_lens"])),
                                  np.sort(np.asarray(c["doc_lens"])))


def test_queries_are_reconstructed_tokens_of_their_target():
    index = indexgen.generate_index(TINY_CONFIG, 3)
    q, targets = indexgen.generate_queries(index, TINY_CONFIG, 3, 16)
    assert q.shape == (16, TINY_CONFIG["engine"]["n_q"], TINY_CONFIG["d"])
    np.testing.assert_allclose(np.linalg.norm(q, axis=-1), 1.0, rtol=1e-5)
    cent = np.asarray(index["centroids"])
    codes = np.asarray(index["codes"])
    for i, t in enumerate(targets):
        own = codes[t][codes[t] < TINY_CONFIG["n_centroids"]]
        best = np.argmax(q[i] @ cent.T, axis=1)
        # most terms sit nearest to a centroid of their target's tokens
        assert np.mean(np.isin(best, own)) > 0.5


def test_pieces_are_found_by_name(tiny_bench, tmp_path):
    root, bench = tiny_bench
    # a new configuration, traffic mix and metric: new files plus entries
    cfg = dict(TINY_CONFIG, name="tiny2", n_passages=300)
    with open(os.path.join(root, "configs", "tiny2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "slow.json"), "w") as f:
        json.dump({"arrival": "poisson", "rate_qps": 3.0, "shape_seed": 1,
                   "max_batch": 2, "max_delay_s": None, "generations": 1,
                   "live_terms": 8}, f)
    with open(os.path.join(bench, "metrics", "serving.calls.py"), "w") as f:
        f.write("def read(run):\n    return float(len(run.calls))\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "tiny2", "source": "test", "reduced": [],
                           "file": "configs/tiny2.json", "why": "test"})
    doc["workloads"].append({"name": "tiny2.slow", "config": "tiny2",
                             "traffic": "slow", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "serving.calls", "unit": "calls",
                             "better": "lower", "source": "host_clock",
                             "layer": "serving", "moves": "latency_p50_ms",
                             "workloads": ["tiny2.slow"]})
    with open(path, "w") as f:
        json.dump(doc, f)
    cell = spec.load_cell(root, "tiny2.slow", bench)
    assert cell.config["n_passages"] == 300
    assert cell.traffic["rate_qps"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["serving.calls"]
    # end-to-end metrics without a workloads list apply to every cell
    assert {m["name"] for m in cell.end_to_end} == {"peak_hbm_gib",
                                                    "setup_s"}
    read = spec.load_metric_reader(bench, "serving.calls")
    assert read(type("R", (), {"calls": [1, 2, 3]})()) == 3.0
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "no.such.cell", bench)
    with pytest.raises(spec.SpecError):
        spec.load_metric_reader(bench, "no.such.metric")
    with pytest.raises(spec.SpecError):
        spec.load_peaks(BENCH, "an unknown device")


def test_poisson_schedule_repeats_for_a_seed():
    mix = {"arrival": "poisson", "rate_qps": 25.0, "shape_seed": 0}
    a = traffic.schedule(mix, 11, 30)
    np.testing.assert_array_equal(a, traffic.schedule(mix, 11, 30))
    b = traffic.schedule(mix, 12, 30)
    assert len(a) == len(b) == 750
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 30.0
    # the arrivals are the mix's: another seed sends at the same times
    np.testing.assert_array_equal(a, b)
    c = traffic.schedule(dict(mix, shape_seed=1), 11, 30)
    assert len(c) == 750 and not np.array_equal(a, c)
    backlog = traffic.schedule({"arrival": "backlog", "backlog": 64}, 1, 30)
    assert len(backlog) == 64 and not backlog.any()
    assert traffic.warm_batch_sizes({"arrival": "backlog",
                                     "max_batch": 8}) == [8]
    assert traffic.warm_batch_sizes({"arrival": "poisson",
                                     "max_batch": 3}) == [1, 2, 3]


TRACES = sorted(f[:-len(".expected.json")] for f in os.listdir(DATA)
                if f.endswith(".expected.json"))


@pytest.mark.parametrize("name", TRACES)
def test_trace_reduction_gives_known_times(name):
    with open(os.path.join(DATA, name + ".expected.json")) as f:
        want = json.load(f)
    trace = tracing.load(os.path.join(DATA, name + ".json"))
    dev = trace["devices"][want["device"]]
    window = tuple(want["window"])
    assert tracing.busy_in(dev, [window]) == pytest.approx(
        want["busy_s"], rel=1e-9)
    seconds, launches = tracing.module_time(dev, "_retrieve_jit")
    assert launches == want["retrieve_launches"]
    assert seconds == pytest.approx(want["retrieve_s"], rel=1e-9)
    calls = [(h[1], h[2]) for h in tracing.annotations(
        trace["host"], {"bench.submit", "bench.poll"})]
    assert tracing.busy_in(dev, calls) == pytest.approx(
        want["busy_in_calls_s"], rel=1e-9)
    gaps = tracing.idle_gaps(dev, trace["host"], window, n=3)
    assert [g[0] for g in gaps] == want["longest_gap_names"]
    assert [g[1] for g in gaps] == pytest.approx(want["longest_gaps_s"],
                                                 rel=1e-9)
    assert tracing.top_ops(dev, 1)[0][0] == want["top_op"]


def test_interval_arithmetic():
    assert tracing.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tracing.total([["a", 0, 1], ["b", 0.5, 2]]) == 2
    assert tracing.overlap([(0, 2), (3, 5)], [(1, 4)]) == 2
    dev = {"ops": [["x", 1.0, 2.0], ["y", 4.0, 5.0]], "modules": []}
    host = [["bench.poll", 2.0, 4.0, "main"], ["inner", 2.5, 3.5, "main"]]
    assert tracing.idle_gaps(dev, host, (0.0, 6.0), n=2) == [
        ["inner", 2.0], ["no host event", 1.0]]


def _run_bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_run_exits_nonzero_without_a_tpu():
    p = _run_bench(["--workload", "msmarco-s32.poisson", "--seed",
                    str(2**31 + 17), "--seconds", "1", "--trace", "0"], REPO)
    assert p.returncode == 3, p.stderr
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _run_bench(["--workload", "msmarco-s32.poisson", "--seed", "1",
                    "--seconds", "1", "--trace", "0"], str(tmp_path))
    assert p.returncode == 2, p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("cell,trace", [("tiny.poisson", False),
                                        ("tiny.poisson", True),
                                        ("tiny.bulk", False)])
def test_tiny_cell_runs_end_to_end_on_cpu(tiny_bench, tmp_path, cell, trace):
    import time

    root, bench = tiny_bench
    result = runner.run(spec.load_cell(root, cell, bench), 2**31 + 99, 2,
                        trace, t_process=time.perf_counter(),
                        out_dir=str(tmp_path / "out"), require_tpu=False)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    names = set(result["metrics"])
    if trace:
        assert {"serving.queue_wait_p95_ms",
                "serving.host_ms_per_query"} <= names
        assert {"busy_s", "window_s"} <= set(result["device"])
    elif cell == "tiny.bulk":
        assert names == {"qps", "peak_hbm_gib", "setup_s"}
    else:
        assert names == {"latency_p50_ms", "latency_p95_ms", "peak_hbm_gib",
                         "setup_s"}
    json.dumps(result, allow_nan=False)
    assert jnp.zeros(()).dtype  # the process still holds a working JAX
