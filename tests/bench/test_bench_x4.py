"""CPU tests of the four-chip MS MARCO cell, ``msmarco-s32x4.poisson``.

The cell resolves from the real ``BENCHMARK.json`` as MS MARCO block-
sharded over four chips, one 1/32 share each; its four readers of the
sharded program (``harness/shardmap.py``) give hand-computed values on a
hand-made four-chip trace and nothing on a trace without the program's
name; and a run of the cell's own configuration and traffic files, cut to
a tiny corpus, on four virtual CPU devices is correct on the program and
not correct under the control.

Run as a script (``python test_bench_x4.py <dir>``) this file is the
four-device subprocess: it writes the tiny benchmark under ``<dir>`` and
prints one JSON line per side.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import BENCH, REPO

CELL = "msmarco-s32x4.poisson"
READERS = ("launch.device_ms_per_query.x4",
           "launch.allgather_ms_per_flush.x4",
           "launch.retrieve_roofline.x4",
           "device.idle_share_in_flush.x4")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# the test's own cut of the cell: a corpus small enough for the CPU
TINY = {"n_passages": 4 * 2048, "n_centroids": 1024}
SEED = 2**31 + 1601


def test_load_cell_resolves_the_four_chip_cell():
    from harness import spec

    cell = spec.load_cell(REPO, CELL, BENCH)
    assert cell.chips == 4
    assert cell.config["n_passages"] // cell.chips == 276_307
    one = spec.load_cell(REPO, "msmarco-s32.poisson", BENCH).config
    differs = {k for k in set(one) | set(cell.config)
               if one.get(k) != cell.config.get(k)}
    assert differs == {"name", "source", "n_passages", "chips",
                       "deployment", "reduced"}
    # the same paper, named down to the part that defines this deployment
    assert cell.config["source"].startswith(one["source"] + " Sec. 5, ")
    assert list(cell.config["reduced"]) == ["n_passages"]
    assert {m["name"] for m in cell.end_to_end} == {
        "latency_p50_ms", "latency_p95_ms", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == set(READERS)
    traffic = cell.traffic
    assert (traffic["arrival"], traffic["max_batch"], traffic["max_delay_s"],
            traffic["generations"], traffic["live_terms"]) == (
        "poisson", 8, None, 1, 32)


def _record(trace):
    from harness import runner, spec

    with open(os.path.join(DATA, "trace_shardmap_x4.expected.json")) as f:
        want = json.load(f)
    cfg = spec.load_json(os.path.join(
        BENCH, "configs", want["config"] + ".json"))
    peaks = spec.load_peaks(BENCH, "TPU v5 lite")
    n = sum(c[3] for c in want["calls"])
    run = runner.RunRecord(
        cfg=cfg, peaks=peaks, due=np.zeros(n), start=np.zeros(n),
        fill=np.ones(n), calls=want["calls"], spans=want["spans"],
        trace=trace, trace_window=tuple(want["window"]))
    return run, want


@pytest.fixture
def handmade():
    from harness import tracing

    return _record(tracing.load(os.path.join(DATA,
                                             "trace_shardmap_x4.json")))


@pytest.mark.parametrize("metric", READERS)
def test_readers_give_hand_computed_values(handmade, metric):
    from harness import spec

    run, want = handmade
    read = spec.load_metric_reader(BENCH, metric)
    assert read(run) == pytest.approx(want[metric], rel=1e-9)


def test_readers_leave_out_the_empty_plane_and_earlier_launches(handmade):
    from harness import shardmap, spec

    run, want = handmade
    assert shardmap.roofline_share(run)[1] == want["roofline_bound"]
    # the trace holds a launch and an all-gather before the window, and a
    # plane with no operation: the accepted idle reader counts that plane
    accepted = spec.load_metric_reader(BENCH, "device.idle_share_in_flush")
    assert accepted(run) == pytest.approx(
        want["idle_share_in_flush_all_planes"], rel=1e-9)
    assert len(shardmap.launches(run)) == 4
    assert all(len(ls) == 2 for _, ls in shardmap.launches(run))


@pytest.mark.parametrize("metric", READERS)
def test_readers_read_nothing_without_the_named_program(handmade, metric):
    from harness import spec

    run, _ = handmade
    read = spec.load_metric_reader(BENCH, metric)
    # the shard_map program as the program named it before: ``step``
    for dev in run.trace["devices"].values():
        dev["modules"] = [[m[0].replace("_shardmap_retrieve", "step"),
                           *m[1:]] for m in dev["modules"]]
    assert read(run) is None
    run.trace = None
    assert read(run) is None


def _write_bench(root: str) -> None:
    """A benchmark tree with the cell, its configuration and its traffic
    mix copied from the real files, the corpus cut to :data:`TINY`."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (cell,) = [w for w in doc["workloads"] if w["name"] == CELL]
    (conf,) = [c for c in doc["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(REPO, conf["file"])) as f:
        cfg = dict(json.load(f), **TINY)
    os.makedirs(os.path.join(root, "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    with open(os.path.join(root, "configs", "x4.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(BENCH, "traffic",
                           cell["traffic"] + ".json")) as src, \
            open(os.path.join(root, "bench", "traffic",
                              cell["traffic"] + ".json"), "w") as dst:
        dst.write(src.read())
    spec = {"configs": [dict(conf, file="configs/x4.json")],
            "workloads": [cell], "end_to_end": [], "per_layer": []}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def main(root: str) -> int:
    """The subprocess: one run of the tiny cell on the program and one
    under the control."""
    import jax
    from harness import runner, spec

    assert len(jax.devices()) == 4, jax.devices()
    _write_bench(root)
    cell = spec.load_cell(root, CELL, os.path.join(root, "bench"))
    for side, wrap in (("program", None), ("control", runner.control_plan)):
        result = runner.run(cell, SEED, 1, False,
                            t_process=time.perf_counter(),
                            out_dir=os.path.join(root, "out"),
                            require_tpu=False, plan_wrap=wrap)
        print(json.dumps({"side": side, "correct": result["correct"],
                          "failed": result["failed"],
                          "attempted": result["attempted"],
                          "checks": result["checks"]}), flush=True)
    return 0


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    root = tmp_path_factory.mktemp("x4")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src"), BENCH] + env.get(
            "PYTHONPATH", "").split(os.pathsep))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        str(root)], env=env, capture_output=True, text=True,
                       timeout=420)
    assert p.returncode == 0, p.stderr[-4000:]
    return {x["side"]: x for x in map(json.loads, (
        ln for ln in p.stdout.splitlines() if ln.startswith("{")))}


@pytest.mark.parametrize("side", ["program", "control"])
def test_tiny_four_device_run_of_the_cell(four_device_runs, side):
    run = four_device_runs[side]
    checks = run["checks"]
    assert run["failed"] == 0 and run["attempted"] > 0
    if side == "program":
        assert run["correct"] is True, checks
        # float32 rounding of a 32-term Eq. 6 sum at d=128 (2**-17)
        assert checks["score_gap"]["value"] < 1e-5, checks
        assert checks["selection_gap"]["value"] == 0.0, checks
    else:
        assert run["correct"] is False, checks
        assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
