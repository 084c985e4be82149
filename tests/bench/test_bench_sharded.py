"""CPU tests of a cell block-sharded over several chips.

The harness serves such a cell through the program's own four-chip path,
``repro.launch.serve.make_service``, and judges its two-level answer per
shard. A whole run of a tiny four-shard cell goes in a subprocess on four
virtual CPU devices; it has to be correct on the program, and not correct
under the control or with a fault in one shard. The other tests pin the
pieces: the one-chip generation to its bytes before sharding existed, the
global IVF the shards make, the two-level merge, and the configuration
checks.

Run as a script (``python test_bench_sharded.py <dir>``) this file is the
subprocess: it writes the tiny benchmark under ``<dir>`` and prints one
JSON line per side.
"""
import hashlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import REPO, TINY_CONFIG

CHIPS = 4
SHARDED = dict(TINY_CONFIG, name="tiny4", n_passages=CHIPS * 512,
               chips=CHIPS, check=dict(TINY_CONFIG["check"], sample=16))
MIX = {"arrival": "poisson", "rate_qps": 16.0, "shape_seed": 0,
       "max_batch": 2, "max_delay_s": None, "generations": 1,
       "live_terms": 8}
SEED = 2**31 + 21


def _write_bench(root: str) -> None:
    """A benchmark tree with the one cell ``tiny4.poisson``."""
    with open(os.path.join(REPO, "bench", "configs",
                           "msmarco-v1-s32.json")) as f:
        limits = json.load(f)["limits"]
    os.makedirs(os.path.join(root, "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "bench", "traffic"), exist_ok=True)
    with open(os.path.join(root, "configs", "tiny4.json"), "w") as f:
        json.dump(dict(SHARDED, limits=limits), f)
    with open(os.path.join(root, "bench", "traffic", "p.json"), "w") as f:
        json.dump(MIX, f)
    spec = {"configs": [{"name": "tiny4", "file": "configs/tiny4.json"}],
            "workloads": [{"name": "tiny4.poisson", "config": "tiny4",
                           "traffic": "p", "chips": CHIPS}],
            "end_to_end": [], "per_layer": []}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)


def _one_shard_offset(base, index, cfg):
    """Shard 1's passages come back under shard 2's ids: a wrong shard
    offset in one shard."""
    import jax.numpy as jnp

    per = cfg["n_passages"] // cfg["chips"]

    def plan(q, m, f=None):
        r = base(q, m, f)
        ids = jnp.where(r.doc_ids // per == 1, r.doc_ids + per, r.doc_ids)
        return type(r)(r.scores, ids)

    return plan


def _one_shard_unmerged(base, index, cfg):
    """Shard 0's own top k, computed by the program's engine on shard 0,
    is served without the merge with the other shards."""
    from harness import indexgen
    from repro.core.engine import (EngineConfig, adapt_config_to_corpus,
                                   retrieve)
    from repro.core.index import PackedIndex

    shard = indexgen.shard_fields(index, 0)
    local = PackedIndex(**{f: shard[f] for f in PackedIndex._fields})
    ecfg = adapt_config_to_corpus(EngineConfig(**cfg["engine"]),
                                  cfg["n_passages"] // cfg["chips"],
                                  cfg["cap"])
    return lambda q, m, f=None: retrieve(local, q, ecfg, m)


def _sides():
    from harness import runner

    return {"program": None, "control": runner.control_plan,
            "one_shard_offset": _one_shard_offset,
            "one_shard_unmerged": _one_shard_unmerged}


def _placement(cell) -> dict:
    """Build the cell's service and count the index leaves that lie one
    shard per device on a mesh of all four."""
    import jax
    from jax.sharding import NamedSharding
    from harness import runner

    st = runner.prepare(cell, SEED, 4)
    devices = jax.devices()[:CHIPS]
    placed = 0
    for a in jax.live_arrays():
        sh = a.sharding
        if (isinstance(sh, NamedSharding) and sh.mesh.size == CHIPS
                and a.shape[0] == CHIPS
                and [x.data.device for x in sorted(
                    a.addressable_shards, key=lambda x: x.index[0].start)]
                == devices):
            placed += 1
    kind = type(st.svc).__module__ + "." + type(st.svc).__name__
    factory = st.svc._plan_factory.__qualname__
    return {"side": "placement", "placed": placed, "service": kind,
            "factory": factory}


def main(root: str) -> int:
    """The subprocess: the service's placement, then one run of the tiny
    cell per side."""
    import jax
    from harness import runner, spec

    assert len(jax.devices()) == CHIPS, jax.devices()
    print(json.dumps({"side": "one_chip_bytes",
                      "digest": _digest(EXACT_SEED, exact=True)[0]}),
          flush=True)
    _write_bench(root)
    cell = spec.load_cell(root, "tiny4.poisson", os.path.join(root, "bench"))
    print(json.dumps(_placement(cell)), flush=True)
    for side, wrap in _sides().items():
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
    root = tmp_path_factory.mktemp("sharded")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(REPO, "src")] + env.get("PYTHONPATH", "").split(
            os.pathsep))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        str(root)], env=env, capture_output=True, text=True,
                       timeout=240)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    return {x["side"]: x for x in lines}


@pytest.mark.parametrize("side", ["program", "control", "one_shard_offset",
                                  "one_shard_unmerged"])
def test_four_device_cell_is_judged_by_its_two_level_answer(
        four_device_runs, side):
    run = four_device_runs[side]
    checks = run["checks"]
    assert run["failed"] == 0 and run["attempted"] > 0
    if side == "program":
        assert run["correct"] is True, checks
        return
    assert run["correct"] is False, checks
    if side == "control":
        assert checks["score_gap"]["value"] > checks["score_gap"]["limit"]
    if side == "one_shard_unmerged":
        # every served score is right; the merge is what is missing
        assert checks["score_gap"]["value"] <= checks["score_gap"]["limit"]
        assert checks["selection_gap"]["value"] is None or \
            checks["selection_gap"]["value"] > \
            checks["selection_gap"]["limit"]


def test_four_device_service_is_make_services(four_device_runs):
    run = four_device_runs["placement"]
    assert run["service"] == "repro.serving.service.RetrievalService"
    assert run["factory"].startswith("make_service.")
    # every field of the program's index, one shard per device
    assert run["placed"] >= 12, run


@pytest.mark.parametrize("case", ["chips_mismatch", "ragged_shards"])
def test_load_cell_refuses_a_config_that_does_not_shard(tiny_bench, case):
    from harness import spec

    root, bench = tiny_bench
    path = os.path.join(root, "configs", "tiny.json")
    with open(path) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        doc = json.load(f)
    if case == "chips_mismatch":
        cfg["chips"] = 4
    else:
        cfg.update(chips=4, n_passages=1026)
        for w in doc["workloads"]:
            w["chips"] = 4
    with open(path, "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "tiny.poisson", bench)


# The one-chip tiny index and 16 queries as generated before sharding was
# added (``_digest`` on the parent): sha256 of every array's name, dtype
# and shape and of the bytes of every integer array, which are the same
# under any XLA optimisation level; the float draws (centroids, codebooks,
# queries) move in their last bits with the CPU's code generation, so
# they are pinned by fixed random projections instead.
ONE_CHIP_DIGESTS = {
    5: ("51c2e5ef3e4dea20ad1a11aa94ec9ac8232861018afff610bdfe204e707619b1",
        {"centroids": -10.788987117052145, "pq_codebooks": -1.5590420534462632,
         "queries": -5.269483789965752}),
    2**31 + 17: (
        "31a389784db6e274ec2fb28c048593bd8dfe4fdd3c2611ff92e6d3d84dc0b613",
        {"centroids": 4.351704349908825, "pq_codebooks": -2.43672796336319,
         "queries": -16.475698563462636}),
    2**33 + 5: (
        "bb1c0fe5e95996d01d38fe8b1cf400d1d27770997c3c1ce3960ac2d65b83dc96",
        {"centroids": -33.408427367959646, "pq_codebooks": -4.194461835790495,
         "queries": 1.80139556134636}),
}
FLOATS = ("centroids", "pq_codebooks", "queries")
# Every byte, floats included, of the same arrays for one seed, as the
# parent generates them in the four-device subprocess, whose XLA flags are
# fixed.
EXACT_SEED = 2**31 + 17
EXACT_DIGEST = \
    "4fd70a28368c54cadabd9cb61e5d81fd26ea7c28b4ab0120e65afcb92a8318eb"


def _digest(seed: int, exact: bool = False) -> tuple:
    """-> (sha256 hex, the float arrays' projections); ``exact`` hashes
    the float arrays' bytes too."""
    from harness import indexgen

    index = indexgen.generate_index(TINY_CONFIG, seed)
    q, targets = indexgen.generate_queries(index, TINY_CONFIG, seed, 16)
    arrays = {k: np.asarray(v) for k, v in index.items()
              if hasattr(v, "shape")}
    arrays.update(queries=q, targets=targets)
    h = hashlib.sha256(repr(index["list_cap"]).encode())
    projections = {}
    for k in sorted(arrays):
        a = np.ascontiguousarray(arrays[k])
        h.update(f"{k} {a.dtype} {a.shape}".encode())
        if k in FLOATS and not exact:
            w = np.random.default_rng(len(k)).standard_normal(a.shape)
            projections[k] = float(np.sum(a.astype(np.float64) * w))
        else:
            h.update(a.tobytes())
    return h.hexdigest(), projections


@pytest.mark.parametrize("seed", sorted(ONE_CHIP_DIGESTS))
def test_one_chip_generation_keeps_its_bytes(seed):
    digest, projections = _digest(seed)
    want_digest, want_projections = ONE_CHIP_DIGESTS[seed]
    assert digest == want_digest
    for k, want in want_projections.items():
        assert projections[k] == pytest.approx(want, rel=1e-5, abs=1e-5), k


def test_one_chip_generation_keeps_every_byte(four_device_runs):
    assert four_device_runs["one_chip_bytes"]["digest"] == EXACT_DIGEST


def test_shards_make_the_global_ivf():
    from harness import indexgen
    from repro.core.index import _build_ivf

    index = indexgen.generate_index(SHARDED, 9)
    per = SHARDED["n_passages"] // CHIPS
    n_c, multiple = SHARDED["n_centroids"], SHARDED["list_cap_multiple"]
    ivf, lens, list_cap, dropped = _build_ivf(index["codes"], n_c,
                                              index["list_cap"])
    assert dropped == 0 and list_cap == index["list_cap"]
    np.testing.assert_array_equal(index["ivf"], ivf)
    np.testing.assert_array_equal(index["ivf_lens"], lens)
    glob_ivf, glob_lens = indexgen.build_ivf(index["codes"], n_c, multiple)
    np.testing.assert_array_equal(index["ivf"], np.asarray(glob_ivf))
    np.testing.assert_array_equal(index["ivf_lens"], np.asarray(glob_lens))
    for s in range(CHIPS):
        shard = indexgen.shard_fields(index, s)
        np.testing.assert_array_equal(
            shard["codes"], index["codes"][s * per:(s + 1) * per])
        local_ivf, local_lens = indexgen.build_ivf(shard["codes"], n_c,
                                                   multiple)
        np.testing.assert_array_equal(shard["ivf"], np.asarray(local_ivf))
        np.testing.assert_array_equal(shard["ivf_lens"],
                                      np.asarray(local_lens))
    # the shards' tokens are drawn apart: no two shards alike
    assert not np.array_equal(index["codes"][:per],
                              index["codes"][per:2 * per])
    q, targets = indexgen.generate_queries(index, SHARDED, 9, 8)
    assert q.shape[0] == 8 and targets.max() < SHARDED["n_passages"]


def test_busy_and_breakdown_read_the_planes_of_the_chips():
    from harness import runner, tracing

    trace = tracing.load(os.path.join(os.path.dirname(__file__), "data",
                                      "trace_program_spans.json"))
    window = (0.1, 0.9)
    chips = dict(trace["devices"])
    want = np.mean([tracing.busy_in(dev, [window])
                    for dev in chips.values()])
    # a TPU host's profile also holds a plane with no operation, here first
    trace["devices"] = {"/device:CUSTOM:Megascale Trace":
                        {"ops": [], "modules": []}, **chips}
    busy, seconds = runner._busy(trace, window)
    assert busy == pytest.approx(want, rel=1e-12)
    assert seconds == pytest.approx(0.8, rel=1e-12)
    assert runner._first_plane(trace) is chips["/device:TPU:0"]
    trace["devices"]["/device:TPU:0"] = {"ops": [], "modules": []}
    assert runner._first_plane(trace) is None


def test_two_level_merge_equals_a_hand_written_merge():
    from harness.reference import merge_answers

    rng = np.random.default_rng(31)
    n_q, k, per, shards = 5, 6, 50, 3
    outs = []
    for _ in range(shards):
        # scores on a coarse grid, so that shards tie
        top = -np.sort(-rng.integers(0, 8, (n_q, k)).astype(np.float32),
                       axis=1)
        ids = np.stack([rng.choice(per, k, replace=False)
                        for _ in range(n_q)]).astype(np.int32)
        outs.append({"top": top, "ids": ids})
    scores, ids = merge_answers(outs, per, k)
    for j in range(n_q):
        pool = [(float(o["top"][j, r]), int(o["ids"][j, r]) + s * per,
                 s * k + r) for s, o in enumerate(outs) for r in range(k)]
        want = sorted(pool, key=lambda x: (-x[0], x[2]))[:k]
        assert scores[j].tolist() == [w[0] for w in want]
        assert ids[j].tolist() == [w[1] for w in want]


def _shard_readings(e):
    """One shard's readings with four candidates that all pass every cut
    (n_filter and n_docs at least 4), with Eq. 6 scores ``e``."""
    n = len(e)
    return {"n_cand": np.int32(n), "cand": np.arange(n),
            "f": np.full(n, 3), "ci": np.arange(n, 0, -1).astype(np.float32),
            "e_rows": np.arange(n), "e": np.asarray(e, np.float32)}


def test_selection_gap_reads_a_merge_that_missed_a_shard():
    from harness import correctness

    eng = {"n_filter": 4, "n_docs": 4, "k": 2}
    per = 4
    refs = [{k: v[None] for k, v in _shard_readings(e).items()}
            for e in ([5.0, 4.0, 1.0, 0.0], [4.5, 2.0, 1.5, 0.5])]
    cases = {(0, 4): 0.0,        # 5.0 and 4.5: the true top 2
             (0, 1): 0.5,        # 4.0 served, shard 1's 4.5 left out
             (4, 5): 3.0,        # shard 1's 4.5, 2.0; shard 0's 5.0 left out
             (1, 4): 1.0}        # shard 0's 4.0 without its own 5.0
    for served, want in cases.items():
        gaps, reasons = correctness.selection_gaps(
            refs, np.array([served]), eng, per)
        assert reasons == []
        assert gaps[0] == pytest.approx(want, rel=0.02, abs=1e-12), served


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
