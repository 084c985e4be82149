"""CPU tests of the readers of the program's own spans (harness/spans.py).

They check that a profiler capture of a served flush holds the program's
spans on the thread of the benchmark's annotations, nested as the span
records say; that each new reader gives a hand-computed value on a
hand-made reduced trace and nothing on a trace without program spans; and
that the host and blocked idle shares add up to the idle share in flushes.
"""
import json
import os
import time

import jax
import numpy as np
import pytest

from harness import indexgen, runner, spec, spans, tracing
from repro import obs

from conftest import BENCH, TINY_CONFIG

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NEW = {
    "device.idle_share_in_flush.host": "idle_share_in_flush.host",
    "device.idle_share_in_flush.blocked": "idle_share_in_flush.blocked",
    "device.idle_share_in_flush.host.bulk": "idle_share_in_flush.host",
    "device.idle_share_in_flush.blocked.bulk":
        "idle_share_in_flush.blocked",
    "serving.batcher_wait_p95_ms": "batcher_wait_p95_ms",
}


def _record(trace_name, calls, span_records):
    n = sum(c[3] for c in calls)
    return runner.RunRecord(
        cfg=TINY_CONFIG, peaks={}, due=np.zeros(n), start=np.zeros(n),
        fill=np.ones(n), calls=calls, spans=span_records,
        trace=tracing.load(os.path.join(DATA, trace_name + ".json")),
        trace_window=(0.0, 1.0))


@pytest.fixture
def handmade():
    with open(os.path.join(DATA, "trace_program_spans.expected.json")) as f:
        want = json.load(f)
    return _record("trace_program_spans", want["calls"], want["spans"]), want


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_readers_give_hand_computed_values(handmade, metric):
    run, want = handmade
    read = spec.load_metric_reader(BENCH, metric)
    assert read(run) == pytest.approx(want[NEW[metric]], rel=1e-9)


def test_host_and_blocked_add_up_to_the_idle_share(handmade):
    run, want = handmade
    split = spans.idle_split(run)
    old = spec.load_metric_reader(BENCH, "device.idle_share_in_flush")
    whole = old(run)
    assert whole == pytest.approx(want["idle_share_in_flush"], rel=1e-9)
    assert split["host"] + split["blocked"] == pytest.approx(whole,
                                                             rel=1e-12)
    # a plane with no operation (a TPU host's Megascale plane) is no
    # device: the split leaves it out, the accepted reader counts it idle
    run.trace["devices"]["/device:CUSTOM:Megascale Trace"] = {
        "ops": [], "modules": []}
    assert spans.idle_split(run) == split
    assert old(run) == pytest.approx((2 * whole + 100.0) / 3, rel=1e-9)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_readers_read_nothing_without_program_spans(metric):
    # a trace from a program that does not mirror its spans, and batcher
    # records without per-query waits
    calls = [["submit", 0.1, 0.3, 1], ["poll", 0.4, 0.42, 0],
             ["poll", 0.7, 0.96, 1]]
    records = [{"name": "batcher.queue_wait", "duration_s": 0.002,
                "attrs": {"batch": 1, "pending": 0}}]
    run = _record("trace_handmade", calls, records)
    assert len(run.calls) == 3 and run.trace["devices"]
    read = spec.load_metric_reader(BENCH, metric)
    assert read(run) is None
    run.trace = None
    run.spans = []
    assert read(run) is None


def test_innermost_program_event_decides_blocked():
    host = [["bench.poll", 0.0, 10.0, "main"],
            ["service.fetch", 1.0, 5.0, "main"],
            ["service.merge", 2.0, 3.0, "main"],      # host work inside
            ["np.asarray(jax.Array)", 3.5, 4.5, "main"],   # not the program
            ["service.merge", 6.0, 9.0, "main"],
            ["service.device_wait", 7.0, 8.0, "main"],
            ["service.device_wait", 0.0, 10.0, "other"]]
    events = spans.program_events(host)
    assert len(events) == 4
    assert spans.blocked_intervals(events) == [(1.0, 2.0), (3.0, 5.0),
                                               (7.0, 8.0)]
    dev = {"ops": [["a", 0.5, 1.5], ["b", 1.2, 2.5], ["c", 7.5, 12.0]],
           "modules": []}
    assert spans.idle_intervals(dev, [(0.0, 3.0), (6.0, 10.0)]) == [
        (0.0, 0.5), (2.5, 3.0), (6.0, 7.5)]
    assert spans.idle_intervals({"ops": [], "modules": []},
                                [(1.0, 2.0)]) == [(1.0, 2.0)]


def test_profiler_capture_holds_the_program_spans(tmp_path):
    traffic = {"max_batch": 2, "max_delay_s": None}
    index = indexgen.generate_index(TINY_CONFIG, 5)
    queries, _ = indexgen.generate_queries(index, TINY_CONFIG, 5, 4)
    svc = runner.build_service(index, TINY_CONFIG, traffic, 1)
    svc.submit(queries[0])
    svc.submit(queries[1])                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing() as t:
            with jax.profiler.TraceAnnotation("bench.submit"):
                svc.submit(queries[2])
            with jax.profiler.TraceAnnotation("bench.submit"):
                svc.submit(queries[3])
    finally:
        jax.profiler.stop_trace()
    reduced = tracing.load_xplane(str(tmp_path))
    events = spans.program_events(reduced["host"])
    names = [e[0] for e in events]
    for name in ("service.flush", "service.execute", "service.miss_execute",
                 "service.device_wait", "service.fetch"):
        assert name in names, (name, names)
    # the k-th record of a name is the k-th event of that name, and every
    # record's event lies inside its parent's event
    records = [s for s in t.finished() if s["name"] != "batcher.queue_wait"]
    assert sorted(names) == sorted(s["name"] for s in records)
    event_of = {}
    for name in set(names):
        evs = sorted((e for e in events if e[0] == name), key=lambda e: e[1])
        recs = sorted((s for s in records if s["name"] == name),
                      key=lambda s: s["start"])
        event_of.update({s["span_id"]: e for s, e in zip(recs, evs)})
    nested = 0
    for s in records:
        if s["parent_id"] in event_of:
            child, parent = event_of[s["span_id"]], event_of[s["parent_id"]]
            assert parent[1] <= child[1] and child[2] <= parent[2], (
                s["name"], child, parent)
            nested += 1
    assert nested >= 8
    bench = tracing.annotations(reduced["host"], {"bench.submit"})
    flush = event_of[next(s["span_id"] for s in records
                          if s["name"] == "service.flush")]
    assert bench[1][1] <= flush[1] and flush[2] <= bench[1][2]


def test_traced_tiny_run_reports_the_batcher_wait(tiny_bench, tmp_path):
    root, bench = tiny_bench
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        doc = json.load(f)
    for name in NEW:
        doc["per_layer"].append({
            "name": name, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": "device",
            "moves": "latency_p50_ms", "workloads": ["tiny.poisson"]})
    with open(path, "w") as f:
        json.dump(doc, f)
    result = runner.run(spec.load_cell(root, "tiny.poisson", bench),
                        2**31 + 7, 2, True, t_process=time.perf_counter(),
                        out_dir=str(tmp_path / "out"), require_tpu=False)
    assert result["correct"] is True, result["checks"]
    # the CPU trace holds no device plane, so the idle shares stay silent
    got = {k for k in result["metrics"] if k in NEW}
    assert got == {"serving.batcher_wait_p95_ms"}
    wait = result["metrics"]["serving.batcher_wait_p95_ms"]["value"]
    assert 0.0 <= wait < 1e3
