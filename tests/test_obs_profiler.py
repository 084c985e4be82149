"""The tracer on the profiler's clock, the spans that split a flush, the
batcher's per-query waits and the engine's phase names
(docs/OBSERVABILITY.md):

* disabled, ``trace.span`` is still the shared ``NOOP_SPAN`` and builds
  no profiler annotation; enabled, each span opens one annotation of its
  name around its two clock reads, and ``record`` opens none;
* a served flush nests ``service.fingerprint``, ``service.device_wait``
  and ``service.fetch`` where the device wait and the copies happen,
  without moving the spans that were there before;
* ``batcher.queue_wait`` carries one wait per drained query;
* the compiled retrieve program names all four phases in its metadata.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import EngineConfig, ShardedTimeline, build_index, engine
from repro.data.synthetic import make_corpus
from repro.obs import trace
from repro.serving import RetrievalService
from repro.serving.batcher import MicroBatcher

CFG = EngineConfig(nprobe=4, th=0.2, th_r=0.4, n_filter=64, n_docs=16, k=5)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation`` and logs its use."""

    log: list = []

    def __init__(self, name):
        self.name = name
        self.log.append(("new", name))

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.fixture
def recorder(monkeypatch):
    _Recorder.log = []
    monkeypatch.setattr(trace, "TraceAnnotation", _Recorder)
    return _Recorder.log


@pytest.fixture(scope="module")
def tiny():
    c = make_corpus(0, n_docs=200, cap=16, min_len=8, n_queries=8,
                    n_topics=8)
    idx, meta = build_index(jax.random.PRNGKey(0), c.doc_embs, c.doc_lens,
                            n_centroids=64, m=8, nbits=4, plaid_b=2,
                            kmeans_iters=2)
    return c, idx, meta


def test_disabled_tracer_builds_no_annotation(recorder):
    assert not trace.get_tracer().enabled
    sp = trace.span("x", a=1)
    assert sp is trace.NOOP_SPAN
    with sp:
        trace.record("y", 0.5)
    assert recorder == []


def test_enabled_span_annotates_around_its_clock_reads(recorder):
    def clock():
        recorder.append(("clock", None))
        return float(len(recorder))

    with obs.tracing(clock=clock) as t:
        with trace.span("outer", k=1):
            with trace.span("inner"):
                pass
        trace.record("past", 0.25)
    assert recorder == [
        ("new", "outer"), ("enter", "outer"), ("clock", None),
        ("new", "inner"), ("enter", "inner"), ("clock", None),
        ("clock", None), ("exit", "inner"),
        ("clock", None), ("exit", "outer"),
        ("clock", None)]                    # the record: a clock, no annotation
    # the annotation carries the name alone; attributes stay in the record
    assert [s["name"] for s in t.finished()] == ["inner", "outer", "past"]
    assert t.finished()[1]["attrs"] == {"k": 1}


def test_annotation_closes_when_the_span_raises(recorder):
    with obs.tracing() as t:
        with pytest.raises(RuntimeError):
            with trace.span("boom"):
                raise RuntimeError("x")
    assert recorder[-1] == ("exit", "boom")
    assert t.finished()[0]["error"] is True


def _children(spans, parent, name):
    return [s for s in spans
            if s["parent_id"] == parent["span_id"] and s["name"] == name]


def test_flush_spans_split_wait_copy_and_fingerprints(tiny):
    c, idx, meta = tiny
    timeline = ShardedTimeline.of((idx, meta))
    plain = RetrievalService(timeline, CFG, max_batch=3)
    svc = RetrievalService(timeline, CFG, max_batch=3)
    want = [plain.submit(c.queries[i]) for i in range(3)]
    with obs.tracing() as t:
        got = [svc.submit(c.queries[i]) for i in range(3)]
    for g, w in zip(got, want):            # bit-exact with tracing on
        np.testing.assert_array_equal(g.result()[0], w.result()[0])
        np.testing.assert_array_equal(g.result()[1], w.result()[1])
    spans = t.finished()
    by_id = {s["span_id"]: s for s in spans}
    (flush,) = [s for s in spans if s["name"] == "service.flush"]
    (execute,) = _children(spans, flush, "service.execute")
    assert len(_children(spans, flush, "service.fingerprint")) == 1
    assert len(_children(spans, flush, "service.fetch")) == 1
    (miss,) = [s for s in spans if s["name"] == "service.miss_execute"]
    assert by_id[by_id[miss["parent_id"]]["parent_id"]] is execute
    assert len(_children(spans, miss, "service.device_wait")) == 1
    assert len(_children(spans, miss, "service.fetch")) == 1
    (final,) = [s for s in spans if s["name"] == "service.merge"
                and s["attrs"].get("final")]
    assert len(_children(spans, final, "service.device_wait")) == 1
    # the new spans lie inside their parents on the tracer's clock
    for s in spans:
        p = by_id.get(s["parent_id"])
        if p is not None and s["name"] != "batcher.queue_wait":
            assert p["start"] <= s["start"]
            assert s["start"] + s["duration_s"] <= \
                p["start"] + p["duration_s"]


def test_batcher_records_one_wait_per_drained_query():
    now = [0.0]
    b = MicroBatcher(n_q=4, max_batch=2, max_delay_s=0.5,
                     clock=lambda: now[0])
    q = np.ones((2, 8), np.float32)
    for t_submit in (0.0, 1.0, 1.5):
        now[0] = t_submit
        b.submit(q)
    now[0] = 4.0
    with obs.tracing() as t:
        b.drain()
        now[0] = 4.25
        b.drain()
    first, second = t.finished()
    assert first["attrs"]["waits_s"] == [4.0, 3.0]
    assert second["attrs"]["waits_s"] == [2.75]
    for rec in (first, second):
        assert len(rec["attrs"]["waits_s"]) == rec["attrs"]["batch"]
        assert max(rec["attrs"]["waits_s"]) == rec["duration_s"]
    assert b.deadline_misses == 3


@pytest.mark.parametrize("name,cfg", [
    ("jnp", CFG),
    ("jnp-compact", dataclasses.replace(CFG, candidate_mode="compact",
                                        cand_cap=100)),
    ("fused-kernels", dataclasses.replace(CFG, use_kernels=True)),
])
def test_compiled_retrieve_names_every_phase(tiny, name, cfg):
    c, idx, meta = tiny
    cfg = engine.adapt_config_to_corpus(cfg, meta.n_docs, meta.cap)
    q = jnp.asarray(np.asarray(c.queries[:2]))
    text = engine._retrieve_jit.lower(idx, q, cfg).compile().as_text()
    scopes = set(re.findall(r'op_name="[^"]*?(engine\.phase\d)', text))
    assert scopes == {f"engine.phase{i}" for i in range(1, 5)}, name
