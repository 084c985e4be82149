"""One run of one cell: set-up, the measured window, the comparison and the
result line.

Set-up generates the cell's index and queries on the device from the seed
(:mod:`harness.indexgen`), builds the program's ``RetrievalService`` over
it (a cell on several chips: ``repro.launch.serve.make_service`` over a
mesh of the first ``chips`` devices), and warms every batch size the
window can flush, through the service.
The window is an open-loop client (:func:`drive`) that submits each query
when it is due and times it from then. Afterwards the program's state is
freed and the plain reference (:mod:`harness.reference`) checks a sample of
the answers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import shutil
import sys
import time
import traceback
from typing import Callable, Optional

import numpy as np

from . import correctness, indexgen, layers, spans, spec, tracing
from . import traffic as traffic_mod
from .reference import REF_FIELDS, merge_answers, reference_shards

GIB = float(1 << 30)
SETTLE_S = 60.0                      # wait for answers past the close


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    """A progress line on standard error."""
    print(*parts, file=sys.stderr, flush=True)


def say(*parts) -> None:
    """An earlier line of the result on standard output."""
    print(*parts, flush=True)


@dataclasses.dataclass
class RunRecord:
    """What one run measured; the per-layer metric readers take this.

    Host times are ``time.perf_counter`` seconds, the clock of the
    program's ``repro.obs`` spans. ``trace`` is the reduced profiler trace
    (:mod:`harness.tracing`) of a ``--trace 1`` run, ``None`` otherwise;
    ``trace_window`` its window on the profiler's clock."""

    cfg: dict
    peaks: dict
    due: np.ndarray          # absolute due time per query
    start: np.ndarray        # start of the call whose flush answered it
    fill: np.ndarray         # when its ticket was seen filled (NaN: never)
    calls: list              # [kind, start, end, answered] per client call
    spans: list              # repro.obs span records (traced runs)
    trace: Optional[dict] = None
    trace_window: Optional[tuple] = None

    @property
    def answered(self) -> np.ndarray:
        """Boolean mask of the queries that got an answer."""
        return ~np.isnan(self.fill)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    ``active`` (the window), and persistent-cache misses overall."""

    def __init__(self):
        import jax
        from jax._src import dispatch

        self.active = False
        self.in_window = 0
        self.cache_misses = 0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event_seen)

    def _duration(self, event, duration, *args, **kwargs):
        if self.active and event == self._event:
            self.in_window += 1

    def _event_seen(self, event, *args, **kwargs):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


def _null_annotation(name, **kw):
    return contextlib.nullcontext()


def drive(svc, queries: np.ndarray, due_rel: np.ndarray, traffic: dict,
          seconds: float, annotate: Callable, clock=time.perf_counter):
    """The open-loop client. Submits query i when it is due (``poisson``)
    or as fast as the service takes them (``backlog``), polls the service
    for deadline flushes, and stamps each ticket when it is seen filled.

    -> (t0, due, submit, start, fill, calls, tickets, n_attempted)"""
    n = len(due_rel)
    submit = np.full(n, np.nan)
    start = np.full(n, np.nan)
    fill = np.full(n, np.nan)
    calls: list = []
    tickets: list = [None] * n
    outstanding: list = []
    backlog = traffic["arrival"] == "backlog"
    group = int(traffic["max_batch"])

    def stamp(kind, t_start, t_end):
        done = [j for j in outstanding if tickets[j].done]
        for j in done:
            start[j] = t_start
            fill[j] = t_end
            outstanding.remove(j)
        calls.append([kind, t_start, t_end, len(done)])

    def call(kind, fn, *args):
        with annotate("bench." + kind):
            t_start = clock()
            out = fn(*args)
            t_end = clock()
        stamp(kind, t_start, t_end)
        return out

    i = 0
    with annotate("bench.window"):
        t0 = clock()
        due = t0 + due_rel
        try:
            if backlog:
                while i < n and clock() - t0 < seconds:
                    for _ in range(min(group, n - i)):
                        submit[i] = clock()
                        tickets[i] = call("submit", svc.submit, queries[i])
                        outstanding.append(i)
                        i += 1
            else:
                while i < n or outstanding:
                    now = clock()
                    if i < n and due[i] <= now:
                        submit[i] = now
                        tickets[i] = call("submit", svc.submit, queries[i])
                        outstanding.append(i)
                        i += 1
                    elif outstanding:
                        call("poll", svc.poll)
                        if outstanding and now - t0 > seconds + SETTLE_S:
                            break
                        if outstanding:
                            wait = 0.0002 if i >= n else min(0.0002,
                                                              due[i] - now)
                            if wait > 0:
                                time.sleep(wait)
                    else:
                        with annotate("bench.wait"):
                            wait = due[i] - clock() - 0.0005
                            if wait > 0:
                                time.sleep(wait)
                            while clock() < due[i]:
                                pass
            if backlog and outstanding:
                call("flush", svc.flush)
        except Exception:
            log("the service raised inside the window; its unanswered "
                "queries count as failed:")
            log(traceback.format_exc())
    return t0, due, submit, start, fill, calls, tickets, i


def _device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs[:chips]]
    if chips > 1:
        log(f"peak bytes in use per chip: {peaks}")
    peak = max(peaks)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def build_service(index: dict, cfg: dict, traffic: dict, chips: int,
                  plan_wrap: Optional[Callable] = None):
    """The system under test: a ``RetrievalService`` over a one-generation
    timeline of the generated index; on one chip the single-device plan,
    on ``chips`` above 1 ``repro.launch.serve.make_service`` over a mesh of
    the first ``chips`` devices, which shards the index itself.

    ``plan_wrap(base_plan, index, cfg) -> plan`` replaces the per-
    generation plan; the correctness tests use it to break the timed path
    or to put the reference in the program's place."""
    from repro.core.engine import EngineConfig, retrieve_generation_topk
    from repro.core.index import IndexMeta, PackedIndex
    from repro.core.store import ShardedTimeline
    from repro.serving import RetrievalService

    ecfg = EngineConfig(**cfg["engine"])
    pidx = PackedIndex(**{f: index[f] for f in PackedIndex._fields})
    meta = IndexMeta(
        n_docs=cfg["n_passages"], n_centroids=cfg["n_centroids"],
        d=cfg["d"], cap=cfg["cap"], m=cfg["m"], nbits=cfg["nbits"],
        plaid_b=cfg["plaid_b"], list_cap=index["list_cap"], n_dropped=0,
        train_quant_mse=float(cfg["corpus"]["residual_norm"]) ** 2,
        n_raw_tokens=int(np.asarray(index["doc_lens"]).sum()))
    timeline = ShardedTimeline.of((pidx, meta))
    kwargs = {"max_batch": int(traffic["max_batch"])}
    if traffic.get("max_delay_s") is not None:
        kwargs["max_delay_s"] = float(traffic["max_delay_s"])
    if chips > 1:
        import jax
        from repro.launch.serve import (make_service,
                                        make_timeline_partial_plans)

        mesh = jax.make_mesh((chips,), ("shard",),
                             devices=jax.devices()[:chips])
        if plan_wrap is None:
            return make_service(mesh, ecfg, timeline, **kwargs)

        def base_plans(tl):
            return make_timeline_partial_plans(mesh, ecfg, tl)
    else:
        if plan_wrap is None:
            return RetrievalService(timeline, ecfg, **kwargs)

        def base_plans(tl):
            return [lambda q, m, f=None, _g=gen, _m=gmeta, _o=off:
                    retrieve_generation_topk(_g, _m, _o, q, ecfg, m,
                                             doc_filter=f)
                    for gen, gmeta, off in tl]

    return RetrievalService(
        timeline, ecfg, plan_factory=lambda tl: [
            plan_wrap(p, index, cfg) for p in base_plans(tl)], **kwargs)


def _shards(index: dict, cfg: dict) -> list:
    return [indexgen.shard_fields(index, s)
            for s in range(int(cfg.get("chips", 1)))]


def _ref_devices(cfg: dict):
    """One device per shard on several chips; JAX's default on one."""
    import jax

    chips = int(cfg.get("chips", 1))
    return jax.devices()[:chips] if chips > 1 else None


def control_plan(base_plan, index: dict, cfg: dict):
    """The control: the reference in bfloat16 in the program's place, per
    shard and merged on several chips."""
    import jax
    import jax.numpy as jnp
    from repro.core.engine import RetrievalResult

    k = cfg["engine"]["k"]
    check = cfg["check"]
    shards = _shards(index, cfg)
    per = cfg["n_passages"] // len(shards)
    devices = _ref_devices(cfg)
    if devices is not None:
        # each shard's fields go to its device once, not at every flush
        shards = [jax.device_put({f: sh[f] for f in REF_FIELDS}, dev)
                  for sh, dev in zip(shards, devices)]

    def plan(q, m, f=None):
        served = np.full((q.shape[0], k), -1, np.int32)
        outs = reference_shards(shards, q, m, served, cfg["engine"],
                                "bfloat16", pool=int(check["pool"]),
                                e_pool=int(check["e_pool"]),
                                devices=devices)
        top, ids = merge_answers(outs, per, k)
        return RetrievalResult(jnp.asarray(top), jnp.asarray(ids))

    return plan


E2E = {
    "latency_p50_ms": lambda x: float(np.percentile(x["latency_ms"], 50)),
    "latency_p95_ms": lambda x: float(np.percentile(x["latency_ms"], 95)),
    "qps": lambda x: x["qps"],
    "peak_hbm_gib": lambda x: x["device"]["memory_peak_bytes"] / GIB,
    "setup_s": lambda x: x["setup_s"],
}


def _busy(reduced, window) -> tuple[float, float]:
    """-> (device-busy seconds averaged over the device planes that ran
    operations, the chips used, window seconds)."""
    planes = spans.devices(reduced) if reduced is not None else []
    if window is None or not planes:
        return 0.0, 0.0
    busy = [tracing.busy_in(dev, [window]) for dev in planes]
    return float(np.mean(busy)), float(window[1] - window[0])


def _first_plane(reduced):
    """The first TPU's plane of a reduced trace, by name; None where it is
    missing or ran no operation."""
    dev = (reduced or {}).get("devices", {}).get("/device:TPU:0")
    return dev if dev and dev["ops"] else None


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def start(cell: spec.Cell, out_dir: str, require_tpu: bool = True):
    """Check the devices, point JAX's persistent compile cache inside the
    checkout, start counting compiles. -> (peaks, CompileCounter)."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < cell.chips:
        raise NoChip(f"needs {cell.chips} chips; JAX found {len(devs)}")
    peaks = spec.load_peaks(cell.bench_dir, devs[0].device_kind) \
        if require_tpu else {}
    os.makedirs(out_dir, exist_ok=True)
    from repro.launch import use_compile_cache

    log(f"compile cache: "
        f"{use_compile_cache(os.path.join(out_dir, 'jax_cache'))}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return peaks, CompileCounter()


@dataclasses.dataclass
class Setup:
    """A cell's generated data and the service over it."""

    index: dict
    svc: object
    queries: np.ndarray      # (n, n_q, d), terms past live_terms zeroed
    live: np.ndarray         # (n, n_q) bool
    targets: np.ndarray      # (n,) planted answers
    warm_q: np.ndarray       # (max_batch, n_q, d)
    live_terms: int


def prepare(cell: spec.Cell, seed: int, n: int,
            plan_wrap: Optional[Callable] = None) -> Setup:
    """Generate the index and ``n`` window queries (plus a batch of
    warm-up queries) from the seed and build the service over them."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    if int(traffic["generations"]) != 1:
        raise ValueError("this harness serves one generation")
    t = time.perf_counter()
    index = indexgen.generate_index(cfg, seed)
    jax.block_until_ready([v for v in index.values() if hasattr(v, "shape")])
    lens = np.asarray(index["ivf_lens"])
    say(f"index: {cfg['n_passages']} passages, "
        f"{indexgen.index_bytes(index)} bytes, list_cap {index['list_cap']}, "
        f"mean IVF list length {lens.mean():.3f}, longest {lens.max()}, "
        f"generated in {time.perf_counter() - t:.3f} s")
    for s, sh in enumerate(index.get("shards", ())):
        say(f"shard {s}: local list_cap {sh['list_cap']}, longest local "
            f"list {np.max(sh['ivf_lens'])}")
    max_batch = int(traffic["max_batch"])
    t = time.perf_counter()
    queries, targets = indexgen.generate_queries(index, cfg, seed,
                                                 n + max_batch)
    live_terms = int(traffic["live_terms"])
    queries[:, live_terms:] = 0.0
    live = np.broadcast_to(np.arange(queries.shape[1]) < live_terms,
                           (n, queries.shape[1]))
    log(f"queries: {n} + {max_batch} warm-up, "
        f"{time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    svc = build_service(index, cfg, traffic, cell.chips, plan_wrap)
    log(f"service built (fingerprints included) in "
        f"{time.perf_counter() - t:.3f} s")
    return Setup(index, svc, queries[:n], live, targets[:n], queries[n:],
                 live_terms)


def warm(st: Setup, traffic: dict) -> None:
    """Flush every batch size the window can send once, through the
    service, so that the window compiles nothing."""
    for b in traffic_mod.warm_batch_sizes(traffic):
        t = time.perf_counter()
        for j in range(b):
            st.svc.submit(st.warm_q[j, :st.live_terms])
        st.svc.flush()
        log(f"warm-up flush of {b}: {time.perf_counter() - t:.3f} s")


def answers(tickets: list, answered: np.ndarray, k: int):
    """-> (scores (n, k) with NaN, ids (n, k) with -1) of the tickets."""
    n = len(answered)
    scores = np.full((n, k), np.nan, np.float32)
    ids = np.full((n, k), -1, np.int32)
    for j in np.flatnonzero(answered):
        scores[j], ids[j] = tickets[j].result()
    return scores, ids


def check_reference(st: Setup, cfg: dict, queries: np.ndarray,
                    live: np.ndarray, ids: np.ndarray,
                    dtype: str = "float32") -> list:
    """The reference's readings of ``queries``, per shard (one on one
    chip; shard s on device s), as host arrays, with the configuration's
    pool sizes."""
    check = cfg["check"]
    return reference_shards(_shards(st.index, cfg), queries, live, ids,
                            cfg["engine"], dtype, pool=int(check["pool"]),
                            e_pool=int(check["e_pool"]),
                            devices=_ref_devices(cfg))


def compare(st: Setup, cfg: dict, seed: int, scores: np.ndarray,
            ids: np.ndarray, answered: np.ndarray) -> dict:
    """Run the reference over a seeded sample of the answered queries.
    -> the compared numbers (``correctness.numbers``)."""
    pick = correctness.sample(np.flatnonzero(answered), seed,
                              int(cfg["check"]["sample"]))
    t = time.perf_counter()
    if not len(pick):
        return {"score_gap": math.inf, "selection_gap": math.inf}
    refs = check_reference(st, cfg, st.queries[pick], st.live[pick],
                           ids[pick])
    n_cand = np.concatenate([r["n_cand"] for r in refs])
    say(f"candidates per query (reference): mean {n_cand.mean():.1f}, "
        f"min {n_cand.min()}, max {n_cand.max()}")
    nums, reasons = correctness.numbers(
        scores[pick], ids[pick], refs, cfg["engine"],
        int(cfg["n_passages"]) // len(refs))
    say(f"reference: {len(pick)} queries in {time.perf_counter() - t:.3f} s")
    for why in sorted(set(reasons)):
        say(f"selection not judged for {reasons.count(why)} queries: {why}")
    return nums


def run(cell: spec.Cell, seed: int, seconds: int, trace: bool, *,
        t_process: float, out_dir: str, require_tpu: bool = True,
        plan_wrap: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result object (not yet printed).

    ``t_process`` is the ``perf_counter`` reading at process start, for
    ``setup_s``. ``require_tpu=False`` and ``plan_wrap`` exist for the
    CPU tests of the harness and of its comparison."""
    import jax

    cfg, traffic = cell.config, cell.traffic
    peaks, counter = start(cell, out_dir, require_tpu)
    due_rel = traffic_mod.schedule(traffic, seed, seconds)
    n = len(due_rel)
    st = prepare(cell, seed, n, plan_wrap)
    warm(st, traffic)
    log(f"persistent-cache misses in set-up: {counter.cache_misses}")

    obs_tracer = None
    trace_dir = os.path.join(out_dir, f"trace-{os.getpid()}")
    annotate = _null_annotation
    if trace:
        from repro.obs import trace as obs_trace

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # the profiler's first traced launch stalls the host; take it here
        warm(st, dict(traffic, arrival="backlog"))
        obs_tracer = obs_trace.enable(capacity=1 << 20)
        annotate = jax.profiler.TraceAnnotation

    # -- the window ---------------------------------------------------------
    counter.active = True
    t0, due, submit, start_t, fill, calls, tickets, n_attempted = drive(
        st.svc, st.queries[:, :st.live_terms], due_rel, traffic, seconds,
        annotate)
    counter.active = False
    setup_s = t0 - t_process
    t_end = np.nanmax(fill) if np.any(~np.isnan(fill)) else t0
    spans, reduced, window = [], None, None
    if trace:
        jax.profiler.stop_trace()
        spans = obs_tracer.finished()
        from repro.obs import trace as obs_trace

        obs_trace.disable()
        reduced = tracing.load_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing.save(reduced, os.path.join(out_dir, "last_trace.json"))
        win = tracing.annotations(reduced["host"], {"bench.window"})
        window = (win[0][1], win[0][2]) if win else None
    device = _device_info(cell.chips)

    attempted = np.arange(n) < n_attempted
    answered = attempted & ~np.isnan(fill)
    failed = int(attempted.sum() - answered.sum())
    latency_ms = 1e3 * (fill[answered] - due[answered])
    lateness = 1e3 * (submit[attempted] - due[attempted])
    say(f"compiles or cache loads inside the window: {counter.in_window}")
    say(f"generator lateness (submit - due): p50 "
        f"{np.percentile(lateness, 50):.3f} ms, p95 "
        f"{np.percentile(lateness, 95):.3f} ms, max {lateness.max():.3f} ms "
        f"over {int(attempted.sum())} queries")
    if traffic["arrival"] == "poisson" and len(latency_ms):
        beyond = int(np.sum(latency_ms > np.percentile(latency_ms, 95)))
        say(f"latency sample: {len(latency_ms)} queries, {beyond} beyond "
            f"the 95th percentile")
    flush_sizes = [c[3] for c in calls if c[3]]
    say(f"flushes: {len(flush_sizes)} calls answered queries, mean "
        f"{np.mean(flush_sizes) if flush_sizes else 0:.3f} per call")
    record = RunRecord(cfg=cfg, peaks=peaks, due=due[:n_attempted],
                       start=start_t[:n_attempted],
                       fill=fill[:n_attempted], calls=calls, spans=spans,
                       trace=reduced, trace_window=window)
    x = {"latency_ms": latency_ms, "device": device, "setup_s": setup_s,
         "qps": float(answered.sum() / max(t_end - t0, 1e-9))}

    # -- metrics ------------------------------------------------------------
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            if len(latency_ms) or not m["name"].startswith("latency"):
                metrics[m["name"]] = {"value": E2E[m["name"]](x),
                                      "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = spec.load_metric_reader(cell.bench_dir,
                                            m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        share = layers.roofline_share(record)
        if share is not None:
            say(f"retrieve roofline: {share[0]:.4f}% ({share[1]} bound)")
        busy, dev_window = _busy(reduced, window)
        device["busy_s"] = busy
        device["window_s"] = dev_window

    # -- the comparison -----------------------------------------------------
    scores, ids = answers(tickets, answered, int(cfg["engine"]["k"]))
    st.svc = None
    gc.collect()
    nums = compare(st, cfg, seed, scores, ids, answered)
    say(f"Recall@100 against the planted answers: "
        f"{correctness.recall_at(ids[answered], st.targets[answered], 100):.4f}"
        f" over {int(answered.sum())} answered queries")
    ok, checks = correctness.verdict(nums, cfg["limits"], failed)

    result = {"correct": bool(ok), "attempted": int(attempted.sum()),
              "failed": failed, "metrics": metrics, "device": device}
    dev0 = _first_plane(reduced)
    if dev0 is not None:
        result["breakdown"] = {
            "device_ops": tracing.top_ops(dev0),
            "idle_gaps": tracing.idle_gaps(dev0, reduced["host"], window)
            if window else []}
    result["checks"] = {k: {"value": _finite(v["value"]),
                            "limit": v["limit"]} for k, v in checks.items()}
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result
