"""Benchmark driver — one module per paper table/figure, plus the roofline
report. ``PYTHONPATH=src python -m benchmarks.run [name ...]``.

Emits ``name,us_per_call,derived`` CSV rows (absolute times are single-core
CPU; the EMVB/PLAID *ratios* are the reproduction target).

``--smoke`` runs the fast default subset (fig1: the phase breakdown, the
fused-vs-unfused megakernel rows and the batched-vs-vmap batch sweep; fig2:
the bit-vector threshold sweep locating the no-recall-loss operating point;
fig4: vectorized-vs-naive set membership and bitfilter-vs-centroid
-interaction; fig6: the query-pruning latency/MRR sweep; fig7: latency +
MRR@10 as the corpus grows 1 -> N streaming generations; fig8:
serving-cache throughput/hit-rate, cold vs warm vs uncached; fig9: the
predicate-filter selectivity sweep, in-kernel vs post-filter; fig10:
the constant-space document-budget sweep, MRR/latency/bytes-per-doc at
m in {4, 8, 16, 32, None}; roofline:
per-megakernel batched-vs-vmap wall time + analytic arithmetic intensity at
B in {1,4,16,64}) and writes the rows to ``BENCH_smoke.json`` — with the
roofline and fig9 suites split out to their own ``BENCH_roofline.json`` /
``BENCH_fig9.json`` so those trajectories are separate CI artifacts —
``--json PATH`` does the same for any suite selection.
BENCH_*.json is gitignored by design — machine-dependent numbers belong in
artifacts, not history.
"""

import argparse
import json
import os
import platform
import sys
import time

from repro.launch import use_compile_cache

from . import (fig1_breakdown, fig2_threshold, fig4_membership,
               fig5_termfilter, fig6_pruning, fig7_streaming, fig8_serving,
               fig9_selectivity, fig10_budget, roofline, table1_msmarco,
               table2_ood)

SUITES = {
    "table1": table1_msmarco,
    "table2": table2_ood,
    "fig1": fig1_breakdown,
    "fig2": fig2_threshold,
    "fig4": fig4_membership,
    "fig5": fig5_termfilter,
    "fig6": fig6_pruning,
    "fig7": fig7_streaming,
    "fig8": fig8_serving,
    "fig9": fig9_selectivity,
    "fig10": fig10_budget,
    "roofline": roofline,
}
SMOKE_SUITES = ["fig1", "fig2", "fig4", "fig6", "fig7", "fig8", "fig9",
                "fig10", "roofline"]


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.run", description=__doc__)
    # nargs="*" + choices rejects the empty default in this argparse
    # version, so membership is checked by hand below
    ap.add_argument("names", nargs="*", metavar="name",
                    help=f"suites to run: {', '.join(SUITES)} "
                         "(default: all, or the smoke subset)")
    ap.add_argument("--smoke", action="store_true",
                    help="fast subset + write BENCH_smoke.json (CI artifact)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows to this JSON file")
    args = ap.parse_args()
    use_compile_cache(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))
    unknown = [n for n in args.names if n not in SUITES]
    if unknown:
        ap.error(f"unknown suite(s): {', '.join(unknown)}")
    names = args.names or (SMOKE_SUITES if args.smoke else list(SUITES))

    results, timings = {}, {}
    for name in names:
        mod = SUITES[name]
        t0 = time.time()
        print(f"# === {name} ({mod.__name__}) ===", flush=True)
        rows = mod.run()
        for line in rows:
            print(line, flush=True)
        timings[name] = time.time() - t0
        results[name] = rows
        print(f"# {name} done in {timings[name]:.0f}s", flush=True)

    json_path = args.json or ("BENCH_smoke.json" if args.smoke else None)
    if json_path:
        import jax

        meta = {
            "unix_time": int(time.time()),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "python": platform.python_version(),
            "machine": platform.machine(),
            "argv": sys.argv[1:],
        }
        # the roofline and fig9 suites ship as their own artifacts (the
        # kernel-lane and filter-lane perf trajectories) next to the figure
        # smoke rows — the CI upload glob (BENCH_*.json) covers all three
        if args.smoke:
            for split, path in (("roofline", "BENCH_roofline.json"),
                                ("fig9", "BENCH_fig9.json")):
                if split not in results:
                    continue
                payload = {"suites": {split: results.pop(split)},
                           "suite_seconds":
                               {split: round(timings.pop(split), 1)},
                           "meta": meta}
                with open(path, "w") as f:
                    json.dump(payload, f, indent=1)
                print(f"# wrote {path}", flush=True)
        payload = {
            "suites": results,
            "suite_seconds": {k: round(v, 1) for k, v in timings.items()},
            "meta": meta,
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {json_path}", flush=True)


if __name__ == "__main__":
    main()
