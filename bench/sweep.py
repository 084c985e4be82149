"""Find a cell's knee: the highest Poisson rate it sustains.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 5,10,20

One process sets the cell up once and then runs one open-loop window per
rate, each with fresh queries, through the same client as ``run.py``. Per
rate it prints the answered rate, the latency median and 95th percentile,
the mean flush size, the drain time after the last due query, and
``growth``: the median latency of the window's last quarter over that of
its second quarter. A rate is sustained when growth stays near 1 and the
drain is short; the knee is the highest such rate. The cell's traffic file
holds the rate its cells run at, as a number; this tool only finds it. A
cell on several chips is set up as ``run.py`` sets it up, through the
program's sharded service.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    """Sweep the offered rates; -> the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np
    from harness import runner, spec
    from harness import traffic as traffic_mod

    cell = spec.load_cell(ROOT, args.workload, BENCH)
    rates = [float(r) for r in args.rates.split(",")]
    try:
        runner.start(cell, os.path.join(BENCH, "out"))
    except runner.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    schedules = [traffic_mod.schedule(dict(cell.traffic, rate_qps=r),
                                      args.seed + i, args.seconds)
                 for i, r in enumerate(rates)]
    st = runner.prepare(cell, args.seed, sum(len(s) for s in schedules))
    runner.warm(st, cell.traffic)
    used = 0
    for rate, due_rel in zip(rates, schedules):
        q = st.queries[used:used + len(due_rel), :st.live_terms]
        used += len(due_rel)
        t0, due, _, _, fill, calls, _, n_att = runner.drive(
            st.svc, q, due_rel, cell.traffic, args.seconds,
            runner._null_annotation)
        ok = ~np.isnan(fill)
        lat = 1e3 * (fill[ok] - due[ok])
        quarter = max(1, len(lat) // 4)
        growth = float(np.median(lat[-quarter:])
                       / np.median(lat[quarter:2 * quarter]))
        sizes = [c[3] for c in calls if c[3]]
        print(json.dumps({
            "rate_qps": rate, "offered": int(n_att),
            "answered": int(ok.sum()),
            "answered_per_s": float(ok.sum() / (np.nanmax(fill) - t0)),
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "mean_flush": float(np.mean(sizes)),
            "drain_ms": float(1e3 * (np.nanmax(fill) - due[-1])),
            "growth": growth}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
