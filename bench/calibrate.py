"""Read the numbers that set a cell's correctness limits.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 101,102,... --control-seeds 201,202,203

For each seed the cell is set up anew, one window is served at the cell's
own load through the same client as ``run.py``, and the answers are
compared with the plain reference: one JSON line per seed with
``score_gap`` and ``rank_gap``. The control seeds do the same with the
control in the program's place: the reference computed in bfloat16, the
step below the configuration's float32. The lower reading of a number is
the largest the program gives; its upper reading the smallest the control
gives; the limit lies between (PERF.md).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    """Read the program's and the control's numbers; -> the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import numpy as np
    from harness import runner, spec
    from harness import traffic as traffic_mod

    cell = spec.load_cell(ROOT, args.workload, BENCH)
    try:
        runner.start(cell, os.path.join(BENCH, "out"))
    except runner.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    runs = [(int(s), None) for s in args.seeds.split(",") if s] + \
        [(int(s), runner.control_plan)
         for s in args.control_seeds.split(",") if s]
    for seed, wrap in runs:
        due_rel = traffic_mod.schedule(cell.traffic, seed, args.seconds)
        st = runner.prepare(cell, seed, len(due_rel), wrap)
        runner.warm(st, cell.traffic)
        _, _, _, _, fill, _, tickets, n_att = runner.drive(
            st.svc, st.queries[:, :st.live_terms], due_rel, cell.traffic,
            args.seconds, runner._null_annotation)
        answered = (np.arange(len(due_rel)) < n_att) & ~np.isnan(fill)
        scores, ids = runner.answers(tickets, answered,
                                     int(cell.config["engine"]["k"]))
        st.svc = None
        gc.collect()
        nums = runner.compare(st, cell.config, seed, scores, ids, answered)
        print(json.dumps({"seed": seed,
                          "side": "control" if wrap else "program",
                          "answered": int(answered.sum()),
                          "failed": int(n_att - answered.sum()), **nums}),
              flush=True)
        del st
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
