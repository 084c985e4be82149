"""Run one cell of the EMVB benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``. The run generates its index and queries on
the device from ``--seed``, warms every program it will use, measures for
``--seconds``, and checks a sample of the answers against the plain
reference. A cell whose ``chips`` is above 1 is served through the
program's own sharded path, ``repro.launch.serve.make_service`` over a mesh
of the first ``chips`` devices, and judged per shard. Earlier lines of
standard output say what was generated and measured; the last line is one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and ``checks`` (with ``--trace 1`` also ``breakdown``). The
numbers compared are also the last lines of standard error. A traced run
leaves its reduced trace in ``bench/out/last_trace.json``
(``harness/tracing.py`` describes the format); the compile cache lives in
``bench/out/jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set.

Exit codes: 0 after a result line; 2 when ``BENCHMARK.json``, a file it
names, or the program is missing, or a configuration does not shard as its
cell asks; 3 when JAX finds no TPU or fewer chips than the cell asks for.
Neither of the last two prints a result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv=None) -> argparse.Namespace:
    """The command line."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window and report per-layer "
                         "metrics")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    """Run one cell once; -> the exit code."""
    args = parse(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import spec

    try:
        cell = spec.load_cell(ROOT, args.workload, BENCH)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is missing ({e})", file=sys.stderr)
        return 2
    from harness import runner

    try:
        result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                            t_process=T_PROCESS,
                            out_dir=os.path.join(BENCH, "out"))
    except runner.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
