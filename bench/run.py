"""Chip benchmark of AIReSim's compiled CTMC engine: one cell, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs a TPU with as many chips
as the cell asks for; anywhere else it exits non-zero and prints no
result.  The last line of standard output is the result object, and the
last lines of standard error give each compared number beside its limit.
See bench/harness.py for what a run does and PERF.md for the metrics.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T_START,
                                  log=lambda m: print(m, flush=True))
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
