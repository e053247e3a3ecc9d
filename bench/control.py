"""The control of the comparison: the plain reference in the program's
place, computed in bfloat16, the precision below the configuration's
float32.  It has to come out not correct.

    python3 bench/control.py --workload <name> --seeds 1 2 3 --replicas 16

runs the harness with the control in place of the simulator's study, on
the machine it is started on (the reference is host code), and prints
the compared numbers of each seed.  ``--replicas`` sets the control's
replicas per point: rounding every stored value through bfloat16 in
Python makes a replica of the control far slower than the simulator's.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]


def bf16(x: float) -> float:
    import ml_dtypes
    import numpy as np

    return float(np.asarray(x, ml_dtypes.bfloat16))


class Reps:
    """What the comparison reads of a Replications: the per-replica
    arrays, and the stats and pooled histograms worked out from them in
    bfloat16."""

    def __init__(self, arrays, edges):
        import numpy as np

        from bench import compare

        self.arrays = arrays
        self.stats, self.histograms = {}, {}
        for k in compare.SCALARS:
            x = np.asarray(arrays[k], np.float64)
            self.stats[k] = SimpleNamespace(
                mean=bf16(x.mean()),
                percentiles={p: bf16(np.percentile(x, p))
                             for p in compare.PCTS})
        for ch in compare.CHANNELS:
            if f"hist_{ch}" not in arrays:
                continue
            counts = np.array([bf16(c) for c in
                               arrays[f"hist_{ch}"].sum(0)])
            self.histograms[ch] = SimpleNamespace(edges=edges,
                                                  counts=counts)
            self.stats[f"{ch}_dist"] = SimpleNamespace(
                mean=bf16(counts @ compare.midpoints(edges) / counts.sum()),
                percentiles={p: bf16(compare.hist_percentile(edges, counts,
                                                             p))
                             for p in compare.PCTS})


def control_study(replicas=None):
    """A ``make_study`` for the harness: the reference in bfloat16."""
    from bench import harness

    class ControlStudy(harness.Study):
        def __init__(self, config, traffic):
            if replicas:
                traffic = dict(traffic, replicas=replicas)
            self.ref = harness.load_module("references",
                                           config["reference"])
            self.points = harness.grid_points(config, traffic)
            self.replicas = int(traffic["replicas"])

        def __call__(self, seed, max_steps=None):
            if max_steps is not None:          # the warm-up: nothing to warm
                return []
            return [Reps(self.ref.simulate_point(p, self.replicas, seed,
                                                 rnd=bf16),
                         self.ref.edges(p["histogram"]))
                    for p in self.points]

        def warm_steps(self):
            return 1

        @property
        def trajectories(self):
            return len(self.points) * self.replicas

    return ControlStudy


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--replicas", type=int, default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    for seed in args.seeds:
        t0 = time.perf_counter()
        r = harness.run_cell(args.workload, seed, 1e-6, False, t0,
                             require_chip=False, log=lambda m: None,
                             make_study=control_study(args.replicas))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "replicas": args.replicas,
                          "correct": r["correct"], "checks": r["checks"],
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
