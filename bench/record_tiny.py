"""Record a small traced study of a cell for the trace-reduction tests.

    python3 bench/record_tiny.py --workload repair_grid --replicas 256 \
        --max-steps 128 --seed 1 --out bench/testdata/repair_tiny

Needs a TPU.  Runs the cell's study once at ``--replicas`` replicas per
point and a ``--max-steps`` budget to compile it, then once more under
the profiler inside the benchmark's study span, and writes the trace as
``<out>.xplane.pb.gz`` (without Python function events) and, as
``<out>.json``, what every per-layer reader of the manifest and every
read of the program's scopes and counters (``bench.spantrace.READS``)
reads from it.
"""

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--replicas", type=int, required=True)
    ap.add_argument("--max-steps", type=int, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness, spantrace, tracereduce

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    from repro import compile_cache

    compile_cache.enable()
    man = harness.manifest()
    cell = harness.find_cell(man, args.workload)
    study = harness.Study(harness.load_config(cell["config"]),
                          dict(harness.load_traffic(cell["traffic"]),
                               replicas=args.replicas))
    warnings.simplefilter("ignore", RuntimeWarning)   # a short budget
    study(args.seed, max_steps=args.max_steps)
    tmp = tempfile.mkdtemp(prefix="bench_record_")
    try:
        # Python function events would make the file several times
        # larger, and no reader uses them
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=options)
        with jax.profiler.TraceAnnotation(harness.STUDY_SPAN):
            study(args.seed, max_steps=args.max_steps)
        jax.profiler.stop_trace()
        raw = tracereduce.find_xplane(tmp).read_bytes()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    out = Path(args.out)
    trace = out.with_name(out.name + ".xplane.pb.gz")
    trace.write_bytes(gzip.compress(raw, 9))
    sv = spantrace.load(trace, harness.STUDY_SPAN, cell["chips"])
    view = sv.view
    read = {}
    for m in man["per_layer"]:
        v = harness.load_module("metrics", m["name"]).read(view)
        if v is not None:
            read[m["name"]] = v
    for name, fn in spantrace.READS.items():
        v = fn(sv)
        if v is not None:
            read[name] = v
    prog = view.chips[0].program()
    record = {
        "recorded_on": devices[0].device_kind,
        "study": f"{args.workload} at {args.replicas} replicas per point, "
                 f"max_steps {args.max_steps}, seed {args.seed}, traced "
                 f"inside the {harness.STUDY_SPAN} span",
        "steps": args.max_steps,
        "span_s": view.span_s,
        "program": prog[0].split("(", 1)[0] if prog else None,
        "trace_bytes": trace.stat().st_size,
        "read": read,
    }
    out.with_name(out.name + ".json").write_text(
        json.dumps(record, indent=1) + "\n")
    print(json.dumps(record))
    return 0


if __name__ == "__main__":
    sys.exit(main())
