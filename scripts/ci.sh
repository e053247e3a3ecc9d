#!/usr/bin/env bash
# Tier-1 verification in one command (what the roadmap calls "tier-1
# verify"): tests, doctests, link check, standalone reruns, the forced
# 4-device sharding suite and the hazard-fitting smoke.  The engine's
# speed is measured on the chip (bench/, BENCHMARK.json), not here.
#
#   scripts/ci.sh
#
# Environment knobs (the hosted workflow sets these):
#   CI_ARTIFACTS_DIR  if set, write pytest junit XML there for upload as
#                     a CI artifact
set -euo pipefail
cd "$(dirname "$0")/.." || exit 1

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# fail fast with a readable message when the pinned toolchain is broken
# (otherwise a bad jax install surfaces as a wall of pytest collection
# errors with the real cause buried)
if ! python - <<'EOF'
import sys
try:
    import jax, jaxlib, numpy, scipy  # noqa: F401
except Exception as exc:  # pragma: no cover - the readable-failure path
    print(f"TOOLCHAIN BROKEN: cannot import the pinned stack: {exc!r}",
          file=sys.stderr)
    sys.exit(1)
print(f"toolchain: python {sys.version.split()[0]}, jax {jax.__version__}, "
      f"jaxlib {jaxlib.__version__}, numpy {numpy.__version__}, "
      f"scipy {scipy.__version__}")
EOF
then
    echo "scripts/ci.sh: aborting before pytest — fix the environment" \
         "(see the import error above; the pins live in" \
         ".github/workflows/ci.yml)" >&2
    exit 1
fi

JUNIT_ARGS=()
if [[ -n "${CI_ARTIFACTS_DIR:-}" ]]; then
    mkdir -p "${CI_ARTIFACTS_DIR}"
    JUNIT_ARGS=(--junitxml="${CI_ARTIFACTS_DIR}/junit.xml")
fi

# -p no:randomly pins collection order if pytest-randomly is ever
# installed, so the tier-1 pass is reproducible run to run.
# (the ${arr[@]+...} guard keeps the empty-array expansion legal under
# `set -u` on bash <= 4.3 — stock macOS bash 3.2 included)
python -m pytest -x -q -p no:randomly ${JUNIT_ARGS[@]+"${JUNIT_ARGS[@]}"}

# public-API doctests: the runnable examples in the core docstrings
# (Params, HistogramSpec, run_replications_batch, the sweep classes,
# vectorized.supports) are executable documentation — they fail here
# the moment the API drifts from what docs/ promises
python -m pytest -q -p no:randomly -p no:cacheprovider --doctest-modules \
    src/repro/core/params.py src/repro/core/histograms.py \
    src/repro/core/backend.py src/repro/core/sweeps.py \
    src/repro/core/vectorized.py src/repro/core/hazards.py \
    src/repro/core/faultdomains.py src/repro/core/empirical.py \
    src/repro/core/replicas.py src/repro/kernels/ops.py

# docs suite link check: every relative markdown link in README/docs
# must resolve to a real file (no network; scheme links are skipped)
python scripts/check_links.py

# ordering-independence check (--lf-safe): the distribution/bucketing/
# non-exponential/multi-job/checkpoint suites must pass rerun standalone
# with a cold pytest cache — exactly what a `pytest --lf` retry after a
# failure would run
python -m pytest -q -p no:randomly -p no:cacheprovider \
    tests/test_histograms.py tests/test_bucketing.py tests/test_nonexp.py \
    tests/test_repair_dist.py tests/test_faultdomains.py \
    tests/test_multijob_parity.py tests/test_empirical.py \
    tests/test_checkpoint_opt.py

# replica-sharding parity on a forced 4-device CPU mesh: the per-shard
# independence contract, exact histogram/ring-buffer merges, and the
# sharded compile invariant all need >= 4 visible devices, which must
# be forced via XLA_FLAGS *before* jax imports — hence a fresh
# interpreter rather than a pytest lane of the tier-1 run above
XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python -m pytest -q -p no:randomly -p no:cacheprovider \
    tests/test_replica_sharding.py

# trace-driven fitting smoke: synthetic log -> fit_piecewise_hazard ->
# JSON round trip -> a short CTMC study from the fitted hazard
python scripts/fit_hazard.py --selftest
