"""Exact run-duration tracking on the CTMC path (no approximation left).

The vectorized engine records failure-to-failure useful-compute intervals
in a fixed ring buffer per replica (``run_durations`` (R, max_runs) +
``n_runs`` + ``cur_run``).  These tests pin the invariants:

  * recorded intervals sum to the total useful time accrued;
  * ``run_duration_pooled`` matches the event engine's per-run records
    within 2 pooled standard errors (the former total_time/(n_failures+1)
    approximation fails this by construction);
  * the ``max_runs`` cap surfaces a truncation stat instead of silently
    dropping runs, and per-replica means stay exact under truncation.
"""

import numpy as np
import pytest

from repro.core import MINUTES_PER_DAY as DAY
from repro.core import Params, run_replications, simulate
from repro.core.metrics import aggregate, aggregate_arrays
from repro.core.vectorized import simulate_ctmc

BASE = Params(job_size=24, working_pool_size=32, spare_pool_size=4,
              warm_standbys=2, job_length=1 * DAY,
              random_failure_rate=2.0 / DAY, recovery_time=5.0,
              auto_repair_time=30.0, manual_repair_time=120.0, seed=5)


def _valid_mask(buf: np.ndarray, n_runs: np.ndarray) -> np.ndarray:
    max_runs = buf.shape[1]
    return np.arange(max_runs)[None, :] < np.minimum(n_runs, max_runs)[:, None]


# ---------------------------------------------------------------------------
# interval bookkeeping invariants
# ---------------------------------------------------------------------------

def test_intervals_sum_to_total_useful_time():
    out = simulate_ctmc(BASE, n_replicas=64, seed=2)
    buf, n_runs, cur = out["run_durations"], out["n_runs"], out["cur_run"]
    assert (n_runs <= buf.shape[1]).all(), "grid sized to avoid truncation"
    sums = (buf * _valid_mask(buf, n_runs)).sum(axis=1)
    # every recorded interval is useful compute; the in-flight interval
    # (cur_run) is the only part not yet recorded
    np.testing.assert_allclose(sums + cur, out["useful_work"],
                               rtol=1e-4, atol=1.0)
    done = out["completed"] > 0
    assert done.any()
    np.testing.assert_allclose(sums[done], BASE.job_length, rtol=1e-4)
    assert (cur[done] == 0.0).all()


def test_run_count_is_failures_plus_completion():
    out = simulate_ctmc(BASE, n_replicas=64, seed=9)
    expected = out["n_failures"].astype(np.int64) \
        + (out["completed"] > 0).astype(np.int64)
    np.testing.assert_array_equal(out["n_runs"].astype(np.int64), expected)
    assert (out["run_durations"] >= 0.0).all()


# ---------------------------------------------------------------------------
# agreement with the event engine's per-run records
# ---------------------------------------------------------------------------

def test_run_duration_pooled_matches_event_engine():
    """Acceptance: CTMC run_duration_pooled within 2 pooled SEs of the
    event engine's exact per-run records on the seed comparison grid."""
    rep_c = run_replications(BASE, 512, engine="ctmc")
    results_e = simulate(BASE, 64)
    stats_e = aggregate(results_e)

    sc = rep_c.stats["run_duration_pooled"]
    se_ = stats_e["run_duration_pooled"]
    n_c = int(np.minimum(rep_c.arrays["n_runs"],
                         rep_c.arrays["run_durations"].shape[1]).sum())
    n_e = sum(len(r.run_durations) for r in results_e)
    assert n_c > 1000 and n_e > 100
    pooled_se = np.sqrt(sc.std ** 2 / n_c + se_.std ** 2 / n_e)
    z = (sc.mean - se_.mean) / max(pooled_se, 1e-9)
    assert abs(z) < 2.0, (sc.mean, se_.mean, z)
    # the distribution shape must agree too, not just the mean
    assert sc.percentiles[50] == pytest.approx(se_.percentiles[50], rel=0.25)


def test_mean_run_duration_is_not_the_old_approximation():
    """total_time/(n_failures+1) counts recovery/stall wall-clock inside
    the intervals; the exact records must exclude it."""
    rep = run_replications(BASE, 256, engine="ctmc")
    approx = rep.arrays["total_time"] / (rep.arrays["n_failures"] + 1.0)
    exact = rep.stats["mean_run_duration"].mean
    # overheads are ~5 min recovery per ~26 min run: the approximation
    # must be biased visibly high
    assert approx.mean() > exact * 1.05


# ---------------------------------------------------------------------------
# truncation behavior
# ---------------------------------------------------------------------------

def test_max_runs_cap_surfaces_truncation_stat():
    out = simulate_ctmc(BASE, n_replicas=16, seed=3, max_runs=4)
    assert out["run_durations"].shape == (16, 4)
    stats = aggregate_arrays(out)
    assert stats["run_duration_truncated"].mean > 0.0
    # n_runs keeps counting past the cap
    assert (out["n_runs"] > 4).any()


def test_mean_run_duration_exact_under_truncation():
    """The ring buffer overwrites old records, but the per-replica mean
    comes from the sum identity and must not move."""
    full = aggregate_arrays(simulate_ctmc(BASE, n_replicas=32, seed=4))
    trunc = aggregate_arrays(simulate_ctmc(BASE, n_replicas=32, seed=4,
                                           max_runs=4))
    assert trunc["mean_run_duration"].mean == pytest.approx(
        full["mean_run_duration"].mean, rel=1e-6)
    # pooled stats survive on the retained records (a tail sample of the
    # same stationary interval distribution)
    assert trunc["run_duration_pooled"].mean == pytest.approx(
        full["run_duration_pooled"].mean, rel=0.2)


def test_max_runs_zero_compiles_recording_out():
    """max_runs=0 drops the ring buffer from the scan (perf opt-out) but
    the exact mean survives via the n_runs/cur_run sum identity."""
    off = simulate_ctmc(BASE, n_replicas=16, seed=3, max_runs=0)
    assert off["run_durations"].shape == (16, 0)
    on = simulate_ctmc(BASE, n_replicas=16, seed=3)
    # recording never affects the trajectory itself
    np.testing.assert_array_equal(off["n_failures"], on["n_failures"])
    s_off, s_on = aggregate_arrays(off), aggregate_arrays(on)
    assert s_off["mean_run_duration"].mean == pytest.approx(
        s_on["mean_run_duration"].mean, rel=1e-6)
    # pooled stats degrade to pooling per-replica means, not NaN
    assert np.isfinite(s_off["run_duration_pooled"].mean)


def test_event_engine_reports_zero_truncation():
    stats = aggregate(simulate(BASE, 4))
    assert stats["run_duration_truncated"].mean == 0.0


def test_stat_of_empty_sequence_is_nan_filled_not_raising():
    """Empty inputs (empty sweeps, zero recorded runs) must produce a
    well-formed NaN Stat instead of raising from np.percentile, and the
    downstream CI helper must stay finite."""
    from repro.core.metrics import Stat, _PERCENTILES

    s = Stat.of([])
    for v in (s.mean, s.median, s.std, s.minimum, s.maximum):
        assert np.isnan(v)
    assert set(s.percentiles) == set(_PERCENTILES)
    assert all(np.isnan(v) for v in s.percentiles.values())
    assert s.ci95_halfwidth(0) == 0.0
    assert s.ci95_halfwidth(10) == 0.0   # NaN std -> 0, not NaN
    # singletons: well-defined with zero spread
    one = Stat.of([5.0])
    assert one.mean == 5.0 and one.std == 0.0 and one.ci95_halfwidth(1) == 0.0


def test_stat_from_empty_histogram_is_nan_filled():
    from repro.core.histograms import Histogram, HistogramSpec
    from repro.core.metrics import Stat

    s = Stat.from_histogram(Histogram(HistogramSpec()))
    assert np.isnan(s.mean) and np.isnan(s.percentiles[99.9])
    assert s.ci95_halfwidth(4) == 0.0


def test_aggregate_of_zero_replications_is_nan_not_error():
    stats = aggregate([])
    assert np.isnan(stats["total_time"].mean)
    assert np.isnan(stats["run_duration_pooled"].percentiles[99])


def test_fallback_approximation_for_foreign_arrays():
    """Arrays without run records (foreign producers) still aggregate,
    via the documented legacy approximation."""
    arrays = {"total_time": np.asarray([100.0, 200.0]),
              "useful_work": np.asarray([90.0, 150.0]),
              "n_failures": np.asarray([1.0, 3.0])}
    stats = aggregate_arrays(arrays)
    assert stats["mean_run_duration"].mean == pytest.approx(
        (100.0 / 2 + 200.0 / 4) / 2)
    assert stats["run_duration_truncated"].mean == 0.0


# ---------------------------------------------------------------------------
# identity with the straightforward order statistics
# ---------------------------------------------------------------------------
# Stat.of reads the median and every percentile from one sort, and
# aggregate_arrays pools a full ring without a gather.  The references
# below are the plain forms they replace, kept verbatim: every field
# must come out equal (NaN to NaN).

def _reference_stat(xs):
    from repro.core.metrics import Stat, _PERCENTILES

    a = np.asarray(list(xs), dtype=np.float64)
    if a.size == 0:
        nan = float("nan")
        return Stat(nan, nan, nan, nan, nan, {p: nan for p in _PERCENTILES})
    return Stat(
        mean=float(a.mean()),
        median=float(np.median(a)),
        std=float(a.std(ddof=1)) if a.size > 1 else 0.0,
        minimum=float(a.min()),
        maximum=float(a.max()),
        percentiles={p: float(np.percentile(a, p)) for p in _PERCENTILES},
    )


def _reference_percentiles_per_row(edges, counts_2d, q):
    counts = np.asarray(counts_2d, np.float64)
    total = counts.sum(axis=1)
    cum = np.cumsum(counts, axis=1)
    target = q / 100.0 * total
    i = np.sum(cum < target[:, None], axis=1)
    i = np.minimum(i, counts.shape[1] - 1)
    lo_edges = np.concatenate([[0.0], edges])
    hi_edges = np.concatenate([edges, [edges[-1]]])
    below = np.where(i > 0,
                     np.take_along_axis(cum, np.maximum(i - 1, 0)[:, None],
                                        axis=1)[:, 0], 0.0)
    in_bin = np.take_along_axis(counts, i[:, None], axis=1)[:, 0]
    frac = np.clip((target - below) / np.maximum(in_bin, 1e-30), 0.0, 1.0)
    val = lo_edges[i] + frac * (hi_edges[i] - lo_edges[i])
    val = np.where(i == counts.shape[1] - 1, edges[-1], val)
    return np.where(total > 0, val, np.nan)


def _reference_aggregate_arrays(arrays):
    from repro.core.metrics import (_SCALAR_METRICS, REPLICA_TAIL_PERCENTILE,
                                    Stat, histograms_from_arrays)
    from repro.core.histograms import HIST_CHANNELS

    R = len(next(iter(arrays.values())))
    zeros = np.zeros(R, dtype=np.float64)
    total_time = np.asarray(arrays["total_time"], np.float64)
    safe_total = np.maximum(total_time, 1e-12)
    useful = np.asarray(arrays["useful_work"], np.float64)
    derived = {
        "overhead_fraction": np.where(total_time > 0,
                                      1.0 - useful / safe_total, 0.0),
        "goodput": np.where(total_time > 0, useful / safe_total, 0.0),
    }
    if "completed" in arrays:
        derived["n_incomplete"] = 1.0 - np.asarray(arrays["completed"],
                                                   np.float64)
    if "run_durations" in arrays and "n_runs" in arrays:
        buf = np.asarray(arrays["run_durations"], np.float64)
        n_runs = np.asarray(arrays["n_runs"], np.int64)
        max_runs = buf.shape[1]
        n_valid = np.minimum(n_runs, max_runs)
        valid = np.arange(max_runs)[None, :] < n_valid[:, None]
        recorded_total = (
            useful + np.asarray(arrays.get("lost_work", zeros), np.float64)
            - np.asarray(arrays.get("cur_run", zeros), np.float64))
        derived["mean_run_duration"] = np.where(
            n_runs > 0, recorded_total / np.maximum(n_runs, 1), 0.0)
        pooled = buf[valid] if max_runs else derived["mean_run_duration"]
        truncated = (n_runs - n_valid).astype(np.float64)
    else:
        derived["mean_run_duration"] = total_time / (
            np.asarray(arrays["n_failures"], np.float64) + 1.0)
        pooled = derived["mean_run_duration"]
        truncated = zeros
    out = {}
    for name in _SCALAR_METRICS:
        if name in arrays:
            xs = np.asarray(arrays[name], np.float64)
        else:
            xs = derived.get(name, zeros)
        out[name] = _reference_stat(xs)
    if "completed" in arrays:
        out["completed"] = _reference_stat(
            np.asarray(arrays["completed"], np.float64))
    out["run_duration_pooled"] = _reference_stat(pooled)
    out["run_duration_truncated"] = _reference_stat(truncated)
    for ch, h in histograms_from_arrays(arrays).items():
        out[f"{ch}_dist"] = Stat.from_histogram(h)
    if "hist_edges" in arrays:
        edges = np.asarray(arrays["hist_edges"], np.float64)
        for ch in HIST_CHANNELS:
            if f"hist_{ch}" in arrays:
                per = _reference_percentiles_per_row(
                    edges, arrays[f"hist_{ch}"], REPLICA_TAIL_PERCENTILE)
                out[f"{ch}_p{REPLICA_TAIL_PERCENTILE}_replica"] = (
                    _reference_stat(per[np.isfinite(per)]))
    return out


def _assert_same_stat(got, want):
    def same(a, b):
        return a == b or (np.isnan(a) and np.isnan(b))

    for f in ("mean", "median", "std", "minimum", "maximum"):
        assert same(getattr(got, f), getattr(want, f)), f
    assert got.percentiles.keys() == want.percentiles.keys()
    for p in want.percentiles:
        assert same(got.percentiles[p], want.percentiles[p]), p


def _rng():
    return np.random.default_rng(17)


_INF = float("inf")
_NAN = float("nan")
#: name -> a function making the same input afresh (a generator is
#: spent once)
_STAT_INPUTS = {
    "empty_array": lambda: np.zeros(0),
    "empty_list": lambda: [],
    "size1": lambda: np.array([3.25]),
    "size2": lambda: np.array([7.0, -2.5]),
    "size3": lambda: np.array([0.1, 0.7, 0.3]),
    "size4": lambda: np.array([4.0, 1.0, 3.0, 2.0]),
    "ring_2097152_float32": lambda: _rng().exponential(
        400.0, 2_097_152).astype(np.float32),
    "odd_1001": lambda: _rng().normal(size=1001),
    "even_1000": lambda: _rng().normal(size=1000),
    "all_equal": lambda: np.full(33, 2.5),
    "heavy_ties": lambda: _rng().integers(0, 3, 1000).astype(np.float64),
    "inf_only": lambda: np.array([_INF]),
    "inf_both_ends": lambda: np.array([-_INF, 1.0, 2.0, _INF]),
    "inf_tail": lambda: np.array([1.0, 5.0, _INF, _INF, 2.0]),
    "neg_inf": lambda: np.array([-_INF, -_INF, 0.0, 5.0, 1.0]),
    "nan": lambda: np.array([1.0, _NAN, 3.0]),
    "nan_only": lambda: np.array([_NAN]),
    "nan_and_inf": lambda: np.array([_INF, 2.0, _NAN, -_INF]),
    "int32": lambda: _rng().integers(-50, 5000, 4099).astype(np.int32),
    "bool": lambda: _rng().random(777) < 0.3,
    "float32": lambda: _rng().lognormal(3.0, 1.2, 5000).astype(np.float32),
    "float64": lambda: _rng().lognormal(3.0, 1.2, 5000),
    "list": lambda: [3.0, 1.5, 9.0, 2.0, 2.0, 8.5],
    "generator": lambda: (float(i % 7) for i in range(50)),
}


@pytest.mark.filterwarnings("ignore:invalid value:RuntimeWarning")
@pytest.mark.parametrize("name", list(_STAT_INPUTS))
def test_stat_of_matches_plain_order_statistics(name):
    from repro.core.metrics import Stat

    make = _STAT_INPUTS[name]
    _assert_same_stat(Stat.of(make()), _reference_stat(make()))


def _hand_built_arrays(kind, R=9, max_runs=5, seed=3):
    """Arrays shaped as the CTMC engine returns them: float32 and int32
    lanes, a float32 run ring, and (R, n_bins + 2) float64 histogram
    channels that are views into one (R, channels, slots) block."""
    from repro.core.histograms import HistogramSpec

    rng = np.random.default_rng(seed)
    useful = rng.uniform(500.0, 900.0, R).astype(np.float32)
    arrays = {
        "total_time": (useful + rng.uniform(0.0, 300.0, R)).astype(
            np.float32),
        "useful_work": useful,
        "lost_work": rng.uniform(0.0, 50.0, R).astype(np.float32),
        "n_failures": rng.integers(0, 12, R).astype(np.int32),
        "n_random_failures": rng.integers(0, 6, R).astype(np.int32),
        "stall_time": rng.exponential(3.0, R).astype(np.float32),
        "completed": (rng.random(R) < 0.8).astype(np.float32),
    }
    if kind == "legacy":
        return arrays
    n_runs = {"full_ring": rng.integers(max_runs, 3 * max_runs, R),
              "partial_ring": rng.integers(0, 2 * max_runs, R),
              "no_ring": rng.integers(0, 9, R)}[kind]
    if kind == "partial_ring":
        n_runs[:2] = (0, max_runs - 1)      # an empty and a short ring
    width = 0 if kind == "no_ring" else max_runs
    arrays.update(
        n_runs=n_runs.astype(np.int32),
        cur_run=rng.uniform(0.0, 20.0, R).astype(np.float32),
        run_durations=rng.exponential(60.0, (R, width)).astype(np.float32))
    spec = HistogramSpec(n_bins=6, channels=("run_duration", "recovery"))
    block = rng.integers(0, 4, (R, 2, spec.n_bins + 2)).astype(np.float64)
    block[0, 1] = 0.0                       # a replica with no records
    block[1:4, 0, :] = [0, 0, 5, 0, 0, 0, 0, 0]   # ties across replicas
    arrays["hist_edges"] = np.asarray(spec.edges(), np.float64)
    arrays["hist_run_duration"] = block[:, 0]
    arrays["hist_recovery"] = block[:, 1]
    return arrays


@pytest.mark.parametrize("kind", ["full_ring", "partial_ring", "no_ring",
                                  "legacy"])
def test_aggregate_arrays_matches_plain_aggregation(kind):
    arrays = _hand_built_arrays(kind)
    got = aggregate_arrays(arrays)
    want = _reference_aggregate_arrays(arrays)
    assert got.keys() == want.keys()
    for name in want:
        _assert_same_stat(got[name], want[name])
    if kind == "full_ring":
        assert (arrays["n_runs"] >= arrays["run_durations"].shape[1]).all()
    if kind == "partial_ring":
        assert got["run_duration_truncated"].minimum == 0.0
