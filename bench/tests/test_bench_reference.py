"""The benchmark's plain reference against the repository's event engine.

Two independent implementations of the same model at a small size on
the CPU: every compared output's mean agrees within |z| < 4.  The event
engine draws replacements last-in first-out where the reference draws
them at random; on a cluster of identical servers that changes nothing
the comparison reads.
"""

import numpy as np
import pytest

from bench import compare, harness
from bench.references import cluster_des as ref

SMALL = dict(job_size=32, working_pool_size=36, spare_pool_size=4,
             warm_standbys=2, job_length=20 * 1440.0,
             random_failure_rate=0.2 / 1440, systematic_failure_rate=1.0 / 1440,
             auto_repair_time=240.0, manual_repair_time=1440.0)


def _event_arrays(params, n, seed):
    """The event engine's RunResults in the per-replica array layout."""
    from repro.core import Params
    from repro.core.simulation import simulate

    spec = params["histogram"]
    edges = ref.edges(spec)
    results = simulate(Params.from_dict(dict(params)), n, base_seed=seed)
    ring = params["max_run_records"]
    out = {k: np.array([float(getattr(r, k)) for r in results])
           for k in compare.SCALARS}
    out["completed"] = np.ones(n)
    out["n_runs"] = np.array([len(r.run_durations) for r in results])
    out["run_durations"] = np.zeros((n, ring))
    for i, r in enumerate(results):
        for j, v in enumerate(r.run_durations):
            out["run_durations"][i, j % ring] = v
    sources = {"run_duration": "run_durations",
               "recovery": "recovery_durations",
               "waiting": "waiting_durations"}
    for ch, src in sources.items():
        out[f"hist_{ch}"] = np.stack([
            np.bincount(np.searchsorted(edges, getattr(r, src),
                                        side="right"),
                        minlength=spec["n_bins"] + 2)
            for r in results]).astype(float)
    return out


@pytest.mark.parametrize("config", ["table1_exp", "table1_lognormal_repair"])
def test_reference_agrees_with_event_engine(config):
    params = dict(harness.load_config(config)["params"], **SMALL)
    edges = np.asarray(ref.edges(params["histogram"]))
    mine = compare.features(ref.simulate_point(params, 300, seed=5), edges)
    theirs = compare.features(_event_arrays(params, 300, seed=9), edges)
    zs = compare.z_scores(mine, theirs)
    assert max(abs(z) for z in zs.values()) < 4.0, zs


def test_reference_is_a_function_of_its_seed():
    params = dict(harness.load_config("table1_exp")["params"], **SMALL)
    a, b = (ref.simulate_point(params, 3, seed=7) for _ in range(2))
    c = ref.simulate_point(params, 3, seed=8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["total_time"], c["total_time"])


@pytest.mark.parametrize("key,value", [
    ("checkpoint_interval", 60.0), ("failure_distribution", "weibull"),
    ("repair_servers", 4), ("unknown_knob", 1)])
def test_reference_refuses_what_it_does_not_model(key, value):
    params = dict(harness.load_config("table1_exp")["params"], **{key: value})
    with pytest.raises(ValueError):
        ref.check_supported(params)
