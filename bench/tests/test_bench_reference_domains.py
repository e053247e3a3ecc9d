"""The plain reference with rack and pod fault domains
(``bench/references/cluster_des_domains.py``) against the repository's
event engine, at a small size with many domains on the CPU: every
compared output's mean, and the shock counts, agree within |z| < 4.
Without domains it agrees with ``cluster_des``, the reference of the
Table I cells.
"""

import numpy as np
import pytest

from bench import compare, harness
from bench.references import cluster_des
from bench.references import cluster_des_domains as ref

#: a 96-server fleet (88 working, 8 spare) in 24 racks of 4, 6 racks a
#: pod, shocking often: about 14 rack and 1 pod shock a replica
SMALL = dict(job_size=80, working_pool_size=88, spare_pool_size=8,
             warm_standbys=2, job_length=20 * 1440.0,
             random_failure_rate=0.08 / 1440,
             systematic_failure_rate=0.4 / 1440,
             auto_repair_time=240.0, manual_repair_time=1440.0,
             fault_domains={"n_racks": 24, "racks_per_pod": 6,
                            "rack_shock_rate": 2e-5,
                            "pod_shock_rate": 1e-5})
#: shock counts compared beside compare.SCALARS
SHOCKS = ("n_domain_shocks", "n_shock_killed")


def _event_arrays(params, n, seed):
    """The event engine's RunResults in the per-replica array layout."""
    from repro.core import Params
    from repro.core.simulation import simulate

    spec = params["histogram"]
    edges = ref.edges(spec)
    results = simulate(Params.from_dict(dict(params)), n, base_seed=seed)
    ring = params["max_run_records"]
    out = {k: np.array([float(getattr(r, k)) for r in results])
           for k in compare.SCALARS + SHOCKS}
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


def _features(arrays, edges):
    out = compare.features(arrays, edges)
    out.update({k: np.asarray(arrays[k], np.float64) for k in SHOCKS})
    return out


def _params(**changes):
    return dict(harness.load_config("table1_exp")["params"],
                **dict(SMALL, **changes))


def test_reference_agrees_with_event_engine():
    params = _params()
    edges = np.asarray(ref.edges(params["histogram"]))
    mine = ref.simulate_point(params, 300, seed=5)
    theirs = _event_arrays(params, 300, seed=9)
    assert mine["completed"].mean() == 1.0
    assert mine["n_domain_shocks"].mean() > 10
    zs = compare.z_scores(_features(mine, edges), _features(theirs, edges))
    assert max(abs(z) for z in zs.values()) < 4.0, zs


def test_reference_without_domains_agrees_with_cluster_des():
    params = _params(fault_domains=None)
    edges = np.asarray(ref.edges(params["histogram"]))
    mine = compare.features(ref.simulate_point(params, 300, seed=5), edges)
    base = compare.features(cluster_des.simulate_point(params, 300, seed=6),
                            edges)
    zs = compare.z_scores(mine, base)
    assert max(abs(z) for z in zs.values()) < 4.0, zs


def test_reference_is_a_function_of_its_seed():
    params = _params()
    a, b = (ref.simulate_point(params, 3, seed=7) for _ in range(2))
    c = ref.simulate_point(params, 3, seed=8)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["total_time"], c["total_time"])


@pytest.mark.parametrize("key,value", [
    ("checkpoint_interval", 60.0), ("failure_distribution", "weibull"),
    ("repair_servers", 4), ("unknown_knob", 1),
    ("campaign", {"events": [{"time": 10.0, "kind": "kill", "domain": 0}]}),
    ("fault_domains", {"n_racks": 4, "zones": 2}),
    ("fault_domains", {"n_racks": 4, "pod_shock_rate": 1e-5}),
    ("fault_domains", {"n_racks": 1000})])
def test_reference_refuses_what_it_does_not_model(key, value):
    params = _params(**{key: value})
    with pytest.raises(ValueError):
        ref.check_supported(params)


def test_rounded_path_runs():
    """The control's path: every stored real and counter rounded to
    bfloat16."""
    from bench import control

    params = _params()
    plain = ref.simulate_point(params, 4, seed=3)
    rounded = ref.simulate_point(params, 4, seed=3, rnd=control.bf16)
    assert rounded.keys() == plain.keys()
    assert all(np.all(np.isfinite(v)) for v in rounded.values())
    assert not np.array_equal(rounded["total_time"], plain["total_time"])
