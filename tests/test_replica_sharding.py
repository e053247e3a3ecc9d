"""Replica-axis sharding: exactness contract, merges, loud refusals.

Two tiers:

* single-device tests (always run): a 1-device mesh must be
  BIT-IDENTICAL to the unsharded engine — same programs, same streams —
  plus the seed-splitting units and every refusal path;
* multi-device tests (``skipif jax.device_count() < N``): run under
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (scripts/ci.sh
  runs them in a forced-4-device subprocess).  These pin the per-shard
  independence contract *exactly*: shard ``s`` of a sharded run equals
  an independent unsharded run over ``R/n`` replicas with the folded key
  ``shard_keys(key, n)[s]`` — across every output lane, including the
  histogram accumulators and the run-duration ring buffers, so the
  ``out_specs`` concatenation merge is exact, not just exact-in-law.

See docs/scaling.md for the contract these tests enforce.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.vectorized as vz
import repro.core.vectorized_multijob as mj
from repro.core import replicas
from repro.core.multijob import JobSpec
from repro.core.params import Params

N_DEV = jax.device_count()

needs4 = pytest.mark.skipif(
    N_DEV < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4")


def small_params(**kw):
    base = dict(working_pool_size=32, spare_pool_size=4, job_size=16,
                job_length=500.0)
    base.update(kw)
    return Params(**base)


def assert_same(a, b, path=""):
    if isinstance(a, dict):
        assert a.keys() == b.keys(), path
        for k in a:
            assert_same(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, list):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            assert_same(x, y, f"{path}[{i}]")
    else:
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


# ---------------------------------------------------------------------------
# seed splitting units
# ---------------------------------------------------------------------------

def test_shard_keys_mesh1_is_base_key():
    key = jax.random.PRNGKey(3)
    keys = replicas.shard_keys(key, 1)
    assert keys.shape == (1,) + key.shape
    assert np.array_equal(np.asarray(keys[0]), np.asarray(key))


def test_shard_keys_are_folded_and_distinct():
    key = jax.random.PRNGKey(3)
    keys = replicas.shard_keys(key, 4)
    assert keys.shape == (4,) + key.shape
    rows = {tuple(np.asarray(k).tolist()) for k in keys}
    assert len(rows) == 4
    for s in range(4):
        expect = jax.random.fold_in(key, np.uint32(s))
        assert np.array_equal(np.asarray(keys[s]), np.asarray(expect))


def test_replica_mesh_too_many_devices_refused():
    with pytest.raises(ValueError, match="host_platform_device_count"):
        replicas.replica_mesh(10 ** 6)


# ---------------------------------------------------------------------------
# mesh-size-1 bit-identity (single device — tier-1)
# ---------------------------------------------------------------------------

def test_mesh1_simulate_ctmc_bit_identical():
    p = small_params()
    r0 = vz.simulate_ctmc(p, n_replicas=64, seed=7, max_steps=256)
    r1 = vz.simulate_ctmc(p, n_replicas=64, seed=7, max_steps=256,
                          shards=1)
    assert_same(r0, r1)


def test_mesh1_sweep_bit_identical():
    pts = [small_params(), small_params(spare_pool_size=8),
           small_params(random_failure_rate=0.001)]
    r0 = vz.simulate_ctmc_sweep(pts, n_replicas=32, seed=7, max_steps=256)
    r1 = vz.simulate_ctmc_sweep(pts, n_replicas=32, seed=7, max_steps=256,
                                shards=1)
    assert_same(r0, r1)


def test_mesh1_via_params_knob():
    p0, p1 = small_params(), small_params(engine_shards=1)
    r0 = vz.simulate_ctmc(p0, n_replicas=64, seed=7, max_steps=256)
    r1 = vz.simulate_ctmc(p1, n_replicas=64, seed=7, max_steps=256)
    assert_same(r0, r1)


def test_mesh1_multijob_bit_identical():
    cluster = Params(working_pool_size=64, spare_pool_size=8,
                     repair_servers=2)
    jobs = (JobSpec(job_size=16, job_length=400.0),
            JobSpec(job_size=24, job_length=300.0, warm_standbys=2))
    pts = [(cluster, jobs), (cluster.replace(spare_pool_size=4), jobs)]
    r0 = mj.simulate_multijob_ctmc_sweep(pts, n_replicas=16, seed=5,
                                         max_steps=256)
    r1 = mj.simulate_multijob_ctmc_sweep(pts, n_replicas=16, seed=5,
                                         max_steps=256, shards=1)
    assert_same(r0, r1)


# ---------------------------------------------------------------------------
# refusal paths (single device)
# ---------------------------------------------------------------------------

def test_non_divisible_replica_count_refused():
    with pytest.raises(ValueError, match="does not divide"):
        vz.simulate_ctmc(small_params(), n_replicas=10, seed=0,
                         max_steps=64, shards=3)


def test_missing_devices_refused():
    if N_DEV >= 8:
        pytest.skip("enough devices — refusal not reachable")
    with pytest.raises(ValueError, match="host_platform_device_count"):
        vz.simulate_ctmc(small_params(), n_replicas=64, seed=0,
                         max_steps=64, shards=8)


def test_mixed_engine_shards_grid_refused():
    pts = [small_params(engine_shards=0), small_params(engine_shards=1)]
    with pytest.raises(ValueError, match="engine_shards"):
        vz.simulate_ctmc_sweep(pts, n_replicas=32, max_steps=64)


def test_bad_knob_values_refused():
    with pytest.raises(ValueError, match="engine_shards"):
        small_params(engine_shards=-1).validate()
    with pytest.raises(ValueError, match="event_race_impl"):
        small_params(event_race_impl="cuda").validate()


# ---------------------------------------------------------------------------
# kernel dispatch through the engine (single device)
# ---------------------------------------------------------------------------

def test_engine_pallas_interpret_matches_ref():
    p = small_params()
    r0 = vz.simulate_ctmc(p, n_replicas=64, seed=7, max_steps=256,
                          impl="ref")
    r1 = vz.simulate_ctmc(p, n_replicas=64, seed=7, max_steps=256,
                          impl="pallas_interpret")
    assert_same(r0, r1)


def test_engine_pallas_off_tpu_refused():
    if jax.default_backend() == "tpu":
        pytest.skip("compiled pallas is legitimate on TPU")
    with pytest.raises(ValueError, match="pallas_interpret"):
        vz.simulate_ctmc(small_params(), n_replicas=32, seed=0,
                         max_steps=64, impl="pallas")


# ---------------------------------------------------------------------------
# multi-device exactness (forced host devices)
# ---------------------------------------------------------------------------

def _reference_shard(p, key_s, R_loc, max_steps, max_runs=None):
    """Unsharded engine run a shard must reproduce exactly."""
    chunk = min(vz.DEFAULT_CHUNK_STEPS, max_steps)
    channels = vz._hist_channels([p])
    init_state = vz._initial_state(p, R_loc, max_runs)
    out = vz._run_chunked(
        vz._params_vector(p), key_s, 1, R_loc, chunk,
        jnp.int32(max_steps // chunk), max_steps % chunk, True,
        vz._single_job_step(p), init_state)
    return vz._extract(out, channels=channels)


@needs4
@pytest.mark.parametrize("n_shards", [2, 4])
def test_per_shard_independence_exact(n_shards):
    """Shard s of a sharded run == an independent unsharded run with the
    folded key — every lane, including histograms and run records."""
    p = small_params(max_run_records=4)   # small ring so it wraps
    R, steps = 64, 512
    R_loc = R // n_shards
    sharded = vz.simulate_ctmc(p, n_replicas=R, seed=3, max_steps=steps,
                               shards=n_shards)
    keys = replicas.shard_keys(jax.random.PRNGKey(3), n_shards)
    for s in range(n_shards):
        ref = _reference_shard(p, keys[s], R_loc, steps)
        rows = slice(s * R_loc, (s + 1) * R_loc)
        got = {k: np.asarray(v)[rows] if np.asarray(v).ndim and
               np.asarray(v).shape[0] == R else np.asarray(v)
               for k, v in sharded.items()}
        assert_same(got, ref, f"shard{s}")


@needs4
def test_histogram_merge_exact_across_devices():
    """The concatenation merge preserves every per-replica histogram row
    — summing merged rows equals summing the per-shard references."""
    p = small_params()
    assert p.histogram is not None
    R, steps = 64, 512
    sharded = vz.simulate_ctmc(p, n_replicas=R, seed=11, max_steps=steps,
                               shards=4)
    keys = replicas.shard_keys(jax.random.PRNGKey(11), 4)
    hist_keys = [k for k in sharded if k.startswith("hist_")
                 and k != "hist_edges"]
    assert hist_keys, "default HistogramSpec should emit channels"
    for hk in hist_keys:
        merged = np.asarray(sharded[hk])
        parts = [np.asarray(_reference_shard(p, keys[s], R // 4,
                                             steps)[hk])
                 for s in range(4)]
        assert np.array_equal(merged, np.concatenate(parts, axis=0)), hk
        assert np.array_equal(merged.sum(0),
                              sum(pt.sum(0) for pt in parts)), hk


@needs4
def test_sharded_sweep_matches_per_shard_runs():
    """A 2-point sweep on 4 devices: per-point rows still concatenate
    shard-major and match the sharded single-point runs."""
    pts = [small_params(), small_params(spare_pool_size=8)]
    sw = vz.simulate_ctmc_sweep(pts, n_replicas=32, seed=9, max_steps=256,
                                shards=4)
    for p, got in zip(pts, sw):
        single = vz.simulate_ctmc(p, n_replicas=32, seed=9, max_steps=256,
                                  shards=4)
        assert_same(got, single)


@needs4
def test_sharded_multijob_runs_and_merges():
    cluster = Params(working_pool_size=96, spare_pool_size=8,
                     repair_servers=2)
    jobs = (JobSpec(job_size=16, job_length=400.0),
            JobSpec(job_size=24, job_length=300.0))
    out = mj.simulate_multijob_ctmc_sweep([(cluster, jobs)], n_replicas=32,
                                          seed=5, max_steps=256, shards=4)
    [res] = out
    assert res["makespan"].shape == (32,)
    assert len(res["per_job"]) == 2
    assert set(np.asarray(res["completed"])) <= {0.0, 1.0}


@needs4
def test_sharded_sweep_one_compile_per_signature():
    pts = [small_params(), small_params(random_failure_rate=0.001)]
    vz.simulate_ctmc_sweep(pts, n_replicas=32, seed=1, max_steps=128,
                           shards=4)
    before = vz.shard_compile_cache_size()
    vz.simulate_ctmc_sweep([small_params(random_failure_rate=0.002),
                            small_params(spare_pool_size=2)],
                           n_replicas=32, seed=2, max_steps=128, shards=4)
    after = vz.shard_compile_cache_size()
    assert after == before, "same static signature must not recompile"
