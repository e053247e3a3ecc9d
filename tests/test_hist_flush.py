"""The chunk loop's deferred histogram add.

Inside the chunk loop each step only records its bins (``hist_bins``),
the scan stacks the records, and one add per chunk folds them into
``hist`` (``vectorized._hist_flush``).  These tests pin that this is the
same, bit for bit, as adding every step's counts at once: one block of
records against the numpy bin rule and against single-step adds, and a
whole chunk loop against a scan whose step adds at once, on both
engines.  CPU, tiny sizes.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import JobSpec, Params
from repro.core import vectorized as vz
from repro.core import vectorized_multijob as vmj

CONFIGS = Path(__file__).resolve().parents[1] / "bench" / "configs"
CHUNK = vz.DEFAULT_CHUNK_STEPS


def table1(name, **changes):
    params = json.loads((CONFIGS / f"{name}.json").read_text())["params"]
    return Params.from_dict(params).replace(**changes)


CASES = {
    "table1_exp": table1("table1_exp"),
    "table1_lognormal_repair": table1("table1_lognormal_repair"),
    # a two-hour job: every replica finishes in the first chunks, so
    # early exit skips the rest and the remainder
    "table1_exp_short_job": table1("table1_exp", job_length=120.0),
}


def records(edges, vals, masks):
    """One :func:`vz._hist_bins` record per step and their words stacked
    over the steps, as the chunk loop's scan stacks them."""
    recs = [vz._hist_bins(jnp.asarray(edges), jnp.asarray(v), jnp.asarray(m))
            for v, m in zip(vals, masks)]
    return recs, tuple(jnp.stack([r[w] for r in recs])
                       for w in range(recs[0].shape[0]))


@pytest.mark.parametrize("n_bins,n_channels", [
    (128, 3),    # the Table I spec: 130 counts, one 8-bit word
    (128, 1),
    (253, 4),    # 255 counts: the sentinel fills the top byte
    (298, 4),    # 300 counts: 16-bit slots, two words
])
def test_one_flush_equals_single_step_adds(n_bins, n_channels):
    n_counts = n_bins + 2
    K, B = 16, 40
    rng = np.random.default_rng(n_bins * 10 + n_channels)
    edges = np.geomspace(0.01, 1e7, n_bins + 1).astype(np.float32)
    # under- and overflow both occur; masked entries record the sentinel
    vals = (10.0 ** rng.uniform(-3, 8, (K, n_channels, B))).astype(np.float32)
    masks = rng.random((K, n_channels, B)) < 0.6
    recs, words = records(edges, vals, masks)
    per_word = 32 // vz._bin_bits(n_counts)
    assert recs[0].shape == (-(-n_channels // per_word), B)
    assert recs[0].dtype == jnp.uint32

    hist0 = rng.integers(0, 9, (B, n_channels, n_counts)).astype(np.float32)
    want = hist0.copy()
    k, c, b = np.nonzero(masks)
    bins = np.searchsorted(edges, vals, side="right")
    np.add.at(want, (b, c, bins[k, c, b]), 1.0)

    flushed = vz._hist_flush(jnp.asarray(hist0), words)
    stepped = jnp.asarray(hist0)
    for r in recs:
        stepped = vz._hist_flush(stepped, r[:, None])
    np.testing.assert_array_equal(np.asarray(flushed), want)
    np.testing.assert_array_equal(np.asarray(stepped), want)


def test_an_all_sentinel_block_adds_nothing():
    edges = np.geomspace(0.01, 1e7, 129).astype(np.float32)
    zeros = np.zeros((8, 3, 24), np.float32)
    _, words = records(edges, zeros, zeros > 0)
    hist0 = jnp.arange(24 * 3 * 130, dtype=jnp.float32).reshape(24, 3, 130)
    np.testing.assert_array_equal(np.asarray(vz._hist_flush(hist0, words)),
                                  np.asarray(hist0))


def adding_every_step(step):
    """``step`` made to add its counts at once: the histogram rides in
    the scan carry as ``hist_now`` and reaches the step as ``hist``,
    while the chunk loop's own record stays all sentinel, so its flush
    adds nothing."""
    def immediate(s, u, *args, **kw):
        inner = {k: v for k, v in s.items()
                 if k not in ("hist_bins", "hist_now")}
        ns = step(dict(inner, hist=s["hist_now"]), u, *args, **kw)
        return dict(ns, hist_now=ns.pop("hist"), hist_bins=s["hist_bins"])
    return immediate


@pytest.mark.parametrize("early_exit", [True, False],
                         ids=["early_exit", "full_budget"])
@pytest.mark.parametrize("case", list(CASES))
def test_deferred_flush_is_bit_identical_to_adding_every_step(
        case, early_exit, monkeypatch):
    p, R = CASES[case], 12
    budget = 2 * CHUNK + 9                        # a remainder chunk
    init = vz._initial_state(p, R, None)
    args = (vz._params_vector(p), jax.random.PRNGKey(7), 1, R, CHUNK,
            np.int32(budget // CHUNK), budget % CHUNK, early_exit,
            vz._single_job_step(p))

    def run(state):
        # a fresh jit each time: the patched step is traced anew
        return jax.jit(lambda st: vz._chunk_loop(*args, st))(state)

    deferred = run(init)
    monkeypatch.setattr(vz, "_step_u", adding_every_step(vz._step_u))
    at_once = run(dict(init, hist_now=init["hist"]))
    # the chunk loop's own flush added nothing there
    np.testing.assert_array_equal(at_once.pop("hist"), init["hist"])
    at_once["hist"] = at_once.pop("hist_now")

    assert set(deferred) == set(at_once)
    assert "hist_bins" not in deferred
    for k, v in at_once.items():
        got, want = np.asarray(deferred[k]), np.asarray(v)
        assert got.dtype == want.dtype and got.shape == want.shape, k
        assert got.tobytes() == want.tobytes(), k
    assert np.asarray(deferred["hist"]).sum() > 0
    steps = int(deferred["steps_run"])
    if early_exit and case == "table1_exp_short_job":
        # every replica finished: the loop skipped chunks and the remainder
        assert steps == int(deferred["chunks_run"]) * CHUNK < budget
    else:
        assert steps == budget


#: two mixed-size jobs contending for one pool and a finite shop
MJ_CLUSTER = Params(working_pool_size=110, spare_pool_size=16, job_size=16,
                    job_length=4000.0, random_failure_rate=0.001,
                    systematic_failure_rate=0.005, auto_repair_time=180.0,
                    manual_repair_time=480.0, repair_servers=6)
MJ_JOBS = (JobSpec(32, 4000.0, warm_standbys=2),
           JobSpec(16, 6000.0, warm_standbys=1))


@pytest.mark.parametrize("early_exit", [True, False],
                         ids=["early_exit", "full_budget"])
def test_multijob_deferred_flush_is_bit_identical_to_adding_every_step(
        early_exit, monkeypatch):
    """The J = 2 multi-job step through the same chunk loop: its
    (B, J * S, n_counts) histogram, added once a chunk, equals adding
    every step's counts at once."""
    R, J = 12, len(MJ_JOBS)
    budget = 2 * CHUNK + 9                        # a remainder chunk
    pv = np.repeat(np.asarray(vmj._mj_params_vector(MJ_CLUSTER, MJ_JOBS))
                   [None], R, axis=0)
    init = vmj._mj_initial_state_batch([(MJ_CLUSTER, MJ_JOBS)], R, 8)
    step = vmj._MultiJobStep(J, None, vz._hist_channels([MJ_CLUSTER]))
    assert init["hist"].shape == (R, J * len(step.hist_channels),
                                  MJ_CLUSTER.histogram.n_counts)
    args = (jnp.asarray(pv), jax.random.PRNGKey(7), 1, R, CHUNK,
            np.int32(budget // CHUNK), budget % CHUNK, early_exit, step)

    def run(state):
        # a fresh jit each time: the patched step is traced anew
        return jax.jit(lambda st: vz._chunk_loop(*args, st))(state)

    deferred = run(init)
    monkeypatch.setattr(vmj, "_mj_step_u",
                        adding_every_step(vmj._mj_step_u))
    at_once = run(dict(init, hist_now=init["hist"]))
    np.testing.assert_array_equal(at_once.pop("hist"), init["hist"])
    at_once["hist"] = at_once.pop("hist_now")

    assert set(deferred) == set(at_once)
    assert "hist_bins" not in deferred
    for k, v in at_once.items():
        got, want = np.asarray(deferred[k]), np.asarray(v)
        assert got.dtype == want.dtype and got.shape == want.shape, k
        assert got.tobytes() == want.tobytes(), k
    hist = np.asarray(deferred["hist"])
    assert hist.sum() > 0
    steps = int(deferred["steps_run"])
    assert steps == budget or (early_exit and steps < budget)
    assert int(deferred["hist_flushes"]) == -(-steps // CHUNK)
