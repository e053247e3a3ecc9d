"""Pallas kernel validation: interpret mode vs pure-jnp oracles.

Per the deliverable spec: each kernel sweeps shapes/dtypes and asserts
allclose against the ref.py oracle.  Interpret mode executes the kernel
body in Python on CPU, so these tests validate the kernel logic (tiling,
masking, accumulator handling) without TPU hardware.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def rand(key_int, shape, dtype):
    return jax.random.normal(jax.random.fold_in(KEY, key_int), shape,
                             jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, Sq, Sk, Hq, Hkv, d, causal)
    (1, 128, 128, 4, 4, 64, True),      # MHA
    (2, 256, 256, 4, 2, 64, True),      # GQA 2:1
    (1, 256, 256, 8, 1, 128, True),     # MQA
    (2, 128, 128, 4, 2, 128, False),    # bidirectional (encoder)
    (1, 384, 384, 2, 2, 64, True),      # non-power-of-two blocks (3 blocks)
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Sk, Hq, Hkv, d, causal = case
    q = rand(1, (B, Sq, Hq, d), dtype)
    k = rand(2, (B, Sk, Hkv, d), dtype)
    v = rand(3, (B, Sk, Hkv, d), dtype)
    out_ref = ref.attention_ref(q, k, v, causal=causal)
    out = ops.flash_attention(q, k, v, causal=causal, impl="pallas_interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_kv_len_mask():
    B, S, H, d = 1, 128, 2, 64
    q = rand(4, (B, S, H, d), jnp.float32)
    k = rand(5, (B, S, H, d), jnp.float32)
    v = rand(6, (B, S, H, d), jnp.float32)
    out_ref = ref.attention_ref(q, k, v, causal=False, kv_len=57)
    out = ops.flash_attention(q, k, v, causal=False, kv_len=57,
                              impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_q_offset():
    """Decode-style continuation: q block positioned mid-sequence."""
    B, Sq, Sk, H, d = 1, 128, 256, 2, 64
    q = rand(7, (B, Sq, H, d), jnp.float32)
    k = rand(8, (B, Sk, H, d), jnp.float32)
    v = rand(9, (B, Sk, H, d), jnp.float32)
    out_ref = ref.attention_ref(q, k, v, causal=True, q_offset=128)
    out = ops.flash_attention(q, k, v, causal=True, q_offset=128,
                              impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-5, rtol=2e-5)


def test_attention_ref_blocked_equals_direct():
    """The q-block scan path of the oracle equals its direct path."""
    B, S, H, d = 2, 512, 4, 64
    q = rand(10, (B, S, H, d), jnp.float32)
    k = rand(11, (B, S, H, d), jnp.float32)
    v = rand(12, (B, S, H, d), jnp.float32)
    direct = ref.attention_ref(q, k, v, causal=True, q_block=None)
    blocked = ref.attention_ref(q, k, v, causal=True, q_block=128)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(direct),
                               atol=1e-5, rtol=1e-5)


def test_flash_attention_grad_matches_ref():
    B, S, H, d = 1, 128, 2, 64
    q = rand(13, (B, S, H, d), jnp.float32)
    k = rand(14, (B, S, H, d), jnp.float32)
    v = rand(15, (B, S, H, d), jnp.float32)

    g1 = jax.grad(lambda q_: ops.flash_attention(
        q_, k, v, impl="pallas_interpret").sum())(q)
    g2 = jax.grad(lambda q_: ref.attention_ref(q_, k, v).sum())(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# selective scan
# ---------------------------------------------------------------------------

SCAN_CASES = [
    # (B, S, di, N, chunk, block_d)
    (1, 64, 64, 8, 16, 32),
    (2, 128, 128, 16, 32, 64),
    (2, 64, 256, 16, 64, 128),
]


@pytest.mark.parametrize("case", SCAN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_selective_scan_matches_ref(case, dtype):
    B, S, di, N, chunk, block_d = case
    x = rand(20, (B, S, di), dtype) * 0.5
    dt = jax.nn.softplus(rand(21, (B, S, di), jnp.float32)).astype(dtype) * 0.1
    A = -jnp.exp(rand(22, (di, N), jnp.float32) * 0.5)
    Bm = rand(23, (B, S, N), dtype)
    Cm = rand(24, (B, S, N), dtype)
    y_ref, h_ref = ref.selective_scan_ref(x, dt, A, Bm, Cm)
    y, h = ops.selective_scan(x, dt, A, Bm, Cm, impl="pallas_interpret",
                              chunk=chunk, block_d=block_d)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), atol=tol,
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=tol,
                               rtol=tol)


def test_selective_scan_initial_state_continuation():
    """Scanning [0:S] equals scanning [0:S/2] then [S/2:S] with h0 carry."""
    B, S, di, N = 1, 64, 64, 8
    x = rand(30, (B, S, di), jnp.float32) * 0.5
    dt = jax.nn.softplus(rand(31, (B, S, di), jnp.float32)) * 0.1
    A = -jnp.exp(rand(32, (di, N), jnp.float32) * 0.5)
    Bm = rand(33, (B, S, N), jnp.float32)
    Cm = rand(34, (B, S, N), jnp.float32)
    y_full, h_full = ref.selective_scan_ref(x, dt, A, Bm, Cm)
    half = S // 2
    y1, h1 = ops.selective_scan(x[:, :half], dt[:, :half], A, Bm[:, :half],
                                Cm[:, :half], impl="pallas_interpret",
                                chunk=16, block_d=32)
    y2, h2 = ops.selective_scan(x[:, half:], dt[:, half:], A, Bm[:, half:],
                                Cm[:, half:], h0=h1, impl="pallas_interpret",
                                chunk=16, block_d=32)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full), atol=1e-4,
                               rtol=1e-4)


def test_selective_scan_step_matches_scan():
    """Decode steps replay the full scan one token at a time."""
    B, S, di, N = 2, 8, 32, 8
    x = rand(40, (B, S, di), jnp.float32) * 0.5
    dt = jax.nn.softplus(rand(41, (B, S, di), jnp.float32)) * 0.1
    A = -jnp.exp(rand(42, (di, N), jnp.float32) * 0.5)
    Bm = rand(43, (B, S, N), jnp.float32)
    Cm = rand(44, (B, S, N), jnp.float32)
    y_full, _ = ref.selective_scan_ref(x, dt, A, Bm, Cm)
    h = jnp.zeros((B, di, N), jnp.float32)
    ys = []
    for t in range(S):
        y_t, h = ops.selective_scan_step(x[:, t], dt[:, t], A, Bm[:, t],
                                         Cm[:, t], h)
        ys.append(y_t)
    y_steps = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_steps), np.asarray(y_full),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# DES event race
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,Ke,Kd", [
    (64, 4, 2), (256, 16, 4), (1024, 18, 2),
    # padded paths: replica axis not a block multiple, K lanes far off
    # the sublane width, degenerate single-lane races
    (100, 3, 1), (8, 1, 1), (130, 9, 5), (96, 23, 7),
    # the correlated-scenario and multi-job race widths
    (300, 36, 4), (2100, 48, 6),
])
def test_event_race_matches_ref(R, Ke, Kd):
    rng = np.random.default_rng(R)
    rates = jnp.asarray(rng.uniform(0, 2, (R, Ke)).astype(np.float32))
    rates = rates.at[:, Ke // 2].set(0.0)  # one family switched off
    resid = jnp.asarray(rng.uniform(0.01, 5, (R, Kd)).astype(np.float32))
    resid = resid.at[: R // 4, 0].set(np.inf)  # some timers off
    ut = jnp.asarray(rng.uniform(1e-6, 1, R).astype(np.float32))
    up = jnp.asarray(rng.uniform(0, 1, R).astype(np.float32))
    dt_r, ev_r = ref.event_race_ref(rates, resid, ut, up)
    dt_p, ev_p = ops.event_race(rates, resid, ut, up,
                                impl="pallas_interpret", block_r=64)
    np.testing.assert_allclose(np.asarray(dt_p), np.asarray(dt_r), rtol=1e-6)
    assert (np.asarray(ev_p) == np.asarray(ev_r)).all()


def test_event_race_all_rates_zero_picks_deterministic():
    R = 64
    rates = jnp.zeros((R, 4), jnp.float32)
    resid = jnp.tile(jnp.asarray([[3.0, 1.5]], jnp.float32), (R, 1))
    ut = jnp.full((R,), 0.5, jnp.float32)
    up = jnp.full((R,), 0.5, jnp.float32)
    dt, ev = ref.event_race_ref(rates, resid, ut, up)
    assert np.allclose(np.asarray(dt), 1.5)
    assert (np.asarray(ev) == 4 + 1).all()


def test_event_race_pallas_off_tpu_refused():
    """An explicit compiled-pallas request off-TPU names the config and
    the escape hatches instead of silently de-materializing."""
    if jax.default_backend() == "tpu":
        pytest.skip("compiled pallas is legitimate on TPU")
    rates = jnp.ones((8, 2), jnp.float32)
    resid = jnp.ones((8, 2), jnp.float32)
    u = jnp.full((8,), 0.5, jnp.float32)
    with pytest.raises(ValueError, match="pallas_interpret"):
        ops.event_race(rates, resid, u, u, impl="pallas")


def test_event_race_unknown_impl_refused():
    rates = jnp.ones((8, 2), jnp.float32)
    u = jnp.full((8,), 0.5, jnp.float32)
    with pytest.raises(ValueError, match="impl"):
        ops.event_race(rates, rates, u, u, impl="vulkan")


def test_event_race_zero_lane_refused():
    """K_det=0 has no next event to race on either side of the dispatch
    (ref cannot reduce a zero-width axis either) — refuse by name."""
    R = 16
    rates = jnp.ones((R, 2), jnp.float32)
    resid = jnp.zeros((R, 0), jnp.float32)
    u = jnp.full((R,), 0.5, jnp.float32)
    with pytest.raises(ValueError, match="zero-width lane"):
        ops.event_race(rates, resid, u, u, impl="pallas_interpret")


def test_event_race_statistics():
    """The winning-family distribution matches the rate proportions."""
    R = 200_000
    rng = np.random.default_rng(0)
    rates = jnp.tile(jnp.asarray([[1.0, 3.0, 0.0, 6.0]], jnp.float32), (R, 1))
    resid = jnp.full((R, 2), jnp.inf, jnp.float32)
    ut = jnp.asarray(rng.uniform(1e-9, 1, R).astype(np.float32))
    up = jnp.asarray(rng.uniform(0, 1, R).astype(np.float32))
    dt, ev = ref.event_race_ref(rates, resid, ut, up)
    ev = np.asarray(ev)
    freq = np.bincount(ev, minlength=4) / R
    np.testing.assert_allclose(freq[:4], [0.1, 0.3, 0.0, 0.6], atol=5e-3)
    # dt mean = 1/total_rate
    np.testing.assert_allclose(float(np.asarray(dt).mean()), 1 / 10.0,
                               rtol=2e-2)
