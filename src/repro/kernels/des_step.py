"""Pallas TPU kernel for the vectorized DES next-event race.

The hot inner step of the JAX CTMC engine (core/vectorized.py): for R
independent replicas, race K_exp exponential clock families (propensities
``rates``) against K_det deterministic timers (``residuals``):

    dt    = min( Exp(sum rates),  min residual )
    event = categorical(rates)  if the exponential wins,
            K_exp + argmin residual otherwise

This is pure VPU work — a log, a prefix sum over a tiny K axis,
compares.  The kernel takes its operands *lane-major*: the replica axis
is folded into dense ``(rows, 128)`` tiles and the K lanes ride a
leading, untiled axis, so every clock family is a full-width vector and
the prefix sum, minimum and first-argmin over K are unrolled
elementwise passes (Mosaic has no lane-axis cumsum or argmin, and a
``(R, K)`` block with K < 128 would pad every vreg to 128 lanes).  The
caller (ops.event_race) transposes, pads the replica axis to whole
blocks with inert replicas (zero rates, +inf residuals) and slices them
off after.

Rounding matches ref.event_race_ref wherever XLA accumulates in lane
order: the total is the same reduction over K, the pick CDF is the
left-to-right running sum divided by the clamped total, and ties in the
deterministic race resolve to the first minimal lane.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: replicas per vector row of a block (the TPU lane width)
LANES = 128


def _event_race_kernel(rates_ref, residuals_ref, u_ref, dt_ref, event_ref,
                       *, k_exp: int, k_det: int):
    """One replica block: rates (k_exp, rows, 128), residuals
    (k_det, rows, 128), u (2, rows, 128) -> dt, event (rows, 128)."""
    rates = rates_ref[...]
    u_time, u_pick = u_ref[0], u_ref[1]

    total = jnp.sum(rates, axis=0)
    safe = jnp.maximum(total, 1e-30)
    t_exp = -jnp.log(jnp.maximum(u_time, 1e-38)) / safe
    t_exp = jnp.where(total > 0.0, t_exp, jnp.float32(jnp.inf))

    # inverse-CDF pick: count the prefix sums the uniform reaches
    prefix = rates[0]
    pick_exp = (u_pick >= prefix / safe).astype(jnp.int32)
    for k in range(1, k_exp):
        prefix = prefix + rates[k]
        pick_exp += (u_pick >= prefix / safe).astype(jnp.int32)
    pick_exp = jnp.minimum(pick_exp, jnp.int32(k_exp - 1))

    # first-argmin: a strictly smaller residual takes over, so ties (and
    # an all-+inf replica) keep the lowest lane, like jnp.argmin
    t_det = residuals_ref[0]
    pick_det = jnp.zeros(t_det.shape, jnp.int32)
    for k in range(1, k_det):
        r_k = residuals_ref[k]
        better = r_k < t_det
        pick_det = jnp.where(better, jnp.int32(k), pick_det)
        t_det = jnp.where(better, r_k, t_det)

    dt_ref[...] = jnp.minimum(t_exp, t_det)
    event_ref[...] = jnp.where(t_exp <= t_det, pick_exp,
                               pick_det + jnp.int32(k_exp))


def event_race_fwd(rates: jax.Array, residuals: jax.Array, u2: jax.Array,
                   *, block_rows: int, interpret: bool = False,
                   ) -> Tuple[jax.Array, jax.Array]:
    """Blocked kernel dispatch over lane-major, pre-padded inputs.

    rates (K_exp, S, 128), residuals (K_det, S, 128), u2 (2, S, 128)
    float32 -> (dt (S, 128) float32, event (S, 128) int32), where
    ``S`` is a multiple of ``block_rows`` and ``block_rows`` a multiple
    of 8.  ops.event_race does the layout and padding — call that, not
    this.
    """
    k_exp, S, lanes = rates.shape
    k_det = residuals.shape[0]
    assert lanes == LANES and S % block_rows == 0 and block_rows % 8 == 0, \
        (rates.shape, block_rows)

    # int32 block indices: Mosaic has no 64-bit integers, which a bare
    # 0 would become under the x64 flag (Params.age_dtype="float64")
    def k_block(r):
        return jnp.int32(0), r, jnp.int32(0)

    def spec(k):
        return pl.BlockSpec((k, block_rows, LANES), k_block)

    out_spec = pl.BlockSpec((block_rows, LANES),
                            lambda r: (r, jnp.int32(0)))
    kernel = functools.partial(_event_race_kernel, k_exp=k_exp, k_det=k_det)
    return pl.pallas_call(
        kernel,
        grid=(S // block_rows,),
        in_specs=[spec(k_exp), spec(k_det), spec(2)],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((S, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((S, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="event_race",
    )(rates, residuals, u2)
