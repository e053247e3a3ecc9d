"""Pallas TPU selective state-space scan (Mamba-1).

TPU adaptation (see DESIGN.md): the CUDA mamba kernel is a warp-parallel
chunked scan; on TPU we tile (d_inner) across the grid and keep the
recurrent state h resident in VMEM across *sequence chunks* (innermost
grid dimension, "arbitrary" semantics).  Inside a chunk the recurrence is
a fori_loop over time steps operating on (N, block_d) vectors — N on
sublanes, d_inner on lanes, so the elementwise decay/drive math runs at
full VPU width.

Grid: (B, d_inner/block_d, S/chunk).  The state scratch (N, block_d) is
initialized from h0 at chunk 0 and written to h_final at the last chunk.

Validated in interpret mode against ref.selective_scan_ref.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(x_ref, dt_ref, At_ref, B_ref, C_ref, h0_ref,
                  y_ref, hf_ref, h_scratch, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = h0_ref[0].astype(jnp.float32)      # (N, bd)

    At = At_ref[...].astype(jnp.float32)                    # (N, bd)

    def step(t, h):
        dt_t = dt_ref[0, t, :].astype(jnp.float32)          # (bd,)
        x_t = x_ref[0, t, :].astype(jnp.float32)            # (bd,)
        B_t = B_ref[0, t, :].astype(jnp.float32)            # (N,)
        C_t = C_ref[0, t, :].astype(jnp.float32)            # (N,)
        decay = jnp.exp(At * dt_t[None, :])                 # (N, bd)
        drive = (dt_t * x_t)[None, :] * B_t[:, None]        # (N, bd)
        h = decay * h + drive
        y_t = jnp.sum(h * C_t[:, None], axis=0)             # (bd,)
        y_ref[0, t, :] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scratch[...])
    h_scratch[...] = h

    @pl.when(ci == n_chunks - 1)
    def _final():
        hf_ref[0] = h.astype(hf_ref.dtype)


def selective_scan_fwd(x: jax.Array, dt: jax.Array, A: jax.Array,
                       Bmat: jax.Array, Cmat: jax.Array, h0: jax.Array, *,
                       chunk: int = 256, block_d: int = 512,
                       interpret: bool = False,
                       ) -> Tuple[jax.Array, jax.Array]:
    """x, dt: (B, S, di); A: (di, N); Bmat/Cmat: (B, S, N); h0: (B, N, di).

    Returns (y (B, S, di), h_final (B, N, di)).  Note h uses the TPU-native
    (N, di) layout (N on sublanes); ops.py adapts to/from the reference
    (B, di, N) layout.
    """
    Bsz, S, di = x.shape
    N = A.shape[-1]
    chunk = min(chunk, S)
    block_d = min(block_d, di)
    assert S % chunk == 0 and di % block_d == 0, (S, chunk, di, block_d)
    n_chunks = S // chunk
    n_dblocks = di // block_d
    At = A.T  # (N, di)

    kernel = functools.partial(_mamba_kernel, chunk=chunk, n_chunks=n_chunks)
    grid = (Bsz, n_dblocks, n_chunks)

    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),  # x
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),  # dt
            pl.BlockSpec((N, block_d), lambda b, d, c: (0, d)),            # A^T
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),        # B
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),        # C
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),      # h0
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),  # y
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),      # hf
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, di), x.dtype),
            jax.ShapeDtypeStruct((Bsz, N, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, At, Bmat, Cmat, h0)
    return y, h_final
