"""Pallas TPU flash attention (forward) with GQA.

Online-softmax attention tiled for VMEM: the grid is
(batch*q_heads, Sq/block_q, Sk/block_k) with the key dimension innermost
("arbitrary" semantics) so the fp32 accumulators (acc, m, l) persist in
VMEM scratch across key blocks.  Causal blocks strictly above the diagonal
are skipped.  MXU dims: block_q x d and block_k x d matmuls with
preferred_element_type=float32.

Layout notes (TPU adaptation, see DESIGN.md):
  * q is reshaped to (B*Hq, Sq, d), k/v to (B*Hkv, Sk, d) by ops.py; the
    kv program index is derived as b*Hkv + (h // group) inside the
    BlockSpec index maps, so GQA costs no extra copies;
  * block_q/block_k default to 128 (MXU-aligned); d pads to lane width.

Validated in interpret mode against ref.attention_ref (tests/test_kernels.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  sm_scale: float, block_q: int, block_k: int,
                  n_kv_blocks: int, causal: bool, q_offset: int,
                  kv_len: Optional[int]):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this block's queries / keys
    q_first = q_offset + qi * block_q
    k_first = ki * block_k

    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (bq, d)
        k = k_ref[0].astype(jnp.float32)                    # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (bq, bk)

        q_pos = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = None
        if causal:
            mask = q_pos >= k_pos
        if kv_len is not None:
            lm = k_pos < kv_len
            mask = lm if mask is None else (mask & lm)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (bq,)
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])                      # (bq, bk)
        l_ref[...] = l_prev * corr + p.sum(axis=-1)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bq, d)
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    if causal:
        # skip key blocks entirely above the causal diagonal
        q_last = q_first + block_q - 1
        pl.when(k_first <= q_last)(_compute)
    else:
        _compute()

    @pl.when(ki == n_kv_blocks - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        n_q_heads: int, n_kv_heads: int,
                        causal: bool = True, q_offset: int = 0,
                        kv_len: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        sm_scale: Optional[float] = None,
                        interpret: bool = False) -> jax.Array:
    """q: (B*Hq, Sq, d); k/v: (B*Hkv, Sk, d) -> (B*Hq, Sq, d)."""
    BH, Sq, d = q.shape
    BHkv, Sk, _ = k.shape
    assert BH % n_q_heads == 0 and BHkv % n_kv_heads == 0
    B = BH // n_q_heads
    group = n_q_heads // n_kv_heads
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q_blocks = Sq // block_q
    n_kv_blocks = Sk // block_k
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)

    def kv_index(bh, qi, ki):
        b = bh // n_q_heads
        h = bh % n_q_heads
        return (b * n_kv_heads + h // group, ki, 0)

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, block_q=block_q, block_k=block_k,
        n_kv_blocks=n_kv_blocks, causal=causal, q_offset=q_offset,
        kv_len=kv_len)

    grid = (BH, n_q_blocks, n_kv_blocks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
            pltpu.VMEM((block_q,), jnp.float32),     # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),     # l (running denom)
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
