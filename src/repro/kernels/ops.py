"""Public kernel entry points with backend dispatch.

Selection policy (per call, overridable with ``impl=``):

  * ``tpu`` backend          -> Pallas kernel (compiled)
  * anything else            -> pure-jnp reference (ref.py)
  * ``impl='pallas_interpret'`` -> Pallas kernel in interpret mode
    (Python emulation on CPU; used by the kernel test suite)

Differentiability: Pallas forward kernels are wrapped in jax.custom_vjp
with the backward pass taken from the reference implementation (recompute
with jax.vjp).  On CPU everything routes through ref and is natively
differentiable, so training in this container and kernel-accelerated
training on TPU share one API.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .des_step import LANES, event_race_fwd
from .flash_attention import flash_attention_fwd
from .mamba_scan import selective_scan_fwd


def _backend() -> str:
    """The backend this process compiles for.  A test that compiles for
    a described, unattached TPU patches this to ``"tpu"``."""
    return jax.default_backend()


def _default_impl() -> str:
    return "pallas" if _backend() == "tpu" else "ref"


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _attn_pallas(q, k, v, *, causal, q_offset, kv_len, block_q, block_k,
                 interpret):
    B, Sq, Hq, d = q.shape
    _, Sk, Hkv, _ = k.shape
    qr = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, d)
    out = flash_attention_fwd(
        qr, kr, vr, n_q_heads=Hq, n_kv_heads=Hkv, causal=causal,
        q_offset=q_offset, kv_len=kv_len, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out.reshape(B, Hq, Sq, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _attention_custom(q, k, v, causal, q_offset, kv_len, block_q, block_k,
                      interpret, q_block):
    return _attn_pallas(q, k, v, causal=causal, q_offset=q_offset,
                        kv_len=kv_len, block_q=block_q, block_k=block_k,
                        interpret=interpret)


def _attention_fwd(q, k, v, causal, q_offset, kv_len, block_q, block_k,
                   interpret, q_block):
    out = _attention_custom(q, k, v, causal, q_offset, kv_len, block_q,
                            block_k, interpret, q_block)
    return out, (q, k, v)


def _attention_bwd(causal, q_offset, kv_len, block_q, block_k, interpret,
                   q_block, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.attention_ref(
            q_, k_, v_, causal=causal, q_offset=q_offset, kv_len=kv_len,
            q_block=q_block), q, k, v)
    return vjp(g)


_attention_custom.defvjp(_attention_fwd, _attention_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, q_offset=0,
                    kv_len: Optional[jax.Array] = None,
                    impl: Optional[str] = None,
                    block_q: int = 128, block_k: int = 128,
                    q_block: Optional[int] = 1024) -> jax.Array:
    """GQA attention. q (B,Sq,Hq,d), k/v (B,Sk,Hkv,d) -> (B,Sq,Hq,d)."""
    impl = impl or _default_impl()
    if impl == "ref":
        return ref.attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                                 kv_len=kv_len, q_block=q_block)
    interpret = impl == "pallas_interpret"
    # Pallas path requires static offsets/lengths and aligned shapes;
    # fall back to ref otherwise (e.g. decode with traced positions).
    static_ok = isinstance(q_offset, int) and (
        kv_len is None or isinstance(kv_len, int))
    Sq, Sk = q.shape[1], k.shape[1]
    if not static_ok or Sq % min(block_q, Sq) or Sk % min(block_k, Sk):
        return ref.attention_ref(q, k, v, causal=causal, q_offset=q_offset,
                                 kv_len=kv_len, q_block=q_block)
    return _attention_custom(q, k, v, causal, q_offset, kv_len, block_q,
                             block_k, interpret, q_block)


# ---------------------------------------------------------------------------
# selective scan (mamba)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan_custom(x, dt, A, Bmat, Cmat, h0, chunk, block_d, interpret):
    # pallas uses (B, N, di) state layout; ref uses (B, di, N)
    y, hf = selective_scan_fwd(x, dt, A, Bmat, Cmat,
                               jnp.swapaxes(h0, 1, 2), chunk=chunk,
                               block_d=block_d, interpret=interpret)
    return y, jnp.swapaxes(hf, 1, 2)


def _scan_fwd(x, dt, A, Bmat, Cmat, h0, chunk, block_d, interpret):
    out = _scan_custom(x, dt, A, Bmat, Cmat, h0, chunk, block_d, interpret)
    return out, (x, dt, A, Bmat, Cmat, h0)


def _scan_bwd(chunk, block_d, interpret, res, g):
    x, dt, A, Bmat, Cmat, h0 = res
    _, vjp = jax.vjp(
        lambda *args: ref.selective_scan_ref(*args), x, dt, A, Bmat, Cmat, h0)
    return vjp(g)


_scan_custom.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
                   Bmat: jax.Array, Cmat: jax.Array,
                   h0: Optional[jax.Array] = None, *,
                   impl: Optional[str] = None, chunk: int = 256,
                   block_d: int = 512) -> Tuple[jax.Array, jax.Array]:
    """Mamba scan. x/dt (B,S,di), A (di,N), B/C (B,S,N), h0 (B,di,N)."""
    impl = impl or _default_impl()
    Bsz, S, di = x.shape
    N = A.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((Bsz, di, N), jnp.float32)
    if impl == "ref":
        return ref.selective_scan_ref(x, dt, A, Bmat, Cmat, h0)
    interpret = impl == "pallas_interpret"
    if S % min(chunk, S) or di % min(block_d, di):
        return ref.selective_scan_ref(x, dt, A, Bmat, Cmat, h0)
    return _scan_custom(x, dt, A, Bmat, Cmat, h0, min(chunk, S),
                        min(block_d, di), interpret)


def selective_scan_step(x_t, dt_t, A, B_t, C_t, h):
    """Single decode step (always jnp; trivially memory-bound)."""
    return ref.selective_scan_step_ref(x_t, dt_t, A, B_t, C_t, h)


# ---------------------------------------------------------------------------
# DES event race
# ---------------------------------------------------------------------------

def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def event_race(rates: jax.Array, residuals: jax.Array, u_time: jax.Array,
               u_pick: jax.Array, *, impl: Optional[str] = None,
               block_r: int = 8192) -> Tuple[jax.Array, jax.Array]:
    """Next-event race; see des_step.py. No gradients (simulation only).

    ``impl``: None auto-selects (``"pallas"`` on TPU, ``"ref"``
    elsewhere); ``"ref"`` is the always-available pure-jnp path;
    ``"pallas"`` requires a TPU backend and raises otherwise (use
    ``"pallas_interpret"`` — the kernel body executed op-by-op on CPU —
    for validation).  The kernel path lays the K lanes out as a leading
    axis over ``(rows, 128)`` replica tiles and pads the replica axis to
    whole blocks of at most ``block_r`` replicas with inert values (see
    des_step.py); padding is sliced off before returning, so every
    (R, K_exp, K_det) shape runs the kernel — there is no silent shape
    fallback.  Zero-width lane blocks are invalid on every backend (the
    reference cannot reduce them either) and raise.

    With all rates zero the deterministic side wins and the event index
    is ``K_exp + argmin(residuals)`` — identical across backends:

    >>> rates = jnp.zeros((1, 2))
    >>> resid = jnp.asarray([[3.0, 1.5]])
    >>> u = jnp.asarray([0.5])
    >>> dt, ev = event_race(rates, resid, u, u, impl="ref")
    >>> float(dt[0]), int(ev[0])
    (1.5, 3)
    >>> dt, ev = event_race(rates, resid, u, u, impl="pallas_interpret")
    >>> float(dt[0]), int(ev[0])
    (1.5, 3)
    """
    impl = impl or _default_impl()
    if impl == "ref":
        return ref.event_race_ref(rates, residuals, u_time, u_pick)
    if impl == "pallas" and _backend() != "tpu":
        raise ValueError(
            f"event_race impl='pallas' requires a TPU backend (default "
            f"backend here is {_backend()!r}); use "
            f"impl='pallas_interpret' for CPU validation or impl='ref' "
            f"for the pure-jnp path (docs/scaling.md)")
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(
            f"event_race impl={impl!r} must be None, 'ref', 'pallas', "
            f"or 'pallas_interpret'")
    R, k_exp = rates.shape
    k_det = residuals.shape[1]
    if k_exp == 0 or k_det == 0:
        raise ValueError(
            f"event_race needs at least one exponential and one "
            f"deterministic lane (got K_exp={k_exp}, K_det={k_det}); a "
            f"zero-width lane block has no next event to race — disable "
            f"the empty side with zero rates / +inf residuals instead")
    # lane-major layout: (K, R) padded with inert replicas (zero rates,
    # +inf residuals) to whole blocks of `rows` x 128, folded into tiles
    rows = min(_round_up(max(block_r // LANES, 1), 8),
               _round_up(-(-R // LANES), 8))
    r_pad = _round_up(R, rows * LANES)

    def lane_major(x, fill):
        x = jnp.pad(x.astype(jnp.float32).T, ((0, 0), (0, r_pad - R)),
                    constant_values=fill)
        return x.reshape(x.shape[0], r_pad // LANES, LANES)

    dt, event = event_race_fwd(
        lane_major(rates, 0.0), lane_major(residuals, jnp.inf),
        lane_major(jnp.stack([u_time, u_pick], axis=-1), 0.5),
        block_rows=rows, interpret=impl == "pallas_interpret")
    return dt.reshape(r_pad)[:R], event.reshape(r_pad)[:R]
