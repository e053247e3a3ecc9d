"""The replica mesh of the compiled CTMC engines' ``shard_map`` path.

A sharded run splits the flat ``(P*R,)`` batch axis by replica over a
1-D ``(REPLICA_AXIS,)`` device mesh (:func:`replica_mesh`); each shard
draws its own uniform stream from a folded key (:func:`shard_keys`),
and every batched state leaf shards its replica dimension
(:func:`replica_state_specs`).  See docs/scaling.md for the end-to-end
recipe.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

#: the replica-axis name of the simulator mesh; every sharded state leaf
#: of the CTMC engine partitions its replica dimension over this axis.
REPLICA_AXIS = "r"


def replica_mesh(n_shards: int) -> Mesh:
    """Build the 1-D ``(REPLICA_AXIS,)`` device mesh for a sharded run.

    Takes the first ``n_shards`` local devices.  Raises (rather than
    silently de-sharding) when fewer devices are visible — on CPU, force
    local devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
    imports (see docs/scaling.md).

    >>> m = replica_mesh(1)
    >>> m.axis_names, m.shape["r"]
    (('r',), 1)
    >>> replica_mesh(10**6)          # doctest: +IGNORE_EXCEPTION_DETAIL
    Traceback (most recent call last):
    ValueError: ...
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    devices = jax.devices()
    if len(devices) < n_shards:
        raise ValueError(
            f"replica mesh needs {n_shards} devices but only "
            f"{len(devices)} are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n_shards} before importing jax (docs/scaling.md)")
    return Mesh(np.asarray(devices[:n_shards]), (REPLICA_AXIS,))


def shard_keys(key: "jax.Array", n_shards: int) -> "jax.Array":
    """Split a base PRNG key into ``n_shards`` per-shard keys, stacked
    ``(n_shards, 2)`` for a ``P('r')``-sharded shard_map input.

    The contract (pinned by tests/test_replica_sharding.py):

    * ``n_shards == 1`` returns the base key itself — a one-device mesh
      draws the *identical* uniform stream, making the sharded engine
      bit-identical to the unsharded one at mesh size 1;
    * ``n_shards > 1`` derives shard ``s``'s key as
      ``fold_in(key, s)``, so shard streams never overlap (threefry
      fold_in is injective per index) and shard ``s`` of a sharded run
      is bit-identical to an *independent unsharded* run over that
      shard's replicas seeded with the same folded key.

    >>> import jax
    >>> base = jax.random.PRNGKey(0)
    >>> bool((shard_keys(base, 1)[0] == base).all())
    True
    >>> ks = shard_keys(base, 4)
    >>> ks.shape
    (4, 2)
    >>> len({tuple(np.asarray(k)) for k in ks})   # pairwise distinct
    4
    """
    if n_shards == 1:
        return key[None]
    return jax.vmap(lambda s: jax.random.fold_in(key, s))(
        np.arange(n_shards, dtype=np.uint32))


def replica_state_specs(state: Dict[str, Any],
                        unbatched: Tuple[str, ...] = ()) -> Dict[str, P]:
    """PartitionSpec tree for a CTMC state dict reshaped to (P, R, ...).

    Every batched leaf shards its replica axis (dim 1) over
    ``REPLICA_AXIS``; leaves named in ``unbatched`` (shared bin-edge
    tables and the like) are replicated.

    >>> specs = replica_state_specs({"t": np.zeros((2, 8)),
    ...                              "hist_edges": np.zeros(130)},
    ...                             unbatched=("hist_edges",))
    >>> specs["t"], specs["hist_edges"]
    (PartitionSpec(None, 'r'), PartitionSpec())
    """
    return {k: (P() if k in unbatched else P(None, REPLICA_AXIS))
            for k in state}
