"""Sharding rules: PartitionSpecs by path for the model-training stack.

Parameter / activation / cache PartitionSpecs as pure functions of
(path, shape, mesh) — see the scheme below.  The simulator's replica
mesh lives in :mod:`repro.core.replicas`.

Scheme (FSDP x TP x EP, with an outer pod axis for multi-pod):

  * mesh axes: ("data", "model") single-pod, ("pod", "data", "model")
    multi-pod.  FSDP shards parameters over (pod, data); TP shards heads /
    ffn / experts over "model".
  * stacked superblock parameters carry a leading n_superblocks axis that
    is never sharded.
  * every rule checks divisibility — a dimension that does not divide the
    axis size is left unsharded (e.g. kv_heads=8 on a 16-way model axis is
    replicated 2x, the standard GQA trick).
  * activations: batch over (pod, data); optional sequence sharding over
    "model" between superblocks (Megatron-style SP) — a TrainStepConfig
    knob and a §Perf hillclimb lever.
  * KV caches: batch over (pod, data), kv-heads over "model".

The rules are pure functions of (path, shape, mesh) so tests can assert
them without devices.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Params = Dict[str, Any]


@dataclass(frozen=True)
class ParallelConfig:
    """Knobs for the distribution strategy (hillclimb levers in §Perf)."""
    shard_sequence: bool = True          # Megatron-style SP between blocks
    shard_embed_vocab: bool = True       # vocab dim of embed/head over TP
    fsdp_params: bool = True             # shard params over (pod, data)
    cache_seq_axis: Optional[str] = None # shard cache seq (long-context decode)
    moe_buffer_mode: str = "ep"          # ep | dp | none (see parallel.context)


def mesh_axes(mesh: Mesh) -> Tuple[Tuple[str, ...], str]:
    """-> (fsdp_axes, tp_axis) present in this mesh."""
    names = mesh.axis_names
    fsdp = tuple(a for a in ("pod", "data") if a in names)
    return fsdp, "model"


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _shard_if(mesh: Mesh, dim: int, axes) -> Optional[Any]:
    """Return ``axes`` if dim divides the axis-product size, else None."""
    if axes is None:
        return None
    size = _axis_size(mesh, axes)
    return axes if (size > 1 and dim % size == 0) else None


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def param_spec(path: str, shape: Tuple[int, ...], mesh: Mesh,
               pcfg: ParallelConfig = ParallelConfig()) -> P:
    """PartitionSpec for one parameter leaf, by its tree path."""
    fsdp, tp = mesh_axes(mesh)
    if not pcfg.fsdp_params:
        fsdp = ()
    fsdp = fsdp or None
    name = path.split("/")[-1]
    stacked = path.split("/")[0] in ("stack",) or "/stack/" in path \
        or path.startswith("encoder/stack")
    lead = (None,) if stacked else ()

    def spec(*trailing):
        parts = lead + trailing
        assert len(parts) == len(shape), (path, shape, parts)
        return P(*parts)

    dims = shape[1:] if stacked else shape

    # -- embeddings / head -------------------------------------------------
    if path == "embed":
        v_ax = _shard_if(mesh, shape[0], tp) if pcfg.shard_embed_vocab else None
        return P(v_ax, _shard_if(mesh, shape[1], fsdp))
    if path == "head":
        v_ax = _shard_if(mesh, shape[1], tp) if pcfg.shard_embed_vocab else None
        return P(_shard_if(mesh, shape[0], fsdp), v_ax)
    if path == "img_proj":
        return P(None, _shard_if(mesh, shape[1], tp))

    # -- norms / scalars ---------------------------------------------------
    if name in ("scale", "step") or name.startswith("norm"):
        return P(*([None] * len(shape)))

    # -- attention -----------------------------------------------------------
    if name == "wq":
        return spec(_shard_if(mesh, dims[0], fsdp),
                    _shard_if(mesh, dims[1], tp), None)
    if name in ("wk", "wv"):
        return spec(_shard_if(mesh, dims[0], fsdp),
                    _shard_if(mesh, dims[1], tp), None)
    if name == "wo":
        return spec(_shard_if(mesh, dims[0], tp), None,
                    _shard_if(mesh, dims[2], fsdp))
    if name == "bq":
        return spec(_shard_if(mesh, dims[0], tp), None)
    if name in ("bk", "bv"):
        return spec(_shard_if(mesh, dims[0], tp), None)

    # -- dense MLP -------------------------------------------------------------
    if name in ("wg", "wu", "wi"):
        if len(dims) == 3:  # MoE expert weights (E, D, F)
            return spec(_shard_if(mesh, dims[0], tp),
                        _shard_if(mesh, dims[1], fsdp), None)
        return spec(_shard_if(mesh, dims[0], fsdp),
                    _shard_if(mesh, dims[1], tp))
    if name in ("wd", "wo_mlp"):
        if len(dims) == 3:  # MoE expert down (E, F, D)
            return spec(_shard_if(mesh, dims[0], tp), None,
                        _shard_if(mesh, dims[2], fsdp))
        return spec(_shard_if(mesh, dims[0], tp),
                    _shard_if(mesh, dims[1], fsdp))
    if name in ("bi", "bo"):
        return spec(_shard_if(mesh, dims[0], tp))
    if name == "router":
        return spec(_shard_if(mesh, dims[0], fsdp), None)

    # -- mamba -------------------------------------------------------------------
    if name == "in_proj":
        return spec(_shard_if(mesh, dims[0], fsdp),
                    _shard_if(mesh, dims[1], tp))
    if name == "out_proj":
        return spec(_shard_if(mesh, dims[0], tp),
                    _shard_if(mesh, dims[1], fsdp))
    if name == "conv_w":
        return spec(None, _shard_if(mesh, dims[1], tp))
    if name in ("conv_b", "dt_b", "D"):
        return spec(_shard_if(mesh, dims[0], tp))
    if name == "x_proj":
        return spec(_shard_if(mesh, dims[0], tp), None)
    if name == "dt_w":
        return spec(None, _shard_if(mesh, dims[1], tp))
    if name == "A_log":
        return spec(_shard_if(mesh, dims[0], tp), None)

    # default: replicate
    return P(*([None] * len(shape)))


def params_shardings(params_spec_tree: Params, mesh: Mesh,
                     pcfg: ParallelConfig = ParallelConfig()) -> Params:
    """NamedShardings mirroring a params (or ShapeDtypeStruct) tree."""
    from repro.models.module import tree_paths

    flat = {p: leaf for p, leaf in tree_paths(params_spec_tree)}
    out: Dict[str, NamedSharding] = {
        p: NamedSharding(mesh, param_spec(p, tuple(leaf.shape), mesh, pcfg))
        for p, leaf in flat.items()
    }

    def rebuild(tree: Params, prefix: str = "") -> Params:
        res: Params = {}
        for key, value in tree.items():
            path = f"{prefix}/{key}" if prefix else key
            if isinstance(value, dict):
                res[key] = rebuild(value, path)
            else:
                res[key] = out[path]
        return res

    return rebuild(params_spec_tree)


# ---------------------------------------------------------------------------
# activation / batch / cache rules
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh, global_batch: int,
               pcfg: ParallelConfig = ParallelConfig()) -> P:
    fsdp, _ = mesh_axes(mesh)
    return P(_shard_if(mesh, global_batch, fsdp), None)


def batch_shardings(batch_tree: Params, mesh: Mesh,
                    pcfg: ParallelConfig = ParallelConfig()) -> Params:
    """Shard every batch input on its leading (batch) dim."""
    fsdp, _ = mesh_axes(mesh)

    def leaf(sds):
        if sds.ndim == 0:
            return NamedSharding(mesh, P())
        ax = _shard_if(mesh, sds.shape[0], fsdp)
        return NamedSharding(mesh, P(*((ax,) + (None,) * (sds.ndim - 1))))

    return jax.tree.map(leaf, batch_tree)


def activation_spec(mesh: Mesh, batch: int, seq: int,
                    pcfg: ParallelConfig = ParallelConfig()) -> P:
    """(B, S, D) boundary-activation spec: batch over FSDP, seq over TP."""
    fsdp, tp = mesh_axes(mesh)
    b_ax = _shard_if(mesh, batch, fsdp)
    s_ax = _shard_if(mesh, seq, tp) if pcfg.shard_sequence else None
    return P(b_ax, s_ax, None)


def cache_shardings(cache_spec_tree: Params, mesh: Mesh,
                    pcfg: ParallelConfig = ParallelConfig()) -> Params:
    """KV/SSM cache shardings.

    Attention k/v: (n_sb, B, S_max, Hkv, hd) -> (None, fsdp, [seq], tp, None)
    Mamba conv:    (n_sb, B, W-1, di)        -> (None, fsdp, None, tp)
    Mamba ssm:     (n_sb, B, di, N)          -> (None, fsdp, tp, None)
    When batch == 1 (long-context decode) the batch axis is unsharded and
    the sequence axis picks up pcfg.cache_seq_axis if set.
    """
    fsdp, tp = mesh_axes(mesh)

    def leaf(sds):
        shape = sds.shape
        if len(shape) == 5:  # attention cache
            b_ax = _shard_if(mesh, shape[1], fsdp)
            s_ax = (_shard_if(mesh, shape[2], pcfg.cache_seq_axis)
                    if (b_ax is None and pcfg.cache_seq_axis) else None)
            h_ax = _shard_if(mesh, shape[3], tp)
            return NamedSharding(mesh, P(None, b_ax, s_ax, h_ax, None))
        if len(shape) == 4:  # mamba conv window (n_sb, B, W-1, di)
            b_ax = _shard_if(mesh, shape[1], fsdp)
            d_ax = _shard_if(mesh, shape[3], tp)
            return NamedSharding(mesh, P(None, b_ax, None, d_ax))
        if len(shape) == 3:
            b_ax = _shard_if(mesh, shape[0], fsdp)
            return NamedSharding(mesh, P(b_ax, None, None))
        return NamedSharding(mesh, P(*([None] * len(shape))))

    def walk(tree):
        out = {}
        for key, value in tree.items():
            if isinstance(value, dict):
                out[key] = walk(value)
            elif key == "ssm" and len(value.shape) == 4:
                # mamba state (n_sb, B, di, N): di over tp
                b_ax = _shard_if(mesh, value.shape[1], fsdp)
                d_ax = _shard_if(mesh, value.shape[2], tp)
                out[key] = NamedSharding(mesh, P(None, b_ax, d_ax, None))
            elif key == "conv" and len(value.shape) == 4:
                # mamba conv window (n_sb, B, W-1, di): di over tp
                b_ax = _shard_if(mesh, value.shape[1], fsdp)
                d_ax = _shard_if(mesh, value.shape[3], tp)
                out[key] = NamedSharding(mesh, P(None, b_ax, None, d_ax))
            else:
                out[key] = leaf(value)
        return out

    return walk(cache_spec_tree)


def opt_state_shardings(opt_spec_tree: Params, param_shardings: Params,
                        mesh: Mesh) -> Params:
    """Adam m/v mirror the parameter shardings; step is replicated."""
    return {
        "m": param_shardings,
        "v": param_shardings,
        "step": NamedSharding(mesh, P()),
    }
