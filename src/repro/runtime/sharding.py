"""Sharding rules: map every parameter / activation / cache leaf to a
PartitionSpec on the production mesh.

Policy (Megatron-style TP over `model`, DP over `data` (+`pod`), optional
FSDP/ZeRO-3 over the data axes):

  column-parallel weights (out-features sharded):  (..., d, f)  -> f: model
  row-parallel weights (in-features sharded):      (..., f, d)  -> f: model
  embeddings (V, d):                                V: model
  MoE expert stacks (L, E, d, f):                   E: model (EP)
  norms / biases / scalars:                         replicated
  FSDP: additionally shard the largest replicated dim over the data axes.

Leading layer-stack dims (from scan-stacked init) are never sharded.
Divisibility is checked against the mesh and the rule silently degrades to
replication for a dim that does not divide (e.g. tiny smoke configs).
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

# parameter-name classes (last path component)
_COL = {"wq", "wk", "wv", "w_gate", "w_up", "w_in", "w_r", "w_k", "w_v",
        "w_g", "w_q", "w_uq", "w_uk", "w_uv", "w_dq", "w_dkv", "head", "proj",
        "decay_A", "decay_B"}
_ROW = {"wo", "w_down", "w_out", "w_o"}
_EMBED = {"embed"}
# rwkv channel-mix: w_k is col (d->f), w_v is row (f->d) -- disambiguated by
# path context below; attention wv stays col.


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _fits(shape, dim: int, mesh: Mesh, axis) -> bool:
    return shape[dim] % _axis_size(mesh, axis) == 0


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel axes: ('pod','data') on multi-pod, ('data',) else."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def batch_spec(mesh: Mesh) -> P:
    da = data_axes(mesh)
    return P(da if len(da) > 1 else da[0])


def _param_spec(path: str, shape, mesh: Mesh, fsdp: bool) -> P:
    parts = path.split("||")
    name = parts[-1].strip("[]'\" .")
    rank = len(shape)
    spec = [None] * rank
    in_moe = any("moe" in p for p in parts)
    in_cm = any("cm" in p.strip("[]'\" .") == "cm" or p.strip("[]'\" .") == "cm"
                for p in parts)

    def set_if(dim, axis):
        if spec[dim] is None and _fits(shape, dim, mesh, axis):
            spec[dim] = axis
            return True
        return False

    if name in _EMBED and rank == 2:
        set_if(0, "model")
    elif in_moe and name in ("w_gate", "w_up", "w_down") and rank >= 3:
        # expert stacks: (..., E, d, f) -- shard E (EP)
        set_if(rank - 3, "model")
    elif in_cm and name == "w_v" and rank >= 2:
        set_if(rank - 2, "model")      # rwkv channel-mix down-proj: row
    elif name in _ROW and rank >= 2:
        set_if(rank - 2, "model")
    elif name in _COL and rank >= 2:
        set_if(rank - 1, "model")
    # FSDP/ZeRO-3: shard one remaining dim over the data axes
    if fsdp and rank >= 2:
        da = data_axes(mesh)
        axis = da if len(da) > 1 else da[0]
        # prefer the largest unsharded trailing dim
        dims = sorted(range(max(rank - 2, 0), rank),
                      key=lambda d: -shape[d])
        for d in dims:
            if spec[d] is None and set_if(d, axis):
                break
    return P(*spec)


def param_shardings(mesh: Mesh, params: PyTree, fsdp: bool = False) -> PyTree:
    """NamedSharding tree mirroring `params` (works on ShapeDtypeStructs)."""
    def one(path, leaf):
        key = "||".join(str(p) for p in path)
        spec = _param_spec(key, leaf.shape, mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)


def opt_state_shardings(mesh: Mesh, opt_state: PyTree,
                        fsdp: bool = False) -> PyTree:
    """AdamW moments mirror the param layout; the step counter replicates."""
    def one(path, leaf):
        key = "||".join(str(p) for p in path)
        if leaf.ndim == 0:
            return NamedSharding(mesh, P())
        spec = _param_spec(key, leaf.shape, mesh, fsdp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, opt_state)


def cache_shardings(mesh: Mesh, cache: PyTree, batch_size: int) -> PyTree:
    """Decode/prefill cache layout. Rules per leaf (leading dim is the
    layer stack for stacked caches):
      * batch dim sharded over the data axes when divisible;
      * a heads-like dim sharded over `model` when divisible;
      * long_500k (batch=1): the SEQUENCE dim shards over `data` instead
        (context parallelism) and heads over `model`.
    """
    da = data_axes(mesh)
    daxis = da if len(da) > 1 else da[0]
    d_sz = _axis_size(mesh, daxis)
    m_sz = mesh.shape["model"]

    def one(path, leaf):
        shape = leaf.shape
        rank = len(shape)
        spec = [None] * rank
        # find the batch dim: first dim equal to batch_size (after any
        # leading layer-stack dims)
        try:
            bdim = next(i for i, s in enumerate(shape) if s == batch_size)
        except StopIteration:
            bdim = None
        if bdim is not None and shape[bdim] % d_sz == 0:
            spec[bdim] = daxis
            seq_shardable = False
        else:
            seq_shardable = True  # batch unshardable: context parallelism
        # shard a heads/seq dim over model: prefer a dim divisible by m_sz
        start = (bdim + 1) if bdim is not None else 1
        for i in range(start, rank):
            if spec[i] is None and shape[i] > 1 and shape[i] % m_sz == 0:
                spec[i] = "model"
                break
        if seq_shardable:
            # context parallelism: the largest remaining dim over data
            dims = sorted(range(rank), key=lambda d: -shape[d])
            for d in dims:
                if spec[d] is None and shape[d] % d_sz == 0 and shape[d] > 1:
                    spec[d] = daxis
                    break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, cache)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ----------------------------------------------------------------------------
# serving data plane: decode-cache layout for the sharded serve step
# ----------------------------------------------------------------------------

def decode_shard_axis(path, shape, batch_size: int) -> Optional[Tuple[str, int]]:
    """Classify one decode-cache leaf for data-parallel serving.

    Returns ("state", 0) for TAF detector-state leaves and the expert row
    counter (per-shard, leading shard dim added by
    `models.lm.shard_decode_state`), ("batch", axis) for
    leaves carrying the request-lane dim (KV cache, TAF memos), or None for
    replicated leaves. Same caveat as `cache_shardings`: the batch dim is
    found as the FIRST dim equal to `batch_size`, so engines should avoid
    slot counts that collide with the layer/head/sequence extents of their
    cache (e.g. slots == n_layers on a smoke config).
    """
    from repro.models.lm import MOE_ROWS, TAF_SHARD_STATE
    parts = [str(p) for p in path]
    name = parts[-1].strip("[]'\" .") if parts else ""
    if any("taf" in p for p in parts) and name in TAF_SHARD_STATE:
        return ("state", 0)
    if name == MOE_ROWS:
        return ("state", 0)
    for i, s in enumerate(shape):
        if s == batch_size:
            return ("batch", i)
    return None


def decode_partition_specs(mesh: Mesh, cache: PyTree,
                           batch_size: int) -> PyTree:
    """PartitionSpec tree for the sharded serve step's cache argument --
    the shard_map sibling of `cache_shardings` (which builds placement
    NamedShardings for jit). TAF detector state shards its leading
    (logical-shard) dim over the data axes; batch-bearing leaves shard the
    lane dim; everything else replicates.
    """
    da = data_axes(mesh)
    daxis = da if len(da) > 1 else da[0]

    def one(path, leaf):
        kind = decode_shard_axis(path, leaf.shape, batch_size)
        if kind is None:
            return P()
        spec = [None] * len(leaf.shape)
        spec[kind[1]] = daxis
        return P(*spec)

    return jax.tree_util.tree_map_with_path(one, cache)
