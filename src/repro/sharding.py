"""Logical-axis sharding rules: params, activations, batches, caches.

Scheme (DESIGN.md §4): mesh axes ("pod", "data", "model") — or ("data",
"model") single-pod.

* batch / DP: ("pod", "data") on the leading batch dim.
* FSDP: parameters shard their non-TP matrix dim over "data".
* TP: Megatron column/row parallel over "model" (heads / ffn / experts /
  SSM inner channels / vocab).
* Params are replicated across "pod" (gradient all-reduce crosses pods;
  FSDP stays intra-pod where ICI is fast).

Everything is keyed off parameter-tree paths so models stay mesh-agnostic.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

FSDP = "data"
TP = "model"
# Pure-FSDP (ZeRO-3) layout: both mesh axes act as one data-parallel /
# parameter-shard axis; no tensor parallelism. Chosen by layout="fsdp" —
# the Perf hillclimb shows when each layout wins (EXPERIMENTS.md §Perf).
ZERO_AXES = ("data", "model")


def _spec_for_path(path: Tuple[str, ...], shape: Tuple[int, ...]) -> P:
    """PartitionSpec for a parameter, from its tree path (layer-stacked
    params get a leading None for the L axis)."""
    keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
    leaf = keys[-1]
    stacked = "layers" in keys

    def wrap(*spec):
        return P(*( (None,) + spec if stacked else spec ))

    if leaf == "embed":
        return P(TP, FSDP)
    if leaf == "lm_head":
        return P(FSDP, TP)
    if leaf in ("wq", "wk", "wv", "w1", "w3", "z_proj", "x_proj"):
        return wrap(FSDP, TP)
    if leaf in ("wo", "w2", "out_proj"):
        # MoE expert weights are 3D (E, ., .): expert-parallel over TP.
        if len(shape) - (1 if stacked else 0) == 3:
            return wrap(TP, None, FSDP) if leaf == "w2" else wrap(TP, FSDP, None)
        return wrap(TP, FSDP)
    if leaf == "router":
        return wrap(None, None)
    if leaf in ("bc_proj", "dt_proj"):
        return wrap(FSDP, None)
    if leaf in ("conv_x_w",):
        return wrap(None, TP)
    if leaf in ("conv_x_b", "norm"):       # (di,) SSM channel params
        return wrap(TP)
    if leaf in ("A_log", "D", "dt_bias"):  # (nh,)
        return wrap(TP)
    # norms, conv_bc_*, q_norm/k_norm, final_norm, scalars
    ndim = len(shape) - (1 if stacked else 0)
    return wrap(*([None] * ndim))


def param_specs(params_shape, layout: str = "tp") -> dict:
    """Pytree of PartitionSpec matching a params (or ShapeDtypeStruct)
    pytree.

    layout="tp"   (default): Megatron TP over `model` x FSDP over `data`.
                  MoE w1/w3 (E, D, F): (TP, FSDP, None); w2: (TP, None, FSDP).
    layout="fsdp": pure ZeRO-3 — the largest divisible dim of every param
                  shards over BOTH axes; activations stay batch-sharded.
    """
    if layout == "fsdp":
        return _fsdp_specs(params_shape)

    def spec(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        leaf_key = keys[-1]
        stacked = "layers" in keys
        base_ndim = len(leaf.shape) - (1 if stacked else 0)
        if leaf_key in ("w1", "w3") and base_ndim == 3:     # MoE experts
            # swep: shard_map EP needs full D/F locally (replicated on data)
            s = (TP, None, None) if layout == "swep" else (TP, FSDP, None)
        elif leaf_key == "w2" and base_ndim == 3:
            s = (TP, None, None) if layout == "swep" else (TP, None, FSDP)
        else:
            return _spec_for_path(path, leaf.shape)
        return P(*((None,) + s if stacked else s))

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def _fsdp_specs(params_shape, n_shards: int = 256) -> dict:
    """ZeRO-3: shard the first dim divisible by both axes (16*16=256) over
    ("data","model"); else first dim divisible by 16 over "data"; else
    replicate."""

    def spec(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        stacked = "layers" in keys
        dims = list(leaf.shape[1:] if stacked else leaf.shape)
        out = [None] * len(dims)
        for i, d in enumerate(dims):
            if d % n_shards == 0:
                out[i] = ZERO_AXES
                break
        else:
            for i, d in enumerate(dims):
                if d % 16 == 0:
                    out[i] = FSDP
                    break
        return P(*((None,) + tuple(out) if stacked else tuple(out)))

    return jax.tree_util.tree_map_with_path(spec, params_shape)


def data_axes(mesh: Mesh, global_batch: int) -> Optional[Tuple[str, ...]]:
    """Batch-sharding axes: as many of (pod, data) as divide the batch."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    while axes:
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        if global_batch % n == 0:
            return tuple(axes)
        axes.pop(0)   # drop pod first
    return None


def make_sharder(mesh: Mesh, dp, layout: str = "tp"):
    """Activation-sharding callback threaded through the models."""
    if layout == "fsdp":
        specs = {
            "hidden": P(dp, None, None),
            "logits": P(dp, None, None),
            "expert_in": P(None, None, None),
        }
    else:
        specs = {
            "hidden": P(dp, None, None),
            "logits": P(dp, None, TP),
            # EP over model x capacity over data: without the capacity-dim
            # sharding XLA replicates the expert einsum across the data
            # axis (~10x redundant FLOPs; EXPERIMENTS.md §Perf H-A1).
            "expert_in": P(TP, FSDP, None),
        }

    def sharder(name: str, x: jax.Array) -> jax.Array:
        spec = specs.get(name)
        if spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return sharder


def batch_specs(batch_struct, dp) -> dict:
    def spec(leaf):
        if leaf.ndim >= 3:                 # input_embeds (B, S, D)
            return P(dp, *([None] * (leaf.ndim - 1)))
        if leaf.ndim >= 1:
            return P(dp, *([None] * (leaf.ndim - 1)))
        return P()
    return jax.tree.map(spec, batch_struct)


def cache_specs(cache_struct, dp) -> dict:
    """KV / SSM cache specs: batch over dp, heads/channels over TP."""

    def spec(path, leaf):
        keys = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        leaf_key = keys[-1]
        if leaf_key in ("k", "v"):            # (L, B, Sc, nkv, hd)
            return P(None, dp, None, TP, None)
        if leaf_key in ("k_scale", "v_scale"):  # (L, B, Sc, nkv)
            return P(None, dp, None, TP)
        if leaf_key == "conv_x":              # (L, B, K-1, di)
            return P(None, dp, None, TP)
        if leaf_key == "conv_bc":             # (L, B, K-1, 2gn)
            return P(None, dp, None, None)
        if leaf_key == "h":                   # (L, B, nh, P, N)
            return P(None, dp, TP, None, None)
        if leaf_key == "positions":           # (B, Sc)
            return P(dp, None)
        if leaf_key == "index":               # (B,)
            return P(dp)
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(spec, cache_struct)


def to_named(mesh: Mesh, specs):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs, is_leaf=lambda x: isinstance(x, P))
