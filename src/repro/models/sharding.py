"""Sharding rules: parameter / cache / batch PartitionSpecs.

Layout on the production mesh (pod, data, model):

  * DP over ("pod", "data") for activations and the gradient allreduce.
  * FSDP (ZeRO-3): parameters, gradients and optimizer state sharded
    over "data" on their first non-TP dimension.
  * TP (Megatron): attention heads / FFN width over "model";
    paired projections are row/col-parallel so each block needs exactly
    one reduce per sublayer.
  * EP: MoE expert dimension over "model" (experts never co-reside with
    the TP shards they would conflict with: expert weights are 3D
    (E, D, F) with E on "model", D on "data").
  * KV caches: batch over DP, sequence over "model" (decode-time TP has
    little head parallelism to exploit for GQA kv=8, so the cache's big
    axis -- sequence -- takes the model axis instead; attention scores
    are then reduced over "model" by GSPMD).

Every rule degrades gracefully: a dimension that does not divide its
mesh axes is replicated instead (``_fit``), so odd vocabularies
(whisper's 51865) and head counts (smollm's 15) lower cleanly.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import ModelConfig


def dp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _fit(mesh: Mesh, shape, dims):
    """Null out spec dims that don't divide the dimension size."""
    out = []
    for size, d in zip(shape, dims):
        out.append(d if d and size % _axis_size(mesh, d) == 0 else None)
    return P(*out)


# --------------------------- parameter rules ---------------------------

def _leaf_spec(mesh, path, leaf, fsdp="data", tp="model"):
    names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    name = names[-1]
    parent = names[-2] if len(names) > 1 else None
    nd = leaf.ndim
    stacked = ("units" in names or "enc" in names or "dec" in names)
    base = nd - 1 if stacked else nd

    def spec(*dims):
        dims = (None,) * (nd - len(dims)) + tuple(dims)  # leading stack dims
        return _fit(mesh, leaf.shape, dims)

    if name == "embed":
        # vocab over TP: the lookup becomes a partitioned gather
        # (mask + psum over the model axis), and — decisive for train
        # memory — logits and their gradients stay vocab-sharded.
        # (V-replicated layouts force a full (B,S,V) logits-grad
        # all-gather per device: ~160 GB/dev for qwen3 multi-pod.
        # Sharding BOTH dims instead trips involuntary full
        # rematerialization in the partitioner.)
        return spec(tp, None)
    if name == "head":
        return spec(None, tp)
    if name in ("wq", "wk", "wv", "gate", "up", "wg", "wx", "in_x", "in_g",
                "w_ig", "w_rg", "wi", "wf"):
        if parent in ("moe",) or base == 3:
            # (E, D, F): EP over the expert dim when it divides the
            # model axis; otherwise fall back to TP on the FFN width
            # (e.g. grok's 8 experts < 16-way model axis).
            E = leaf.shape[-3]
            if tp and E % _axis_size(mesh, tp) == 0:
                return spec(tp, fsdp, None)
            return spec(None, fsdp, tp)
        return spec(fsdp, tp)
    if name in ("wo", "down", "out"):
        if parent in ("moe",) or base == 3:
            E = leaf.shape[-3]
            if tp and E % _axis_size(mesh, tp) == 0:
                return spec(tp, None, fsdp)
            return spec(None, tp, fsdp)
        return spec(tp, fsdp)
    if name == "router":
        return spec(fsdp, None)
    if name == "r":                            # sLSTM recurrent (H, hd, 4hd)
        return spec(None, None, tp)
    if name == "conv_w":
        return spec(None, tp)
    # norms, biases, lambdas, scalars: replicate
    return P(*([None] * nd))


def roles(mesh: Mesh, mode: str = "2d"):
    """Map sharding mode -> (fsdp_axes, tp_axis).

    "2d" (default): FSDP over "data", TP over "model".
    "fsdp_all": pure ZeRO-3 — parameters sharded over data x model, no
        tensor parallelism; activations take the model axis as sequence
        parallelism (see batch_specs).  Kills the per-layer TP
        reductions, which suits small collective-bound models."""
    if mode == "2d":
        return "data", "model"
    if mode == "fsdp_all":
        return ("data", "model"), None
    raise ValueError(mode)


def param_specs(cfg: ModelConfig, params, mesh: Mesh, mode: str = "2d"):
    """PartitionSpec pytree matching ``params`` (works on shapes from
    jax.eval_shape too)."""
    fsdp, tp = roles(mesh, mode)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _leaf_spec(mesh, path, leaf, fsdp=fsdp, tp=tp),
        params)


def param_shardings(cfg, params, mesh, mode: str = "2d"):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(cfg, params, mesh, mode))


# ----------------------------- cache rules -----------------------------

def _cache_leaf_spec(mesh, path, leaf, tp="model"):
    names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
    name = names[-1]
    dp = dp_axes(mesh)
    nd = leaf.ndim
    stacked = "units" in names or "self" in names
    lead = 1 if stacked else 0

    def spec(*dims):
        dims = (None,) * lead + tuple(dims)
        dims = dims + (None,) * (nd - len(dims))
        return _fit(mesh, leaf.shape, dims)

    if name in ("k", "v", "k_scale", "v_scale"):
        return spec(dp, tp)           # (B, S, G, ...): batch DP, seq TP
    if name == "C":                   # mLSTM (B, H, hd, hd)
        return spec(dp, None, tp)
    if name in ("n", "h", "c", "m"):  # recurrent states (B, ...)
        return spec(dp)
    if name == "conv":                # (B, w-1, D)
        return spec(dp, None, tp)
    if name == "pos":
        return P(*([None] * nd))
    return spec(dp)


def cache_specs(cfg: ModelConfig, cache, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _cache_leaf_spec(mesh, path, leaf), cache)


# ----------------------------- batch rules -----------------------------

def batch_specs(batch, mesh: Mesh, mode: str = "2d"):
    dp = dp_axes(mesh)
    seq_axis = "model" if mode == "fsdp_all" else None

    def one(path, leaf):
        nd = leaf.ndim
        dims = (dp, seq_axis) + (None,) * (nd - 2) if nd >= 2 \
            else (dp,)
        return _fit(mesh, leaf.shape, dims[:nd])

    return jax.tree_util.tree_map_with_path(one, batch)
