"""Production meshes and sharding resolution.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single pod: (16,16) = ("data","model") — 256 chips.
Multi-pod: (2,16,16) = ("pod","data","model") — 512 chips; the "pod" axis is
pure data parallelism in the paper-faithful baseline (pods ≈ Cloud²Sim
clusters; cross-pod traffic limited to gradient reduction).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.param import axis_rules, resolve_shardings, resolve_spec


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / local runs)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


# ------------------------------------------------------------- sharding trees

def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_axis_size(mesh: Mesh) -> int:
    n = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        n *= mesh.shape["pod"]
    return n


def param_shardings(model, mesh: Mesh, fsdp_pod: bool = False,
                    overrides: dict = None):
    return resolve_shardings(model.defs(), model.cfg.policy, mesh,
                             fsdp_pod=fsdp_pod, overrides=overrides)


def state_shardings(model, mesh: Mesh, fsdp_pod: bool = False,
                    overrides: dict = None):
    p = param_shardings(model, mesh, fsdp_pod=fsdp_pod, overrides=overrides)
    return {"params": p, "opt": {"m": p, "v": p},
            "step": NamedSharding(mesh, P())}


def batch_sharding(mesh: Mesh, ndim: int, *, shard_batch=True):
    b = batch_axes(mesh) if shard_batch else None
    return NamedSharding(mesh, P(b, *([None] * (ndim - 1))))


def cache_shardings(model, mesh: Mesh, batch: int, caches_tree=None):
    """Shardings for the stacked cache pytree.

    Large-batch decode: batch over (pod,data), heads/channels over model.
    Small-batch long-context (B < data extent): KV sequence over data (SP) —
    distributed flash-decode emerges from the SPMD partial-softmax reduction.
    """
    cfg = model.cfg
    seq_parallel = batch < data_axis_size(mesh)
    b_ax = None if seq_parallel else batch_axes(mesh)
    # KV sequence is ALWAYS sharded over "model" (distributed flash-decode:
    # the softmax over the sharded KV axis lowers to partial-sum+all-reduce);
    # long-context small-batch cells additionally take the "data" axis (SP).
    s_ax = ("data", "model") if seq_parallel else "model"

    def spec_for(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if any(n in ("k", "v") for n in names):        # (L,B,S,KV,hd)
            return P(None, b_ax, s_ax, None, None)
        if "state" in names:                           # (L,B,H,P,N)
            return P(None, b_ax, "model" if cfg.policy == "tp" else None,
                     None, None)
        if "conv_x" in names:                          # (L,B,w-1,C)
            return P(None, b_ax, None, "model" if cfg.policy == "tp" else None)
        if "conv_bc" in names:
            return P(None, b_ax, None, None)
        return P(*([None] * leaf.ndim))

    if caches_tree is None:   # structure template only
        caches_tree = jax.eval_shape(lambda: model.make_caches(batch, max_len=8))
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, spec_for(path, leaf)),
        caches_tree)
