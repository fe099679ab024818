"""Expert-parallel MoE via shard_map — the beyond-paper optimized path.

The baseline ``sliced`` implementation (moe.py) is written against *global*
arrays: its dispatch gathers tokens across the data axis (XLA inserts a token
all-gather) and its expert einsum is replicated over the data axis — the
roofline parser shows ~dp× redundant compute and a collective-bound step.

This implementation applies the paper's own principle (``executeOnKeyOwner``:
ship logic to the data) explicitly with shard_map:

  * tokens NEVER move: each (pod, data) shard routes and dispatches its own
    tokens (the matchmaking broker runs member-locally, §3.1.1's
    "partition-aware" execution);
  * expert weights are sharded over the model axis — on the expert dim when
    E % tp == 0 (olmoe 64, jamba 16), else on the FFN dim (grok 8 < 16);
  * each model shard computes its share and the combine is one ``psum`` over
    the model axis (the only collective the layer needs besides the usual
    FSDP weight gather).

Per-device FLOPs drop dp× vs the baseline; the token all-gather disappears.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.moe import matchmaking_route
from repro.models.shard_ctx import current_rules


def ep_weight_layout(cfg, tp: int) -> str:
    """'expert' — shard E over model; 'ffn' — shard d_ff_expert over model."""
    return "expert" if cfg.n_experts % max(tp, 1) == 0 else "ffn"


def moe_block_ep(params, x, cfg, *, compute_dtype=jnp.bfloat16):
    """Drop-in for moe_block. Requires an active sharding context (mesh)."""
    ctx = current_rules()
    if ctx is None:
        from repro.models.moe import moe_block
        return moe_block(params, x, cfg, compute_dtype=compute_dtype,
                         moe_impl="sliced")
    mesh, rules = ctx
    tp = mesh.shape.get("model", 1)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    layout = ep_weight_layout(cfg, tp)
    E, K = cfg.n_experts, cfg.n_experts_active

    if layout == "expert":
        w_spec = P("model", "data", None)      # (E, d, f): E over model
        wo_spec = P("model", None, "data")     # (E, f, d)
    else:
        w_spec = P(None, "data", "model")      # (E, d, f): f over model
        wo_spec = P(None, "model", "data")
    x_spec = P(dp_axes, None, None)
    r_spec = P("data", None)                   # router (d, E): FSDP over d

    def body(xl, wr, wg, wi, wo):
        # gather the FSDP (data-axis) weight shards — per-layer, bf16
        wr = jax.lax.all_gather(wr, "data", axis=0, tiled=True)
        wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
        wi = jax.lax.all_gather(wi, "data", axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, "data", axis=2, tiled=True)
        wr = wr.astype(compute_dtype)
        wg = wg.astype(compute_dtype)
        wi = wi.astype(compute_dtype)
        wo = wo.astype(compute_dtype)

        Bl, Sl, D = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, D)
        logits = xf @ wr                                     # (T, E)
        capacity = max(8, min(int(cfg.capacity_factor * T * K / E), T))
        probs, ids, keep, pos = matchmaking_route(logits, K, capacity)

        flat_ids = ids.reshape(-1)
        flat_pos = pos.reshape(-1)
        flat_keep = keep.reshape(-1)
        slot = jnp.where(flat_keep, flat_ids * capacity + flat_pos,
                         E * capacity)
        slot_tok = jnp.zeros(E * capacity + 1, jnp.int32).at[slot].set(
            jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
        slot_used = jnp.zeros(E * capacity + 1, jnp.bool_).at[slot].set(
            True, mode="drop")
        slot_tok, slot_used = slot_tok[:-1], slot_used[:-1]
        x_disp = jnp.take(xf, slot_tok, axis=0) * slot_used[:, None].astype(
            xf.dtype)
        E_loc = wg.shape[0]                                   # E or E/tp
        x_disp = x_disp.reshape(E, capacity, D)
        if layout == "expert":
            # this model shard owns experts [e0, e0+E_loc)
            eidx = jax.lax.axis_index("model") * E_loc
            x_disp = jax.lax.dynamic_slice_in_dim(x_disp, eidx, E_loc, 0)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_disp, wg)) * jnp.einsum(
            "ecd,edf->ecf", x_disp, wi)
        y_disp = jnp.einsum("ecf,efd->ecd", h, wo)

        if layout == "expert":
            # re-embed this shard's expert slices at their global offsets so
            # the local gather-combine sees zeros for unowned experts
            y_all = jnp.zeros((E, capacity, D), y_disp.dtype)
            y_all = jax.lax.dynamic_update_slice_in_dim(y_all, y_disp, eidx, 0)
        else:
            y_all = y_disp                                     # f-partial sums

        # local combine, then ONE psum of token-sized activations (model axis)
        gather_slot = jnp.where(flat_keep, flat_ids * capacity + flat_pos, 0)
        y_tok = jnp.take(y_all.reshape(E * capacity, D), gather_slot, axis=0)
        w = (probs.reshape(-1) * flat_keep).astype(compute_dtype)
        y = (y_tok * w[:, None]).reshape(T, K, D).sum(axis=1)
        y = jax.lax.psum(y, "model")
        return y.reshape(Bl, Sl, D)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(x_spec, r_spec, w_spec, w_spec, wo_spec),
                      out_specs=x_spec, check_vma=False)
    return f(x, params["w_router"], params["we_gate"], params["we_in"],
             params["we_out"])
