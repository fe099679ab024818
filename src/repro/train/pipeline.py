"""Pipeline parallelism (GPipe) over a "pipe" mesh axis.

Stages own contiguous layer blocks (the stacked layer dim is sharded over
"pipe"); microbatches stream through a (n_micro + n_stages − 1)-tick schedule
inside ``shard_map``, with stage-to-stage activation transfer via
``ppermute`` — the TPU-idiomatic point-to-point.  ``jax.grad`` through the
schedule yields the reverse (backward) pipeline automatically; remat of the
stage body keeps activation memory at GPipe's O(n_micro) boundary tensors.

This composes with the data axis (DP inside each stage) and is exercised by
``tests/test_pipeline.py`` (pipe=2 × data=2: identical loss/grads vs the
non-pipelined reference) plus a 512-device dry-run smoke
(mesh (4,8,16) = ("pipe","data","model") — see EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipelined_apply(layer_fn: Callable, stacked_params, x, mesh: Mesh, *,
                    n_microbatch: int, data_axes=("data",)):
    """Run ``layer_fn(params_i, h) -> h`` over stacked layers, pipelined.

    stacked_params: pytree with leading layer dim L (L % n_stages == 0),
                    sharded over "pipe".
    x: (B, S, D) activations (B % n_microbatch == 0), sharded over data axes.
    Returns y: (B, S, D).
    """
    n_stages = mesh.shape["pipe"]
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(params_local, x_local):
        # params_local leaves: (L/n_stages, ...); x_local: (b, S, D)
        idx = jax.lax.axis_index("pipe")
        b = x_local.shape[0]
        mb = b // n_microbatch
        xs = x_local.reshape(n_microbatch, mb, *x_local.shape[1:])
        n_ticks = n_microbatch + n_stages - 1

        def stage_block(h):
            def scan_body(c, p):
                return layer_fn(p, c), None
            h, _ = jax.lax.scan(jax.checkpoint(scan_body, prevent_cse=False),
                                h, params_local)
            return h

        def tick(carry, t):
            buf, ys = carry                       # buf: activation entering
            feed_idx = jnp.clip(t, 0, n_microbatch - 1)
            fresh = jax.lax.dynamic_index_in_dim(xs, feed_idx, 0,
                                                 keepdims=False)
            inp = jnp.where(idx == 0, fresh, buf)
            out = stage_block(inp)
            # last stage emits microbatch (t - n_stages + 1) when valid
            emit_t = t - (n_stages - 1)
            valid = jnp.logical_and(idx == n_stages - 1, emit_t >= 0)
            ys = jax.lax.cond(
                valid,
                lambda ys_: jax.lax.dynamic_update_index_in_dim(
                    ys_, out, jnp.clip(emit_t, 0, n_microbatch - 1), 0),
                lambda ys_: ys_, ys)
            buf = jax.lax.ppermute(out, "pipe", fwd)
            return (buf, ys), None

        buf0 = jnp.zeros_like(xs[0])
        ys0 = jnp.zeros_like(xs)
        (_, ys), _ = jax.lax.scan(tick, (buf0, ys0), jnp.arange(n_ticks))
        # only the last stage holds real outputs; replicate via masked psum
        ys = jax.lax.psum(
            jnp.where(idx == n_stages - 1, ys, jnp.zeros_like(ys)), "pipe")
        return ys.reshape(b, *x_local.shape[1:])

    p_spec = jax.tree_util.tree_map(
        lambda l: P("pipe", *([None] * (l.ndim - 1))), stacked_params)
    x_spec = P(data_axes, None, None)
    f = jax.shard_map(body, mesh=mesh, in_specs=(p_spec, x_spec),
                      out_specs=x_spec, check_vma=False)
    return f(stacked_params, x)
