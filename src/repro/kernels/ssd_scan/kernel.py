"""Mamba2 SSD chunked scan — Pallas TPU kernel.

The CUDA mamba2 kernel is a warp-level segmented scan; the TPU adaptation
(DESIGN.md §2) uses the state-space *duality*: within a chunk the output is an
attention-like (L×L) masked matmul (MXU), across chunks a first-order state
recurrence carried in VMEM scratch.  Grid = (batch·heads, chunks) with the
chunk axis sequential, so the (P,N) state lives in VMEM for a whole sequence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (L, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (L,)
    A = a_ref[0]                                  # scalar (negative)
    B = b_ref[0, 0].astype(jnp.float32)          # (L, N)
    C = c_ref[0, 0].astype(jnp.float32)          # (L, N)

    dA = dt * A                                   # (L,)
    seg = jnp.cumsum(dA)                          # (L,)
    dtx = x * dt[:, None]                         # (L, P)

    # inter-chunk: carry-in state contribution
    state = state_ref[...]                        # (P, N)
    y_inter = jax.lax.dot_general(
        C, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * jnp.exp(seg)[:, None]  # (L,P)

    # intra-chunk: masked attention-like term
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)     # (L,L)
    L = cb.shape[0]
    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.exp(seg[:, None] - seg[None, :])
    m = jnp.where(li >= si, cb * decay, 0.0)
    y_intra = jax.lax.dot_general(m, dtx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_inter + y_intra).astype(y_ref.dtype)

    # state update: decay full chunk + inject chunk state
    decay_to_end = jnp.exp(seg[-1] - seg)         # (L,)
    new_state = state * jnp.exp(seg[-1]) + jax.lax.dot_general(
        dtx, B * decay_to_end[:, None], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)       # (P, N)
    state_ref[...] = new_state


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    """x: (BH, S, P); dt: (BH, S); A: (BH,); B, C: (BH, S, N) -> y (BH,S,P)."""
    BH, S, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    xr = x.reshape(BH, nc, chunk, P)
    dtr = dt.reshape(BH, nc, chunk)
    Br = B.reshape(BH, nc, chunk, N)
    Cr = C.reshape(BH, nc, chunk, N)

    out = pl.pallas_call(
        _ssd_kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, P), lambda b, c: (b, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, nc, chunk, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, dtr, A, Br, Cr)
    return out.reshape(BH, S, P)
