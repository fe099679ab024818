"""Segmented prefix-sum — Pallas TPU kernel (the DES scan core's hot loop).

Same chunked-scan idiom as ``ssd_scan``: within a chunk the segmented cumsum
is an (L×L) masked matmul (MXU-friendly), across chunks a single running
value is carried in scratch — the carry only survives into a chunk until its
first segment boundary.  Grid = (chunks,) sequential, so the carry lives on
chip for the whole array.

Interpret mode only: its (1, L) blocks and lane-to-scalar carry have no
Mosaic lowering, and nothing on the main path uses it (the DES core runs
the bit-identical v2 kernel, ``v2.py``).  ``interpret=False`` is refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _seg_cumsum_kernel(term_ref, reset_ref, out_ref, carry_ref):
    ci = pl.program_id(0)

    @pl.when(ci == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    term = term_ref[0].astype(jnp.float32)        # (L,)
    reset = reset_ref[0].astype(jnp.float32)      # (L,) 1.0 at segment starts
    L = term.shape[0]

    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)   # row i (output pos)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)   # col j (input pos)
    rj = reset[None, :] > 0.5                              # (1, L)

    # last segment start at-or-before i (0 if the segment spans the chunk edge)
    start_i = jnp.max(jnp.where((si <= li) & rj, si, 0), axis=1)   # (L,)
    # does ANY reset occur at-or-before i?  (kills the inter-chunk carry)
    has_reset = jnp.max(jnp.where((si <= li) & rj, 1, 0), axis=1)  # (L,)

    mask = ((si <= li) & (si >= start_i[:, None])).astype(jnp.float32)
    f_local = jax.lax.dot_general(
        mask, term[:, None], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)[:, 0]          # (L,)

    carry = carry_ref[0, 0]
    f = f_local + carry * (1.0 - has_reset.astype(jnp.float32))
    out_ref[0] = f.astype(out_ref.dtype)
    carry_ref[0, 0] = f[L - 1]


def seg_cumsum(term, reset, *, chunk: int = 128, interpret: bool = False):
    """Segmented inclusive prefix sum of ``term`` (1D), restarting wherever
    ``reset`` is nonzero.  term: (C,) f32; reset: (C,) f32 -> (C,) f32."""
    if not interpret:
        raise NotImplementedError(
            "the v1 seg_cumsum kernel runs only in interpret mode; the "
            "compiled path is seg_cumsum_v2")
    C = term.shape[0]
    chunk = min(chunk, max(C, 1))
    pad = (-C) % chunk
    if pad:
        # padded tail: term 0 / no reset — extends the last segment harmlessly
        term = jnp.pad(term, (0, pad))
        reset = jnp.pad(reset, (0, pad))
    nc = (C + pad) // chunk
    tr = term.reshape(nc, chunk).astype(jnp.float32)
    rr = reset.reshape(nc, chunk).astype(jnp.float32)

    out = pl.pallas_call(
        _seg_cumsum_kernel,
        grid=(nc,),
        in_specs=[
            pl.BlockSpec((1, chunk), lambda c: (c, 0)),
            pl.BlockSpec((1, chunk), lambda c: (c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk), lambda c: (c, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, chunk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tr, rr)
    return out.reshape(-1)[:C]
