"""Grouped (expert) matmul — Pallas TPU kernel.

The MoE hot spot: ``(E, C, D) @ (E, D, F) -> (E, C, F)`` — one matmul per
expert over its capacity slice.  TPU adaptation of CUDA "megablocks"-style
grouped GEMM: instead of a ragged block table (GPU SM scheduling), the expert
dim is the outer *parallel* grid axis and each (c, f) tile accumulates over
D-tiles in VMEM scratch — the MXU-aligned blocking is (block_c × block_d) ×
(block_d × block_f).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_d_blocks: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0, ...].astype(jnp.float32)      # (bc, bd)
    w = w_ref[0, ...].astype(jnp.float32)      # (bd, bf)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(di == n_d_blocks - 1)
    def _write():
        o_ref[0, ...] = acc_ref[...].astype(o_ref.dtype)


def grouped_matmul(x, w, *, block_c: int = 128, block_f: int = 128,
                   block_d: int = 128, interpret: bool = False):
    """x: (E, C, D); w: (E, D, F) -> (E, C, F)."""
    E, C, D = x.shape
    _, _, F = w.shape
    block_c = min(block_c, C)
    block_f = min(block_f, F)
    block_d = min(block_d, D)
    assert C % block_c == 0 and F % block_f == 0 and D % block_d == 0
    nc, nf, nd = C // block_c, F // block_f, D // block_d

    kernel = functools.partial(_gmm_kernel, n_d_blocks=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda e, c, f, d: (e, c, d)),
            pl.BlockSpec((1, block_d, block_f), lambda e, c, f, d: (e, d, f)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f),
                               lambda e, c, f, d: (e, c, f)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
