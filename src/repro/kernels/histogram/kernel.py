"""Histogram (word count) — Pallas TPU kernel.

The MapReduce layer's map() hot spot: counting token occurrences.  A GPU
would use shared-memory atomics; the TPU adaptation replaces atomics with a
broadcast-compare + add (VPU-friendly).  Tokens arrive as 128-lane rows;
each row is compared against a (block_v, 128) slab of vocabulary ids and
the hits accumulate elementwise in a VMEM scratch of that shape, so the
token grid axis needs no reduction until its last step.  There one
transpose turns the slab into a lane-dense (1, block_v) row of counts.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_TILE = 8 * _LANES       # one int32 (8, 128) tile of tokens


def _hist_kernel(t_ref, o_ref, acc_ref, *, block_v: int, n_t_blocks: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v_ids = pl.program_id(0) * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_v, _LANES), 0)

    def group(g, carry):
        toks = t_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :]      # (8, 128)
        for r in range(8):
            acc_ref[...] += (toks[r:r + 1, :] == v_ids).astype(jnp.int32)
        return carry

    jax.lax.fori_loop(0, t_ref.shape[0] // 8, group, 0)

    @pl.when(ti == n_t_blocks - 1)
    def _write():
        o_ref[...] = jnp.sum(acc_ref[...].T, axis=0, keepdims=True)


def histogram_kernel(tokens, vocab: int, *, block_t: int = 8192,
                     block_v: int = 512, interpret: bool = False):
    """tokens: (T,) int32 -> counts (vocab,) int32 of the ids in [0, vocab);
    ids outside that range are not counted.

    ``block_t`` tokens and ``block_v`` vocabulary ids per grid step, each
    rounded up to whole int32 tiles (1024 tokens, 128 ids) and clamped to
    the padded problem; T and vocab are padded to whole blocks (token pad
    -1, never counted)."""
    T = tokens.shape[0]
    up = lambda n, m: -(-max(n, 1) // m) * m
    block_t = min(up(block_t, _TILE), up(T, _TILE))
    block_v = min(up(block_v, _LANES), up(vocab, _LANES))
    t_pad = up(T, block_t)
    v_pad = up(vocab, block_v)
    toks = jnp.concatenate(
        [tokens.astype(jnp.int32),
         jnp.full((t_pad - T,), -1, jnp.int32)]).reshape(-1, _LANES)
    nt, nv = t_pad // block_t, v_pad // block_v

    kernel = functools.partial(_hist_kernel, block_v=block_v, n_t_blocks=nt)
    counts = pl.pallas_call(
        kernel,
        grid=(nv, nt),
        in_specs=[pl.BlockSpec((block_t // _LANES, _LANES),
                               lambda v, t: (t, 0))],
        out_specs=pl.BlockSpec((1, block_v), lambda v, t: (0, v)),
        out_shape=jax.ShapeDtypeStruct((1, v_pad), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_v, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(toks)
    return counts[0, :vocab]
