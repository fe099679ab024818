"""Position-gated segmented cumsum v2 — bit-identical to the lax scan core.

The v1 kernel (``kernel.py``) computes each chunk with an (L×L) masked
matmul and rebases on an inter-chunk carry.  That is fast but reassociates
the per-segment sum, so it is only TOLERANCE-equivalent to des_scan's
``_segmented_cumsum`` — and every bit-identity guarantee (elastic replay,
journal resume, deterministic reduce) therefore pins ``use_kernel=False``.

v2 reproduces ``_segmented_cumsum``'s EXACT addition tree.  The lax core is
a position-gated Hillis–Steele doubling scan over the whole array:

    x_0       = term
    x_{j+1}(p) = x_j(p) + [pos(p) >= d] * x_j(p - d),   d = 2^j,  d < C

where ``pos`` is the element's in-segment position.  v2 splits the SAME
step set at the chunk length L (a power of two):

  * steps ``d < L`` run inside a Pallas kernel.  The flat array is laid
    out as rows of W = max(L, 128) lanes and each grid step takes an
    (8, W) block: eight consecutive rows, one vreg-aligned tile row.  The
    operand ``x_j(p - d)`` of a row's first ``d`` lanes lives in the
    previous row's last ``d`` lanes, so each level rolls the block down one
    sublane and takes row 0's operand from a ``(log2 L, 8, W)`` VMEM
    scratch that holds the previous block's last row at every level.  The
    grid is sequential, so the carry never leaves the chip.
  * steps ``d >= L`` (all multiples of L) run as plain jnp shifts on the
    flat result — a shift by a multiple of L preserves chunk-local offsets,
    so these are ordinary global Hillis–Steele steps.

The union of both step sets is exactly ``{2^j : 2^j < C}`` — the lax step
set — because ``L = min(chunk, pow2_ceil(C))`` and, for a power of two P,
``P < pow2_ceil(C)  <=>  P < C``.  Every gated-off step adds an exact 0 of
the operand dtype, so the floating-point result is BIT-identical to
``_segmented_cumsum`` for any chunk size, array length, or layout.

Execution modes (``interpret`` resolved by ``compat.resolve_kernel_interpret``):

  * compiled (TPU)          — the Pallas kernel above + jnp tail steps.
  * interpret fallback      — bit-exact jnp EMULATION: the verbatim
    ``_segmented_cumsum`` op sequence.  Off-TPU the Pallas interpreter
    pays per-grid-step Python overhead (~seconds at C=1M); the emulation
    is the same math at lax speed, so CPU runs keep the bit-identity
    contract without the interpreter tax.
  * ``force_pallas=True``   — run the REAL kernel under the Pallas
    interpreter regardless of backend; the parity suite uses this to pin
    the kernel logic itself (small C only — the interpreter unrolls the
    grid).

``scatter_finish_v2`` is the fused epilogue: sentinel masking + the
scatter back to pre-sort row order in one kernel (one pass over the
result instead of a masked select materialized between two XLA ops).  Its
per-element operands are read as scalars from SMEM; the output is held in
VMEM as (8, 128) tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compat import resolve_kernel_interpret


def _pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def _in_segment_pos(start):
    """In-segment position — the op sequence ``_segmented_cumsum`` uses
    (exact int scan), so the gate values are bit-identical."""
    from repro.core.des_scan import _segment_start_index
    return jnp.arange(start.shape[0], dtype=jnp.int32) - \
        _segment_start_index(start)


def _emulate(term, pos):
    """The lax doubling scan, gated on a precomputed ``pos`` — op-for-op the
    body of ``des_scan._segmented_cumsum`` (the parity suite pins this)."""
    C = term.shape[0]
    x = term
    d = 1
    while d < C:
        shifted = jnp.concatenate([jnp.zeros((d,), x.dtype), x[:-d]])
        x = x + jnp.where(pos >= d, shifted, jnp.zeros((), x.dtype))
        d *= 2
    return x


_ROWS = 8        # sublanes per block: each block is one (8, W) tile row
_LANES = 128     # lanes per vreg; the block width W is a multiple of it


def _scan_kernel(levels, term_ref, pos_ref, out_ref, carry_ref):
    """In-chunk steps d = 1..L/2 over an (R, W) block of R consecutive
    W-lane rows of the flat array.  The operand ``x_j(p - d)`` of lane
    ``i < d`` lives in the previous row's last ``d`` lanes: a sublane roll
    brings row r-1 under row r, and ``carry_ref[j]`` supplies row 0 with
    the PREVIOUS block's last row at level j.  The carry is saved before
    the gated add, so the next grid step sees exactly ``x_j`` of this
    block.  Every shift is a ``pltpu.roll`` plus a select: no unaligned
    slice or concatenate, so the body lowers on the chip as it runs in the
    interpreter."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = term_ref[...]                       # (R, W)
    pos = pos_ref[...]                      # (R, W) int32
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    zero = jnp.zeros((), x.dtype)
    for j in range(levels):
        d = 1 << j
        up = pltpu.roll(x, 1, 0)            # row r <- row r-1 (0 <- R-1)
        prev = jnp.where(row == 0, carry_ref[j], up)
        carry_ref[j] = up                   # row 0 holds this block's last row
        shifted = jnp.where(lane >= d, pltpu.roll(x, d, 1),
                            pltpu.roll(prev, d, 1))
        x = x + jnp.where(pos >= d, shifted, zero)
    out_ref[...] = x


def _pallas_in_chunk(term, pos, levels: int, W: int, interpret: bool):
    """Run the in-chunk levels (d < 2**levels <= W) over (R, W) blocks."""
    nr = term.shape[0] // W
    spec = pl.BlockSpec((_ROWS, W), lambda c: (c, 0))
    out = pl.pallas_call(
        functools.partial(_scan_kernel, levels),
        grid=(nr // _ROWS,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((nr, W), term.dtype),
        scratch_shapes=[pltpu.VMEM((max(levels, 1), _ROWS, W), term.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(term.reshape(nr, W), pos.reshape(nr, W))
    return out.reshape(-1)


def seg_cumsum_v2(term, start, *, chunk: int = 128,
                  interpret: Optional[bool] = None,
                  force_pallas: bool = False):
    """Segmented inclusive prefix sum of ``term`` (1D, any add-closed
    dtype), restarting where ``start`` is True — BIT-identical to
    ``des_scan._segmented_cumsum(term, start)`` on every path.

    ``chunk`` (power of two) sets the in-kernel level split L; it changes
    the execution schedule only, never the addition tree, so every chunk
    size produces the same bytes.  ``interpret=None`` resolves to the
    backend default (compiled on TPU, jnp emulation elsewhere);
    ``force_pallas`` runs the real kernel under the Pallas interpreter
    (parity testing)."""
    if chunk < 1 or (chunk & (chunk - 1)):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    C = term.shape[0]
    if C == 0:
        return term
    start = start.astype(bool) if start.dtype != jnp.bool_ else start
    pos = _in_segment_pos(start)
    interpret = resolve_kernel_interpret(interpret, warn=False)
    if interpret and not force_pallas:
        return _emulate(term, pos)

    # L = min(chunk, pow2_ceil(C)) keeps the in-kernel step set inside the
    # lax step set {2^j < C} even when one chunk covers the whole array;
    # the block is at least one full 128-lane row wide whatever L is.
    L = min(chunk, _pow2_ceil(C))
    W = max(L, _LANES)
    pad = (-C) % (_ROWS * W)
    if pad:        # tail pad: fresh zero segments; sliced off below
        term = jnp.concatenate([term, jnp.zeros((pad,), term.dtype)])
        pos = jnp.concatenate([pos, jnp.zeros((pad,), pos.dtype)])
    x = _pallas_in_chunk(term, pos, L.bit_length() - 1, W,
                         interpret=interpret and force_pallas)

    # tail steps d = L, 2L, ... while d < C — plain global shifts; padding
    # sits at the END of the array so element p < C reads exactly the same
    # operands as the unpadded lax scan.
    d = L
    while d < C:
        shifted = jnp.concatenate([jnp.zeros((d,), x.dtype), x[:-d]])
        x = x + jnp.where(pos >= d, shifted, jnp.zeros((), x.dtype))
        d *= 2
    return x[:C]


def _scatter_kernel(f_ref, order_ref, sent_ref, out_ref):
    """Fused epilogue: ``out[order[i]] = sentinel ? 0 : f[i]``.  The block's
    operands sit in SMEM, so every per-element read is a scalar load; the
    whole output stays in VMEM as (8, 128) tiles for the grid's lifetime
    and each element is a select into its tile, stored back whole.
    ``order`` (identity-padded) is a permutation of the padded range, so
    every output slot is written exactly once."""
    R, W = order_ref.shape
    sub = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 1)
    tile = _ROWS * _LANES

    def body(i, carry):
        r, c = i // W, i % W
        o = order_ref[r, c]
        val = jnp.where(sent_ref[r, c] != 0, jnp.zeros((), out_ref.dtype),
                        f_ref[r, c])
        t = o // tile
        hit = (sub == (o % tile) // _LANES) & (lane == o % _LANES)
        out_ref[t] = jnp.where(hit, val, out_ref[t])
        return carry

    jax.lax.fori_loop(0, R * W, body, 0)


def scatter_finish_v2(f, order, is_sentinel, *,
                      interpret: Optional[bool] = None,
                      force_pallas: bool = False):
    """Scatter sorted results back to original rows with the sentinel mask
    folded in: returns ``out`` with ``out[order[i]] = 0 if is_sentinel[i]
    else f[i]`` — bitwise the lax ``where`` + ``.at[order].set`` epilogue,
    in one pass.  ``order`` must be a permutation of ``range(len(f))``.
    The compiled kernel keeps the whole output in VMEM (4 bytes per row,
    under the default scoped limit up to 2**20 rows)."""
    C = f.shape[0]
    if C == 0:
        return f
    interpret = resolve_kernel_interpret(interpret, warn=False)
    if interpret and not force_pallas:
        masked = jnp.where(is_sentinel, jnp.zeros((), f.dtype), f)
        return jnp.zeros((C,), f.dtype).at[order].set(masked)

    tile = _ROWS * _LANES
    pad = (-C) % tile
    if pad:        # identity-pad the permutation; padded rows write 0
        tail = jnp.arange(C, C + pad, dtype=order.dtype)
        order = jnp.concatenate([order, tail])
        f = jnp.concatenate([f, jnp.zeros((pad,), f.dtype)])
        is_sentinel = jnp.concatenate(
            [is_sentinel, jnp.ones((pad,), is_sentinel.dtype)])
    n_tiles = (C + pad) // tile
    rows = (C + pad) // _LANES
    spec = pl.BlockSpec((_ROWS, _LANES), lambda c: (c, 0),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        _scatter_kernel,
        grid=(n_tiles,),
        in_specs=[spec, spec, spec],
        out_specs=pl.BlockSpec((n_tiles, _ROWS, _LANES), lambda c: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, _ROWS, _LANES), f.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret and force_pallas,
    )(f.reshape(rows, _LANES), order.astype(jnp.int32).reshape(rows, _LANES),
      is_sentinel.astype(jnp.int32).reshape(rows, _LANES))
    return out.reshape(-1)[:C]
