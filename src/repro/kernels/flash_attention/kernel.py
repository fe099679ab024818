"""Flash attention forward — Pallas TPU kernel.

TPU adaptation of the (GPU) flash-attention insight: online-softmax tiling so
the (Sq, Skv) score matrix never leaves VMEM.  Tiling is chosen for the MXU
(128-aligned q/kv blocks, head_dim lanes) and the HBM→VMEM pipeline: grid =
(batch·heads, q_blocks, kv_blocks) with the kv axis innermost and sequential,
carrying the running (m, l, acc) statistics in VMEM scratch.

Causal and sliding-window masks are applied in-kernel; fully-masked kv blocks
are skipped via ``pl.when`` (so local attention does O(S·w) work, not O(S²)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               block_q: int, block_k: int, causal: bool, window: int,
               sm_scale: float, n_kv_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # block-level reachability (skips O(S^2-S*w) work for local attention):
    q_end = q_start + block_q - 1
    k_end = k_start + block_k - 1
    reachable = jnp.bool_(True)
    if causal:
        reachable = jnp.logical_and(reachable, k_start <= q_end)
    if window > 0:
        reachable = jnp.logical_and(reachable, k_end > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, ...].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, ...].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, ...].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window > 0:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                             # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur[:, None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        sm_scale=None, block_q: int = 128,
                        block_k: int = 128, interpret: bool = False):
    """q: (BH, Sq, hd); k, v: (BH, Skv, hd) — batch·heads pre-flattened."""
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    if sm_scale is None:
        sm_scale = hd ** -0.5

    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, sm_scale=sm_scale, n_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),        # m: running max
            pltpu.VMEM((block_q,), jnp.float32),        # l: running denom
            pltpu.VMEM((block_q, hd), jnp.float32),     # acc: running out
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------- backward

def _fa_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                       acc_ref, *, block_q, block_k, causal, window, sm_scale,
                       n_kv_blocks):
    """Forward that also emits log-sum-exp rows (backward residual)."""
    _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
               block_q=block_q, block_k=block_k, causal=causal, window=window,
               sm_scale=sm_scale, n_kv_blocks=n_kv_blocks)

    @pl.when(pl.program_id(2) == n_kv_blocks - 1)
    def _write_lse():
        lse_ref[0, ...] = (m_ref[...] +
                           jnp.log(jnp.maximum(l_ref[...], 1e-30)))


def flash_attention_fwd_lse(q, k, v, *, causal=True, window=0, sm_scale=None,
                            block_q=128, block_k=128, interpret=False):
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq, nk = Sq // block_q, Skv // block_k
    if sm_scale is None:
        sm_scale = hd ** -0.5
    kernel = functools.partial(
        _fa_fwd_lse_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, sm_scale=sm_scale, n_kv_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def _recompute_p_ds(q, k, lse, do, v, delta, *, q_start, k_start, block_q,
                    block_k, causal, window, sm_scale):
    """Shared backward block math: returns (p, ds) both (bq, bk) f32."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
    if window > 0:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * sm_scale
    return p, ds


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref, dq_ref,
                      acc_ref, *, block_q, block_k, causal, window, sm_scale,
                      n_kv_blocks):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start, k_start = qi * block_q, ki * block_k
    reachable = jnp.bool_(True)
    if causal:
        reachable = jnp.logical_and(reachable, k_start <= q_start + block_q - 1)
    if window > 0:
        reachable = jnp.logical_and(reachable,
                                    k_start + block_k - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        _, ds = _recompute_p_ds(q, k, lse_ref[0], do_ref[0].astype(jnp.float32),
                                v, delta_ref[0], q_start=q_start,
                                k_start=k_start, block_q=block_q,
                                block_k=block_k, causal=causal, window=window,
                                sm_scale=sm_scale)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv_blocks - 1)
    def _write():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, lse_ref, do_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                       causal, window, sm_scale, n_q_blocks):
    ki, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = qi * block_q, ki * block_k
    reachable = jnp.bool_(True)
    if causal:
        reachable = jnp.logical_and(reachable, k_start <= q_start + block_q - 1)
    if window > 0:
        reachable = jnp.logical_and(reachable,
                                    k_start + block_k - 1 > q_start - window)

    @pl.when(reachable)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        p, ds = _recompute_p_ds(q, k, lse_ref[0], do, v, delta_ref[0],
                                q_start=q_start, k_start=k_start,
                                block_q=block_q, block_k=block_k,
                                causal=causal, window=window,
                                sm_scale=sm_scale)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(qi == n_q_blocks - 1)
    def _write():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, window=0,
                        sm_scale=None, block_q=128, block_k=128,
                        interpret=False):
    """Pallas backward: (dq, dk, dv). delta = rowsum(do * out) precomputed."""
    BH, Sq, hd = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    nq, nk = Sq // block_q, Skv // block_k
    if sm_scale is None:
        sm_scale = hd ** -0.5
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, block_q=block_q, block_k=block_k,
                          causal=causal, window=window, sm_scale=sm_scale,
                          n_kv_blocks=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, i, j: (b, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, lse, do, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, window=window,
                          sm_scale=sm_scale, n_q_blocks=nq),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q), lambda b, j, i: (b, i)),
            pl.BlockSpec((1, block_q, hd), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q), lambda b, j, i: (b, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Skv, hd), k.dtype),
            jax.ShapeDtypeStruct((BH, Skv, hd), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, lse, do, delta)
    return dq, dk, dv
