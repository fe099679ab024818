"""Jitted wrapper: (B,S,H,hd) layout handling + TPU/interpret dispatch.

Forward AND backward are Pallas kernels (flash fwd emits log-sum-exp rows as
the backward residual; backward recomputes P blockwise — dq kernel + fused
dk/dv kernel).  The pure-jnp oracle lives in ref.py."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.compat import resolve_kernel_interpret
from repro.kernels.flash_attention.kernel import (flash_attention_bwd,
                                                  flash_attention_fwd,
                                                  flash_attention_fwd_lse)
from repro.kernels.flash_attention.ref import attention_ref


def _interpret() -> bool:
    return resolve_kernel_interpret(None, warn=False,
                                    context="flash_attention")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _fa(q, k, v, causal, window, block_q, block_k):
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


def _fa_fwd(q, k, v, causal, window, block_q, block_k):
    out, lse = flash_attention_fwd_lse(q, k, v, causal=causal, window=window,
                                       block_q=block_q, block_k=block_k,
                                       interpret=_interpret())
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, window, block_q, block_k, res, g):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, g, causal=causal,
                                     window=window, block_q=block_q,
                                     block_k=block_k, interpret=_interpret())
    return dq, dk, dv


_fa.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "q_offset", "kv_len",
                                    "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0, kv_len=None,
                    block_q=128, block_k=128):
    """q,k,v: (B,S,H,hd) — the model-side layout. GQA repeat happens upstream."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    if kv_len is not None or q_offset not in (0, None):
        # decode-style stepping is served by the XLA path (gather-bound)
        raise NotImplementedError("kernel serves full self-attention")
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(B * H, Skv, hd)
    out = _fa(qt, kt, vt, causal, window, min(block_q, Sq), min(block_k, Skv))
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
