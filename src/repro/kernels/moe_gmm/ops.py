"""Jitted wrapper for the grouped matmul kernel (TPU/interpret dispatch)."""
import functools

import jax

from repro.core.compat import resolve_kernel_interpret
from repro.kernels.moe_gmm.kernel import grouped_matmul
from repro.kernels.moe_gmm.ref import grouped_matmul_ref


def _interpret() -> bool:
    return resolve_kernel_interpret(None, warn=False, context="moe_gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gmm(x, w, block_c, block_f, block_d):
    return grouped_matmul(x, w, block_c=block_c, block_f=block_f,
                          block_d=block_d, interpret=_interpret())


def _gmm_fwd(x, w, block_c, block_f, block_d):
    return _gmm(x, w, block_c, block_f, block_d), (x, w)


def _gmm_bwd(block_c, block_f, block_d, res, g):
    # both cotangents are themselves grouped matmuls -> reuse the kernel:
    #   dx (E,C,D) = g (E,C,F) @ w^T (E,F,D);  dw (E,D,F) = x^T (E,D,C) @ g
    x, w = res
    interp = _interpret()
    dx = grouped_matmul(g, w.transpose(0, 2, 1), block_c=block_c,
                        block_f=block_d, block_d=block_f, interpret=interp)
    dw = grouped_matmul(x.transpose(0, 2, 1), g, block_c=block_d,
                        block_f=block_f, block_d=block_c, interpret=interp)
    return dx, dw


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d"))
def gmm(x, w, *, block_c=128, block_f=128, block_d=128):
    E, C, D = x.shape
    F = w.shape[-1]
    return _gmm(x, w, min(block_c, C), min(block_f, F), min(block_d, D))
