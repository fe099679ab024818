"""Jitted wrapper for the SSD scan kernel."""
import functools

import jax

from repro.core.compat import resolve_kernel_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, A, B, C, chunk):
    return ssd_scan(x, dt, A, B, C, chunk=chunk,
                    interpret=resolve_kernel_interpret(
                        None, warn=False, context="ssd_scan"))


def _ssd_fwd(x, dt, A, B, C, chunk):
    return _ssd(x, dt, A, B, C, chunk), (x, dt, A, B, C)


def _ssd_bwd(chunk, res, g):
    x, dt, A, B, C = res
    _, vjp = jax.vjp(ssd_ref, x, dt, A, B, C)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, A, B, C, *, chunk=128):
    return _ssd(x, dt, A, B, C, chunk)
