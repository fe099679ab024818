"""Jitted wrapper for the histogram kernel."""
import functools

import jax

from repro.core.compat import resolve_kernel_interpret
from repro.kernels.histogram.kernel import histogram_kernel


@functools.partial(jax.jit, static_argnames=("vocab", "block_t", "block_v"))
def histogram(tokens, vocab: int, *, block_t=8192, block_v=512):
    return histogram_kernel(tokens, vocab, block_t=block_t, block_v=block_v,
                            interpret=resolve_kernel_interpret(
                                None, warn=False, context="histogram"))
