"""Jitted wrappers for the segmented-cumsum kernels (interpret off-TPU).

The interpret default is the ONE in ``core/compat.py``
(``resolve_kernel_interpret``), the same helper des_scan's entry points use.
"""
import functools

import jax

from repro.core.compat import resolve_kernel_interpret
from repro.kernels.seg_scan.kernel import seg_cumsum
from repro.kernels.seg_scan.ref import seg_cumsum_ref
from repro.kernels.seg_scan.v2 import scatter_finish_v2, seg_cumsum_v2


@functools.partial(jax.jit, static_argnames=("chunk",))
def segmented_cumsum(term, reset, *, chunk: int = 128):
    """The legacy v1 kernel: tolerance-equivalent chunked matmul scan."""
    return seg_cumsum(term, reset, chunk=chunk,
                      interpret=resolve_kernel_interpret(None, warn=False))


@functools.partial(jax.jit, static_argnames=("chunk",))
def segmented_cumsum_v2(term, start, *, chunk: int = 128):
    """The v2 position-gated kernel: BIT-identical to
    ``des_scan._segmented_cumsum(term, start)`` on every backend."""
    return seg_cumsum_v2(term, start, chunk=chunk,
                         interpret=resolve_kernel_interpret(None, warn=False))
