"""The Pallas kernel mode: the ONE definition of whether a kernel runs
compiled or interpreted.

Kernels run compiled on a TPU backend.  On the CPU backend, where the tests
run, they run under the Pallas interpreter (or, for the seg-scan kernels, as
a bit-exact jnp emulation) and a one-time warning says so.  Any other
backend is refused: a Mosaic kernel cannot compile there, and interpreting
it would hide that.  ``kernel_path`` records which path a run took.
"""
from __future__ import annotations

import warnings

import jax


class KernelInterpretFallbackWarning(UserWarning):
    """``use_kernel=True`` on the CPU backend runs the kernel's interpret/
    emulation path, not a compiled accelerator kernel — kernel timings
    measured in this mode are NOT hardware kernel performance."""


_warned_interpret_fallback = False


def resolve_kernel_interpret(interpret, *, warn: bool = True,
                             context: str = "seg_scan") -> bool:
    """Resolve an ``interpret=None`` kernel flag to the backend's mode:
    compiled (False) on TPU, interpreted (True) on CPU, an error anywhere
    else.  The CPU default emits a one-time
    ``KernelInterpretFallbackWarning`` so CPU "kernel" runs can't masquerade
    as compiled-kernel measurements; an EXPLICIT ``interpret`` is a
    deliberate choice and never warns."""
    global _warned_interpret_fallback
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend != "cpu":
        raise RuntimeError(
            f"the {context} kernel has no path on backend {backend!r}: it "
            f"compiles for a TPU and is interpreted on the CPU; pass "
            f"interpret=True to interpret it here")
    if warn and not _warned_interpret_fallback:
        _warned_interpret_fallback = True
        warnings.warn(
            f"use_kernel=True on backend 'cpu': the {context} kernel runs "
            f"in interpret/emulation mode (kernel_path='interpret'); timings "
            f"do not reflect compiled accelerator kernels",
            KernelInterpretFallbackWarning, stacklevel=3)
    return True


def kernel_path(use_kernel: bool, interpret=None):
    """The kernel path a scan configuration will actually execute:
    ``None`` (lax path), ``"compiled"``, or ``"interpret"`` — recorded in
    ``DispatchReport.kernel_path`` for honest benchmark provenance."""
    if not use_kernel:
        return None
    return "interpret" if resolve_kernel_interpret(
        interpret, warn=False) else "compiled"
