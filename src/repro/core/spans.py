"""Named spans and JAX compile counts for the program's own stages.

``span(name, stats=None, **args)`` times a block of host code and opens a
``jax.profiler.TraceAnnotation`` over it: while a profiler runs, the span
lands on the profiler's host plane, beside the device planes, with
``args`` as its metadata; otherwise that costs one check.  Given a
``DispatchStats`` collector it also records the span there: count, total
seconds and self seconds (total minus the spans nested in it on the same
thread).  ``jax_counts()`` reads this thread's running totals of JAX
traces, XLA compiles and persistent-cache loads, from one process-wide
``jax.monitoring`` listener installed on first use.
"""
from __future__ import annotations

import threading
import time

import jax

_local = threading.local()


class span:
    """``with span("dispatch.launch", stats, chunk=3) as s: ...``; then
    ``s.seconds`` holds the block's duration."""

    __slots__ = ("name", "stats", "seconds", "_child_s", "_t0", "_trace")

    def __init__(self, name: str, stats=None, **args):
        self.name, self.stats = name, stats
        self.seconds = self._child_s = 0.0
        self._trace = jax.profiler.TraceAnnotation(name, **args)

    def annotate(self, **args) -> None:
        """Add metadata known only inside the block (e.g. ``built=1``)."""
        self._trace.set_metadata(**args)

    def __enter__(self) -> "span":
        stack = _local.__dict__.setdefault("spans", [])
        stack.append(self)
        self._trace.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._trace.__exit__(*exc)
        stack = _local.spans
        stack.pop()
        if stack:
            stack[-1]._child_s += self.seconds
        if self.stats is not None:
            self.stats.record_span(self.name, self.seconds,
                                   self.seconds - self._child_s)


# ------------------------------------------------------------ JAX counters

_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
           "/jax/core/compile/backend_compile_duration": "programs",
           "/jax/compilation_cache/cache_hits": "cache_loads"}


class _Counts(threading.local):
    def __init__(self):
        self.traces = self.programs = self.cache_loads = 0


_counts = _Counts()
_install_lock = threading.Lock()
_installed = False


def _on_event(event: str, *_, **__) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        setattr(_counts, key, getattr(_counts, key) + 1)


def jax_counts() -> dict:
    """This thread's totals since the listener was installed: ``traces``
    (jaxprs traced), ``compiles`` (programs XLA compiled) and
    ``cache_loads`` (programs loaded from the persistent cache instead).
    A stream reads the difference over its own run."""
    global _installed
    with _install_lock:
        if not _installed:
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _installed = True
    # JAX times a cache load as a backend compile too: count it once
    return {"traces": _counts.traces,
            "compiles": _counts.programs - _counts.cache_loads,
            "cache_loads": _counts.cache_loads}
