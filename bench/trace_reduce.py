"""From a profiler trace of the measured window to the numbers the per-layer
metrics read.

A trace is reduced in two steps.  ``load`` reads the ``.xplane.pb`` the JAX
profiler writes into plain lists of events: for each device plane its ops
(line ``XLA Ops``), its asynchronous ops (``Async XLA Ops``: copies and
collectives in flight) and its modules (``XLA Modules``), and every host
event.  ``reduce`` then works on those lists alone, so a test can build a
trace by hand:

- the window is the host event ``bench.window`` (the harness's own span);
- a device is busy where any of its ops runs (the union of their
  intervals, clipped to the window), and idle elsewhere; asynchronous ops
  overlap that work and do not count as busy;
- device time per module and per op (asynchronous ones included), summed
  over the device's events and averaged over the devices;
- each idle gap is attributed to the host event that covers it: the
  shortest event that covers at least half of the gap, else the one that
  overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from typing import Dict, List, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
OPS_LINE, ASYNC_LINE, MODULES_LINE = "XLA Ops", "Async XLA Ops", "XLA Modules"
GAPS_ATTRIBUTED = 400          # the longest gaps named by their host event


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]          # device plane -> op events
    async_ops: Dict[str, List[Event]]    # device plane -> async op events
    modules: Dict[str, List[Event]]      # device plane -> module events
    host: List[Event]


@dataclasses.dataclass
class Reduction:
    n_devices: int
    window_s: float
    busy_s: float                        # mean over devices
    module_s: Dict[str, float]           # mean over devices
    op_s: Dict[str, float]               # "module/op", mean over devices
    gap_s: Dict[str, float]              # host event -> idle s, mean
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, pattern: str) -> float:
        """Device seconds of the modules whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for m, s in self.module_s.items() if rx.search(m))


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {log_dir}, "
                                f"found {len(paths)}")
    ops, async_ops, modules, host = {}, {}, {}, []
    lines = {OPS_LINE: ops, ASYNC_LINE: async_ops, MODULES_LINE: modules}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].setdefault(plane.name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events if e.duration_ns > 0)
    return Trace(ops=ops, async_ops=async_ops, modules=modules, host=host)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.r_[np.nonzero(new)[0][1:] - 1, len(iv) - 1]
    return np.stack([starts, ends[last]], axis=1)


def _module_name(name: str) -> str:
    """Module events carry a run id, ``jit_f(123)``: keep ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """TPU op events carry the whole HLO instruction, ``%fusion.3 = f32[..]
    fusion(..)``: keep ``%fusion.3``."""
    return name.split(" = ", 1)[0]


def _covering(host: List[Event]):
    hs = np.array([e.start_ns for e in host], np.float64)
    he = np.array([e.end_ns for e in host], np.float64)
    names = [e.name for e in host]

    def name(a: float, b: float) -> str:
        over = np.clip(np.minimum(he, b) - np.maximum(hs, a), 0.0, None)
        if not over.size or over.max() <= 0:
            return "(no host event)"
        half = np.nonzero(over >= 0.5 * (b - a))[0]
        if half.size:
            return names[half[np.argmin((he - hs)[half])]]
        return names[int(np.argmax(over))]

    return name


def reduce(trace: Trace, top: int = 10) -> Reduction:
    """Reduce ``trace`` over its ``bench.window`` span."""
    win = [e for e in trace.host if e.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, got {len(win)}")
    w0, w1 = win[0].start_ns, win[0].end_ns
    host = [e for e in trace.host if e.end_ns > w0 and e.start_ns < w1
            and e.name != WINDOW_SPAN]
    cover = _covering(host)
    devices = sorted(trace.ops)
    n = max(len(devices), 1)
    busy, module_s, op_s, gaps = 0.0, {}, {}, []
    for dev in devices:
        ops = [e for e in trace.ops[dev] if e.end_ns > w0 and e.start_ns < w1]
        iv = np.clip(np.array([[e.start_ns, e.end_ns] for e in ops],
                              np.float64).reshape(-1, 2), w0, w1)
        merged = _union(iv)
        busy += float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9 / n
        mods = sorted((e for e in trace.modules.get(dev, [])
                       if e.end_ns > w0 and e.start_ns < w1),
                      key=lambda e: e.start_ns)
        m_start = np.array([e.start_ns for e in mods], np.float64)
        for e in mods:
            key = _module_name(e.name)
            module_s[key] = module_s.get(key, 0.0) + e.dur_ns * 1e-9 / n
        for e in ops + [e for e in trace.async_ops.get(dev, [])
                        if e.end_ns > w0 and e.start_ns < w1]:
            i = int(np.searchsorted(m_start, e.start_ns, side="right")) - 1
            mod = (_module_name(mods[i].name)
                   if i >= 0 and e.start_ns < mods[i].end_ns else "?")
            key = f"{mod}/{_op_name(e.name)}"
            op_s[key] = op_s.get(key, 0.0) + e.dur_ns * 1e-9 / n
        edges = np.r_[w0, merged.ravel(), w1].reshape(-1, 2)
        gaps.extend((a, b) for a, b in edges if b > a)
    gaps.sort(key=lambda g: g[0] - g[1])
    gap_s: Dict[str, float] = {}
    for a, b in gaps[:GAPS_ATTRIBUTED]:
        key = cover(a, b)
        gap_s[key] = gap_s.get(key, 0.0) + (b - a) * 1e-9 / n
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(n_devices=len(devices), window_s=(w1 - w0) * 1e-9,
                     busy_s=busy, module_s=module_s, op_s=op_s, gap_s=gap_s,
                     top_ops=rank(op_s), idle_gaps=rank(gap_s))
