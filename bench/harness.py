"""Run one cell of ``BENCHMARK.json`` once: set up, measure, check, report.

Everything that belongs to one configuration, traffic mix, program entry or
metric is a file found by its name: ``configs/<config>.json`` (through the
``file`` of the configuration's entry), ``traffic/<mix>.json``,
``entries/<entry>.py`` (named by the mix) and ``metrics/<metric>.py``.  A
new cell is new files and entries; this module does not change.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared beside its limit.
The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Optional

import jax
import numpy as np

import generator
import trace_reduce

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")


def use_compile_cache(path: str = CACHE_DIR):
    """JAX's persistent compilation cache at its fixed path in the checkout,
    holding every program however short its compile, so that a run after
    the first one compiles nothing."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class NoChip(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(root: str, spec: dict, workload: str,
               bench_dir: str = BENCH_DIR):
    """(cell, configuration, traffic) of ``workload``, each read from its
    own file."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{cell['traffic']}.json"))
    return cell, config, traffic


def metrics_of(spec: dict, workload: str, trace: bool):
    """The metric entries this cell reports: per-layer ones when traced,
    end-to-end ones otherwise."""
    group = spec["per_layer" if trace else "end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def reader(name: str, bench_dir: str = BENCH_DIR):
    """The module ``metrics/<name>.py``, with its ``read(ctx)``."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    cell: dict
    config: dict
    traffic: dict
    setup_s: float
    window_start: float
    records: list                  # one dict per completed request
    trace: Optional[object]        # trace.Reduction of a traced run
    device_kind: str
    # JAX's traces, XLA compiles and persistent-cache loads in the window
    # (``CompileCounter.counts``, the ``window`` line)
    window_programs: dict = dataclasses.field(default_factory=dict)

    def rate(self, unit: str) -> Optional[float]:
        """All ``unit`` work completed in the window over the time from the
        window's start to the end of the last completed request."""
        done = [r for r in self.records if unit in r["work"]]
        if not done:
            return None
        span = max(r["t1"] for r in done) - self.window_start
        return sum(r["work"][unit] for r in done) / span

    def span_ms(self, name: str) -> Optional[float]:
        """Mean over requests of the program's own span ``name``, in ms."""
        vals = [r["spans"][name] for r in self.records
                if name in r.get("spans", {})]
        return 1e3 * float(np.mean(vals)) if vals else None

    def chunk_wait_ms(self) -> Optional[float]:
        """Mean dispatcher queue wait over every chunk of the window, in ms,
        from each request's ``DispatchReport.stats``."""
        waits = chunks = 0.0
        for r in self.records:
            q = ((r.get("dispatch") or {}).get("stats") or {}).get("queue")
            if q and q["n_completed"]:
                waits += q["mean_queue_length"] * q["horizon_s"]
                chunks += q["n_completed"]
        return 1e3 * waits / chunks if chunks else None


class CompileCounter:
    """Counts JAX's traces, XLA compiles and persistent-cache loads while
    ``on``."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_hits": "cache_loads"}

    def __init__(self):
        self.on = False
        self.counts = dict.fromkeys(self.EVENTS.values(), 0)
        self._timed = lambda event, duration, **kw: self._count(event)
        self._plain = lambda event, **kw: self._count(event)
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._plain)

    def _count(self, event: str):
        if self.on and event in self.EVENTS:
            self.counts[self.EVENTS[event]] += 1

    def close(self):
        jax.monitoring.unregister_event_duration_listener(self._timed)
        jax.monitoring.unregister_event_listener(self._plain)


def _devices(chips: int, require_chip: bool):
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _window(gen, seconds: float, traced: bool, counter: CompileCounter):
    """Issue requests back to back until ``seconds`` have passed; every
    request started is served to its end.  Returns (start, records,
    attempted, failed, trace directory or None)."""
    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    records, attempted, failed = [], 0, 0
    counter.on = True
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            attempted += 1
            start = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.request"):
                    rec = gen.request(attempted - 1)
            except Exception:
                failed += 1
                traceback.print_exc()
                continue
            records.append(dict(rec, t0=start, t1=time.perf_counter()))
    counter.on = False
    counter.close()
    if traced:
        jax.profiler.stop_trace()
    return t0, records, attempted, failed, log_dir


def run(root: str, spec: dict, workload: str, seed: int, seconds: float,
        traced: bool, t_start: float, require_chip: bool = True,
        bench_dir: str = BENCH_DIR, out=None, err=None) -> int:
    """Run ``workload`` once; print its result line.  Returns the exit
    code: 2 without the chips the cell needs (and no result line)."""
    out, err = out or sys.stdout, err or sys.stderr
    cell, config, traffic = cell_files(root, spec, workload, bench_dir)
    wanted = metrics_of(spec, workload, traced)
    readers = {m["name"]: reader(m["name"], bench_dir) for m in wanted}
    entry = generator.entry_class(traffic["entry"], bench_dir)
    phases = {"start and imports": time.perf_counter() - t_start}
    t = time.perf_counter()
    try:
        devices = _devices(int(cell["chips"]), require_chip)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=err)
        return 2
    phases["devices"] = time.perf_counter() - t

    gen = entry(config, traffic, seed, devices, collect_stats=traced)
    counter = CompileCounter()
    gen.setup()
    setup_s = time.perf_counter() - t_start
    phases.update(gen.phases)
    print(f"bench: set-up {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in phases.items()), file=err)
    t0, records, attempted, failed, log_dir = _window(gen, seconds, traced,
                                                      counter)
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                      for d in devices)
    print("window " + json.dumps(dict(counter.counts, requests=len(records),
                                      seconds=seconds)), file=out)
    print(f"bench: window compiles {counter.counts}", file=err)

    reduction = None
    if log_dir:
        reduction = trace_reduce.reduce(trace_reduce.load(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        if reduction.n_devices == 0:        # no accelerator plane: no device
            reduction = None                # metric is read from the host
        else:
            mods = sorted(reduction.module_s.items(), key=lambda kv: -kv[1])
            print("bench: device modules " + json.dumps(mods[:20]), file=err)
    gen.release()
    rng = np.random.default_rng(generator.derive(seed, 8))
    values = gen.check(rng)
    limits = config["limits"]
    missing = sorted(set(values) - set(limits))
    if missing:
        raise KeyError(f"no limit for {missing} in the configuration")
    correct = bool(failed == 0 and records
                   and all(float(values[k]) <= float(limits[k])
                           for k in values))

    ctx = Context(cell=cell, config=config, traffic=traffic, setup_s=setup_s,
                  window_start=t0, records=records, trace=reduction,
                  device_kind=devices[0].device_kind,
                  window_programs=dict(counter.counts))
    metrics = {}
    for m in wanted:
        v = readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if reduction is not None:
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        line["breakdown"] = {"device_ops": [list(x) for x in
                                            reduction.top_ops],
                             "idle_gaps": [list(x) for x in
                                           reduction.idle_gaps]}
    line["checks"] = {k: {"value": values[k], "limit": limits[k]}
                      for k in sorted(values)}
    for k in sorted(values):
        print(f"check {k} {values[k]!r} limit {limits[k]!r}", file=err)
    err.flush()
    print(json.dumps(line), file=out)
    out.flush()
    return 0
