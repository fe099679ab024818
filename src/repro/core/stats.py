"""Queueing-theoretic observability for the dispatch pipeline.

The thesis scales its Hazelcast/Infinispan clusters on coarse load probes;
the production-grade alternative (the Queueing middleware pattern: windowed
stats with warm-up/cool-down trimming, per-stage latency decomposition,
log-bucketed percentile histograms, operational-law bottleneck analysis)
lives here.  Three layers:

  ``StatsWindow``     append-only sample window with warm-up/cool-down
                      trimming: the first ``warmup`` and last ``cooldown``
                      samples are excluded from every statistic, so compile
                      transients and end-of-stream drain effects never skew
                      the percentiles the scaler reads.
  ``Histogram`` /     log-bucketed (geometric) histograms — p50/p95/p99 in
  ``HistogramSet``    O(buckets) memory with bounded relative error: the
                      reported quantile q̂ satisfies q ≤ q̂ ≤ q·growth for
                      in-range samples.
  ``DispatchStats``   the per-stream collector ``ElasticDispatcher.submit``
                      stamps at its four pipeline stages —

                        enqueue   chunk admitted to the dispatch queue
                                  (stream start, or requeue on retry/replay)
                        dispatch  chunk launched (staged + compiled + the
                                  async dispatch call issued)
                        retire    chunk's device computation completed
                                  (``block_until_ready`` returned)
                        validate  validation finished (probe and deadline
                                  on an armed stream, right after retire
                                  otherwise); the reduce boundary closes
                                  the stream

                      and turns into decomposed latencies (queue wait vs
                      service vs validation), arrival/throughput rates,
                      utilization, and time-averaged queue lengths via the
                      OPERATIONAL laws — no distributional assumption:
                      Little's law L = λW holds exactly on the recorded
                      event log because ∫N(t)dt = Σ sojourn_i when the
                      horizon covers every record.

On top sit the analytic M/M/n helpers (``erlang_c``, ``mmn_metrics``,
``mmn_required_members``) and the queue-aware scaling signal ``mmn_load``
that ``HealthConfig(policy="mmn")`` feeds to the IAS: measured per-member
service rate + demand arrival rate + queue backlog instead of a wall-time
EMA alone.  Tier-1 tests drive synthetic jobs of known service-time
distribution through this layer and pin the measured utilization and queue
length to the Erlang-C predictions (tests/test_stats.py).

Instrumentation is pure host-side timestamping — it never touches chunk
payloads, shapes, or reduce order, so streamed results are BIT-identical
with stats enabled (pinned by test_stats_instrumentation_bit_identical).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# interval names derived from the four stage stamps
INTERVALS = ("queue_wait", "service", "validate", "sojourn")


# --------------------------------------------------------------- StatsWindow

class StatsWindow:
    """Append-only sample window with warm-up/cool-down trimming.

    ``warmup`` samples at the head and ``cooldown`` at the tail are excluded
    from every statistic (the Queueing-middleware pattern: the measurement
    phase must not include ramp-up or drain transients).  Both accept an
    int (sample count) or a float in (0, 1) (fraction of samples, rounded
    down).  All statistics are computed over the trimmed view; ``raw()``
    exposes everything.
    """

    def __init__(self, warmup: float = 0, cooldown: float = 0):
        if warmup < 0 or cooldown < 0:
            raise ValueError("warmup/cooldown must be >= 0")
        self.warmup = warmup
        self.cooldown = cooldown
        self._samples: List[float] = []

    def add(self, value: float) -> None:
        self._samples.append(float(value))

    def extend(self, values: Sequence[float]) -> None:
        for v in values:
            self.add(v)

    def __len__(self) -> int:
        return len(self._samples)

    def _trim_counts(self) -> Tuple[int, int]:
        n = len(self._samples)
        w = (int(self.warmup * n) if isinstance(self.warmup, float)
             and self.warmup < 1 else int(self.warmup))
        c = (int(self.cooldown * n) if isinstance(self.cooldown, float)
             and self.cooldown < 1 else int(self.cooldown))
        return w, c

    def raw(self) -> np.ndarray:
        return np.asarray(self._samples, np.float64)

    def trimmed(self) -> np.ndarray:
        """The measurement phase: samples[warmup : n - cooldown] (empty when
        trimming consumes the window — statistics then return NaN)."""
        n = len(self._samples)
        w, c = self._trim_counts()
        if w + c >= n:
            return np.empty(0, np.float64)
        return np.asarray(self._samples[w:n - c], np.float64)

    def mean(self) -> float:
        t = self.trimmed()
        return float(t.mean()) if t.size else float("nan")

    def std(self) -> float:
        t = self.trimmed()
        return float(t.std()) if t.size else float("nan")

    def percentile(self, q: float) -> float:
        t = self.trimmed()
        return float(np.percentile(t, q)) if t.size else float("nan")

    def summary(self) -> Dict[str, float]:
        t = self.trimmed()
        if not t.size:
            return {"n": 0.0, "mean": float("nan"), "p50": float("nan"),
                    "p95": float("nan"), "p99": float("nan")}
        return {"n": float(t.size), "mean": float(t.mean()),
                "p50": float(np.percentile(t, 50)),
                "p95": float(np.percentile(t, 95)),
                "p99": float(np.percentile(t, 99))}


# ----------------------------------------------------------------- Histogram

class Histogram:
    """Log-bucketed histogram: geometric buckets from ``lo`` to ``hi`` with
    ratio ``growth``.  ``quantile(q)`` reports the upper edge of the bucket
    holding the q-th sample, clamped to the observed [min, max] — for
    samples inside [lo, hi] the estimate q̂ obeys  q_true ≤ q̂ ≤
    q_true·growth  (the bounded-relative-error contract the property tests
    pin).  Sub-``lo`` samples land in an underflow bucket reported as
    ``lo``; super-``hi`` samples land in an overflow bucket reported as the
    observed max."""

    def __init__(self, lo: float = 1e-6, hi: float = 1e4,
                 growth: float = 1.25):
        if not (lo > 0 and hi > lo and growth > 1):
            raise ValueError("need 0 < lo < hi and growth > 1")
        self.lo, self.hi, self.growth = float(lo), float(hi), float(growth)
        self._log_lo = math.log(lo)
        self._log_g = math.log(growth)
        self.n_buckets = int(math.ceil((math.log(hi) - self._log_lo)
                                       / self._log_g))
        # [0] underflow, [1..n_buckets] log buckets, [-1] overflow
        self.counts = np.zeros(self.n_buckets + 2, np.int64)
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def _bucket(self, v: float) -> int:
        if v <= self.lo:
            return 0
        if v > self.hi:
            return self.n_buckets + 1
        # ceil so bucket b's range is (lo·g^(b-1), lo·g^b]
        b = int(math.ceil((math.log(v) - self._log_lo) / self._log_g))
        return min(max(b, 1), self.n_buckets)

    def edge(self, bucket: int) -> float:
        """Upper edge of ``bucket`` (underflow -> lo, overflow -> hi)."""
        if bucket <= 0:
            return self.lo
        if bucket > self.n_buckets:
            return self.hi
        return self.lo * self.growth ** bucket

    def add(self, value: float) -> None:
        v = float(value)
        if not math.isfinite(v) or v < 0:
            raise ValueError(f"histogram samples must be finite and >= 0, "
                             f"got {value!r}")
        self.counts[self._bucket(v)] += 1
        self.count += 1
        self.total += v
        self.min = min(self.min, v)
        self.max = max(self.max, v)

    def quantile(self, q: float) -> float:
        """Upper bucket edge at cumulative count ⌈q·n⌉, clamped to the
        observed extrema; NaN when empty."""
        if self.count == 0:
            return float("nan")
        rank = max(int(math.ceil(q / 100.0 * self.count)), 1)
        cum = np.cumsum(self.counts)
        bucket = int(np.searchsorted(cum, rank))
        if bucket > self.n_buckets:
            return self.max               # overflow: report the observed max
        return float(min(max(self.edge(bucket), self.min), self.max))

    def mean(self) -> float:
        return self.total / self.count if self.count else float("nan")

    def merge(self, other: "Histogram") -> None:
        if (other.lo, other.hi, other.growth) != (self.lo, self.hi,
                                                  self.growth):
            raise ValueError("cannot merge histograms with different buckets")
        self.counts += other.counts
        self.count += other.count
        self.total += other.total
        self.min = min(self.min, other.min)
        self.max = max(self.max, other.max)

    def summary(self) -> Dict[str, float]:
        return {"n": float(self.count), "mean": self.mean(),
                "p50": self.quantile(50), "p95": self.quantile(95),
                "p99": self.quantile(99)}


class HistogramSet:
    """Named histograms sharing one bucket layout — one per pipeline stage /
    derived interval, created on first record."""

    def __init__(self, lo: float = 1e-6, hi: float = 1e4,
                 growth: float = 1.25):
        self.lo, self.hi, self.growth = lo, hi, growth
        self.hists: Dict[str, Histogram] = {}

    def record(self, name: str, value: float) -> None:
        h = self.hists.get(name)
        if h is None:
            h = self.hists[name] = Histogram(self.lo, self.hi, self.growth)
        h.add(value)

    def __getitem__(self, name: str) -> Histogram:
        return self.hists[name]

    def __contains__(self, name: str) -> bool:
        return name in self.hists

    def quantiles(self, qs: Sequence[float] = (50, 95, 99)
                  ) -> Dict[str, Dict[str, float]]:
        return {name: {f"p{int(q)}": h.quantile(q) for q in qs}
                for name, h in self.hists.items()}

    def summary(self) -> Dict[str, Dict[str, float]]:
        return {name: h.summary() for name, h in self.hists.items()}


# ----------------------------------------------------- per-stream collector

@dataclasses.dataclass
class ChunkTimeline:
    """Stage stamps for ONE launch attempt of one chunk (retries append a
    fresh record).  ``tainted`` marks compile/remesh-spanning attempts whose
    walls are trace/rebuild noise, not steady-state latency — they are kept
    in the time-integrals (real wall time) but excluded from the latency
    windows and histograms, mirroring the EMA-reset logic in ``submit``."""
    chunk: int
    t_enqueue: float
    t_dispatch: float = float("nan")
    t_retire: float = float("nan")
    t_validate: float = float("nan")
    tainted: bool = False

    @property
    def complete(self) -> bool:
        return math.isfinite(self.t_retire)


class DispatchStats:
    """The per-stream stage-stamp collector.

    ``record_span`` keeps the dispatcher's stage spans (``core.spans``)
    beside the stamps: count, total and self seconds per span name.

    ``serialized=True`` (the dispatcher's pipeline) measures SERVICE as the
    exclusive device interval ``retire_i - max(dispatch_i, retire_{i-1})``:
    under pipelining a chunk's launch-to-retire wall includes time queued
    BEHIND the previous chunk on the device, and the retirement-to-
    retirement gap is the honest per-chunk cost (the same quantity the
    auto-scale EMA samples).  ``serialized=False`` (direct feeding: tests,
    the serve layer, any open system with parallel servers) takes service
    as ``retire - dispatch`` verbatim.

    ``summary(n_servers=...)`` derives the queueing view:

      arrival_rate       records / horizon  (first enqueue -> last validate)
      throughput         completions / horizon
      utilization        Σ service / (horizon · n_servers)  — the
                         operational utilization law  U = X·S/n
      mean_queue_length  time-averaged #waiting  = Σ queue_wait / horizon
                         (exact:  ∫N_q(t)dt = Σ w_i)
      mean_in_system     time-averaged #in-system = Σ sojourn / horizon
                         (Little's law:  L = λ·W  holds exactly here)
    """

    def __init__(self, warmup: float = 1, cooldown: float = 0,
                 clock=time.perf_counter, serialized: bool = True,
                 hist_lo: float = 1e-6, hist_hi: float = 1e4,
                 hist_growth: float = 1.25):
        self.clock = clock
        self.serialized = serialized
        self.warmup, self.cooldown = warmup, cooldown
        self.records: List[ChunkTimeline] = []
        self.hist = HistogramSet(hist_lo, hist_hi, hist_growth)
        self.windows: Dict[str, StatsWindow] = {
            name: StatsWindow(warmup, cooldown) for name in INTERVALS}
        self.stall_s: List[float] = []
        self.checkpoint_s: List[float] = []
        self.rejections: Dict[str, int] = {}   # reason -> count (admission/
        #                                        shedding/serve-layer drops)
        self.spans: Dict[str, List[float]] = {}  # name -> [n, total, self]
        self._open: Dict[int, ChunkTimeline] = {}    # enqueued, not launched
        self._live: Dict[int, ChunkTimeline] = {}    # launched, not validated
        self._last_retire: Optional[float] = None

    # ------------------------------------------------------------- stamping
    def enqueue(self, chunk: int, t: Optional[float] = None) -> None:
        self._open[chunk] = ChunkTimeline(
            chunk=chunk, t_enqueue=self.clock() if t is None else t)

    def dispatch(self, chunk: int, t: Optional[float] = None,
                 tainted: bool = False) -> None:
        rec = self._open.pop(chunk, None)
        if rec is None:                    # defensive: un-stamped admission
            rec = ChunkTimeline(chunk=chunk, t_enqueue=self.clock())
        rec.t_dispatch = self.clock() if t is None else t
        rec.tainted = rec.tainted or tainted
        self._live[chunk] = rec
        self.records.append(rec)

    def retire(self, chunk: int, t: Optional[float] = None,
               tainted: bool = False) -> None:
        rec = self._live.get(chunk)
        if rec is None:
            return
        rec.t_retire = self.clock() if t is None else t
        rec.tainted = rec.tainted or tainted

    def validate(self, chunk: int, t: Optional[float] = None,
                 tainted: bool = False) -> None:
        rec = self._live.pop(chunk, None)
        if rec is None:
            return
        now = self.clock() if t is None else t
        if not rec.complete:
            rec.t_retire = now
        rec.t_validate = now
        rec.tainted = rec.tainted or tainted
        self._close(rec)

    def record(self, chunk: int, t_enqueue: float, t_dispatch: float,
               t_retire: float, t_validate: Optional[float] = None,
               tainted: bool = False) -> None:
        """Feed one complete record directly (tests, serve layer, synthetic
        M/M/n streams) — equivalent to the four stamps in order."""
        self.enqueue(chunk, t_enqueue)
        self.dispatch(chunk, t_dispatch, tainted=tainted)
        self.retire(chunk, t_retire)
        self.validate(chunk, t_retire if t_validate is None else t_validate)

    def record_stall(self, delay_s: float) -> None:
        """An injected/detected stall's extra latency — fed to its own
        histogram so docs/robustness.md's stall records are quantified."""
        self.stall_s.append(float(delay_s))
        self.hist.record("stall", delay_s)

    def record_rejection(self, reason: str, n: int = 1) -> None:
        """One structured rejection (admission denial, overload shed, serve
        drop).  Rejected work never enters the four-stage pipeline, so the
        latency/queue views are unaffected; ``summary()`` surfaces the
        per-reason counts so shed load is observable, never silent."""
        self.rejections[reason] = self.rejections.get(reason, 0) + int(n)

    def record_checkpoint(self, write_s: float) -> None:
        """One durable checkpoint's write latency (tmp-dir + rename wall on
        the writer thread — overlap means it is NOT stream wall time; the
        stream-side cost is the host fold + digest, bounded by
        BENCH_resume.json's overhead entries)."""
        self.checkpoint_s.append(float(write_s))
        self.hist.record("checkpoint", write_s)

    def record_span(self, name: str, total_s: float, self_s: float) -> None:
        """One closed ``core.spans.span``: its wall time and the part of it
        not covered by the spans nested in it."""
        rec = self.spans.setdefault(name, [0, 0.0, 0.0])
        rec[0] += 1
        rec[1] += total_s
        rec[2] += self_s

    # ------------------------------------------------------------ intervals
    def _close(self, rec: ChunkTimeline) -> None:
        prev_retire, self._last_retire = self._last_retire, rec.t_retire
        if rec.tainted:
            return                      # trace/rebuild noise: integrals only
        wait = rec.t_dispatch - rec.t_enqueue
        if self.serialized and prev_retire is not None:
            service = rec.t_retire - max(rec.t_dispatch, prev_retire)
        else:
            service = rec.t_retire - rec.t_dispatch
        validate = rec.t_validate - rec.t_retire
        sojourn = rec.t_validate - rec.t_enqueue
        for name, v in (("queue_wait", wait), ("service", service),
                        ("validate", validate), ("sojourn", sojourn)):
            v = max(v, 0.0)
            self.windows[name].add(v)
            self.hist.record(name, v)

    # -------------------------------------------------------------- queueing
    def horizon(self) -> Tuple[float, float]:
        done = [r for r in self.records if r.complete]
        if not done:
            return 0.0, 0.0
        t0 = min(r.t_enqueue for r in done)
        t1 = max(r.t_validate if math.isfinite(r.t_validate) else r.t_retire
                 for r in done)
        return t0, t1

    def queue_summary(self, n_servers: int = 1) -> Dict[str, float]:
        """The operational-law view over the FULL horizon (time-integrals
        are real elapsed time; trimming applies to the latency windows, not
        to conservation laws)."""
        done = [r for r in self.records if r.complete]
        t0, t1 = self.horizon()
        span = t1 - t0
        if not done or span <= 0:
            return {"n_completed": float(len(done)), "horizon_s": 0.0,
                    "arrival_rate": 0.0, "throughput": 0.0,
                    "utilization": 0.0, "mean_queue_length": 0.0,
                    "mean_in_system": 0.0}
        waits = [max(r.t_dispatch - r.t_enqueue, 0.0) for r in done]
        sojourns = [max((r.t_validate if math.isfinite(r.t_validate)
                         else r.t_retire) - r.t_enqueue, 0.0) for r in done]
        if self.serialized:
            services, prev = [], None
            for r in sorted(done, key=lambda r: r.t_retire):
                start = (r.t_dispatch if prev is None
                         else max(r.t_dispatch, prev))
                services.append(max(r.t_retire - start, 0.0))
                prev = r.t_retire
        else:
            services = [max(r.t_retire - r.t_dispatch, 0.0) for r in done]
        n = float(len(done))
        return {
            "n_completed": n,
            "horizon_s": span,
            "arrival_rate": n / span,
            "throughput": n / span,
            "utilization": sum(services) / (span * max(n_servers, 1)),
            "mean_queue_length": sum(waits) / span,
            "mean_in_system": sum(sojourns) / span,
        }

    def mean_service(self) -> float:
        """Trimmed mean service time (NaN until the window has steady
        samples) — the mmn policy's per-chunk cost input."""
        return self.windows["service"].mean()

    def summary(self, n_servers: int = 1) -> Dict[str, object]:
        """Everything ``DispatchReport.stats`` exposes: per-interval
        windowed stats, log-bucket percentiles, stall records, and the
        operational-law queueing view.  Plain dict of floats — survives
        ``dataclasses.asdict`` and JSON."""
        out: Dict[str, object] = {
            "n_records": float(len(self.records)),
            "n_tainted": float(sum(r.tainted for r in self.records)),
            "warmup": float(self.warmup), "cooldown": float(self.cooldown),
        }
        for name in INTERVALS:
            w = self.windows[name].summary()
            if name in self.hist:
                h = self.hist[name]
                w["hist_p50"] = h.quantile(50)
                w["hist_p95"] = h.quantile(95)
                w["hist_p99"] = h.quantile(99)
            out[name] = w
        if self.stall_s:
            out["stall"] = {"n": float(len(self.stall_s)),
                            "total_s": float(sum(self.stall_s)),
                            "p99": self.hist["stall"].quantile(99)}
        if self.checkpoint_s:
            out["checkpoint"] = {"n": float(len(self.checkpoint_s)),
                                 "total_s": float(sum(self.checkpoint_s)),
                                 "p99": self.hist["checkpoint"].quantile(99)}
        if self.rejections:
            out["rejections"] = {k: float(v)
                                 for k, v in sorted(self.rejections.items())}
            out["n_rejected"] = float(sum(self.rejections.values()))
        if self.spans:
            out["spans"] = {k: {"n": float(n), "total_s": t, "self_s": s}
                            for k, (n, t, s) in sorted(self.spans.items())}
        out["queue"] = self.queue_summary(n_servers)
        return out


# ------------------------------------------------------------ M/M/n analytics

def erlang_c(n: int, a: float) -> float:
    """P(wait) for an M/M/n queue with offered load ``a = λ/μ`` Erlangs.
    1.0 when the queue is unstable (a >= n)."""
    if n < 1:
        raise ValueError("n must be >= 1")
    if a <= 0:
        return 0.0
    if a >= n:
        return 1.0
    # iterative Erlang-B, then the standard C-from-B transform (numerically
    # stable for any n — no factorials)
    b = 1.0
    for k in range(1, n + 1):
        b = a * b / (k + a * b)
    rho = a / n
    return b / (1.0 - rho + rho * b)


def mmn_metrics(lam: float, mu: float, n: int) -> Dict[str, float]:
    """Analytic steady-state M/M/n quantities for arrival rate ``lam``,
    PER-SERVER service rate ``mu``, ``n`` servers: per-server utilization
    ``rho``, wait probability ``p_wait`` (Erlang C), mean waiting count
    ``lq``, mean in-system count ``l``, mean wait ``wq``, mean sojourn
    ``w``.  Infinite where the queue is unstable (rho >= 1)."""
    if lam < 0 or mu <= 0:
        raise ValueError("need lam >= 0 and mu > 0")
    a = lam / mu
    rho = a / n
    if rho >= 1.0:
        inf = float("inf")
        return {"rho": rho, "p_wait": 1.0, "lq": inf, "l": inf,
                "wq": inf, "w": inf}
    pw = erlang_c(n, a)
    lq = pw * rho / (1.0 - rho)
    wq = lq / lam if lam > 0 else 0.0
    return {"rho": rho, "p_wait": pw, "lq": lq, "l": lq + a,
            "wq": wq, "w": wq + 1.0 / mu}


def mmn_required_members(lam: float, mu: float, rho_target: float,
                         max_members: int = 1 << 16) -> int:
    """Smallest ``n`` with per-server utilization λ/(n·μ) below
    ``rho_target`` — the analytic bottleneck call the scaler's decisions
    are validated against."""
    if not 0 < rho_target:
        raise ValueError("rho_target must be > 0")
    n = max(int(math.ceil(lam / (mu * rho_target))), 1)
    return min(n, max_members)


@dataclasses.dataclass(frozen=True)
class QueueSnapshot:
    """One measured queue-state observation the mmn policy consumes.

    arrival_rate    demand in chunks/s.  For an OPEN stream (serve layer)
                    this is the measured admission rate; for a CLOSED
                    ``submit`` stream the queue is full by construction, so
                    the dispatcher anchors demand at the job class's target:
                    ``1 / target_step_time`` chunks/s.
    service_rate    per-MEMBER service rate μ₁ in chunks/s.  The dispatcher
                    derives it from the measured cluster service time s_n
                    under the linear-scaling assumption:  one chunk costs
                    ``s_n · n`` member-seconds, so  μ₁ = 1 / (s_n · n).
    n_members       current cluster size.
    queue_length    measured mean number waiting (0 for closed streams —
                    backlog there is not a demand signal).
    """
    arrival_rate: float
    service_rate: float
    n_members: int
    queue_length: float = 0.0

    @property
    def rho(self) -> float:
        """Per-member utilization demand λ/(n·μ₁) — the load the probe
        thresholds compare (directly in the paper's [0, 1+] CPU-load
        scale)."""
        return self.arrival_rate / (max(self.n_members, 1)
                                    * max(self.service_rate, 1e-12))


def mmn_load(snapshot: QueueSnapshot, max_threshold: float = 0.8,
             queue_cap: float = 4.0) -> float:
    """The probe-compatible load signal of the mmn policy: per-member
    utilization demand ρ = λ/(n·μ₁), pushed to at least ``max_threshold``
    when the measured backlog exceeds ``queue_cap`` waiting chunks per
    member — a saturated queue means the cluster is the bottleneck even
    when per-chunk service alone looks acceptable (Erlang-C's Lq explodes
    as ρ→1 long before measured utilization does)."""
    load = snapshot.rho
    if queue_cap > 0 and snapshot.queue_length > 0:
        pressure = (snapshot.queue_length
                    / (max(snapshot.n_members, 1) * queue_cap))
        if pressure >= 1.0:
            load = max(load, max_threshold * min(pressure, 2.0))
    return load
