"""Fault injection + retry policy — failure as a first-class middleware event.

The thesis's premise is that simulations inherit the properties of the
middleware they model, and Hazelcast's defining property beyond elasticity is
SURVIVING MEMBER DEPARTURE: Cloud²Sim's dynamic scaler treats nodes joining
and leaving as normal operation, and CloudSim itself models failure as a
first-class simulation event (arXiv:0903.2525; the federated extensions of
arXiv:0907.4878 argue real cloud tooling must).  This module supplies the two
halves the ``ElasticDispatcher`` needs to make an INVOLUNTARY failure
mid-stream a recoverable event instead of a dead job:

  ``FaultInjector``   a deterministic, seeded chaos harness a test or
                      benchmark hands to the dispatcher.  Each fault is
                      addressable by ``(chunk_index, member, kind)`` so chaos
                      schedules replay bit-for-bit:

                        member_crash   the device backing mesh slot ``member``
                                       at chunk ``chunk`` dies; every launch
                                       touching it fails until the dispatcher
                                       removes it from the pool (Hazelcast's
                                       member-departure signal)
                        nan_poison     chunk ``chunk``'s float output rows on
                                       slot ``member`` become NaN — the
                                       silent-corruption case the
                                       ``HealthMonitor`` docstring calls the
                                       "member crash" signal
                        stall          chunk ``chunk``'s retirement is delayed
                                       ``delay_s`` past its launch — a hung
                                       launch / straggler, detected by the
                                       ``RetryPolicy`` chunk deadline
                        compile_fail   building chunk ``chunk``'s executable
                                       raises once
                        coordinator_crash  the COORDINATOR process itself
                                       dies at chunk ``chunk``'s launch —
                                       ``CoordinatorCrashError`` by default
                                       (in-process preemption a test can
                                       catch), ``os._exit(137)`` when
                                       ``hard_exit=True`` (indistinguishable
                                       from ``kill -9``); recovery is
                                       ``ElasticDispatcher.resume`` from the
                                       journal, not a retry

  ``RetryPolicy``     what ``submit`` does about a detected failure: per-chunk
                      attempt budget, chunk deadline, exponential backoff,
                      and member quarantine (N retryable failures attributed
                      to one member ⇒ treat the member as failed and remesh
                      onto the survivors).

Because chunks are pure functions of (item slice, replicated operands) and
the deterministic chunk-tree reduce fixes the combine order by chunk INDEX,
a replayed chunk — on the same mesh or on the post-failure mesh — produces
bit-identical bytes, so a recovered stream equals a fault-free run exactly.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

FAULT_KINDS = ("member_crash", "nan_poison", "stall", "compile_fail",
               "coordinator_crash")


# ------------------------------------------------------------------ failures

class MemberFailedError(RuntimeError):
    """A launch touched a dead member (the involuntary-departure signal).
    Carries the failing mesh slot and its backing device so the dispatcher
    can retire exactly that device from the pool."""

    def __init__(self, chunk: int, member: int, device):
        super().__init__(f"member {member} (device {device}) failed at "
                         f"chunk {chunk}")
        self.chunk = chunk
        self.member = member
        self.device = device


class CompileFailedError(RuntimeError):
    """Building a chunk's executable failed (retryable)."""

    def __init__(self, chunk: int):
        super().__init__(f"compile failed for chunk {chunk}")
        self.chunk = chunk


class CoordinatorCrashError(RuntimeError):
    """The coordinator process was killed mid-stream (a scheduled
    ``coordinator_crash`` fault in its default in-process mode).  NOT a
    retryable chunk failure: the dispatcher lets it propagate — the stream
    dies exactly as a real preemption would — and recovery is
    ``ElasticDispatcher.resume`` from the journaled state."""

    def __init__(self, chunk: int):
        super().__init__(f"coordinator crashed at chunk {chunk}")
        self.chunk = chunk


class JobFailedError(RuntimeError):
    """A stream exhausted its recovery options (per-chunk attempts spent, or
    survivors dropped below ``min_instances``).  Carries the structured
    ``DispatchReport`` — failures, retries, recovery events — instead of a
    bare traceback; the dispatcher is left drained (``in_flight == 0``) and
    fully reusable."""

    def __init__(self, message: str, report):
        super().__init__(message)
        self.report = report


# ------------------------------------------------------------------- policy

@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """How ``submit`` turns a detected chunk failure into a recovery.

    max_attempts      per-chunk failure budget: the job fails loudly
                      (``JobFailedError``) once one chunk accumulates this
                      many failures (member-crash replays don't count — the
                      member failed, not the chunk)
    chunk_timeout_s   launch-to-retirement deadline; exceeding it is a
                      retryable "stall" failure (None = no deadline).  Under
                      pipelining the measured wall includes queue wait, so
                      size it against ``dispatch_ahead`` steady-state walls,
                      not raw compute
    backoff_s         sleep before attempt k's replay:
                      ``backoff_s * backoff_factor**(k-1)`` (0 = immediate)
    quarantine_after  N retryable failures attributed to ONE member ⇒ the
                      member is treated as failed: forced failure remesh onto
                      the survivors (0 = never quarantine)
    check_finite      opt-in cheap non-finite check on every chunk output —
                      the ``HealthMonitor`` docstring's "member crash"
                      signal.  Costs one device reduction + scalar sync per
                      chunk on the already-retired output (see
                      BENCH_fault.json's overhead entry)
    """
    max_attempts: int = 3
    chunk_timeout_s: Optional[float] = None
    backoff_s: float = 0.0
    backoff_factor: float = 2.0
    quarantine_after: int = 2
    check_finite: bool = False

    def __post_init__(self):
        if self.max_attempts < 1:
            raise ValueError("max_attempts must be >= 1")
        if self.chunk_timeout_s is not None and self.chunk_timeout_s <= 0:
            raise ValueError("chunk_timeout_s must be positive (or None)")

    @property
    def active(self) -> bool:
        """True when the policy asks for per-chunk checks (a deadline or a
        finiteness check) — the dispatcher then arms every chunk's
        validation and blocks on the stream's tail instead of dropping
        it."""
        return self.chunk_timeout_s is not None or self.check_finite

    def backoff_for(self, attempt: int) -> float:
        if self.backoff_s <= 0:
            return 0.0
        return self.backoff_s * self.backoff_factor ** max(attempt - 1, 0)


# ----------------------------------------------------------------- injector

@dataclasses.dataclass
class FaultSpec:
    """One addressable fault: ``(chunk, member, kind)`` + kind parameters.
    ``times`` bounds how often it fires (default once — the transient-fault
    model: the replay succeeds), so recovery is observable, not a loop.
    ``tenant`` scopes the fault to ONE tenant's streams: it fires only when
    the injector is bound to that tenant (``bind_tenant``, which
    ``ElasticDispatcher.submit(tenant=...)`` does for the stream's
    duration) — the multi-tenant front end uses this to target chaos at a
    single misbehaving tenant while every other tenant's requests pass the
    same injector untouched.  ``None`` (the default) matches any stream,
    tenant-bound or not — pre-existing schedules behave exactly as
    before."""
    kind: str
    chunk: int
    member: int = 0
    delay_s: float = 0.25            # stall: injected extra latency
    times: int = 1
    tenant: Optional[str] = None     # None = fires for every stream

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; "
                             f"expected one of {FAULT_KINDS}")
        if self.chunk < 0 or self.member < 0:
            raise ValueError("chunk and member must be >= 0")


class FaultInjector:
    """Deterministic chaos harness for the dispatcher's chunk stream.

    Hand one to ``ElasticDispatcher(fault_injector=...)`` (or per-stream via
    ``submit``); the dispatcher calls the hooks below at its launch / compile
    / retire points.  The schedule is a plain list of ``FaultSpec``s — no
    hidden clocks or RNG at fire time — so a chaos run replays exactly;
    ``random_schedule`` derives a reproducible schedule from a seed.
    ``fired`` logs every fault that actually triggered, in firing order."""

    def __init__(self, schedule: Sequence[FaultSpec] = (),
                 hard_exit: bool = False):
        self.schedule: List[FaultSpec] = list(schedule)
        self.dead_devices: Set = set()
        self.fired: List[dict] = []
        # coordinator_crash mode: False raises CoordinatorCrashError (an
        # in-process preemption tests can catch and resume from), True calls
        # os._exit(137) — no atexit, no finally blocks, the SIGKILL shape
        self.hard_exit = hard_exit
        # the tenant the CURRENT stream belongs to (bind_tenant): tenant-
        # scoped specs fire only when it matches; None-tenant specs always do
        self._tenant: Optional[str] = None

    @classmethod
    def random_schedule(cls, seed: int, n_chunks: int, max_members: int = 1,
                        n_faults: int = 3,
                        kinds: Sequence[str] = FAULT_KINDS,
                        stall_delay_s: float = 0.25,
                        tenants: Optional[Sequence[str]] = None
                        ) -> "FaultInjector":
        """A reproducible chaos schedule: ``n_faults`` specs drawn uniformly
        over (kind, chunk, member) from ``np.random.RandomState(seed)`` —
        the same seed always yields the same schedule, on any host.  The
        default pool is ALL of ``FAULT_KINDS`` (``coordinator_crash``
        included since the durable-dispatch PR); pass an explicit ``kinds``
        to pin a pre-existing schedule.  ``tenants`` additionally draws a
        tenant target per spec (the extra rng draws happen AFTER every
        pre-existing one, so a given seed's (kind, chunk, member) triples
        are unchanged whether or not tenants are requested) — chaos tests
        can aim a whole schedule at one tenant deterministically with
        ``tenants=["t3"]``."""
        rng = np.random.RandomState(seed)
        triples = [(str(rng.choice(list(kinds))),
                    int(rng.randint(0, max(n_chunks, 1))),
                    int(rng.randint(0, max(max_members, 1))))
                   for _ in range(n_faults)]
        owners = ([None] * n_faults if tenants is None else
                  [str(rng.choice(list(tenants))) for _ in range(n_faults)])
        return cls([FaultSpec(kind=k, chunk=c, member=m,
                              delay_s=stall_delay_s, tenant=t)
                    for (k, c, m), t in zip(triples, owners)])

    # ------------------------------------------------------------- scoping
    @contextlib.contextmanager
    def bind_tenant(self, tenant: Optional[str]):
        """Scope the injector to ``tenant`` for one stream: tenant-addressed
        specs fire only while their tenant is bound (``ElasticDispatcher.
        submit(tenant=...)`` holds the binding for the whole stream,
        replays included).  Bindings don't nest — the dispatcher runs one
        stream at a time — and the previous binding is restored on exit."""
        prev, self._tenant = self._tenant, tenant
        try:
            yield self
        finally:
            self._tenant = prev

    # ------------------------------------------------------------- matching
    def _take(self, kind: str, chunk: int) -> Optional[FaultSpec]:
        """Consume one firing of the first live spec matching (kind, chunk)
        whose tenant scope matches the bound stream (None = any)."""
        for spec in self.schedule:
            if (spec.kind == kind and spec.chunk == chunk and spec.times > 0
                    and (spec.tenant is None
                         or spec.tenant == self._tenant)):
                spec.times -= 1
                return spec
        return None

    def _log(self, kind: str, chunk: int, member, **extra) -> None:
        if self._tenant is not None:
            extra.setdefault("tenant", self._tenant)
        self.fired.append({"kind": kind, "chunk": chunk, "member": member,
                           **extra})

    # ---------------------------------------------------------------- hooks
    def on_launch(self, chunk: int, devices: Sequence) -> None:
        """Called before every chunk launch with the devices backing the
        current mesh.  Fires a pending ``coordinator_crash`` first — the
        coordinator dies before it can launch anything (raise, or hard
        ``os._exit(137)``; see ``hard_exit``) — then pending
        ``member_crash`` specs for this chunk (marking the slot's device
        dead), then fails the launch if ANY mesh device is dead — a killed
        member fails every launch touching it until the dispatcher retires
        it from the pool."""
        if self._take("coordinator_crash", chunk) is not None:
            self._log("coordinator_crash", chunk, None)
            if self.hard_exit:
                import os
                os._exit(137)
            raise CoordinatorCrashError(chunk)
        while True:
            spec = self._take("member_crash", chunk)
            if spec is None:
                break
            dev = devices[spec.member % len(devices)]
            self.dead_devices.add(dev)
            self._log("member_crash", chunk, spec.member % len(devices))
        for slot, dev in enumerate(devices):
            if dev in self.dead_devices:
                raise MemberFailedError(chunk, slot, dev)

    def on_compile(self, chunk: int) -> None:
        """Called before an executable build; fires ``compile_fail``."""
        if self._take("compile_fail", chunk) is not None:
            self._log("compile_fail", chunk, None)
            raise CompileFailedError(chunk)

    def maybe_poison(self, chunk: int, out, n_rows: int, n_members: int):
        """Fire a pending ``nan_poison`` for this chunk: float leaves with a
        row-shaped leading dim get the target slot's rows NaN'd (so the
        detector can ATTRIBUTE the corruption to a member); other float
        leaves (replicated partials) are poisoned whole."""
        import jax
        import jax.numpy as jnp

        spec = self._take("nan_poison", chunk)
        if spec is None:
            return out
        slot = spec.member % max(n_members, 1)
        self._log("nan_poison", chunk, slot)
        shard = max(n_rows // max(n_members, 1), 1)
        lo, hi = slot * shard, (slot + 1) * shard

        def poison(leaf):
            if not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            if leaf.ndim >= 1 and leaf.shape[0] == n_rows:
                rows = jnp.arange(n_rows)
                mask = ((rows >= lo) & (rows < hi)).reshape(
                    (n_rows,) + (1,) * (leaf.ndim - 1))
                return jnp.where(mask, jnp.nan, leaf)
            return jnp.full_like(leaf, jnp.nan)

        return jax.tree_util.tree_map(poison, out)

    def stall_for(self, chunk: int) -> Tuple[float, Optional[int]]:
        """Fire a pending ``stall`` for this chunk: returns (extra latency
        the dispatcher should sleep before measuring the chunk's wall,
        responsible member slot) — (0.0, None) when nothing is scheduled."""
        spec = self._take("stall", chunk)
        if spec is None:
            return 0.0, None
        # stall entries carry the injected latency so the fired log can be
        # cross-checked against the collector's stall histogram
        self._log("stall", chunk, spec.member, delay_s=spec.delay_s)
        return spec.delay_s, spec.member

    # ---------------------------------------------------------------- views
    def pending(self) -> Dict[str, int]:
        """Remaining firings per kind (chaos tests assert exhaustion)."""
        out: Dict[str, int] = {}
        for spec in self.schedule:
            if spec.times > 0:
                out[spec.kind] = out.get(spec.kind, 0) + spec.times
        return out
