"""ElasticDispatcher — the unified, remesh-aware, chunk-streaming job layer.

The thesis closes by claiming Cloud²Sim's "distributed execution model and
adaptive scaling solution could be leveraged as a general purpose auto scaler
middleware".  This module IS that middleware for the repo: one dispatch layer
that the scenario grids, the MapReduce engine, and the elastic simulation
cluster all sit on, instead of each carrying its own ad-hoc mesh/shard/cache
logic.  Concept map to the thesis's middleware vocabulary:

  IExecutorService / executeOnKeyOwner   ``DispatchJob.member_fn`` — logic
                                         ships to each member's local chunk
                                         partition via ``DistributedExecutor``
  distributed task queue                 the chunk stream of ``submit``: a job
                                         larger than one dispatch (or than
                                         device memory) is cut into fixed-
                                         shape chunks and executed in order,
                                         each chunk a task taken off the queue
  Hazelcast partition table (§4.1.3)     the 271-virtual-shard
                                         ``PartitionTable`` owned here; its
                                         VM→member map is a RUNTIME operand of
                                         the distributed cores, so rebalances
                                         never recompile
  adaptive scaler (Algorithms 4–6, §5)   ``ElasticController`` → IAS; when it
                                         fires BETWEEN chunks the dispatcher
                                         rebalances the table, retires exactly
                                         the outgoing geometry's executables,
                                         rebuilds the mesh, re-homes the
                                         ``DataGrid``, and the stream resumes
                                         on the new member set
  compiled-task near-cache               ``CompileCache`` — one executable per
                                         (geometry, job-signature), LRU, with
                                         hit/miss/build counters, absorbing
                                         and generalizing the scan core's
                                         ``_DIST_CORE_CACHE``/
                                         ``_AUTO_BLOCK_CACHE``

Jobs are declared as ``DispatchJob`` descriptors — ``(member_fn | global_fn,
reduce)``.  ``member_fn(local_items, local_valid, *replicated)`` runs on each
member's shard of the chunk (the Hazelcast-style explicit path);
``global_fn(items, valid, *replicated)`` expresses the same job as one global
computation whose schedule the partitioner chooses (the Infinispan-style
auto-SPMD path).  ``reduce`` combines chunks: "concat" streams row results,
"sum"/"max" accumulate associative partials, so integer reductions (e.g. word
count) are BIT-identical for any member count, chunking, or mid-stream scale
event — the thesis's accuracy-under-elasticity claim at the job layer.
``deterministic=True`` extends that guarantee to FLOAT sums: the job emits
per-row contributions and the dispatcher reduces them with position-aligned
pairwise trees (rows) plus a fixed-arity tree keyed on chunk index (chunks).

The streaming path is an ASYNC, DOUBLE-BUFFERED pipeline (``dispatch_ahead``
launched-but-unretired chunks, default 2): chunk k+1 is staged on the host —
or, when the item set is already device-resident, cut on DEVICE: in place by
the job's own executable on a one-member mesh that holds the source, by
``executor.slice_chunk`` otherwise — while chunk k computes, and the host
blocks only to bound the queue, to take the wall-time samples the IAS needs
(an EMA of retirement-to-retirement step times over a per-job-class
calibrated ``target_step_time``), and at reduce/remesh boundaries.  A scale
event is a pipeline BARRIER: drain in-flight chunks, rebalance, rebuild,
resume — chunk boundaries and reduce order never change, so results stay
bit-identical no matter how many chunks were in flight.

FAILURE is a recoverable event at this layer, not a dead job (Hazelcast's
defining property beyond elasticity is surviving member departure; see
``core/faults.py`` and docs/robustness.md).  ``submit`` takes a
``RetryPolicy`` (attempt budget, chunk deadline, backoff, quarantine) and an
optional ``FaultInjector``; the previously-unused ``HealthMonitor`` is the
detector (non-finite chunk outputs are its documented "member crash" signal,
per-member launch walls feed ``straggler_skew``).  A detected member failure
becomes a FORCED failure remesh — drain survivors' in-flight chunks, retire
the dead device from the pool, rebalance the table and remesh grid onto the
survivors — and the failed plus lost chunks are REPLAYED there.  Chunks are
pure functions of (item slice, replicated operands) and the combine order is
fixed by chunk INDEX, so a recovered stream is bit-identical to a fault-free
run.  Unrecoverable jobs raise ``JobFailedError`` carrying the structured
``DispatchReport`` (failures / retries / recovery_events); the dispatcher is
left drained and reusable either way.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import math
import re
import signal as _signal
import threading
import time
import warnings
from typing import Callable, Deque, Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.executor import DistributedExecutor, cut_chunk
from repro.core.faults import (CompileFailedError, FaultInjector,
                               JobFailedError, MemberFailedError, RetryPolicy)
from repro.core.grid import DataGrid
from repro.core.journal import (CheckpointPolicy, DrainInterrupted,
                                JobJournal, ResumeMismatchError, counter_push,
                                journal_dir, load_checkpoint, load_journal,
                                stable_signature, tree_digest)
from repro.core.partition import (DEFAULT_PARTITION_COUNT, PartitionTable,
                                  pad_to_shards, partition_weights_from_keys)
from repro.core.spans import jax_counts, span
from repro.core.stats import DispatchStats, QueueSnapshot


class NonPow2ChunkWarning(UserWarning):
    """A ``deterministic=True`` float-sum stream was chunked at a
    non-power-of-two size: results are still deterministic FOR THIS chunking
    (replays included) but are not bit-identical to runs using a DIFFERENT
    chunk size — only equal power-of-two chunks form exact subtrees of the
    global row-aligned reduction tree (see ``_chunk_tree_reduce``)."""


# --------------------------------------------------------------- compile cache

_MISSING = object()
_STREAM_IDS = itertools.count()      # the ``stream`` arg of a submit's spans


def _named(fn: Callable, job: DispatchJob, stage: str = "") -> Callable:
    """Name ``fn`` after ``job`` (and ``stage``) so the module ``jax.jit``
    compiles from it is ``jit_dispatch_<job>[_<stage>]`` on the device
    trace: ``mapreduce/word_count`` -> ``jit_dispatch_mapreduce_word_count``.
    """
    name = "dispatch_" + re.sub(r"\W", "_", job.name) + (
        f"_{stage}" if stage else "")
    fn.__name__ = fn.__qualname__ = name
    return fn


class CompileCache:
    """LRU cache of compiled executables keyed by (geometry, signature...).

    Insertion-ordered dict semantics with the FRONT as the eviction victim;
    ``get`` moves a hit to the back (so sweeps over many geometries never
    evict the hottest one) and counts hits/misses; ``put`` counts builds.
    Dict-style access (``len``/``in``/iteration/``[]``) peeks WITHOUT
    disturbing recency — the elastic invalidation path and tests use it to
    inspect entries.  The counters are the observable the dispatch acceptance
    tests pin: a chunk stream must build at most one executable per
    (geometry, job-signature) and hit the cache for every later chunk.
    """

    def __init__(self, max_entries: int = 64):
        self.max_entries = max_entries
        self._store: Dict[Hashable, object] = {}
        self.hits = 0
        self.misses = 0
        self.builds = 0

    # ------------------------------------------------------------ LRU access
    def get(self, key, default=None):
        val = self._store.pop(key, _MISSING)
        if val is _MISSING:
            self.misses += 1
            return default
        self._store[key] = val            # move to back: most recently used
        self.hits += 1
        return val

    def put(self, key, value, max_entries: Optional[int] = None,
            count_build: bool = True):
        """``count_build=False`` for metadata writes (cached ints, measured
        capacities) so ``builds`` keeps meaning COMPILED EXECUTABLES — the
        observable the dispatch acceptance tests pin."""
        cap = self.max_entries if max_entries is None else max_entries
        self._store.pop(key, None)
        while len(self._store) >= max(cap, 1):
            del self._store[next(iter(self._store))]   # evict the LRU front
        self._store[key] = value
        if count_build:
            self.builds += 1

    def get_or_build(self, key, builder: Callable[[], object],
                     max_entries: Optional[int] = None):
        val = self.get(key, _MISSING)
        if val is _MISSING:
            val = builder()
            self.put(key, val, max_entries)
        return val

    # ----------------------------------------------------------- maintenance
    def invalidate(self, match: Optional[Callable[[Hashable], bool]] = None
                   ) -> int:
        """Drop entries whose key satisfies ``match`` (all, when None).
        Returns the number of entries dropped — the scale-event path uses it
        to report exactly how many executables the outgoing geometry held."""
        keys = [k for k in self._store if match is None or match(k)]
        for k in keys:
            del self._store[k]
        return len(keys)

    def stats(self) -> Dict[str, int]:
        return {"size": len(self._store), "hits": self.hits,
                "misses": self.misses, "builds": self.builds}

    # ------------------------------------------------- dict-style inspection
    def __len__(self):
        return len(self._store)

    def __iter__(self):
        return iter(self._store)

    def __contains__(self, key):
        return key in self._store

    def __getitem__(self, key):          # peek: no recency update, no count
        return self._store[key]

    def __setitem__(self, key, value):   # metadata write: not an executable
        self.put(key, value, count_build=False)

    def __delitem__(self, key):
        del self._store[key]


# ----------------------------------------------------- geometry-cache registry
#
# Any module that keeps its own (mesh, axis, ...)-keyed executable cache
# registers it here at import time; a dispatcher scale event then retires the
# outgoing mesh's entries from EVERY registered cache without the middleware
# having to know client modules by name (des_scan registers its distributed
# scan cores and auto-sized exchange capacities this way).

_GEOMETRY_CACHES: List[Tuple[str, CompileCache, bool]] = []


def register_geometry_cache(name: str, cache: CompileCache,
                            counts_as_core: bool = True) -> None:
    """Register a cache whose keys lead with ``(mesh, axis, ...)`` for
    automatic retirement on scale events.  ``counts_as_core=False`` for
    metadata caches (e.g. measured exchange capacities) that should be
    dropped but not reported as retired executables."""
    _GEOMETRY_CACHES.append((name, cache, counts_as_core))


# ------------------------------------------------------- reduction primitives

def _row_tree_sum(rows, valid):
    """Position-aligned pairwise-tree sum over the leading (row) axis.

    Invalid rows are zeroed, the array is zero-padded to the next power of
    two, and adjacent pairs are combined level by level — the addition tree
    of row r is a function of r ALONE, never of the padded length.  Because
    an all-zero subtree contributes an exact ``+0.0`` (x + 0.0 == x), the
    result is BIT-identical for any pad length >= the live row count, i.e.
    for any member count's chunk padding.  This is the row-level half of the
    deterministic float reduction; ``_chunk_tree_reduce`` is the cross-chunk
    half.

    This function MUST be compiled in its own executable, never fused with
    the job's producer (see ``_build_member``): a member_fn ending in a bare
    multiply (``x * w``) otherwise compiles differently at M=1 — the whole
    chunk is one XLA fusion and the multiply contracts into the level-0
    adds as FMA (single rounding), while at M>1 the shard_map boundary
    blocks that contraction — losing member-count bit-identity for
    product-shaped jobs.  HLO-level guards (``optimization_barrier``,
    ``reduce_precision(8, 23)``, bitcast round-trips) are all folded away
    by the CPU pipeline before codegen; an executable boundary is the only
    fence LLVM's FMA contraction cannot cross."""
    mask_shape = (rows.shape[0],) + (1,) * (rows.ndim - 1)
    x = jnp.where(valid.reshape(mask_shape), rows, jnp.zeros((), rows.dtype))
    n = x.shape[0]
    p2 = 1 if n <= 1 else 1 << (n - 1).bit_length()
    if p2 != n:
        x = jnp.concatenate(
            [x, jnp.zeros((p2 - n,) + x.shape[1:], x.dtype)])
    while x.shape[0] > 1:
        x = x[0::2] + x[1::2]
    return x[0]


def _row_tree_jit(job: DispatchJob):
    """``_row_tree_sum`` over every leaf of ``job``'s per-row output, as an
    executable of its own (see ``_row_tree_sum``) named ``..._tree``."""
    def tree(out, valid):
        return jax.tree_util.tree_map(lambda a: _row_tree_sum(a, valid), out)
    return jax.jit(_named(tree, job, "tree"))


def _chunk_tree_reduce(parts, combine, pending=None):
    """Fixed-arity pairwise combine tree keyed on chunk index (a binary
    counter: partial subtrees of equal height merge as chunks arrive, the
    final drain folds survivors highest-level — i.e. earliest chunks —
    first).  The tree shape depends only on the number of chunks, so float
    ``reduce="sum"`` streams are deterministic for a given chunking, and —
    because equal power-of-two chunks form exact subtrees of the global
    row-aligned tree — bit-identical ACROSS power-of-two chunk sizes.  For
    int/max reductions the combine is associative and the tree is
    indistinguishable from the old left fold.

    ``pending`` seeds the counter with a RESTORED state: a checkpoint of the
    counter after k in-order chunks is exactly the pow2 subtrees of k's
    binary decomposition, so resuming pushes chunks k..n-1 through literally
    the same fold sequence the uninterrupted run would have — bit-identical
    bytes (the durable-dispatch resume guarantee)."""
    if pending is None:
        pending = {}
    for part in parts:
        counter_push(pending, part, combine)
    out = None
    for level in sorted(pending):        # ascending: latest chunks first,
        # so each fold keeps earlier chunks on the LEFT of the combine
        out = (pending[level] if out is None
               else jax.tree_util.tree_map(combine, pending[level], out))
    return out


# ------------------------------------------------------- failure detection

@jax.jit
def _finite_probe(tree):
    """One fused all-float-leaves-finite reduction, ENQUEUED at launch so it
    overlaps the pipelined compute it guards — the validator only syncs the
    resulting scalar, which by retirement time has already been computed.
    Keeps an armed stream's fault-free overhead (BENCH_fault.json) to one
    device scalar sync per chunk instead of per-leaf blocking round-trips."""
    flags = [jnp.isfinite(leaf).all()
             for leaf in jax.tree_util.tree_leaves(tree)
             if jnp.issubdtype(leaf.dtype, jnp.floating)]
    if not flags:
        return jnp.asarray(True)
    return functools.reduce(jnp.logical_and, flags)


def _nonfinite_member(tree, n_rows: int, n_members: int) -> Optional[int]:
    """Attribute a non-finite chunk output to a mesh slot: leaves keeping the
    chunk's row-shaped leading dim map their first bad row to the member that
    computed it (rows are range-sharded over the executor axis).  ``None``
    when only row-free leaves (replicated aggregates) are corrupt — the
    corruption is real but unattributable, so nothing is quarantined.  Host
    work, on the failure path only."""
    shard = max(n_rows // max(n_members, 1), 1)
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.asarray(leaf)
        if arr.dtype.kind != "f" or arr.ndim < 1 or arr.shape[0] != n_rows:
            continue
        bad = ~np.isfinite(arr.reshape(n_rows, -1)).all(axis=1)
        idx = np.nonzero(bad)[0]
        if idx.size:
            return min(int(idx[0]) // shard, max(n_members, 1) - 1)
    return None


# ------------------------------------------------------------ job descriptors

@dataclasses.dataclass(frozen=True)
class DispatchJob:
    """One streaming job: how a chunk executes and how chunks combine.

    Exactly one of ``member_fn``/``global_fn`` must be set:

      member_fn(local_items, local_valid, *replicated)
          runs on each member's shard of the chunk (executeOnKeyOwner).  For
          ``reduce="concat"`` it returns per-row outputs (leading dim = the
          local shard) which the dispatcher reassembles in global row order;
          for "sum"/"max" it returns a partial aggregate which the dispatcher
          combines across members (psum/pmax) and then across chunks.
      global_fn(items, valid, *replicated)
          expresses the whole chunk as one global computation; the partitioner
          (auto-SPMD) chooses the schedule.  Cross-chunk combination still
          follows ``reduce``.

    ``local_valid``/``valid`` is a bool mask marking the chunk's live rows —
    the dispatcher pads every chunk to a fixed shard-divisible shape so the
    compile cache hits, and padded rows MUST NOT contribute to "sum"/"max"
    aggregates (mask them; for "concat" the dispatcher trims them off).

    ``signature`` is the job's static compile identity: it must determine the
    traced computation completely (the dispatcher may reuse an executable
    built from an earlier ``DispatchJob`` carrying an equal signature).

    ``deterministic`` (``reduce="sum"`` only) changes the fn contract: the
    job returns PER-ROW contributions (leading dim = rows, like "concat")
    WITHOUT masking or summing them, and the dispatcher reduces rows itself
    with a position-aligned pairwise tree (``_row_tree_sum``) and chunks
    with a fixed-arity tree keyed on chunk index — so FLOAT sums get the
    same bit-identity guarantee across member counts, mid-stream scale
    events, and (power-of-two) chunkings that int32 word count has.

    ``target_step_time`` is the job class's IAS calibration: under
    ``auto_scale`` the dispatcher feeds ``step_time_ema / target`` as the
    load sample.  ``None`` self-calibrates — the first steady-state sample
    of the job class is pinned to the neutral midpoint of the scaling
    thresholds, so only subsequent drift drives the scaler.
    """
    name: str
    signature: Hashable
    member_fn: Optional[Callable] = None
    global_fn: Optional[Callable] = None
    reduce: str = "concat"               # "concat" | "sum" | "max"
    deterministic: bool = False          # per-row tree-reduced float sum
    target_step_time: Optional[float] = None   # per-job-class IAS target
    # which seg-scan path the job's computation runs, for benchmark
    # provenance: None (lax), "compiled" (real Pallas kernel), or
    # "interpret" (off-TPU fallback) — see compat.kernel_path
    kernel_path: Optional[str] = None
    # which path a MapReduce job's map counts with, by platform ("default"
    # for every other): resolved into DispatchReport.map_path
    map_paths: Optional[Dict[str, str]] = None

    def __post_init__(self):
        if (self.member_fn is None) == (self.global_fn is None):
            raise ValueError("exactly one of member_fn/global_fn required")
        if self.reduce not in ("concat", "sum", "max"):
            raise ValueError(f"unknown reduce {self.reduce!r}")
        if self.deterministic and self.reduce != "sum":
            raise ValueError("deterministic=True requires reduce='sum'")


@dataclasses.dataclass
class DispatchReport:
    """What one ``submit`` stream did — the acceptance-test observable."""
    job: str
    n_items: int
    chunk: int
    n_chunks: int = 0
    compiles: int = 0                    # executables built this stream
    cache_hits: int = 0                  # chunks served by a cached executable
    members_per_chunk: List[int] = dataclasses.field(default_factory=list)
    scale_events: int = 0                # remesh events fired mid-stream
    wall_s: float = 0.0
    dispatch_ahead: int = 0              # pipeline depth this stream ran at
    max_in_flight: int = 0               # peak launched-but-unretired chunks
    staged_device: int = 0               # chunks cut on device (either way)
    staged_in_place: int = 0             # ... by the job's own executable
    staged_host: int = 0                 # chunks sliced/padded host-side
    # seg-scan kernel provenance (from DispatchJob.kernel_path): None for
    # the lax path, "compiled" for the real Pallas kernel, "interpret" for
    # the off-TPU fallback — so a CPU "kernel" benchmark can't silently
    # report interpreter timings as kernel timings
    kernel_path: Optional[str] = None
    # the path the job's map counted with on these devices (from
    # DispatchJob.map_paths): e.g. "mxu_onehot", "scatter", "kernel"
    map_path: Optional[str] = None
    ema_step_s: float = 0.0              # last step-time EMA (auto_scale)
    retries: int = 0                     # chunk replays this stream
    # structured failure record: one dict per DETECTED failure —
    # {chunk, kind, attempt, member, detail, wall_s, recovered_after_s}
    failures: List[dict] = dataclasses.field(default_factory=list)
    # one dict per forced failure remesh: the scale event's fields plus
    # {cause, dead_member, dead_device, failed_chunk, replayed_chunks,
    #  recovery_s} — recovery_s is detect-to-last-replayed-chunk-validated
    recovery_events: List[dict] = dataclasses.field(default_factory=list)
    # durable dispatch (``checkpoint=``/``resume``): where this stream's
    # journal lives, how many durable checkpoints it wrote (write latencies
    # on the background writer thread), and — on a resumed stream — the
    # journal it came from, the journaled chunks it skipped, and the lost
    # in-flight chunks it replayed
    journal_path: Optional[str] = None
    checkpoints: int = 0
    checkpoint_write_s: List[float] = dataclasses.field(default_factory=list)
    resumed_from: Optional[str] = None
    chunks_skipped: int = 0
    chunks_replayed: int = 0
    # multi-tenant serving: the tenant this stream belongs to (``submit``'s
    # ``tenant=`` — set by TenantFrontEnd so failures, journals, and stats
    # are attributable to the submitting tenant); None for direct callers
    tenant: Optional[str] = None
    # queueing-theoretic observability (``collect_stats`` / policy="mmn"):
    # per-stage latency decomposition (queue_wait / service / validate /
    # sojourn: windowed mean + percentiles, log-bucket histogram quantiles),
    # stall records, and the operational-law queue view (arrival rate,
    # throughput, utilization, mean queue length) — see repro/core/stats.py
    # and docs/observability.md.  None when instrumentation is off.
    stats: Optional[dict] = None
    # what JAX did on this stream's thread while it ran (``core.spans.
    # jax_counts``): jaxprs traced, programs XLA compiled, and programs
    # loaded from the persistent compilation cache instead of compiled
    jax_traces: int = 0
    jax_compiles: int = 0
    jax_cache_loads: int = 0

    def summary(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


# ------------------------------------------------------------- the dispatcher

class ElasticDispatcher:
    """Owns mesh, ownership table, compile cache, and the chunk stream.

    One instance per tenant/cluster.  ``submit`` streams a job chunk by
    chunk; between chunks the ``ElasticController`` may fire (driven by
    ``observe_load`` from an ``on_chunk`` callback, or automatically from
    measured chunk wall time when ``auto_scale=True``) and the stream
    resumes on the re-built mesh — compiled executables for the outgoing
    geometry are retired, every other geometry's stay warm.
    """

    def __init__(self, devices=None, axis: str = "data",
                 health_cfg=None, start_members: int = 1,
                 partition_count: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 cache_entries: int = 64, auto_scale: bool = False,
                 dispatch_ahead: int = 2,
                 retry_policy: Optional[RetryPolicy] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 collect_stats: bool = False,
                 checkpoint: Optional[CheckpointPolicy] = None):
        from repro.core.elastic import ElasticController, entity_pad_multiple
        from repro.core.health import HealthConfig, HealthMonitor

        self.devices = list(devices if devices is not None else jax.devices())
        self.axis = axis
        if not 1 <= start_members <= len(self.devices):
            raise ValueError(
                f"start_members={start_members} needs 1..{len(self.devices)}"
                f" members: only {len(self.devices)} device(s) in the pool")
        n0 = int(start_members)
        self.table = PartitionTable(
            partition_count=partition_count or DEFAULT_PARTITION_COUNT,
            n_instances=n0)
        hc = health_cfg or HealthConfig()
        hc = dataclasses.replace(
            hc, max_instances=min(hc.max_instances, len(self.devices)))
        if hc.policy not in ("ema", "mmn"):
            raise ValueError(f"unknown HealthConfig.policy {hc.policy!r}; "
                             "expected 'ema' or 'mmn'")
        self.health_cfg = hc
        # queueing observability: stamp every chunk's pipeline stages and
        # expose DispatchReport.stats.  The mmn scaling policy NEEDS the
        # measured service decomposition, so it forces collection on.
        self.collect_stats = collect_stats or hc.policy == "mmn"
        # ENTITY sizes pad to this multiple so shapes are identical at every
        # member count the IAS can reach (bit-stable scale events for the
        # elastic cluster).  Chunk streams don't need it: each geometry pads
        # chunks to its own shard multiple, and chunk rows are independent.
        self.entity_pad = entity_pad_multiple(hc, n0)
        self.controller = ElasticController(hc, n0, remesh_fn=self._remesh)
        self.cache = CompileCache(cache_entries)
        self.chunk_size = chunk_size
        self.auto_scale = auto_scale
        # pipeline depth: how many chunks may stay launched but unretired
        # (0 retires each chunk right after its launch)
        self.dispatch_ahead = max(int(dispatch_ahead), 0)
        # device-resident item sets at least this big are chunked on device
        # (in place, or by ``executor.slice_chunk``) instead of round-
        # tripping through host numpy; below it the per-chunk device cut
        # costs more than the copies it saves (tests pin 0 to force the
        # device path)
        self.device_slice_min_bytes = 1 << 20
        self.grid: Optional[DataGrid] = None
        self.scale_events: List[dict] = []
        self._key_weights: Optional[np.ndarray] = None
        # fault tolerance: default per-stream policy/injector (submit can
        # override per call), devices retired by member failure, and a
        # DEDICATED HealthMonitor fed one sample per validated chunk — kept
        # separate from the controller's monitor so failure-path walls never
        # pollute the voluntary scaler's load window
        self.retry_policy = retry_policy
        self.fault_injector = fault_injector
        # durability: default CheckpointPolicy for every stream (submit can
        # override per call) and the graceful-preemption flag — settable
        # from a signal handler / another thread, honored at the next chunk
        # boundary of the active journaled stream (see request_drain)
        self.checkpoint_policy = checkpoint
        self._drain_requested = threading.Event()
        self.dead_devices: List = []
        self.fault_monitor = HealthMonitor(hc)
        # per-job-class calibrated IAS step-time targets (auto_scale);
        # signatures pinned EXPLICITLY via calibrate_target survive the
        # failure-path calibration reset, self-calibrated ones do not
        self.job_targets: Dict[Hashable, float] = {}
        self._explicit_targets: set = set()
        # the stream ``submit`` is running (None between streams): the
        # remesh barrier drains its flight queue
        self._active_stream: Optional[_Stream] = None
        self._valid_masks: Dict[Tuple[int, int], jnp.ndarray] = {}
        self._epoch = 0                  # bumped per remesh (geometry epoch)
        self._build(n0)

    @classmethod
    def for_mesh(cls, mesh, axis: Optional[str] = None) -> "ElasticDispatcher":
        """A FROZEN dispatcher bound to an existing 1-D mesh: same devices,
        same axis name, min_instances == max_instances so the IAS can never
        fire.  Lets mesh-first callers (the legacy MapReduce constructor)
        run on the unified job layer without opting into elasticity."""
        from repro.core.health import HealthConfig

        if mesh.devices.ndim != 1:
            raise ValueError("for_mesh requires a 1-D mesh, got shape "
                             f"{mesh.devices.shape}")
        axis = axis or mesh.axis_names[0]
        n = int(mesh.devices.size)
        hc = HealthConfig(min_instances=n, max_instances=n)
        return cls(devices=list(mesh.devices.ravel()), axis=axis,
                   health_cfg=hc, start_members=n)

    # --------------------------------------------------------------- topology
    def _build(self, n: int) -> None:
        self.executor = DistributedExecutor.for_devices(self.devices[:n],
                                                        self.axis)
        self.mesh = self.executor.mesh

    @property
    def n_members(self) -> int:
        return self.controller.n_instances

    def ensure_grid(self) -> DataGrid:
        """The dispatcher-owned DataGrid, created lazily on the current mesh
        and re-homed automatically on every scale event."""
        if self.grid is None:
            self.grid = DataGrid(self.mesh, axis=self.axis)
        return self.grid

    def vm_owner(self, n_keys: int) -> jnp.ndarray:
        """Current key→member ownership (the distributed cores' runtime
        operand) for int keys 0..n_keys-1."""
        return jnp.asarray(self.table.owners_of_range(n_keys))

    # ---------------------------------------------------------------- scaling
    def observe_load(self, load: float):
        """Feed one normalized load sample (observed/target) to the
        monitor→probe→IAS chain; a threshold crossing triggers ``_remesh``
        at this chunk/step boundary."""
        return self.controller.tick(load)

    def observe_key_weights(self, weights) -> None:
        """Record observed per-key load (e.g. the scan core's
        ``exchange_load`` summed per VM).  The NEXT rebalance becomes
        locality-aware: virtual partitions level by weighted load, so a hot
        key's partition stops dragging a full share of cold partitions onto
        its member (ROADMAP exchange follow-on c).  One-shot: the sample is
        CONSUMED by that rebalance — later scale events fall back to count
        leveling unless a fresh observation is fed, so a long-stale load
        profile never keeps steering placement."""
        self._key_weights = None if weights is None else np.asarray(
            weights, np.float64)

    def _partition_weights(self) -> Optional[np.ndarray]:
        if self._key_weights is None:
            return None
        return partition_weights_from_keys(self._key_weights,
                                           self.table.partition_count)

    def _remesh(self, n: int, reason: str = "scale") -> None:
        """The scale-event callback — a PIPELINE BARRIER: drain every
        in-flight chunk of the active stream, then rebalance table → retire
        exactly the outgoing geometry's executables (every registered
        geometry cache + this dispatcher's job cache) → rebuild mesh →
        re-home DataGrid → resume.  Draining first keeps the event clean
        (no old-geometry compute overlapping the new geometry's compiles)
        and is the only mid-stream synchronization the async pipeline does;
        chunk boundaries and reduce order are unaffected by how many chunks
        were in flight, so results stay bit-identical.  ``reason`` is
        "scale" for voluntary IAS events, "member_failure" for the forced
        remesh of the involuntary-departure path."""
        drained = self._drain_in_flight()
        old_mesh, axis = self.mesh, self.axis
        moved = self.table.rebalance(n, weights=self._partition_weights())
        self._key_weights = None        # one-shot: consumed by this event
        match = lambda k: k[0] == old_mesh and k[1] == axis
        retired = 0
        for _, cache, counted in _GEOMETRY_CACHES:
            dropped = cache.invalidate(match)
            if counted:
                retired += dropped
        retired_jobs = self.cache.invalidate(match)
        self._build(n)
        self._epoch += 1                # wall-clock samples spanning the
        # barrier are meaningless: the stream loop resets its timer on epoch
        if self.grid is not None:
            self.grid.remesh(self.mesh)
        self.scale_events.append(
            {"n_members": n, "moved_partitions": moved,
             "retired_cores": retired, "retired_jobs": retired_jobs,
             "drained_in_flight": drained, "reason": reason})

    def _member_failure_remesh(self, device, slot: int, report) -> dict:
        """The involuntary-departure path: retire ``device`` from the pool,
        restore any backed-up grid entries from their neighbor replicas,
        clamp the IAS ceiling to the survivors, and force a FAILURE REMESH
        (same barrier as a voluntary scale event: rebalance table → retire
        dead geometry's executables → rebuild mesh → re-home grid) onto
        ``min(n_members, survivors)`` members.  Spare pool devices beyond
        the mesh keep the member COUNT intact when possible — the Hazelcast
        model, where a standby absorbs a departed member's partitions.
        Returns the recorded scale event (reason "member_failure") for the
        caller to extend with recovery details.  Raises ``JobFailedError``
        when the survivors cannot carry the job (fewer than
        ``min_instances``) — after first shrinking the dispatcher onto
        whatever survived, so the MIDDLEWARE stays usable even when the JOB
        is lost."""
        if device in self.devices:
            self.devices.remove(device)
            self.dead_devices.append(device)
        survivors = len(self.devices)
        if survivors == 0:
            raise JobFailedError(
                "every member failed: no surviving devices", report)
        restored = (self.grid.fail_over(slot)
                    if self.grid is not None and self.grid.backup_count
                    else [])
        recoverable = survivors >= self.health_cfg.min_instances
        if not recoverable:
            # degrade the floor so the dispatcher itself stays remeshable;
            # the job still fails loudly below
            self.health_cfg.min_instances = survivors
        self.health_cfg.max_instances = min(self.health_cfg.max_instances,
                                            survivors)
        n_new = min(self.n_members, survivors)
        self.controller.force_instances(n_new)
        self._remesh(n_new, reason="member_failure")
        event = self.scale_events[-1]
        if restored:
            event["grid_restored"] = restored
        if not recoverable:
            raise JobFailedError(
                f"member at slot {slot} (device {device}) failed; "
                f"{survivors} survivor(s) < min_instances — job "
                "unrecoverable", report)
        return event

    @property
    def in_flight(self) -> int:
        """Launched-but-unretired chunks of the active stream (0 between
        streams — the exception-safety observable: a failed ``submit`` must
        never leak launched buffers)."""
        stream = self._active_stream
        return 0 if stream is None else len(stream.flight)

    def _drain_in_flight(self) -> int:
        """Block until every launched chunk of the active stream has
        retired.  Returns how many were in flight (0 between streams) —
        the remesh barrier records it per scale event."""
        stream = self._active_stream
        return 0 if stream is None else stream.drain_in_flight()

    def calibrate_target(self, job: DispatchJob, target_step_time: float
                         ) -> None:
        """Pin a job class's IAS step-time target explicitly (overrides the
        first-sample self-calibration; ``job.target_step_time`` still wins).
        Explicit pins survive the failure-path calibration reset — the
        operator asserted the number, a dying stream can't falsify it."""
        self.job_targets[job.signature] = float(target_step_time)
        self._explicit_targets.add(job.signature)

    def _job_target(self, job: DispatchJob, first_sample: float) -> float:
        """Resolve the job class's step-time target: the job's own >
        previously calibrated > self-calibrate NOW so ``first_sample`` sits
        at the neutral midpoint of the scaling thresholds (load there
        triggers nothing; later drift does)."""
        if job.target_step_time is not None:
            return job.target_step_time
        target = self.job_targets.get(job.signature)
        if target is None:
            mid = 0.5 * (self.health_cfg.max_threshold
                         + self.health_cfg.min_threshold)
            target = first_sample / max(mid, 1e-9)
            self.job_targets[job.signature] = target
        return target

    def _map_path(self, job: DispatchJob) -> Optional[str]:
        """``job.map_paths`` resolved for the platform of this pool."""
        if job.map_paths is None:
            return None
        platform = self.devices[0].platform
        return job.map_paths.get(platform, job.map_paths.get("default"))

    # ---------------------------------------------------- durable dispatch
    def request_drain(self) -> None:
        """Ask the active JOURNALED stream to preempt gracefully: at the
        next chunk boundary it stops launching, retires + validates every
        in-flight chunk, checkpoints the validated prefix, journals a drain
        record, and raises ``DrainInterrupted`` (carrying the partial report
        and journal path) — ``resume`` picks the stream back up later.
        Thread- and signal-safe; a stream running without a
        ``CheckpointPolicy`` ignores it (nothing durable to drain to)."""
        self._drain_requested.set()

    def install_drain_signal(self, signum: int = _signal.SIGTERM) -> None:
        """Route a process signal (default SIGTERM — the preemption notice
        cluster schedulers send before SIGKILL) to ``request_drain``.  Call
        from the main thread (CPython restricts ``signal.signal``)."""
        _signal.signal(signum, lambda _s, _f: self.request_drain())

    def _env_signature(self, job: DispatchJob, B: int, chunk: int,
                       n_chunks: int, items, replicated) -> dict:
        """The JSON-able environment identity a journal header pins and
        ``resume`` re-verifies: geometry (backend/devices/axis/partition
        layout), job identity (name + process-stable signature + reduce
        semantics), and the chunk plan + dtype/shape structs.  Any
        difference makes the journaled bytes unreproducible, so resume
        refuses loudly (``ResumeMismatchError``) instead of diverging."""
        struct = [[list(a.shape[1:]), np.dtype(a.dtype).str]
                  for a in jax.tree_util.tree_leaves(items)]
        rep_struct = [[list(np.shape(a)), np.dtype(np.asarray(a).dtype).str]
                      for a in jax.tree_util.tree_leaves(replicated)]
        return {"platform": self.devices[0].platform,
                "n_devices": len(self.devices),
                "axis": self.axis,
                "partition_count": int(self.table.partition_count),
                "job": job.name,
                "signature": stable_signature(job.signature),
                "reduce": job.reduce,
                "deterministic": bool(job.deterministic),
                "n_items": int(B), "chunk": int(chunk),
                "n_chunks": int(n_chunks),
                "item_struct": struct, "rep_struct": rep_struct}

    def _restore_topology(self, snap: dict) -> None:
        """Rebuild mesh + ``PartitionTable`` from a journaled snapshot: force
        the member count (clamped to the surviving pool / IAS bounds), run
        the normal remesh barrier, then overwrite the freshly-rebalanced
        owners with the journaled map.  Restoring owners is FIDELITY (the
        locality-aware placement the dead coordinator had learned), not
        correctness — results are owner-map-invariant — so a clamped member
        count skips the owner overwrite rather than failing the resume."""
        n = max(min(int(snap["n_members"]), len(self.devices),
                    self.health_cfg.max_instances),
                self.health_cfg.min_instances)
        if n != self.n_members:
            self.controller.force_instances(n)
            self._remesh(n, reason="resume")
        if n == int(snap["n_members"]) and "owner" in snap:
            try:
                self.table.restore(
                    {"partition_count": self.table.partition_count,
                     "n_instances": n, "owner": snap["owner"]})
            except ValueError as e:
                raise ResumeMismatchError(
                    f"journaled partition snapshot does not fit this "
                    f"dispatcher: {e}") from e

    def resume(self, path, job: DispatchJob, items, *, replicated=(),
               chunk: Optional[int] = None,
               on_chunk: Optional[Callable] = None,
               dispatch_ahead: Optional[int] = None,
               retry_policy: Optional[RetryPolicy] = None,
               fault_injector: Optional[FaultInjector] = None,
               collect_stats: Optional[bool] = None,
               checkpoint: Optional[CheckpointPolicy] = None
               ) -> Tuple[object, DispatchReport]:
        """Continue a journaled stream after the coordinator died (or was
        drained).  ``path`` is the journal directory a previous ``submit``
        wrote under a ``CheckpointPolicy``; ``job``/``items``/``replicated``
        must be the same job — resume VERIFIES the environment signature
        (geometry, backend, job identity, chunk plan, dtype/shape structs)
        against the journal header and raises ``ResumeMismatchError`` on any
        difference, never silently diverging.

        A COMPLETE journal short-circuits: the final checkpoint is loaded
        (integrity-digested) and returned with ZERO chunk executions —
        ``resume`` of a finished stream is idempotent.  Otherwise the mesh
        and ``PartitionTable`` are rebuilt from the last journaled snapshot,
        the latest checkpoint's partial reduce state is restored (an exact
        pow2-subtree state of the deterministic chunk tree), journaled
        chunks before it are SKIPPED, and only the lost in-flight suffix is
        replayed — each replayed chunk digest-checked against its journal
        record.  The combined output is bit-identical to the uninterrupted
        run and is delivered on HOST (the restored base lives in host
        memory).  Returns ``(outputs, DispatchReport)`` with
        ``resumed_from`` / ``chunks_skipped`` / ``chunks_replayed`` set."""
        path = journal_dir(path)
        state = load_journal(path)
        if state.header is None:
            raise ResumeMismatchError(f"no journal header at {path!r} — "
                                      "nothing to resume")
        _, B, chunk_, n_chunks = self._chunk_plan(items, chunk)
        mine = self._env_signature(job, B, chunk_, n_chunks, items,
                                   replicated)
        theirs = state.header.get("env", {})
        diffs = [f"{k}: journal={theirs.get(k)!r} vs here={mine[k]!r}"
                 for k in mine if theirs.get(k) != mine[k]]
        if diffs:
            raise ResumeMismatchError(
                "journal environment signature mismatch — resuming would "
                "not reproduce the journaled bytes:\n  " + "\n  ".join(diffs))
        policy = checkpoint
        if policy is None:
            policy = CheckpointPolicy(
                path=path,
                every_n_chunks=int(state.header.get("every_n_chunks", 4)))
        elif policy.path != path:
            raise ValueError("checkpoint.path must equal the resume path")

        if state.complete is not None:
            rec = state.usable_checkpoint(final=True)
            if rec is None:
                raise ResumeMismatchError(
                    f"journal at {path!r} is complete but its final "
                    "checkpoint directory is missing")
            outputs, _ = load_checkpoint(path, rec)
            report = DispatchReport(
                job=job.name, n_items=B, chunk=chunk_, n_chunks=n_chunks,
                journal_path=path, resumed_from=path,
                chunks_skipped=n_chunks, chunks_replayed=0,
                kernel_path=job.kernel_path,
                map_path=self._map_path(job))
            return outputs, report

        snap = state.last_snapshot
        if snap is not None:
            self._restore_topology(snap)
        base_k, base_state = 0, None
        rec = state.usable_checkpoint()
        if rec is not None:
            base_state, manifest = load_checkpoint(path, rec)
            base_k = int(manifest["k"])
        digests = {ci: r["digest"] for ci, r in state.chunks.items()
                   if ci >= base_k and r.get("digest")}
        journal = JobJournal.reopen(policy)
        journal.append({"type": "resume", "k": base_k,
                        "replayed_from": base_k}, fsync=True)
        return self.submit(
            job, items, replicated=replicated, chunk=chunk_,
            on_chunk=on_chunk, dispatch_ahead=dispatch_ahead,
            deliver="host", retry_policy=retry_policy,
            fault_injector=fault_injector, collect_stats=collect_stats,
            checkpoint=policy,
            _resume={"journal": journal, "path": path, "base_k": base_k,
                     "base_state": base_state, "digests": digests})

    # ------------------------------------------------------------- submission
    def submit(self, job: DispatchJob, items, *, replicated=(),
               chunk: Optional[int] = None,
               on_chunk: Optional[Callable] = None,
               dispatch_ahead: Optional[int] = None,
               deliver: str = "device",
               retry_policy: Optional[RetryPolicy] = None,
               fault_injector: Optional[FaultInjector] = None,
               collect_stats: Optional[bool] = None,
               checkpoint: Optional[CheckpointPolicy] = None,
               tenant: Optional[str] = None,
               _resume: Optional[dict] = None
               ) -> Tuple[object, DispatchReport]:
        """Stream ``items`` (a pytree of arrays sharing leading dim B)
        through ``job`` in fixed-shape chunks, as an ASYNC double-buffered
        pipeline.

        Every chunk is padded to ``pad_to_shards(chunk, n_members)`` rows
        (live rows flagged by the valid mask), so all chunks of a geometry
        share ONE executable — grids larger than device memory stream with
        at most one compile per (geometry, job-signature).

        Pipelining: chunk k+1 is staged (sliced + padded) and dispatched
        while chunk k still runs on device — JAX dispatch is asynchronous,
        so the host never blocks mid-stream except to (1) bound the queue at
        ``dispatch_ahead`` launched-but-unretired chunks (memory bound; 0
        retires each chunk right after its launch) and (2) take the
        wall-time samples the IAS needs.  The only other synchronization
        points are the REMESH BARRIER (``_remesh`` drains the queue before
        rebuilding) and the final reduce.  Chunk boundaries and reduce order
        never depend on how many chunks were in flight, so results are
        bit-identical at every depth for every scale sequence.

        Staging: a DEVICE-resident item set (every leaf a ``jax.Array``) of
        at least ``device_slice_min_bytes`` never round-trips to host — the
        source is padded once on device and each chunk's window is cut on
        device (``lax.dynamic_slice`` + valid masking).  On a one-member
        mesh whose device holds the source, the job's executable takes the
        whole source with the chunk's ``lo``/``n_live`` and cuts its window
        itself, so the window is read in place and no chunk buffer is
        written (``staged_in_place``; a deterministic job's rows stage
        excepted); where passing the source would move it (several members,
        another device) ``executor.slice_chunk`` copies the chunk out first.
        Host-resident (or tiny, where the per-chunk device cut costs more
        than the copies it saves) items use numpy slicing as before.
        When no scale event fired mid-stream, outputs stay on device and are
        exposed LAZILY (callers chain them into the next job or block at
        their own reduce boundary); a remesh mixes geometries, so the final
        combine falls back to host.

        After each chunk ``on_chunk(dispatcher, chunk_index, n_chunks)``
        runs (feed ``observe_load`` there to drive the IAS
        deterministically).  With ``auto_scale=True`` the dispatcher instead
        feeds an EMA of measured retirement-to-retirement step times over
        the job class's ``target_step_time`` (see ``_job_target``) — one
        ``block_until_ready`` per sample, exactly where the IAS needs a
        wall-time reading, never a per-chunk stop-the-world.

        ``deliver`` places the final reduce: "device" (default) keeps it
        lazy on device — the right choice when the output chains into
        another job; "host" materializes it at the reduce boundary — the
        right choice when the caller converts to numpy immediately (one
        gather instead of a sharded device concat PLUS a gather; the values
        are bitwise identical either way).

        Fault tolerance: every chunk is validated once it retires.  An
        active ``retry_policy`` or a ``fault_injector`` (falling back to the
        dispatcher-level defaults) ARMS the validation — deadline, optional
        finite check — so detected failures are retried under the policy's
        budget, repeat-offender members are quarantined via a forced failure
        remesh, and the failed plus lost in-flight chunks are REPLAYED;
        because the combine walks chunk INDEX order, a recovered stream is
        bit-identical to a fault-free run.  Unarmed, validation only stamps
        the stats and journals the chunk.  Unrecoverable streams raise
        ``JobFailedError`` carrying the report.  Returns ``(outputs,
        DispatchReport)``.

        Durability: ``checkpoint`` (a ``CheckpointPolicy``, falling back to
        the dispatcher default) journals the stream — header with the
        environment signature and chunk plan, a digest record per validated
        chunk, fault and scale records (with partition snapshots) — and
        atomically persists the partial reduce state every
        ``every_n_chunks`` validated chunks (pow2-aligned boundaries of the
        deterministic chunk tree; writes overlap on a background thread).
        Kill the coordinator at ANY point and ``resume(path, ...)``
        reproduces the uninterrupted bytes; ``request_drain`` /
        ``install_drain_signal`` turn preemption notices into a graceful
        checkpoint + ``DrainInterrupted``.  A ``JobFailedError``'s report
        is journaled before raising, so post-mortems survive process death.
        ``_resume`` is the private handoff from ``resume`` (restored base
        state, chunks to skip, digests to re-verify).
        """
        if deliver not in ("device", "host"):
            raise ValueError(f"unknown deliver {deliver!r}")
        leaves, B, chunk, n_chunks = self._chunk_plan(items, chunk)
        # per-stage queueing stats: enqueue → dispatch → retire → validate
        # stamps per chunk, plus the stage spans.  Collection never touches
        # chunk payloads or reduce order (results stay bit-identical); the
        # mmn policy depends on the measured service decomposition, so it
        # forces a collector.
        collect = (self.collect_stats if collect_stats is None
                   else collect_stats)
        collector = (DispatchStats(warmup=self.health_cfg.stats_warmup,
                                   cooldown=self.health_cfg.stats_cooldown)
                     if collect or self.health_cfg.policy == "mmn" else None)
        # a tenant-scoped stream binds the fault injector, so tenant-
        # addressed specs fire only inside THIS stream (replays included)
        inj = (fault_injector if fault_injector is not None
               else self.fault_injector)
        bound = (inj.bind_tenant(tenant) if tenant is not None
                 and inj is not None else contextlib.nullcontext())
        sid = next(_STREAM_IDS)
        jax0 = jax_counts()
        with bound, span("dispatch.stream", collector, job=job.name,
                         n_chunks=n_chunks, stream=sid):
            try:
                self._active_stream = _Stream(
                    self, job, items, leaves, B, chunk, n_chunks, collector,
                    sid, replicated=replicated, on_chunk=on_chunk,
                    dispatch_ahead=dispatch_ahead, deliver=deliver,
                    retry_policy=retry_policy, fault_injector=inj,
                    checkpoint=checkpoint, resume=_resume, tenant=tenant)
                outputs, report = self._active_stream.run()
            finally:
                self._active_stream = None
        jax1 = jax_counts()
        report.jax_traces = jax1["traces"] - jax0["traces"]
        report.jax_compiles = jax1["compiles"] - jax0["compiles"]
        report.jax_cache_loads = jax1["cache_loads"] - jax0["cache_loads"]
        if collector is not None:            # with the stream's own span
            report.stats = collector.summary(n_servers=1)
        return outputs, report

    def _chunk_plan(self, items, chunk: Optional[int]):
        """``(leaves, B, chunk, n_chunks)`` of a stream over ``items``.
        B == 0 still runs ONE fully-padded chunk (valid all-False): concat
        outputs trim to correct empty arrays, sum/max partials reduce over
        masked-out rows only — parity with the non-dispatcher vmap path."""
        leaves = jax.tree_util.tree_leaves(items)
        if not leaves:
            raise ValueError("a stream needs at least one item array")
        B = int(leaves[0].shape[0])
        if any(int(l.shape[0]) != B for l in leaves):
            raise ValueError("item arrays must share their leading dim")
        chunk = chunk if chunk is not None else (self.chunk_size or B)
        chunk = max(1, min(int(chunk), max(B, 1)))
        return leaves, B, chunk, max(-(-B // chunk), 1)

    # ---------------------------------------------------- staging + combine
    def _pad_device_source(self, items, chunk: int, n_chunks: int, B: int):
        """Pad a device-resident item source ONCE (repeating the last row —
        the same well-defined dead-row fill the host path uses) so every
        fixed-shape chunk window stays in bounds at ANY member
        count the IAS can reach.  ``pad_to_shards(chunk, m)`` is NOT
        monotone in m (pad_to_shards(4, 3) = 6 > pad_to_shards(4, 4) = 4),
        so the bound is the max over every possible member count — an
        undersized pad would let ``dynamic_slice`` clamp the window and
        silently compute on the wrong rows.  One eager device op per
        stream; no host round-trip."""
        L_max = max(pad_to_shards(chunk, m)
                    for m in range(1, len(self.devices) + 1))
        need = (n_chunks - 1) * chunk + L_max
        if need <= B:
            return items
        return jax.tree_util.tree_map(
            lambda a: jnp.concatenate(
                [a, jnp.repeat(a[-1:], need - B, axis=0)]), items)

    def _stage_host(self, items_np, lo: int, n_live: int, L: int):
        """Host-side staging: numpy slice + pad-by-repeating-the-last-row
        (zeros when the slice is empty: nothing to repeat).  Padded rows are
        marked dead by the valid mask — which depends only on (L, n_live),
        so the device mask is memoized: full chunks of a stream reuse ONE
        array instead of paying a device_put per chunk."""
        sl = jax.tree_util.tree_map(lambda a: a[lo:lo + n_live], items_np)
        if L != n_live:
            sl = jax.tree_util.tree_map(
                lambda a: np.concatenate(
                    [a, np.repeat(a[-1:], L - n_live, axis=0)])
                if n_live else np.zeros((L,) + a.shape[1:], a.dtype), sl)
        valid = self._valid_masks.get((L, n_live))
        if valid is None:
            valid = jnp.asarray(np.arange(L) < n_live)
            self._valid_masks[(L, n_live)] = valid
        return sl, valid

    @staticmethod
    def _combine(job: DispatchJob, parts, combine_on_device: bool,
                 base=None):
        """Cross-chunk reduction at the stream's reduce boundary.  Each part
        is ``(n_live, chunk_output)``; padded rows of concat outputs are
        trimmed HERE, off the hot loop.  On ONE geometry (no mid-stream
        remesh) an async stream stays on device and the result is exposed
        lazily; across geometries the parts live on different device sets
        (eager device ops would not colocate), so those combine on host, as
        depth 0 does — the IEEE-754 f32 ops are bitwise identical either
        way.

        ``base`` is a resumed stream's restored checkpoint state (chunks
        before the checkpoint never re-ran): the concatenated row prefix
        for "concat", or the binary-counter pending dict for "sum"/"max" —
        seeding ``_chunk_tree_reduce`` so the replayed suffix folds through
        the identical tree the uninterrupted run used."""
        if combine_on_device:
            asarray = lambda a: a
            cat = lambda *p: jnp.concatenate(p, axis=0)
            add, mx = jnp.add, jnp.maximum
        else:
            asarray = np.asarray
            cat = lambda *p: np.concatenate(p, axis=0)
            add, mx = np.add, np.maximum
        if job.reduce == "concat":
            trimmed = [jax.tree_util.tree_map(
                lambda a: asarray(a)[:n_live], out) for n_live, out in parts]
            if base is not None:
                trimmed.insert(0, jax.tree_util.tree_map(asarray, base))
            return jax.tree_util.tree_map(cat, *trimmed)
        aggs = [jax.tree_util.tree_map(asarray, out) for _, out in parts]
        pending = (None if base is None
                   else {int(lvl): jax.tree_util.tree_map(asarray, t)
                         for lvl, t in base.items()})
        return _chunk_tree_reduce(aggs, add if job.reduce == "sum" else mx,
                                  pending=pending)

    # ------------------------------------------------------------ executables
    def _reads_in_place(self, job: DispatchJob, src) -> bool:
        """Whether the job's executable can take the whole device-resident
        ``src`` and cut each chunk's window itself: the mesh is one member
        and every leaf already lives on that member's device alone.  On a
        wider mesh, or from another device, passing the source would move
        all of it, so the chunk is copied out instead.  A deterministic
        job's rows stage keeps the copy: its row tree needs the mask as an
        operand of a second executable."""
        if job.deterministic or self.executor.n_members != 1:
            return False
        here = {self.executor.device_list[0]}
        return all(a.sharding.device_set == here
                   for a in jax.tree_util.tree_leaves(src))

    def _executable(self, job: DispatchJob, chunk_tree, replicated, L: int,
                    in_place: bool = False):
        """One compiled callable per (mesh, axis, signature, reduce, shapes).
        The mesh in the key is the ONLY geometry binding: a scale event
        retires exactly the outgoing mesh's entries (``_remesh``), every
        other geometry's executables stay warm for when the IAS returns.

        ``in_place``: ``chunk_tree`` is the whole padded source and the
        callable is ``fn(src, lo, n_live, *rep)``; it is compiled against
        the source's shape, so its rows are part of the key."""
        leaves = jax.tree_util.tree_leaves(chunk_tree)
        struct = tuple(
            (tuple(a.shape[1:]), np.dtype(a.dtype).str) for a in leaves)
        rep_struct = tuple(
            (tuple(np.shape(a)), np.dtype(np.asarray(a).dtype).str)
            for a in jax.tree_util.tree_leaves(replicated))
        mode = "member" if job.member_fn is not None else "global"
        src_rows = int(leaves[0].shape[0]) if in_place else None
        key = (self.mesh, self.axis, job.signature, job.reduce,
               job.deterministic, mode, L, struct, rep_struct, src_rows)
        fn = self.cache.get(key)
        if fn is None:
            builder = (self._build_member if mode == "member"
                       else self._build_global)
            fn = builder(job, L if in_place else None)
            self.cache.put(key, fn)
        return fn

    # donate_argnums for the chunk buffer (argnum 0, the chunk tree): it is
    # staged afresh for every launch, replays included, and used exactly
    # once, so XLA can recycle its memory for outputs — steady-state
    # streaming then allocates nothing.  The valid mask is NOT donated: it
    # is memoized across chunks (``_stage_host``).  An in-place executable
    # donates nothing: its argument 0 is the whole source, which every
    # later chunk, replay and caller reads again.
    _CHUNK_DONATE = (0,)

    def _jit_chunk(self, call: Callable, job: DispatchJob,
                   cut: Optional[int], stage: str = ""):
        """``jax.jit`` of ``call(chunk_tree, valid, *rep)`` named for the
        job.  ``cut`` None: it takes the staged chunk, donated.  ``cut`` an
        int L: it takes ``(src, lo, n_live, *rep)`` and cuts the L-row
        window itself (``cut_chunk``), so XLA fuses the slice into the
        window's first consumer and no chunk buffer is written."""
        if cut is None:
            return jax.jit(_named(call, job, stage),
                           donate_argnums=self._CHUNK_DONATE)

        def call_in_place(src, lo, n_live, *rep):
            return call(*cut_chunk(src, lo, n_live, cut), *rep)

        return jax.jit(_named(call_in_place, job, stage))

    def _build_member(self, job: DispatchJob, cut: Optional[int] = None):
        executor = self.executor          # bound to the key's mesh
        axis = self.axis
        # a deterministic job's fn returns PER-ROW contributions which the
        # executable itself tree-reduces (position-aligned row tree) AFTER
        # the gather — no member-count-shaped psum grouping ever touches
        # the float values, and the donated chunk buffers are never touched
        # again after the call returns
        row_out = job.reduce == "concat" or job.deterministic

        def body(data, *rep):
            local, lval = data
            out = job.member_fn(local, lval, *rep)
            if not row_out and job.reduce == "sum":
                return jax.tree_util.tree_map(executor.psum, out)
            if not row_out and job.reduce == "max":
                return jax.tree_util.tree_map(executor.pmax, out)
            return out

        out_specs = P(axis) if row_out else P()

        def call(chunk_tree, valid, *rep):
            out = executor.execute_on_key_owners(
                body, (chunk_tree, valid), replicated_args=rep,
                out_specs=out_specs)
            if job.deterministic:
                out = jax.tree_util.tree_map(
                    lambda a: _row_tree_sum(a, valid), out)
            return out

        if not job.deterministic:
            return self._jit_chunk(call, job, cut)

        # deterministic: the row tree compiles as its OWN executable so the
        # member_fn's producer can never FMA-contract into the level-0 adds
        # at M=1 (the executable boundary is the only fence the CPU backend
        # respects — see _row_tree_sum).  The rows stage keeps the chunk
        # donation; both stages enqueue async, so pipelining is unchanged.
        def rows_call(chunk_tree, valid, *rep):
            return executor.execute_on_key_owners(
                body, (chunk_tree, valid), replicated_args=rep,
                out_specs=out_specs)

        rows_fn = self._jit_chunk(rows_call, job, None, "rows")
        tree_fn = _row_tree_jit(job)

        def split_call(chunk_tree, valid, *rep):
            return tree_fn(rows_fn(chunk_tree, valid, *rep), valid)

        return split_call

    def _build_global(self, job: DispatchJob, cut: Optional[int] = None):
        executor = self.executor
        axis = self.axis

        def run(chunk_tree, valid, *rep):
            return job.global_fn(chunk_tree, valid, *rep)

        jitted = self._jit_chunk(run, job, cut)

        def replicate(rep):
            return tuple(jax.tree_util.tree_map(
                lambda a: executor.put(jnp.asarray(a), P()), r) for r in rep)

        if cut is not None:
            # one member holds the source: the window is its own placement
            def call_in_place(src, lo, n_live, *rep):
                return jitted(src, lo, n_live, *replicate(rep))

            return call_in_place

        # deterministic: the row tree compiles as its OWN executable (a
        # nested jit would inline into the outer trace) so the global_fn's
        # producer can never FMA-contract into the level-0 adds — the same
        # fence as _build_member (see _row_tree_sum)
        tree_fn = _row_tree_jit(job)

        def call(chunk_tree, valid, *rep):
            # auto-SPMD: place the chunk partitioned, the rest replicated,
            # and let the partitioner choose the schedule (Infinispan flavor)
            sharded = jax.tree_util.tree_map(
                lambda a: executor.put(jnp.asarray(a), P(axis)), chunk_tree)
            valid = executor.put(jnp.asarray(valid), P(axis))
            out = jitted(sharded, valid, *replicate(rep))
            if job.deterministic:
                out = tree_fn(out, valid)
            return out

        return call


# ------------------------------------------------------------ the chunk stream

class _Stream:
    """One ``submit``'s chunk stream and all of its state.

    Every chunk walks one path: launched (``stage`` + ``launch``) → retired
    (``retire_oldest``, or a remesh barrier's drain) → validated
    (``sync_validation``) → final (``note_validated``, which journals it).
    What the stream arms decides only how much validation does: with an
    active ``RetryPolicy`` or a ``FaultInjector`` it reads the finiteness
    probe enqueued at launch, checks the deadline, feeds the dispatcher's
    fault monitor, and turns a detected failure into a replay; unarmed, it
    stamps the collector and marks the chunk final.  ``depth`` bounds the
    flight queue: at 0 the loop retires each chunk right after its launch.

    The dispatcher holds the active stream: ``ElasticDispatcher.in_flight``
    and the remesh barrier's ``_drain_in_flight`` read its flight queue."""

    def __init__(self, d: ElasticDispatcher, job: DispatchJob, items, leaves,
                 B: int, chunk: int, n_chunks: int,
                 collector: Optional[DispatchStats], sid: int, *,
                 replicated, on_chunk, dispatch_ahead, deliver, retry_policy,
                 fault_injector, checkpoint, resume, tenant):
        self.d, self.job, self.replicated = d, job, replicated
        self.B, self.chunk, self.n_chunks = B, chunk, n_chunks
        self.collector, self.sid = collector, sid
        self.on_chunk, self.deliver, self.resume = on_chunk, deliver, resume
        self.depth = (d.dispatch_ahead if dispatch_ahead is None
                      else max(int(dispatch_ahead), 0))
        # device-side cuts save the host round-trip once the copies matter;
        # tiny item sets (a grid's per-variant scalars) and depth 0 (the
        # synchronous reference runs) stage through host numpy
        n_bytes = sum(l.size * l.dtype.itemsize for l in leaves)
        self.on_device = (self.depth > 0 and B > 0
                          and n_bytes >= d.device_slice_min_bytes
                          and all(isinstance(l, jax.Array) for l in leaves))
        self.src = (d._pad_device_source(items, chunk, n_chunks, B)
                    if self.on_device
                    else jax.tree_util.tree_map(np.asarray, items))

        policy = retry_policy if retry_policy is not None else d.retry_policy
        self.injector = fault_injector   # submit resolved the default
        if policy is None:
            # an injector without an explicit policy still needs a detector:
            # default attempt budget with the finiteness probe armed
            policy = RetryPolicy(check_finite=self.injector is not None)
        self.policy = policy
        self.armed = self.injector is not None or policy.active
        self.probe = policy.check_finite or self.injector is not None
        if job.deterministic and n_chunks > 1 and chunk & (chunk - 1) != 0:
            warnings.warn(
                f"deterministic float sum chunked at {chunk} (not a power of"
                " two): results are deterministic and replay-stable for THIS"
                " chunking but not bit-identical across chunk sizes — use a"
                " power-of-two chunk for the cross-chunking guarantee",
                NonPow2ChunkWarning, stacklevel=2)

        self.report = DispatchReport(
            job=job.name, n_items=B, chunk=chunk, n_chunks=n_chunks,
            dispatch_ahead=self.depth, kernel_path=job.kernel_path,
            map_path=d._map_path(job), tenant=tenant)
        self.hits0, self.builds0 = d.cache.hits, d.cache.builds
        self.events0 = len(d.scale_events)
        self.base_k = 0 if resume is None else int(resume["base_k"])
        self.open_journal(
            checkpoint if checkpoint is not None else d.checkpoint_policy,
            items)
        # (n_live, output) by chunk index: a REPLAY overwrites its slot and
        # the combine walks slots in index order, so retries never perturb
        # the reduce tree; a resumed stream fills only slots >= base_k
        self.parts: List[Optional[Tuple[int, object]]] = [None] * n_chunks
        self.part_epochs: set = set()    # geometries the parts live on
        self.queue: Deque[int] = collections.deque(range(self.base_k,
                                                         n_chunks))
        # launched, not retired: (chunk, out, compiled, t_launch)
        self.flight: Deque[Tuple] = collections.deque()
        # left the flight queue, not validated: the flight queue is always
        # its suffix; what lies before it a retirement or a barrier drained
        self.pending_val: Deque[Tuple] = collections.deque()
        self.launch_epoch: Dict[int, int] = {}  # chunk -> epoch at launch
        self.fired_cb: set = set()       # chunks whose on_chunk has run
        self.attempts: Dict[int, int] = collections.Counter()
        self.strikes: Dict = collections.Counter()  # retryable fails/device
        self.open_recoveries: List[dict] = []  # awaiting their replays
        self.fail_t: Dict[int, float] = {}  # chunk -> last failure detected
        self.val_step = 0                # fault-monitor sample index
        # the step-time sampler (``mark``)
        self.alpha = getattr(d.health_cfg, "ema_alpha", 0.4)
        self.t_mark: Optional[float] = None
        self.ema: Optional[float] = None
        self.epoch = d._epoch
        # nothing after retirement needs the chunks one by one (no checks,
        # stamps, journal or auto-scale samples): ``settle_tail`` drops them
        self.lazy_tail = not (self.armed or collector is not None
                              or self.journal is not None
                              or (d.auto_scale and on_chunk is None))
        if collector is not None:
            # a submit stream is a CLOSED arrival process: every chunk is
            # ready at stream start, so they share one enqueue stamp and
            # queue_wait measures time spent behind the pipeline bound
            t0_enq = collector.clock()
            for ci in self.queue:
                collector.enqueue(ci, t0_enq)

    # ------------------------------------------------------------ durability
    def open_journal(self, ckpolicy: Optional[CheckpointPolicy], items):
        """Open the stream's journal (or adopt a resume's) and its durable
        reduce state: ``ck_k`` the folded prefix length, ``ck_state`` the
        binary-counter pending dict (sum/max) or the concatenated prefix
        (concat), ``ck_host`` validated host copies awaiting the next fold,
        ``journaled`` the chunks recorded, ``digests`` the journaled
        digests a resumed run re-verifies its replays against."""
        d, report, resume = self.d, self.report, self.resume
        self.ckpolicy = ckpolicy
        self.journal: Optional[JobJournal] = None
        self.ck_k, self.ck_state = self.base_k, None
        self.ck_host: Dict[int, object] = {}
        self.journaled: set = set()
        self.digests: Dict[int, str] = {}
        self.n_scale = 0                 # scale events journaled
        if ckpolicy is None:
            return
        if resume is not None:
            self.journal = resume["journal"]
            self.ck_state = resume["base_state"]
            self.digests = dict(resume["digests"])
            report.resumed_from = resume["path"]
            report.chunks_skipped = self.base_k
            report.chunks_replayed = self.n_chunks - self.base_k
        else:
            env = d._env_signature(self.job, self.B, self.chunk,
                                   self.n_chunks, items, self.replicated)
            self.journal = JobJournal.create(ckpolicy, {
                "env": env, "n_members": d.n_members,
                "owner": d.table.owner.tolist(),
                "every_n_chunks": ckpolicy.every_n_chunks})
        report.journal_path = self.journal.path

    def journal_scales(self):
        """Journal scale events fired since the last call, each with the
        post-event member count and partition-owner snapshot — what
        ``resume`` rebuilds the topology from."""
        d = self.d
        while self.journal is not None and \
                self.n_scale < len(d.scale_events) - self.events0:
            ev = d.scale_events[self.events0 + self.n_scale]
            self.journal.append({"type": "scale", "event": ev,
                                 "n_members": d.n_members,
                                 "owner": d.table.owner.tolist()})
            self.n_scale += 1

    def advance_checkpoint(self, force: bool = False):
        """Fold newly-contiguous validated chunks into the durable reduce
        state and persist it (atomic dir) when a stride boundary is crossed
        — or at the exact watermark when a drain forces it.  The counter
        state after ANY validated prefix k is exactly the pow2 subtrees of
        k's binary decomposition, so resume is bit-identical.  Runs on the
        journal WRITER thread; ``drain_now`` calls it only after
        ``journal.wait`` has idled that thread."""
        w = self.ck_k
        while w in self.ck_host:
            w += 1
        stride = self.ckpolicy.every_n_chunks
        boundary = w if force else (w // stride) * stride
        if boundary <= self.ck_k or (not force and boundary >= self.n_chunks):
            return                       # completion writes the final state
        if self.job.reduce == "concat":
            pieces = [] if self.ck_state is None else [self.ck_state]
            pieces += [self.ck_host.pop(ci)
                       for ci in range(self.ck_k, boundary)]
            self.ck_state = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs, axis=0), *pieces)
            kind = "prefix"
        else:
            combine = np.add if self.job.reduce == "sum" else np.maximum
            # shallow-copy so a resume's restored base dict is never
            # mutated — the final combine still needs it untouched
            pending = dict(self.ck_state or {})
            for ci in range(self.ck_k, boundary):
                counter_push(pending, self.ck_host.pop(ci), combine)
            self.ck_state = pending
            kind = "pending"
        self.ck_k = boundary
        self.journal.checkpoint_now(boundary, kind, self.ck_state,
                                    {"n_members": self.d.n_members})

    def host_part(self, out, n_live: int):
        """A chunk's output gathered to host, concat rows trimmed."""
        host = jax.tree_util.tree_map(np.asarray, out)
        if self.job.reduce == "concat":
            host = jax.tree_util.tree_map(lambda a: a[:n_live], host)
        return host

    def finish_chunk(self, ci: int, out, n_live: int, record: dict):
        """Writer-thread tail of ``journal_chunk``: gather, digest, write
        the chunk record, stage the host copy for the fold, advance the
        checkpoint.  Everything here walks output bytes, so the dispatch
        thread pays one queue put per chunk."""
        host = self.host_part(out, n_live)
        if "digest" not in record:
            record["digest"] = tree_digest(host)
        self.journal.sync_append(record)
        self.ck_host[ci] = host
        self.advance_checkpoint()

    def journal_chunk(self, ci: int):
        """Close the durable books on one FINAL chunk.  The heavy tail —
        host gather, digest, fold, checkpoint — rides the journal writer
        thread via ``defer``; only a resumed replay digests HERE, inline,
        because a divergent replay must stop the stream immediately, not
        surface after more chunks launched."""
        if self.journal is None or ci in self.journaled or ci < self.base_k:
            return
        n_live, out = self.parts[ci]
        record = {"type": "chunk", "chunk": int(ci),
                  "attempt": int(self.attempts[ci]), "n_live": int(n_live)}
        expect = self.digests.get(ci)
        if expect is not None:
            host = self.host_part(out, n_live)
            digest = tree_digest(host)
            if digest != expect:
                raise ResumeMismatchError(
                    f"replayed chunk {ci} digest {digest[:12]}… does "
                    f"not match the journaled {expect[:12]}… — the "
                    "items or job differ from the journaled stream")
            record["digest"] = digest
            out = host                   # gathered once; the fold reuses it
        elif not self.ckpolicy.digest_chunks:
            record["digest"] = None
        self.journal.defer(lambda c=ci, o=out, nl=n_live, r=record:
                           self.finish_chunk(c, o, nl, r))
        self.journaled.add(ci)
        self.journal_scales()

    def close_journal(self):
        """Settle the writer thread, report the checkpoints, close."""
        journal, report = self.journal, self.report
        journal.wait()
        report.checkpoints = journal.n_checkpoints
        report.checkpoint_write_s = list(journal.write_s)
        if self.collector is not None:
            for w_s in journal.write_s:
                self.collector.record_checkpoint(w_s)
        journal.close()

    def drain_now(self):
        """Graceful preemption (``request_drain``/SIGTERM): stop
        launching, retire + validate everything in flight, checkpoint
        the exact validated watermark, journal the drain, and raise
        ``DrainInterrupted`` — ``resume`` continues the stream later."""
        self.d._drain_requested.clear()
        while self.flight:
            self.retire_oldest()
        self.sync_validation()
        self.journal_scales()
        self.journal.wait()              # settle the deferred fold tails so
        # ck_k/ck_state are this thread's to touch
        self.advance_checkpoint(force=True)
        self.journal.append({"type": "drain", "k": int(self.ck_k),
                             "remaining": sorted(self.queue)}, fsync=True)
        self.close_journal()
        if self.collector is not None:
            self.report.stats = self.collector.summary(n_servers=1)
        self.report.wall_s = time.perf_counter() - self.t_start
        raise DrainInterrupted(
            f"stream of job {self.job.name!r} drained at validated prefix "
            f"{self.ck_k}/{self.n_chunks} on request", self.report,
            self.journal.path)

    # ------------------------------------------------------------ retirement
    def mark(self, compiled: bool, t_launch: float):
        """Sample one step time — retirement-to-retirement in steady state,
        launch-to-completion when nothing retired before (short streams) —
        and, under auto_scale, feed EMA/target to the IAS.  Compile chunks
        and remesh barriers reset the timer instead: their wall is compile
        or rebuild noise that would ratchet the scaler to max_instances."""
        d = self.d
        now = time.perf_counter()
        if compiled or self.epoch != d._epoch:
            self.epoch = d._epoch
            self.t_mark = now
            return
        since = (t_launch if self.t_mark is None
                 else max(self.t_mark, t_launch))
        dt, self.t_mark = now - since, now
        self.ema = (dt if self.ema is None
                    else self.alpha * dt + (1.0 - self.alpha) * self.ema)
        self.report.ema_step_s = self.ema
        if not (d.auto_scale and self.on_chunk is None):
            return
        if d.health_cfg.policy == "mmn" and self.collector is not None:
            # queue-aware feed: measured per-member service rate vs the
            # demand anchor 1/target.  Closed streams have no meaningful
            # arrival process, so queue_length stays 0 — backlog is
            # pipeline structure, not unmet demand (open callers like
            # serve/ pass a measured Lq themselves).
            s = self.collector.mean_service()
            if math.isfinite(s) and s > 0:
                target = d._job_target(self.job, s)
                d.controller.tick_queue(QueueSnapshot(
                    arrival_rate=1.0 / target,
                    service_rate=1.0 / (s * d.n_members),
                    n_members=d.n_members, queue_length=0.0))
        else:
            d.observe_load(self.ema / d._job_target(self.job, self.ema))

    def retire_oldest(self):
        """Block on the oldest launched chunk, sample its step time, then
        validate every chunk that has left the flight queue."""
        ci, out, compiled, t_launch = self.flight.popleft()
        with span("dispatch.retire", self.collector, stream=self.sid,
                  chunk=ci):
            jax.block_until_ready(out)
        if self.collector is not None:
            self.collector.retire(
                ci, tainted=compiled or self.launch_epoch.get(ci)
                != self.d._epoch)
        self.mark(compiled, t_launch)
        self.sync_validation()

    def drain_in_flight(self) -> int:
        """Block until every launched chunk has retired; returns how many
        were in flight.  If a chunk raises at the blocking point the rest
        is still dropped: no stale chunk may leak into the next stream."""
        n = len(self.flight)
        try:
            while self.flight:
                jax.block_until_ready(self.flight.popleft()[1])
        finally:
            self.flight.clear()
        return n

    def sync_validation(self):
        """Validate every chunk that has left the flight queue — normal
        retirements AND remesh-barrier drains."""
        while len(self.pending_val) > len(self.flight):
            self.validate(*self.pending_val.popleft())

    def validate(self, ci: int, out, t_launch: float, M: int, L: int, fin,
                 compiled: bool):
        """Settle one retired chunk: fire any scheduled stall, take the
        chunk's wall, read the finiteness probe (``fin``, enqueued at
        launch on armed streams), feed the fault monitor (armed streams
        only, so its window sees what it always saw), and route a detected
        failure to ``fail_chunk``; otherwise stamp and mark final."""
        d, c = self.d, self.collector
        delay, stall_slot = (self.injector.stall_for(ci)
                             if self.injector is not None else (0.0, None))
        if delay > 0:
            time.sleep(delay)             # the hung launch: retirement late
        now = time.perf_counter()
        wall = now - t_launch
        tainted = compiled or self.launch_epoch.get(ci) != d._epoch
        finite = fin is None or bool(fin)
        if self.armed:
            member_times = None
            if stall_slot is not None:
                member_times = [max(wall - delay, 0.0)] * M
                member_times[stall_slot % M] = wall
            self.val_step += 1
            d.fault_monitor.observe_chunk(
                step=self.val_step, wall_s=wall, finite=finite,
                member_times=member_times, tainted=tainted)
        if c is not None and delay > 0:
            c.record_stall(delay)
        if not finite:
            if c is not None:
                # a failed attempt's wall is fault noise: keep the
                # record's time integrals, drop it from the windows
                c.validate(ci, t=now, tainted=True)
            self.fail_chunk(ci, "nan_poison",
                            member=_nonfinite_member(out, L, M),
                            detail="non-finite chunk output", wall=wall)
            return
        timeout = self.policy.chunk_timeout_s
        if timeout is not None and wall > timeout:
            if c is not None:
                c.validate(ci, t=now, tainted=True)
            self.fail_chunk(
                ci, "stall", member=stall_slot,
                detail=(f"wall {wall:.3f}s exceeded deadline {timeout}s "
                        f"(straggler skew "
                        f"{d.fault_monitor.straggler_skew():.2f})"),
                wall=wall)
            return
        if c is not None:
            c.validate(ci, t=now, tainted=tainted)
        self.note_validated(ci, now)

    def note_validated(self, ci: int, now: float):
        """Mark a validated chunk final: stamp the recovery latency on its
        latest failure record and on any open member-failure recovery
        awaiting its replay, and journal it."""
        t0 = self.fail_t.pop(ci, None)
        if t0 is not None:
            for rec in reversed(self.report.failures):
                if rec["chunk"] == ci and "recovered_after_s" not in rec:
                    rec["recovered_after_s"] = now - t0
                    break
        for open_rec in self.open_recoveries[:]:
            open_rec["outstanding"].discard(ci)
            if not open_rec["outstanding"]:
                open_rec["event"]["recovery_s"] = now - open_rec["t0"]
                self.open_recoveries.remove(open_rec)
        self.journal_chunk(ci)

    # --------------------------------------------------------------- failure
    def record_failure(self, ci: int, kind: str, member, detail: str,
                       wall=None):
        """One detected failure: in the report, and in the journal (retry
        and fault events are durable too)."""
        rec = {"chunk": ci, "kind": kind, "attempt": self.attempts[ci],
               "member": member, "detail": detail}
        self.report.failures.append(dict(rec, wall_s=wall))
        if self.journal is not None:
            self.journal.append(dict(type="fault", **rec))

    def requeue(self, ci: int):
        self.queue.appendleft(ci)
        if self.collector is not None:
            self.collector.enqueue(ci)

    def recover_member(self, device, slot: int, failed_ci: int, cause: str):
        """Member-failure recovery: the replay set is the failed chunk
        plus every launched-but-unvalidated chunk (their buffers may
        live on the dead member); drain the survivors, force the
        failure remesh, and requeue the replays in ascending order."""
        t0 = time.perf_counter()
        lost = sorted({failed_ci} | {e[0] for e in self.pending_val}
                      | {e[0] for e in self.flight})
        self.drain_in_flight()
        self.pending_val.clear()
        self.strikes.pop(device, None)
        event = self.d._member_failure_remesh(device, slot, self.report)
        event.update({"cause": cause, "dead_member": slot,
                      "dead_device": str(device), "failed_chunk": failed_ci,
                      "replayed_chunks": lost})
        self.report.recovery_events.append(event)
        self.report.retries += len(lost)
        self.open_recoveries.append(
            {"event": event, "t0": t0, "outstanding": set(lost)})
        for ci in reversed(lost):
            self.requeue(ci)

    def fail_chunk(self, ci: int, kind: str, member=None, detail: str = "",
                   wall=None):
        """Record one retryable chunk failure, enforce the attempt
        budget, quarantine a repeat-offender member, back off, and
        requeue the chunk for replay."""
        d, policy, report = self.d, self.policy, self.report
        self.attempts[ci] += 1
        attempt = self.attempts[ci]
        self.fail_t[ci] = time.perf_counter()
        self.record_failure(ci, kind, member, detail, wall)
        if attempt >= policy.max_attempts:
            raise JobFailedError(
                f"chunk {ci} of job {self.job.name!r} failed {attempt}x"
                f" (last: {kind}); attempts exhausted (max_attempts="
                f"{policy.max_attempts})", report)
        if member is not None and policy.quarantine_after > 0:
            mesh_devices = d.executor.device_list
            dev = mesh_devices[member % len(mesh_devices)]
            self.strikes[dev] += 1
            # quarantine only when the pool can afford to lose the
            # member; otherwise keep retrying under the attempt budget
            can_drop = len(d.devices) - 1 >= max(1,
                                                 d.health_cfg.min_instances)
            if self.strikes[dev] >= policy.quarantine_after and can_drop:
                self.recover_member(
                    dev, member, ci,
                    cause=(f"quarantined: {self.strikes[dev]} retryable "
                           f"failures attributed to one member "
                           f"(last: {kind})"))
                return
        report.retries += 1
        backoff = policy.backoff_for(attempt)
        if backoff > 0:
            time.sleep(backoff)
        self.requeue(ci)

    # ---------------------------------------------------------------- launch
    def stage(self, ci: int, lo: int, n_live: int, L: int):
        """Cut chunk ``ci``'s rows ``[lo, lo + L)``: in place by the job's
        own executable (one member holding the device source), by
        ``executor.slice_chunk`` (other device sources), or by host numpy.
        Returns the executable's leading arguments and whether they are
        the whole source."""
        d, report = self.d, self.report
        with span("dispatch.stage", self.collector, stream=self.sid,
                  chunk=ci):
            in_place = self.on_device and d._reads_in_place(self.job,
                                                            self.src)
            if in_place:
                # the executable cuts rows [lo, lo + L) itself
                args = (self.src, lo, n_live)
                report.staged_in_place += 1
            elif self.on_device:
                args = d.executor.slice_chunk(self.src, lo, L, n_live)
            else:
                args = d._stage_host(self.src, lo, n_live, L)
                report.staged_host += 1
            report.staged_device += self.on_device
        return args, in_place

    def launch(self, ci: int) -> bool:
        """Stage + compile + dispatch chunk ``ci`` into the flight queue.
        Returns False when a fault hook failed the launch (the chunk was
        requeued, or a member recovery already re-queued the replay set)."""
        d, report, injector = self.d, self.report, self.injector
        lo, hi = ci * self.chunk, min((ci + 1) * self.chunk, self.B)
        n_live = hi - lo
        M = d.executor.n_members
        L = pad_to_shards(self.chunk, M)
        if injector is not None:
            try:
                injector.on_launch(ci, d.executor.device_list)
            except MemberFailedError as e:
                # the MEMBER failed, not the chunk: no attempt consumed
                self.record_failure(ci, "member_crash", e.member, str(e))
                self.recover_member(e.device, e.member, ci,
                                    cause="member crash detected at launch")
                return False
        args, in_place = self.stage(ci, lo, n_live, L)
        with span("dispatch.launch", self.collector, stream=self.sid,
                  chunk=ci) as launching:
            builds_before = d.cache.builds
            try:
                if injector is not None:
                    injector.on_compile(ci)
                fn = d._executable(self.job, args[0], self.replicated, L,
                                   in_place)
            except CompileFailedError as e:
                self.fail_chunk(ci, "compile_fail", detail=str(e))
                return False
            compiled = d.cache.builds != builds_before
            launching.annotate(built=int(compiled))
            t_launch = time.perf_counter()
            self.launch_epoch[ci] = d._epoch
            if self.collector is not None:
                self.collector.dispatch(ci, t_launch, tainted=compiled)
            out = fn(*args, *self.replicated)             # async dispatch
        # (deterministic jobs: the executable itself tree-reduced the rows,
        # so `out` is already the chunk partial)
        if injector is not None:
            out = injector.maybe_poison(ci, out, L, M)
        self.flight.append((ci, out, compiled, t_launch))
        report.max_in_flight = max(report.max_in_flight, len(self.flight))
        # combine lazily, in chunk order — retirement (blocking) is
        # decoupled from reduction, so order never depends on how many
        # chunks are in flight.  concat rows are trimmed at the reduce
        # boundary, not here: an eager mid-stream slice of an unevenly-
        # sharded chunk would cost a per-chunk reshard
        self.parts[ci] = (n_live, out)
        self.part_epochs.add(d._epoch)
        report.members_per_chunk.append(M)
        fin = _finite_probe(out) if self.probe else None
        self.pending_val.append((ci, out, t_launch, M, L, fin, compiled))
        return True

    # ------------------------------------------------------------ the stream
    def run(self) -> Tuple[object, DispatchReport]:
        """Launch every queued chunk, keeping at most ``depth`` in flight,
        then combine and close the books."""
        d, job = self.d, self.job
        self.t_start = time.perf_counter()
        try:
            while self.queue:
                if self.journal is not None and d._drain_requested.is_set():
                    self.drain_now()     # raises DrainInterrupted
                ci = self.queue.popleft()
                if not self.launch(ci):
                    continue
                while len(self.flight) > self.depth:
                    self.retire_oldest()
                if self.on_chunk is not None and ci not in self.fired_cb:
                    # scale schedules stay deterministic under faults: the
                    # callback fires once per chunk INDEX, on its first
                    # launch, never again on replays
                    self.fired_cb.add(ci)
                    self.on_chunk(d, ci, self.n_chunks)
                    self.sync_validation()   # an on_chunk remesh drained
                if not self.queue:
                    self.settle_tail()   # validation may refill the queue
        except DrainInterrupted:
            raise                        # graceful preemption, not a dying
            # stream: the journal is closed, calibration stays valid
        except Exception as exc:
            self.abort(exc)
            raise
        finally:
            # quiesce and forget every launched chunk, so after an
            # exception mid-stream (a failing on_chunk, a bad replicated
            # operand, an unrecoverable fault) the dispatcher is reusable
            # and no buffer outlives the stream
            self.drain_in_flight()
        return self.finish()

    def settle_tail(self):
        """The end of the queue.  A lazy tail drops the flight queue without
        blocking (``parts`` keeps the arrays alive; the caller blocks at
        its own reduce boundary); any other stream retires, samples and
        validates every chunk, short streams included."""
        if self.lazy_tail:
            self.flight.clear()
            self.pending_val.clear()
            return
        while self.flight and not self.queue:
            self.retire_oldest()
        if not self.queue:
            self.sync_validation()

    def abort(self, exc: Exception):
        """A dying stream's post-mortem: journal a ``JobFailedError``'s
        report (or an aborted marker) before the error propagates — best
        effort, never masking it — and drop the job class's self-calibrated
        IAS target, whose inflated first sample would steer the NEXT
        stream's scaler (explicit ``calibrate_target`` pins survive)."""
        if self.journal is not None:
            try:
                if isinstance(exc, JobFailedError):
                    self.journal.append(
                        {"type": "job_failed", "message": str(exc),
                         "report": exc.report.summary()}, fsync=True)
                else:
                    self.journal.append({"type": "aborted",
                                         "error": repr(exc)}, fsync=True)
                self.journal.close()
            except Exception:
                pass
        if self.job.signature not in self.d._explicit_targets:
            self.d.job_targets.pop(self.job.signature, None)

    def finish(self) -> Tuple[object, DispatchReport]:
        """Combine the parts at the reduce boundary; a journaled stream
        then writes the combined output as its FINAL checkpoint and marks
        itself complete (fsync'd), so resuming it returns this state with
        zero executions."""
        d, report = self.d, self.report
        # one geometry throughout, a pipelined stream, and device delivery:
        # combine on device and expose the result lazily; host delivery, a
        # mid-stream remesh (parts on different device sets), a resumed
        # stream (the restored base lives in host memory) or depth 0 (the
        # synchronous reference's host combine) combine on host
        on_device = (self.deliver == "device" and self.depth > 0
                     and len(self.part_epochs) <= 1 and self.resume is None)
        base = None if self.resume is None else self.resume["base_state"]
        with span("dispatch.combine", self.collector, stream=self.sid):
            outputs = d._combine(self.job, self.parts[self.base_k:],
                                 on_device, base=base)
        if self.journal is not None:
            self.journal_scales()
            host_out = jax.tree_util.tree_map(np.asarray, outputs)
            self.journal.write_checkpoint(self.n_chunks, "final", host_out,
                                          {"n_members": d.n_members})
            self.journal.append({"type": "complete",
                                 "n_chunks": self.n_chunks}, fsync=True)
            self.close_journal()
            d._drain_requested.clear()   # a drain that lost the race to
            # completion must not preempt the NEXT stream
        report.compiles = d.cache.builds - self.builds0
        report.cache_hits = d.cache.hits - self.hits0
        report.scale_events = len(d.scale_events) - self.events0
        report.wall_s = time.perf_counter() - self.t_start
        return outputs, report
