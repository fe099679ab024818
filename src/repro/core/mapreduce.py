"""MapReduce engine — the paper's dual-backend MapReduce layer (§3.4.2, §4.2).

Cloud²Sim implements the SAME job API over Hazelcast and Infinispan and
benchmarks them against each other (Figs 5.9–5.11).  We keep that design,
but both backends now execute as jobs on the unified ``ElasticDispatcher``
middleware (``core/dispatch.py``):

  backend="hazelcast"   a ``member_fn`` dispatch job: map() runs on each
                        member's local chunk, reduce() is an explicit
                        collective (psum) — the member-owned, logic-to-data
                        execution model.
  backend="infinispan"  a ``global_fn`` dispatch job: the same job expressed
                        as a global computation; the partitioner chooses the
                        schedule (Infinispan's "local-first cache" flavor).

Because the job layer is the dispatcher, MapReduce gains what the thesis's
§5 dynamic scaler promised: chunked streaming of corpora larger than one
dispatch, and ADAPTIVE SCALING — the IntelligentAdaptiveScaler can grow or
shrink the member set between chunks and the stream resumes on the new
mesh.  Word count reduces in int32, so results are BIT-identical for any
member count, chunking, or mid-stream scale event (both backends agree
exactly — the thesis's accuracy claim, now at the MapReduce layer too);
FLOAT jobs (``word_weight_job``) opt into the dispatcher's deterministic
tree reduction and get the same guarantee despite non-associative adds.
The old ``n_files % members == 0`` restriction is gone: the dispatcher pads
chunks to whole shards and masks the padding out of the reduction.

Jobs follow the paper's default example: word count over a corpus of files.
``map_invocations`` = number of files (leading shard dim); ``reduce
invocations`` = number of distinct keys touched (vocab bins), matching how the
thesis scales its experiments (§4.2.3).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.dispatch import DispatchJob, ElasticDispatcher


@dataclasses.dataclass(frozen=True)
class MapReduceJob:
    """map_fn: (file_chunk) -> partial aggregate; combine: pairwise reduce.

    ``deterministic`` routes the job through the dispatcher's deterministic
    float reduction: per-file map outputs are combined by position-aligned
    pairwise trees instead of shard-shaped sums, so FLOAT jobs get the same
    bit-identity guarantee across backends, member counts, scale events and
    (power-of-two) chunkings that int32 word count has for free."""
    map_fn: Callable
    n_keys: int                     # size of the reduced key space
    name: str = "job"
    deterministic: bool = False     # fixed-tree float reduction


def word_count_job(vocab: int, use_kernel: bool = False) -> MapReduceJob:
    """The paper's default word-count application: counts token occurrences.

    use_kernel: route the per-shard histogram through the Pallas histogram
    kernel (interpret mode on CPU) instead of the jnp one-hot path.
    """
    if use_kernel:
        from repro.kernels.histogram import ops as hist_ops
        fn = lambda chunk: hist_ops.histogram(chunk.reshape(-1), vocab)
    else:
        def fn(chunk):
            flat = chunk.reshape(-1)
            return jnp.zeros((vocab,), jnp.int32).at[flat].add(
                jnp.ones_like(flat), mode="drop")
    return MapReduceJob(map_fn=fn, n_keys=vocab, name="word_count")


def word_weight_job(vocab: int) -> MapReduceJob:
    """A FLOAT MapReduce job: each token contributes a rank-decaying f32
    weight ``1 / (1 + token)`` to its vocab bin (a tf-idf-flavoured twist on
    the thesis's word count).  Float adds are not associative, so this job
    opts into the dispatcher's deterministic tree reduction — results are
    bit-identical across backends, member counts, mid-stream scale events
    and power-of-two chunkings, exactly like the int32 word count."""
    def fn(chunk):
        flat = chunk.reshape(-1)
        w = 1.0 / (1.0 + flat.astype(jnp.float32))
        return jnp.zeros((vocab,), jnp.float32).at[flat].add(w, mode="drop")

    return MapReduceJob(map_fn=fn, n_keys=vocab, name="word_weight",
                        deterministic=True)


class MapReduceEngine:
    """Dual-backend MapReduce as dispatcher jobs.

    Construct either from a fixed 1-D ``mesh`` (legacy API — wraps a FROZEN
    dispatcher, no elasticity) or from an ``ElasticDispatcher`` (the
    middleware path: chunked streaming + IAS adaptive scaling between
    chunks).
    """

    def __init__(self, mesh: Optional[Mesh] = None, backend: str = "hazelcast",
                 axis: str = "data",
                 dispatcher: Optional[ElasticDispatcher] = None):
        assert backend in ("hazelcast", "infinispan")
        if dispatcher is None:
            if mesh is None:
                raise ValueError("MapReduceEngine needs a mesh or a "
                                 "dispatcher")
            dispatcher = ElasticDispatcher.for_mesh(mesh, axis=axis)
        self.dispatcher = dispatcher
        self.backend = backend
        self.axis = dispatcher.axis
        self.last_report = None          # DispatchReport of the latest run

    @property
    def mesh(self) -> Mesh:
        return self.dispatcher.mesh      # tracks scale events

    def run(self, job: MapReduceJob, files: jax.Array, *,
            chunk: Optional[int] = None, on_chunk: Optional[Callable] = None,
            checkpoint=None):
        """files: (n_files, file_len) int tokens.  ``chunk`` streams the
        corpus ``chunk`` files per dispatch (None = one dispatch); the IAS
        may re-home the stream between chunks (``on_chunk`` feeds load).
        ``files`` is left as-is: a large DEVICE-resident corpus (e.g. the
        output of a previous dispatcher job; see the dispatcher's
        ``device_slice_min_bytes``) is chunked on device by ``slice_chunk``
        and never round-trips to host; a host (or tiny) corpus is sliced
        host-side while the previous chunk computes (the async pipeline).
        ``checkpoint`` (a ``core.journal.CheckpointPolicy``) makes the
        stream DURABLE: journal + pow2-aligned reduce-state checkpoints;
        after a coordinator death, ``resume_run`` continues it."""
        out, report = self.dispatcher.submit(
            self._dispatch_job(job), files, chunk=chunk, on_chunk=on_chunk,
            checkpoint=checkpoint)
        self.last_report = report
        return jnp.asarray(out)

    def resume_run(self, path: str, job: MapReduceJob, files: jax.Array, *,
                   chunk: Optional[int] = None,
                   on_chunk: Optional[Callable] = None):
        """Continue a journaled ``run`` after a coordinator crash/drain —
        the MapReduce face of ``ElasticDispatcher.resume``: same job + same
        corpus (the environment signature is verified), journaled chunks
        are skipped, and the reduced result is bit-identical to the
        uninterrupted run."""
        out, report = self.dispatcher.resume(
            path, self._dispatch_job(job), files, chunk=chunk,
            on_chunk=on_chunk)
        self.last_report = report
        return jnp.asarray(out)

    def _dispatch_job(self, job: MapReduceJob) -> DispatchJob:
        return dispatch_job_for(job, self.backend)

    def benchmark(self, job: MapReduceJob, files, repeats: int = 3, *,
                  chunk: Optional[int] = None):
        """Timed run (compile excluded) -> (result, seconds)."""
        out = self.run(job, files, chunk=chunk)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = self.run(job, files, chunk=chunk)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / repeats


def dispatch_job_for(job: MapReduceJob,
                     backend: str = "hazelcast") -> DispatchJob:
    """The MapReduce job as a dispatch descriptor — module-level so engine-
    LESS callers (``serve.frontend.mapreduce_request``) can build dispatch
    jobs too.  ``map_fn`` itself is part of the signature: a fresh closure
    never reuses another job's executable, while repeated submissions of
    the SAME job object hit the compile cache (the multi-tenant
    amortization path: tenants sharing one job object share one
    executable)."""
    assert backend in ("hazelcast", "infinispan")
    sig = ("mapreduce", backend, job.name, job.n_keys, job.map_fn,
           job.deterministic)

    if job.deterministic:
        # per-FILE map outputs stream out unreduced; the dispatcher owns
        # the (position-aligned, member-count-invariant) tree reduction,
        # so the float result never sees a shard-shaped sum.  Both
        # backends emit identical per-row values — bit-parity for free.
        def per_row(files, valid, *_):
            del valid                # dispatcher masks the padded rows
            return jax.vmap(job.map_fn)(files)

        kw = ({"member_fn": per_row} if backend == "hazelcast"
              else {"global_fn": per_row})
        return DispatchJob(name=f"mapreduce/{job.name}", signature=sig,
                           reduce="sum", deterministic=True, **kw)

    if backend == "hazelcast":
        # explicit member-local map + collective reduce (psum)
        def member_fn(local_files, valid, *_):
            counts = jax.vmap(job.map_fn)(local_files)   # one per file
            counts = jnp.where(valid[:, None], counts, 0)
            return counts.sum(axis=0)

        return DispatchJob(name=f"mapreduce/{job.name}", signature=sig,
                           member_fn=member_fn, reduce="sum")

    # infinispan: one global expression, auto-SPMD partitioning
    def global_fn(files, valid, *_):
        counts = jax.vmap(job.map_fn)(files)
        return jnp.where(valid[:, None], counts, 0).sum(axis=0)

    return DispatchJob(name=f"mapreduce/{job.name}", signature=sig,
                       global_fn=global_fn, reduce="sum")


def make_corpus(n_files: int, file_len: int, vocab: int, seed: int = 0,
                zipf_a: float = 1.3) -> np.ndarray:
    """USENET-like corpus: zipf-distributed token ids (the thesis used large
    text files from the Westbury USENET corpus)."""
    rng = np.random.default_rng(seed)
    toks = rng.zipf(zipf_a, size=(n_files, file_len)).astype(np.int64)
    return (toks % vocab).astype(np.int32)
