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

How word count's map turns one file's ids into counts depends on where it
runs, and is chosen when the program is lowered
(``jax.lax.platform_dependent``), never by a flag:

  TPU, vocab <= ONEHOT_MAX_VOCAB   ``onehot_counts``: a two-level one-hot
                        contraction on the MXU.  The padded vocabulary is
                        factored as H x L; each id gives one-hots of its
                        high and low part, and ``onehot_hiᵀ · onehot_lo`` is
                        the [H, L] count table.  A scatter-add whose indices
                        collide is applied one update after another on the
                        TPU; the contraction costs H + L compares a token on
                        the VPU and moves the sum over tokens to the MXU.
  anywhere else         ``scatter_counts``: ``.at[ids].add(1)``, which the
                        CPU runs fast and whose per-token cost does not grow
                        with the vocabulary.

Both give the same int32 counts bit for bit: the contraction's operands are
0/1 int8 and it accumulates in int32, so every count is exact.  Which path
counted a stream is ``DispatchReport.map_path``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.dispatch import DispatchJob, ElasticDispatcher

# Largest vocabulary the one-hot contraction counts on the TPU.  Its VPU work
# grows with H + L ~ 2·sqrt(vocab) a token and its MXU work with vocab, while
# the serialised scatter costs about the same a token at any vocabulary.
ONEHOT_MAX_VOCAB = 65536


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
    # which path map_fn counts with, by platform ("default": every other):
    # resolved against the dispatcher's devices into DispatchReport.map_path
    map_paths: Optional[dict] = None


def _factor(vocab: int):
    """(H, L, log2 L) with L a power of two and H·L >= vocab, H + L least."""
    best = None
    for k in range(max(vocab, 1).bit_length() + 1):
        h = -(-vocab // (1 << k))
        if best is None or h + (1 << k) < best[0] + best[1]:
            best = (h, 1 << k, k)
    return best


def onehot_counts(flat: jax.Array, vocab: int) -> jax.Array:
    """int32 counts of ``flat``'s ids over ``vocab`` bins as one int8
    contraction: ``onehot(id >> k)ᵀ · onehot(id & (L - 1))`` is the [H, L]
    table of the padded vocabulary, sliced to ``vocab``.

    Exact for any ids: the operands are 0/1 int8 and the dot accumulates in
    int32.  The index semantics are ``scatter_counts``'s: an id in
    ``[-vocab, 0)`` counts at ``id + vocab`` (``.at[]`` wraps negative
    indices), any other id outside ``[0, vocab)`` is dropped — its high part
    is outside ``[0, H)`` or it lands in a padding bin the slice removes.

    The parts are narrowed to int8 (int16 past 127 rows) before the
    compares, the high part clipped to ``[-1, H]`` so nothing wraps into
    range; narrowed, XLA keeps them out of HBM too.  The one-hots are
    [H, n] and [L, n], lane-dense along the tokens: XLA fuses them into the
    dot's operands and never writes them out."""
    h, l, k = _factor(vocab)
    dt = jnp.int8 if max(h, l) < 128 else jnp.int16
    ids = jnp.where(flat < 0, flat + vocab, flat)
    hi = jnp.clip(ids >> k, -1, h).astype(dt)
    lo = (ids & (l - 1)).astype(dt)
    onehot_hi = (hi == jnp.arange(h, dtype=dt)[:, None]).astype(jnp.int8)
    onehot_lo = (lo == jnp.arange(l, dtype=dt)[:, None]).astype(jnp.int8)
    counts = jax.lax.dot_general(onehot_hi, onehot_lo,
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.int32)
    return counts.reshape(-1)[:vocab]


def scatter_counts(flat: jax.Array, vocab: int) -> jax.Array:
    """int32 counts of ``flat``'s ids over ``vocab`` bins by a scatter-add
    (negative ids wrap once, the rest out of range are dropped)."""
    return jnp.zeros((vocab,), jnp.int32).at[flat].add(
        jnp.ones_like(flat), mode="drop")


def word_count_job(vocab: int, use_kernel: bool = False) -> MapReduceJob:
    """The paper's default word-count application: counts token occurrences.

    ``map_fn`` counts one file.  On the TPU, for ``vocab <=
    ONEHOT_MAX_VOCAB``, it is ``onehot_counts`` (the MXU contraction); on
    any other platform, or above that vocabulary, ``scatter_counts``.  The
    branch is taken when the program is lowered for its platform.  Both are
    exact int32 counts with the same index semantics, so the result is
    bit-identical whichever path runs.

    use_kernel: route the per-shard histogram through the Pallas histogram
    kernel (interpret mode on CPU) instead.
    """
    if use_kernel:
        from repro.kernels.histogram import ops as hist_ops
        fn = lambda chunk: hist_ops.histogram(chunk.reshape(-1), vocab)
        paths = {"default": "kernel"}
    elif vocab <= ONEHOT_MAX_VOCAB:
        def fn(chunk):
            return jax.lax.platform_dependent(
                chunk.reshape(-1),
                tpu=functools.partial(onehot_counts, vocab=vocab),
                default=functools.partial(scatter_counts, vocab=vocab))
        paths = {"tpu": "mxu_onehot", "default": "scatter"}
    else:
        fn = lambda chunk: scatter_counts(chunk.reshape(-1), vocab)
        paths = {"default": "scatter"}
    return MapReduceJob(map_fn=fn, n_keys=vocab, name="word_count",
                        map_paths=paths)


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
                        deterministic=True, map_paths={"default": "scatter"})


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
        ``device_slice_min_bytes``) is chunked on device and never round-
        trips to host — on one member that holds it, the job's executable
        reads each chunk's window of it in place; across members each
        chunk is copied out by ``slice_chunk``; a host (or tiny) corpus is
        sliced host-side while the previous chunk computes (the async
        pipeline).
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
                           map_paths=job.map_paths,
                           reduce="sum", deterministic=True, **kw)

    if backend == "hazelcast":
        # explicit member-local map + collective reduce (psum)
        def member_fn(local_files, valid, *_):
            counts = jax.vmap(job.map_fn)(local_files)   # one per file
            counts = jnp.where(valid[:, None], counts, 0)
            return counts.sum(axis=0)

        return DispatchJob(name=f"mapreduce/{job.name}", signature=sig,
                           map_paths=job.map_paths,
                           member_fn=member_fn, reduce="sum")

    # infinispan: one global expression, auto-SPMD partitioning
    def global_fn(files, valid, *_):
        counts = jax.vmap(job.map_fn)(files)
        return jnp.where(valid[:, None], counts, 0).sum(axis=0)

    return DispatchJob(name=f"mapreduce/{job.name}", signature=sig,
                       map_paths=job.map_paths,
                       global_fn=global_fn, reduce="sum")


def make_corpus(n_files: int, file_len: int, vocab: int, seed: int = 0,
                zipf_a: float = 1.3) -> np.ndarray:
    """USENET-like corpus: zipf-distributed token ids (the thesis used large
    text files from the Westbury USENET corpus)."""
    rng = np.random.default_rng(seed)
    toks = rng.zipf(zipf_a, size=(n_files, file_len)).astype(np.int64)
    return (toks % vocab).astype(np.int32)
