"""Durable dispatch: journal round-trips, checkpoint alignment, coordinator
crash + resume.

Covers the PR acceptance gauntlet: the coordinator killed at EVERY chunk
index of a scaled 8-chunk stream (both the injected ``coordinator_crash``
fault and a hard SIGKILL / ``os._exit`` in a subprocess) resumes
bit-identically; a COMPLETE journal resumes idempotently with zero chunk
executions and zero compiles; environment mismatches and corrupted
checkpoints are loud ``ResumeMismatchError``s; graceful SIGTERM drain is the
resumable twin of the crash.  Property layer (hypothesis when available,
seeded sweep otherwise): pytree encode/decode/digest round-trips and the
binary-counter prefix property that makes pow2-aligned checkpoints exact
subtree states.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.dispatch import DispatchJob, ElasticDispatcher
from repro.core.faults import (CoordinatorCrashError, FaultInjector,
                               FaultSpec, JobFailedError, RetryPolicy)
from repro.core.journal import (CheckpointPolicy, DrainInterrupted,
                                JobJournal, ResumeMismatchError, counter_drain,
                                counter_push, journal_dir, load_checkpoint,
                                load_journal, stable_signature, tree_decode,
                                tree_digest, tree_encode)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _job():
    return DispatchJob(name="affine", signature="affine-journal",
                       member_fn=lambda x, v, w: x * w + 1.0,
                       reduce="concat")


def _det_job():
    import jax.numpy as jnp
    return DispatchJob(name="det", signature="det-journal", reduce="sum",
                       deterministic=True,
                       member_fn=lambda x, v, w: jnp.sqrt(x * x + w))


def _items(n=32):
    rng = np.random.RandomState(0)
    return (rng.randn(n, 4) * 10 ** rng.uniform(-2, 2, (n, 4))).astype(
        np.float32)


# ---------------------------------------------------------------- unit layer

def test_checkpoint_policy_validation_and_pow2_rounding(tmp_path):
    with pytest.raises(ValueError):
        CheckpointPolicy(path=str(tmp_path), every_n_chunks=0)
    with pytest.raises(ValueError):
        CheckpointPolicy(path=str(tmp_path), fsync="sometimes")
    # every_n_chunks rounds UP to a power of two: boundaries must sit on
    # pow2 subtree roots of the deterministic chunk tree
    for ask, want in ((1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (9, 16)):
        assert CheckpointPolicy(path=str(tmp_path),
                                every_n_chunks=ask).every_n_chunks == want


def test_stable_signature_is_process_stable():
    # callables render module.qualname, not a repr with a memory address
    s = stable_signature(("mapreduce", "hazelcast", _job, 7))
    assert "0x" not in s and "test_journal._job" in s
    assert stable_signature(_job) == stable_signature(_job)
    assert stable_signature({"b": 1, "a": 2}) == \
        stable_signature({"a": 2, "b": 1})


def _tree_case(rng, depth=2):
    """One random nested pytree with array leaves, scalars, and Nones."""
    def node(d):
        r = rng.randint(0, 6 if d > 0 else 3)
        if r == 0:
            return rng.randn(rng.randint(1, 4),
                             rng.randint(1, 4)).astype(np.float32)
        if r == 1:
            return rng.randint(-5, 5, size=rng.randint(1, 5)).astype(np.int32)
        if r == 2:
            return [None, float(rng.randn()), int(rng.randint(10)),
                    bool(rng.randint(2)), "s%d" % rng.randint(9)][
                        rng.randint(5)]
        if r == 3:
            return {("k%d" % i): node(d - 1) for i in range(rng.randint(1, 3))}
        if r == 4:
            return tuple(node(d - 1) for _ in range(rng.randint(1, 3)))
        return [node(d - 1) for _ in range(rng.randint(1, 3))]
    return node(depth)


def _assert_tree_equal(a, b):
    if isinstance(a, dict):
        assert isinstance(b, dict) and list(a) == list(b)
        for k in a:
            _assert_tree_equal(a[k], b[k])
    elif isinstance(a, tuple):
        assert isinstance(b, tuple) and len(a) == len(b)
        for x, y in zip(a, b):
            _assert_tree_equal(x, y)
    elif isinstance(a, list):
        assert isinstance(b, list) and len(a) == len(b)
        for x, y in zip(a, b):
            _assert_tree_equal(x, y)
    elif isinstance(a, np.ndarray):
        assert isinstance(b, np.ndarray)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    else:
        assert a == b and type(a) is type(b)


def _roundtrip_case(seed):
    rng = np.random.RandomState(seed)
    tree = _tree_case(rng)
    spec, leaves = tree_encode(tree)
    json.dumps(spec)                       # spec must be JSON-serializable
    back = tree_decode(spec, leaves)
    _assert_tree_equal(tree, back)
    assert tree_digest(tree) == tree_digest(back)


def test_tree_encode_decode_digest_roundtrip():
    """Property: encode/decode is the identity on nested pytrees (exact
    bytes, dtypes, container types and key order) and the digest is stable
    under the round trip but sensitive to any leaf bit flip."""
    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        for seed in range(25):
            _roundtrip_case(seed)
    else:
        @settings(max_examples=25, deadline=None)
        @given(seed=st.integers(0, 10 ** 6))
        def run(seed):
            _roundtrip_case(seed)
        run()

    # digest sensitivity: one changed element changes the digest
    a = {"x": np.arange(6, dtype=np.float32), "y": (1, None)}
    b = {"x": np.arange(6, dtype=np.float32), "y": (1, None)}
    assert tree_digest(a) == tree_digest(b)
    b["x"][3] += 1
    assert tree_digest(a) != tree_digest(b)
    # ...and dtype matters even when bytes agree elementwise
    assert tree_digest(np.zeros(4, np.int32)) != \
        tree_digest(np.zeros(4, np.float32))


def _counter_case(n, split):
    """The checkpoint-alignment property in miniature: pushing ``split``
    parts, snapshotting the counter, and continuing from the snapshot folds
    to the SAME bytes as the uninterrupted run — for any prefix length, not
    just pow2 ones — because the counter state after k pushes is exactly
    the pow2 subtrees of k's binary decomposition."""
    rng = np.random.RandomState(1000 * n + split)
    parts = [(rng.randn(3) * 10 ** rng.uniform(-2, 2, 3)).astype(np.float32)
             for _ in range(n)]
    combine = np.add

    full = {}
    for p in parts:
        counter_push(full, p, combine)

    head = {}
    for p in parts[:split]:
        counter_push(head, p, combine)
    # occupied levels == binary decomposition of the prefix length
    assert set(head) == {i for i in range(split.bit_length())
                         if (split >> i) & 1}
    snap = {lvl: np.array(t) for lvl, t in head.items()}   # the checkpoint
    for p in parts[split:]:
        counter_push(snap, p, combine)

    assert sorted(full) == sorted(snap)
    ref = counter_drain(full, combine)
    out = counter_drain(snap, combine)
    assert np.asarray(ref).tobytes() == np.asarray(out).tobytes()


def test_counter_prefix_resume_property():
    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        for n in (1, 2, 3, 5, 8, 13, 16, 21):
            for split in range(n + 1):
                _counter_case(n, split)
        return

    @settings(max_examples=40, deadline=None)
    @given(n=st.integers(1, 24), data=st.data())
    def run(n, data):
        _counter_case(n, data.draw(st.integers(0, n)))
    run()


def test_journal_roundtrip_torn_tail_and_dir_normalization(tmp_path):
    pol = CheckpointPolicy(path=str(tmp_path / "j"), async_write=False)
    j = JobJournal.create(pol, {"env": {"job": "t"}, "n_members": 1,
                                "every_n_chunks": pol.every_n_chunks})
    j.append({"type": "chunk", "chunk": 0, "attempt": 0, "digest": "d0"})
    j.write_checkpoint(1, "pending", {0: np.arange(3.0)}, {})
    j.append({"type": "chunk", "chunk": 1, "attempt": 0, "digest": "d1"})
    j.close()
    # a torn tail line (the coordinator died mid-append) is ignored on load
    with open(j.journal_file, "a") as f:
        f.write('{"type": "chunk", "chunk": 2, "att')

    for ref in (str(tmp_path / "j"), j.journal_file):   # dir or file both ok
        st = load_journal(ref)
        assert st.header is not None
        assert sorted(st.chunks) == [0, 1]
        assert [c["k"] for c in st.checkpoints] == [1]
        assert st.complete is None
    assert journal_dir(j.journal_file) == str(tmp_path / "j")

    # the checkpoint loads and integrity-checks
    state, manifest = load_checkpoint(str(tmp_path / "j"), st.checkpoints[0])
    assert np.array_equal(state[0], np.arange(3.0))

    # tampering with the stored arrays is loud
    d = tmp_path / "j" / st.checkpoints[0]["dir"]
    arr = np.load(d / "a0.npy")
    arr[0] += 1
    np.save(d / "a0.npy", arr)
    with pytest.raises(ResumeMismatchError):
        load_checkpoint(str(tmp_path / "j"), st.checkpoints[0])


def test_checkpoint_rotation_keeps_latest_and_final(tmp_path):
    pol = CheckpointPolicy(path=str(tmp_path), async_write=False, keep=2)
    j = JobJournal.create(pol, {"env": {}, "n_members": 1})
    for k in range(1, 6):
        j.write_checkpoint(k, "pending", {0: np.full(2, float(k))}, {})
    j.write_checkpoint(8, "final", np.arange(4.0), {})
    j.close()
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("ck_"))
    assert dirs == ["ck_00000004", "ck_00000005", "ck_final"]
    st = load_journal(str(tmp_path))
    # rotated records remain in the journal; usable_checkpoint skips them
    assert len(st.checkpoints) == 6
    assert st.usable_checkpoint()["k"] == 5
    assert st.usable_checkpoint(final=True)["kind"] == "final"


# ------------------------------------------------- in-process crash + resume

def _run_crash_resume(tmp_path, job, items, w, crash_at, *, chunk=4,
                      every=1, deliver="host"):
    """Crash a journaled stream at ``crash_at`` via the injected
    ``coordinator_crash`` fault, then resume on a FRESH dispatcher."""
    d0 = ElasticDispatcher(start_members=1, dispatch_ahead=0)
    ref, _ = d0.submit(job, items, replicated=(w,), chunk=chunk,
                       deliver="host")
    ref = np.asarray(ref)

    ck = str(tmp_path / f"ck{crash_at}")
    d1 = ElasticDispatcher(start_members=1, dispatch_ahead=2)
    with pytest.raises(CoordinatorCrashError):
        d1.submit(job, items, replicated=(w,), chunk=chunk, deliver=deliver,
                  checkpoint=CheckpointPolicy(path=ck, every_n_chunks=every),
                  fault_injector=FaultInjector(
                      [FaultSpec("coordinator_crash", chunk=crash_at)]))
    st = load_journal(ck)
    assert st.header is not None and st.complete is None
    assert all(ci < crash_at for ci in st.chunks)   # nothing past the crash

    d2 = ElasticDispatcher(start_members=1, dispatch_ahead=2)
    out, rep = d2.resume(ck, job, items, replicated=(w,), chunk=chunk)
    assert np.asarray(out).tobytes() == ref.tobytes()
    assert rep.resumed_from == ck
    assert rep.chunks_skipped + rep.chunks_replayed == rep.n_chunks
    assert load_journal(ck).complete is not None
    return ck, ref


def test_crash_resume_bit_identical_concat_and_det_sum(tmp_path):
    items, w = _items(), np.float32(1.7)
    _run_crash_resume(tmp_path / "c", _job(), items, w, crash_at=3)
    _run_crash_resume(tmp_path / "s", _det_job(), items, w, crash_at=5)
    # int reduce (word-count shape): associative, any alignment
    ints = np.arange(64, dtype=np.int32).reshape(16, 4)
    ijob = DispatchJob(name="isum", signature="isum-journal", reduce="sum",
                       member_fn=lambda x, v, w: (x * 0 + 1).sum(0))
    _run_crash_resume(tmp_path / "i", ijob, ints, np.int32(1), crash_at=2)


def test_completed_journal_resumes_idempotently_zero_compiles(tmp_path):
    job, items, w = _det_job(), _items(), np.float32(1.7)
    ck = str(tmp_path / "ck")
    d1 = ElasticDispatcher(start_members=1, dispatch_ahead=2)
    out, rep = d1.submit(job, items, replicated=(w,), chunk=4, deliver="host",
                         checkpoint=CheckpointPolicy(path=ck,
                                                     every_n_chunks=2))
    assert rep.journal_path == ck and rep.checkpoints > 0
    assert len(rep.checkpoint_write_s) == rep.checkpoints
    st = load_journal(ck)
    assert st.complete is not None and sorted(st.chunks) == list(range(8))

    # resume of a COMPLETE journal: the final checkpoint is loaded and
    # returned with ZERO chunk executions and ZERO executable builds
    d2 = ElasticDispatcher(start_members=1, dispatch_ahead=2)
    out2, rep2 = d2.resume(ck, job, items, replicated=(w,), chunk=4)
    assert np.asarray(out2).tobytes() == np.asarray(out).tobytes()
    assert rep2.chunks_replayed == 0 and rep2.chunks_skipped == rep2.n_chunks
    assert d2.cache.builds == 0 and d2.in_flight == 0


def test_resume_mismatch_is_loud(tmp_path):
    job, items, w = _job(), _items(), np.float32(1.7)
    ck, _ = _run_crash_resume(tmp_path, job, items, w, crash_at=3)

    d = ElasticDispatcher(start_members=1)
    with pytest.raises(ResumeMismatchError, match="chunk"):
        d.resume(ck, job, items, replicated=(w,), chunk=8)   # different plan
    other = DispatchJob(name="affine", signature="other",
                        member_fn=lambda x, v, w: x * w + 1.0,
                        reduce="concat")
    with pytest.raises(ResumeMismatchError, match="signature"):
        d.resume(ck, other, items, replicated=(w,), chunk=4)
    with pytest.raises(ResumeMismatchError, match="n_items"):
        d.resume(ck, job, items[:16], replicated=(w,), chunk=4)
    with pytest.raises(ResumeMismatchError, match="nothing to resume"):
        d.resume(str(tmp_path / "nowhere"), job, items, replicated=(w,),
                 chunk=4)


def test_drain_request_checkpoints_and_resumes(tmp_path):
    job, items, w = _job(), _items(), np.float32(2.5)
    d0 = ElasticDispatcher(start_members=1, dispatch_ahead=0)
    ref = np.asarray(d0.submit(job, items, replicated=(w,), chunk=4,
                               deliver="host")[0])
    ck = str(tmp_path / "drain")
    d1 = ElasticDispatcher(start_members=1, dispatch_ahead=2)

    def preempt(disp, ci, n):
        if ci == 2:
            disp.request_drain()

    with pytest.raises(DrainInterrupted) as exc:
        d1.submit(job, items, replicated=(w,), chunk=4, deliver="host",
                  on_chunk=preempt,
                  checkpoint=CheckpointPolicy(path=ck, every_n_chunks=1))
    assert exc.value.journal_path == ck
    assert exc.value.report.journal_path == ck
    assert d1.in_flight == 0
    st = load_journal(ck)
    assert st.chunks and st.complete is None     # partial progress persisted

    d2 = ElasticDispatcher(start_members=1, dispatch_ahead=2)
    out, rep = d2.resume(ck, job, items, replicated=(w,), chunk=4)
    assert np.asarray(out).tobytes() == ref.tobytes()
    assert rep.chunks_skipped >= 1


def test_unarmed_stream_journals_barrier_drained_chunks_once(tmp_path):
    """An unarmed journaled stream (no policy, no injector) pipelined two
    deep, with an ``on_chunk`` scale event whose remesh barrier drains
    chunks past the normal retirement: every chunk is journaled exactly
    once, and resuming a truncated copy of the journal reproduces the
    uninterrupted bytes."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import collections, os, shutil, sys
import numpy as np, jax
from repro.core.dispatch import DispatchJob, ElasticDispatcher
from repro.core.health import HealthConfig
from repro.core.journal import CheckpointPolicy, load_journal

workdir = sys.argv[1]
job = DispatchJob(name="affine", signature="affine-journal",
                  member_fn=lambda x, v, w: x * w + 1.0, reduce="concat")
rng = np.random.RandomState(0)
items = (rng.randn(32, 4) * 10 ** rng.uniform(-2, 2, (32, 4))).astype(
    np.float32)
w = np.float32(1.7)

def hc():
    return HealthConfig(target_step_time=1.0, max_threshold=0.8,
                        min_threshold=0.2, time_between_scaling=1,
                        window=1, max_instances=2)

def scale_out_at_2(disp, ci, n):
    if ci == 2:
        disp.observe_load(2.0)

ref = np.asarray(ElasticDispatcher(devices=jax.devices()[:1],
                                   dispatch_ahead=0).submit(
    job, items, replicated=(w,), chunk=4, deliver="host")[0])

ck = os.path.join(workdir, "ck")
d = ElasticDispatcher(devices=jax.devices(), health_cfg=hc(),
                      start_members=1, dispatch_ahead=2)
out, rep = d.submit(job, items, replicated=(w,), chunk=4, deliver="host",
                    on_chunk=scale_out_at_2,
                    checkpoint=CheckpointPolicy(path=ck, every_n_chunks=2))
assert np.asarray(out).tobytes() == ref.tobytes()
assert rep.scale_events == 1 and rep.members_per_chunk == [1] * 3 + [2] * 5
assert d.scale_events[-1]["drained_in_flight"] >= 1, d.scale_events
st = load_journal(ck)
times = collections.Counter(r["chunk"] for r in st.records
                            if r["type"] == "chunk")
assert times == {ci: 1 for ci in range(8)}, times
assert st.complete is not None and d.in_flight == 0

# the coordinator "died" right before chunk 5's record hit the disk
cut = os.path.join(workdir, "cut")
shutil.copytree(ck, cut)
lines = open(os.path.join(ck, "journal.jsonl")).read().splitlines()
keep = next(i for i, l in enumerate(lines)
            if '"type": "chunk"' in l and '"chunk": 5,' in l)
with open(os.path.join(cut, "journal.jsonl"), "w") as f:
    f.write("\\n".join(lines[:keep]) + "\\n")
st = load_journal(cut)
assert st.complete is None and max(st.chunks) == 4, sorted(st.chunks)
d2 = ElasticDispatcher(devices=jax.devices(), health_cfg=hc(),
                       start_members=1, dispatch_ahead=2)
out2, rep2 = d2.resume(cut, job, items, replicated=(w,), chunk=4)
assert np.asarray(out2).tobytes() == ref.tobytes()
assert rep2.chunks_skipped + rep2.chunks_replayed == 8
assert load_journal(cut).complete is not None
print("OK")
""", str(tmp_path)], env=env, capture_output=True, text=True, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_job_failure_report_persisted_to_journal(tmp_path):
    job, items, w = _job(), _items(), np.float32(2.0)
    ck = str(tmp_path / "fail")
    d = ElasticDispatcher(
        start_members=1, dispatch_ahead=2,
        fault_injector=FaultInjector(
            [FaultSpec("nan_poison", chunk=1, times=10)]),
        retry_policy=RetryPolicy(max_attempts=2, quarantine_after=0,
                                 check_finite=True))
    with pytest.raises(JobFailedError):
        d.submit(job, items, replicated=(w,), chunk=4, deliver="host",
                 checkpoint=CheckpointPolicy(path=ck, every_n_chunks=1))
    st = load_journal(ck)
    assert st.failed is not None                 # the post-mortem survives
    assert "nan_poison" in json.dumps(st.failed)
    # fault records landed alongside the failure report
    assert any(r.get("type") == "fault" for r in st.records)


def test_checkpoint_latency_in_stats_summary(tmp_path):
    job, items, w = _job(), _items(), np.float32(1.0)
    d = ElasticDispatcher(start_members=1, dispatch_ahead=2)
    _, rep = d.submit(job, items, replicated=(w,), chunk=4, deliver="host",
                      collect_stats=True,
                      checkpoint=CheckpointPolicy(path=str(tmp_path / "s"),
                                                  every_n_chunks=1))
    assert rep.checkpoints >= 8                  # every chunk + final
    assert all(s >= 0 for s in rep.checkpoint_write_s)
    summ = rep.stats
    assert summ and "checkpoint" in summ
    assert summ["checkpoint"]["n"] == rep.checkpoints
    assert summ["checkpoint"]["total_s"] == pytest.approx(
        sum(rep.checkpoint_write_s))


def test_random_schedule_includes_coordinator_crash():
    """Satellite: the chaos pool now carries ``coordinator_crash`` and a
    seeded schedule that drew it fires it deterministically."""
    from repro.core.faults import FAULT_KINDS
    assert "coordinator_crash" in FAULT_KINDS
    # same seed -> same schedule, even with the enlarged pool
    a = FaultInjector.random_schedule(seed=3, n_chunks=8, n_faults=40)
    b = FaultInjector.random_schedule(seed=3, n_chunks=8, n_faults=40)
    assert [vars(s) for s in a.schedule] == [vars(s) for s in b.schedule]
    drawn = {s.kind for s in a.schedule}
    assert "coordinator_crash" in drawn          # 40 draws over 5 kinds
    # and a drawn coordinator_crash actually kills the coordinator
    import jax
    inj = FaultInjector([s for s in a.schedule
                         if s.kind == "coordinator_crash"][:1])
    chunk = inj.schedule[0].chunk
    with pytest.raises(CoordinatorCrashError):
        inj.on_launch(chunk, jax.devices()[:1])


def test_mapreduce_resume_run_bit_identical(tmp_path):
    """The MapReduce face: a float word-weight stream crashed mid-corpus
    resumes through ``resume_run`` to the exact bytes of the uninterrupted
    run — the job signature (which contains ``map_fn``) survives the process
    boundary via ``stable_signature``."""
    from repro.core.mapreduce import (MapReduceEngine, make_corpus,
                                      word_weight_job)
    files = make_corpus(n_files=16, file_len=64, vocab=50, seed=4)
    wj = word_weight_job(50)
    eng0 = MapReduceEngine(dispatcher=ElasticDispatcher(start_members=1))
    ref = np.asarray(eng0.run(wj, files, chunk=4))

    ck = str(tmp_path / "mr")
    eng1 = MapReduceEngine(dispatcher=ElasticDispatcher(
        start_members=1, fault_injector=FaultInjector(
            [FaultSpec("coordinator_crash", chunk=2)])))
    with pytest.raises(CoordinatorCrashError):
        eng1.run(wj, files, chunk=4,
                 checkpoint=CheckpointPolicy(path=ck, every_n_chunks=1))
    assert load_journal(ck).header is not None

    eng2 = MapReduceEngine(dispatcher=ElasticDispatcher(start_members=1))
    out = np.asarray(eng2.resume_run(ck, wj, files, chunk=4))
    assert out.tobytes() == ref.tobytes()
    rep = eng2.last_report
    # crash at launch of chunk 2 with dispatch_ahead=2: chunks 0-1 may die
    # in flight unvalidated, so everything is legitimately replayable — the
    # invariant is full coverage, not a particular split
    assert rep.chunks_skipped + rep.chunks_replayed == rep.n_chunks
    assert rep.resumed_from == ck


def test_scenario_grid_resume_bit_identical(tmp_path):
    from repro.core.cloudsim import ElasticSimulationCluster, SimulationConfig
    from repro.core.des_scan import make_scenario_grid

    cfg = SimulationConfig(n_cloudlets=24, n_vms=6, core="scan")
    grid = make_scenario_grid(seeds=range(8), mi_scales=(1.0, 1.5))

    ref = ElasticSimulationCluster(start_members=1).simulate_grid(
        cfg, grid, chunk=4)

    ck = str(tmp_path / "grid")
    cl = ElasticSimulationCluster(start_members=1)
    from repro.core.des_scan import grid_batch_args
    args, job, _ = grid_batch_args(cfg, grid)
    with pytest.raises(CoordinatorCrashError):
        cl.dispatcher.submit(
            job, args, chunk=4, deliver="host",
            checkpoint=CheckpointPolicy(path=ck, every_n_chunks=1),
            fault_injector=FaultInjector(
                [FaultSpec("coordinator_crash", chunk=2)]))

    out, rep = ElasticSimulationCluster(start_members=1).resume_grid(
        ck, cfg, grid, chunk=4)
    _, _, makespans, _ = out
    assert np.asarray(makespans).tobytes() == ref.makespans.tobytes()
    assert rep.chunks_skipped + rep.chunks_replayed == rep.n_chunks
    assert rep.resumed_from == ck


# ------------------------------------------- acceptance (subprocess, 8 dev)

def test_coordinator_killed_every_chunk_index_resumes_bit_identical(tmp_path):
    """THE acceptance test: the coordinator dies at EVERY chunk index of an
    8-chunk async stream riding a 1→2→4→2 scale sequence — hard
    (``SIGKILL`` / ``os._exit(137)`` in a victim subprocess, alternating to
    cover both death shapes) — and a fresh process resumes each journal to
    bytes identical to the uninterrupted run; the injected in-process
    ``coordinator_crash`` sweep covers the same indices cheaply."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    victim = tmp_path / "victim.py"
    victim.write_text("""
import os, signal, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core.dispatch import DispatchJob, ElasticDispatcher
from repro.core.faults import FaultInjector, FaultSpec
from repro.core.health import HealthConfig
from repro.core.journal import CheckpointPolicy

kill_at, ck, mode = int(sys.argv[1]), sys.argv[2], sys.argv[3]
job = DispatchJob(name="det", signature="det", reduce="sum",
                  deterministic=True,
                  member_fn=lambda x, v, w: jnp.sqrt(x * x + w))
rng = np.random.RandomState(0)
items = (rng.randn(32, 4) * 10 ** rng.uniform(-2, 2, (32, 4))).astype(
    np.float32)
w = np.float32(1.7)
hc = HealthConfig(target_step_time=1.0, max_threshold=0.8, min_threshold=0.2,
                  time_between_scaling=1, window=1, max_instances=4)
LOADS = [2.0, 2.0, 0.05]
it = iter(LOADS)

def on_chunk(disp, ci, n):
    if mode == "sigkill" and ci == kill_at:
        os.kill(os.getpid(), signal.SIGKILL)
    l = next(it, None)
    if l is not None:
        disp.observe_load(l)

inj = (FaultInjector([FaultSpec("coordinator_crash", chunk=kill_at)],
                     hard_exit=True)
       if mode == "exit137" else FaultInjector())
d = ElasticDispatcher(devices=jax.devices(), health_cfg=hc,
                      start_members=1, dispatch_ahead=2, fault_injector=inj)
d.submit(job, items, replicated=(w,), chunk=4, deliver="host",
         on_chunk=on_chunk,
         checkpoint=CheckpointPolicy(path=ck, every_n_chunks=1))
print("SURVIVED")                       # only the fault-free control reaches
""")
    r = subprocess.run([sys.executable, "-c", """
import os, subprocess, sys
import numpy as np, jax, jax.numpy as jnp
from repro.core.dispatch import DispatchJob, ElasticDispatcher
from repro.core.faults import CoordinatorCrashError, FaultInjector, FaultSpec
from repro.core.health import HealthConfig
from repro.core.journal import CheckpointPolicy, load_journal

victim, workdir = sys.argv[1], sys.argv[2]
job = DispatchJob(name="det", signature="det", reduce="sum",
                  deterministic=True,
                  member_fn=lambda x, v, w: jnp.sqrt(x * x + w))
rng = np.random.RandomState(0)
items = (rng.randn(32, 4) * 10 ** rng.uniform(-2, 2, (32, 4))).astype(
    np.float32)
w = np.float32(1.7)

def hc():
    return HealthConfig(target_step_time=1.0, max_threshold=0.8,
                        min_threshold=0.2, time_between_scaling=1,
                        window=1, max_instances=4)

LOADS = [2.0, 2.0, 0.05]          # 1 -> 2 -> 4 -> 2 across the stream

def feeder():
    it = iter(LOADS)
    def on_chunk(disp, ci, n):
        l = next(it, None)
        if l is not None:
            disp.observe_load(l)
    return on_chunk

# uninterrupted oracle (deterministic sum: member-count invariant)
d0 = ElasticDispatcher(devices=jax.devices()[:1], health_cfg=hc(),
                       start_members=1, dispatch_ahead=0)
ref = np.asarray(d0.submit(job, items, replicated=(w,), chunk=4,
                           deliver="host")[0])

# (a) injected coordinator_crash at every index, resumed in THIS process
for kill_at in range(8):
    ck = os.path.join(workdir, "inj%d" % kill_at)
    d = ElasticDispatcher(devices=jax.devices(), health_cfg=hc(),
                          start_members=1, dispatch_ahead=2,
                          fault_injector=FaultInjector(
                              [FaultSpec("coordinator_crash",
                                         chunk=kill_at)]))
    try:
        d.submit(job, items, replicated=(w,), chunk=4, deliver="host",
                 on_chunk=feeder(),
                 checkpoint=CheckpointPolicy(path=ck, every_n_chunks=1))
        raise SystemExit("crash %d did not fire" % kill_at)
    except CoordinatorCrashError:
        pass
    assert d.in_flight == 0
    d2 = ElasticDispatcher(devices=jax.devices(), health_cfg=hc(),
                           start_members=1, dispatch_ahead=2)
    out, rep = d2.resume(ck, job, items, replicated=(w,), chunk=4)
    assert np.asarray(out).tobytes() == ref.tobytes(), kill_at
    assert rep.chunks_skipped + rep.chunks_replayed == 8
    assert load_journal(ck).complete is not None
print("INJECTED OK")

# (b) hard death: SIGKILL / os._exit(137) victims, resumed here
for kill_at in range(8):
    mode = "sigkill" if kill_at % 2 == 0 else "exit137"
    ck = os.path.join(workdir, "hard%d" % kill_at)
    r = subprocess.run([sys.executable, victim, str(kill_at), ck, mode],
                       capture_output=True, text=True, timeout=600)
    assert r.returncode in (-9, 137), (kill_at, r.returncode, r.stderr)
    assert "SURVIVED" not in r.stdout
    st = load_journal(ck)
    assert st.header is not None, kill_at   # header always hits disk first
    d2 = ElasticDispatcher(devices=jax.devices(), health_cfg=hc(),
                           start_members=1, dispatch_ahead=2)
    out, rep = d2.resume(ck, job, items, replicated=(w,), chunk=4)
    assert np.asarray(out).tobytes() == ref.tobytes(), (kill_at, mode)
    assert load_journal(ck).complete is not None
print("HARD-KILL OK")

# control: the fault-free victim config completes and its journal resumes
# idempotently (zero replay)
ck = os.path.join(workdir, "ctl")
r = subprocess.run([sys.executable, victim, "-1", ck, "none"],
                   capture_output=True, text=True, timeout=600)
assert r.returncode == 0 and "SURVIVED" in r.stdout, r.stderr
d2 = ElasticDispatcher(devices=jax.devices(), health_cfg=hc(),
                       start_members=1, dispatch_ahead=2)
out, rep = d2.resume(ck, job, items, replicated=(w,), chunk=4)
assert np.asarray(out).tobytes() == ref.tobytes()
assert rep.chunks_replayed == 0 and d2.cache.builds == 0
print("IDEMPOTENT-CONTROL-DONE")
""", str(victim), str(tmp_path)], env=env, capture_output=True, text=True,
                       timeout=900)
    for sentinel in ("INJECTED OK", "HARD-KILL OK", "IDEMPOTENT-CONTROL-DONE"):
        assert sentinel in r.stdout, (sentinel, r.stdout, r.stderr)
