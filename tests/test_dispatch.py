"""ElasticDispatcher — the unified remesh-aware, chunk-streaming job layer.

Acceptance contract of the middleware refactor:

  * a scenario grid and a MapReduce word-count job submitted through the
    dispatcher survive a mid-stream scale-out 1→2→4 and scale-in 4→2 with
    results BIT-identical to a single-member run;
  * a grid with more variants than one dispatch chunk streams in ≥2 chunks
    with at most ONE compile per (geometry, job-signature) — verified via
    the CompileCache hit/build counters;
  * the elastic simulation cluster is a thin client of the dispatcher;
  * ``PartitionTable.rebalance`` with observed per-key weights spreads a hot
    key's partition load across members (locality-aware rebalance seed).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.dispatch import (CompileCache, DispatchJob, ElasticDispatcher,
                                 NonPow2ChunkWarning)
from repro.core.partition import (DEFAULT_PARTITION_COUNT, PartitionTable,
                                  partition_weights_from_keys)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# ----------------------------------------------------------- CompileCache

def test_compile_cache_lru_and_counters():
    c = CompileCache(max_entries=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1                  # hit moves "a" to the back
    c.put("c", 3)                           # evicts "b" (LRU front)
    assert "b" not in c and "a" in c and "c" in c
    assert c.get("b") is None               # miss
    assert c.stats() == {"size": 2, "hits": 1, "misses": 1, "builds": 3}
    # dict-style peeking doesn't disturb recency or counters
    assert c["a"] == 1 and len(c) == 2 and set(c) == {"a", "c"}
    assert c.stats()["hits"] == 1
    built = []
    v = c.get_or_build("a", lambda: built.append(1) or 99)
    assert v == 1 and not built             # cached: builder never ran
    v = c.get_or_build("d", lambda: 42)
    assert v == 42 and c["d"] == 42


def test_compile_cache_invalidate_by_predicate():
    c = CompileCache()
    c.put(("m1", "x"), 1)
    c.put(("m1", "y"), 2)
    c.put(("m2", "x"), 3)
    assert c.invalidate(lambda k: k[0] == "m1") == 2
    assert set(c) == {("m2", "x")}
    assert c.invalidate() == 1 and len(c) == 0


def test_dispatch_job_validation():
    with pytest.raises(ValueError):
        DispatchJob(name="x", signature="x")              # no fn
    with pytest.raises(ValueError):
        DispatchJob(name="x", signature="x", member_fn=lambda *a: a,
                    global_fn=lambda *a: a)               # both fns
    with pytest.raises(ValueError):
        DispatchJob(name="x", signature="x", member_fn=lambda *a: a,
                    reduce="median")


# ------------------------------------------------- chunk-streamed submission

def test_grid_streams_chunks_with_one_compile():
    """≥2 chunks through one geometry: exactly ONE executable built, every
    later chunk a cache hit; a re-submit is all hits — the cache-hit-counter
    acceptance criterion on a single member."""
    from repro.core.cloudsim import SimulationConfig
    from repro.core.des_scan import make_scenario_grid, run_scenario_grid

    cfg = SimulationConfig(n_vms=8, n_cloudlets=32)
    grid = make_scenario_grid(seeds=range(10), mi_scales=[0.5, 2.0])
    B = len(grid["seeds"])
    ref = run_scenario_grid(cfg, grid)

    d = ElasticDispatcher(start_members=1)
    r = run_scenario_grid(cfg, grid, dispatcher=d, chunk=6)
    assert r.dispatch["n_chunks"] == -(-B // 6) >= 2
    assert r.dispatch["compiles"] == 1
    assert r.dispatch["cache_hits"] == r.dispatch["n_chunks"] - 1
    np.testing.assert_array_equal(ref.finish_times, r.finish_times)
    np.testing.assert_array_equal(ref.makespans, r.makespans)

    r2 = run_scenario_grid(cfg, grid, dispatcher=d, chunk=6)
    assert r2.dispatch["compiles"] == 0
    assert r2.dispatch["cache_hits"] == r2.dispatch["n_chunks"]
    np.testing.assert_array_equal(ref.makespans, r2.makespans)


def test_submit_validates_items():
    d = ElasticDispatcher(start_members=1)
    job = DispatchJob(name="j", signature="j",
                      member_fn=lambda x, v, *_: x, reduce="concat")
    with pytest.raises(ValueError):
        d.submit(job, ())
    with pytest.raises(ValueError):
        d.submit(job, (np.zeros(4), np.zeros(5)))   # ragged leading dims


def test_submit_empty_batch():
    """B = 0 must behave like the non-dispatcher vmap path: empty concat
    outputs with the right trailing shape, identity (zeros) sum outputs —
    one fully-padded all-invalid chunk, never a crash."""
    import jax.numpy as jnp

    d = ElasticDispatcher(start_members=1)
    job = DispatchJob(name="rows", signature="rows",
                      member_fn=lambda x, v, *_: x * 2.0, reduce="concat")
    out, rep = d.submit(job, np.zeros((0, 3), np.float32))
    assert out.shape == (0, 3) and rep.n_chunks == 1

    sum_job = DispatchJob(
        name="hist", signature="hist", reduce="sum",
        member_fn=lambda x, v, *_: jnp.where(v[:, None], x, 0).sum(axis=0))
    out, _ = d.submit(sum_job, np.ones((0, 5), np.int32))
    assert out.shape == (5,) and (np.asarray(out) == 0).all()

    # the dispatcher-routed grid matches the vmap path on an empty seed set
    from repro.core.cloudsim import SimulationConfig
    from repro.core.des_scan import run_simulation_batch
    cfg = SimulationConfig(n_vms=8, n_cloudlets=16)
    r = run_simulation_batch(cfg, np.zeros((0,), np.int32), dispatcher=d)
    assert r.finish_times.shape == (0, 16) and r.makespans.shape == (0,)


def test_grid_and_mapreduce_survive_scale_events():
    """THE acceptance test: scenario grid + MapReduce word count streamed
    through one dispatcher, IAS firing 1→2→4→2 between chunks, results
    bit-identical to the single-member run; compile counters show one
    executable per (geometry, job-signature)."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import numpy as np, jax, jax.numpy as jnp
from repro.core.dispatch import ElasticDispatcher
from repro.core.cloudsim import SimulationConfig
from repro.core.des_scan import make_scenario_grid, run_scenario_grid
from repro.core.health import HealthConfig
from repro.core.mapreduce import MapReduceEngine, make_corpus, word_count_job

hc = HealthConfig(target_step_time=1.0, max_threshold=0.8, min_threshold=0.2,
                  time_between_scaling=1, window=1, max_instances=4)
cfg = SimulationConfig(n_vms=12, n_cloudlets=48, broker="matchmaking")
grid = make_scenario_grid(seeds=range(6), mi_scales=[0.7, 1.3],
                          vm_counts=[6, 12])
B = len(grid["seeds"])
ref = run_scenario_grid(cfg, grid)                 # single-member oracle

def loads_feeder(seq):
    it = iter(seq)
    def on_chunk(disp, ci, n):
        l = next(it, None)
        if l is not None:
            disp.observe_load(l)
    return on_chunk

d = ElasticDispatcher(health_cfg=hc, start_members=1)
r = run_scenario_grid(cfg, grid, dispatcher=d, chunk=6,
                      on_chunk=loads_feeder([2.0, 2.0, 0.05]))
assert r.dispatch["members_per_chunk"] == [1, 2, 4, 2], r.dispatch
assert r.dispatch["n_chunks"] == 4 and r.dispatch["scale_events"] == 3
# bit-identical across the whole scale path
assert np.array_equal(ref.finish_times, r.finish_times)
assert np.array_equal(ref.makespans, r.makespans)
assert np.array_equal(ref.vm_assign, r.vm_assign)
# one compile per geometry visited (2-member mesh was retired at 2->4 and
# recompiled on the way back down: 1, 2, 4, 2 -> 4 builds, 0 hits)
assert r.dispatch["compiles"] == 4, r.dispatch
# each scale event retired the old geometry's grid-job executable
assert [ev["retired_jobs"] for ev in d.scale_events] == [1, 1, 1]

# stay at 2 members, stream again: chunk 3 of the first stream already
# rebuilt the 2-member executable (after 4->2), so this is ALL cache hits
r2 = run_scenario_grid(cfg, grid, dispatcher=d, chunk=6)
assert r2.dispatch["members_per_chunk"] == [2, 2, 2, 2]
assert r2.dispatch["compiles"] == 0 and r2.dispatch["cache_hits"] == 4
assert np.array_equal(ref.makespans, r2.makespans)

# ---- MapReduce word count through the SAME middleware, same scale path
d2 = ElasticDispatcher(health_cfg=hc, start_members=1)
corpus = make_corpus(10, 512, vocab=64)
expected = np.bincount(corpus.reshape(-1), minlength=64)
for backend in ("hazelcast", "infinispan"):
    eng = MapReduceEngine(backend=backend, dispatcher=ElasticDispatcher(
        health_cfg=hc, start_members=1))
    out = eng.run(word_count_job(64), jnp.asarray(corpus), chunk=3,
                  on_chunk=loads_feeder([2.0, 2.0, 0.05]))
    rep = eng.last_report
    assert rep.members_per_chunk == [1, 2, 4, 2], (backend, rep)
    assert np.array_equal(np.asarray(out), expected), backend

# DataGrid entries with a leading dim the new member count can't divide are
# downgraded to replicated placement instead of failing the scale event —
# and re-sharded automatically once a later remesh fits them again
from repro.core.grid import DataGrid
d3 = ElasticDispatcher(health_cfg=hc, start_members=2)
g = d3.ensure_grid()
g.put("odd", jnp.arange(6.0))                      # 6 % 4 != 0
sharded_spec = g.spec("odd")
d3.observe_load(2.0)                               # 2 -> 4 members
assert d3.n_members == 4
assert np.array_equal(np.asarray(g.get("odd")), np.arange(6.0))
assert "odd" in g.downgraded
d3.observe_load(0.05)                              # 4 -> 2: fits again
assert d3.n_members == 2
assert "odd" not in g.downgraded
assert g.spec("odd") == sharded_spec               # sharding restored
assert np.array_equal(np.asarray(g.get("odd")), np.arange(6.0))
# a put() AFTER a downgrade is authoritative: the stale record must not
# resurrect the old sharded spec on the next remesh
from jax.sharding import PartitionSpec as P
d3.observe_load(2.0)                               # 2 -> 4: downgrade again
assert "odd" in g.downgraded
g.put("odd", jnp.arange(8.0), spec=P())            # caller wants REPLICATED
d3.observe_load(0.05)                              # 4 -> 2
assert g.spec("odd") == P(), g.spec("odd")
# fail-over after a downgrade remesh: the entry's backup is the DEGENERATE
# (full replicated) copy — restore must NOT unroll it as if neighbor-rolled
from jax.sharding import Mesh
g2 = DataGrid(Mesh(np.array(jax.devices()[:2]), ("data",)), backup_count=1)
g2.put("six", jnp.arange(6.0))                     # 6 % 2 == 0: rolled
g2.remesh(Mesh(np.array(jax.devices()[:4]), ("data",)))  # 6 % 4: downgrade
assert "six" in g2.downgraded
restored = g2.restore_from_backup("six", lost_member=0)
assert np.array_equal(np.asarray(restored), np.arange(6.0)), restored
print("OK")
"""], env=env, capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_async_stream_bit_identical_with_chunks_in_flight():
    """Satellite acceptance: the async double-buffered stream survives
    1→2→4→2 scale events with ≥2 chunks IN FLIGHT at every remesh barrier,
    bit-identical to the synchronous baseline AND the no-dispatcher oracle;
    the deterministic float MapReduce job holds bit-identity over the same
    scale path; and auto_scale's EMA feeding scales out with no on_chunk
    feeder."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.core.dispatch import ElasticDispatcher
from repro.core.cloudsim import SimulationConfig
from repro.core.des_scan import make_scenario_grid, run_scenario_grid
from repro.core.health import HealthConfig
from repro.core.mapreduce import MapReduceEngine, make_corpus, word_weight_job

hc = HealthConfig(target_step_time=1.0, max_threshold=0.8, min_threshold=0.2,
                  time_between_scaling=1, window=1, max_instances=4)
cfg = SimulationConfig(n_vms=12, n_cloudlets=48, broker="matchmaking")
grid = make_scenario_grid(seeds=range(6), mi_scales=[0.7, 1.3],
                          vm_counts=[6, 12])           # B = 24, 6 chunks of 4
ref = run_scenario_grid(cfg, grid)                     # no-dispatcher oracle

def loads_feeder(seq):
    it = iter(seq)
    def on_chunk(disp, ci, n):
        l = next(it, None)
        if l is not None:
            disp.observe_load(l)
    return on_chunk

LOADS = [0.5, 2.0, 0.5, 2.0, 0.5, 0.05]                # events at ci 1, 3, 5
runs = {}
for label, ahead in (("async", 2), ("sync", 0)):
    d = ElasticDispatcher(health_cfg=hc, start_members=1,
                          dispatch_ahead=ahead)
    r = run_scenario_grid(cfg, grid, dispatcher=d, chunk=4,
                          on_chunk=loads_feeder(LOADS))
    assert r.dispatch["members_per_chunk"] == [1, 1, 2, 2, 4, 4], (label, r.dispatch)
    assert r.dispatch["scale_events"] == 3
    drained = [ev["drained_in_flight"] for ev in d.scale_events]
    if label == "async":
        # the pipeline really was >= 2 chunks ahead at EVERY remesh barrier
        assert all(n >= 2 for n in drained), drained
        assert r.dispatch["max_in_flight"] >= 2, r.dispatch
    else:
        assert all(n == 0 for n in drained), drained   # sync: nothing queued
    runs[label] = r

for label, r in runs.items():
    assert np.array_equal(ref.finish_times, r.finish_times), label
    assert np.array_equal(ref.makespans, r.makespans), label
    assert np.array_equal(ref.vm_assign, r.vm_assign), label

# ---- deterministic FLOAT MapReduce across the same scale path ----------
corpus = make_corpus(16, 512, vocab=64, seed=5)
base = None
for ahead in (2, 0):
    for backend in ("hazelcast", "infinispan"):
        eng = MapReduceEngine(backend=backend, dispatcher=ElasticDispatcher(
            health_cfg=hc, start_members=1, dispatch_ahead=ahead))
        out = np.asarray(eng.run(word_weight_job(64), jnp.asarray(corpus),
                                 chunk=4, on_chunk=loads_feeder([2.0, 2.0, 0.05])))
        assert eng.last_report.members_per_chunk == [1, 2, 4, 2], (backend, ahead)
        base = out if base is None else base
        assert np.array_equal(base, out), (backend, ahead)
# ... and, with a power-of-two chunking (pow2 chunks form exact subtrees of
# the global row-aligned tree), equals the single-member SINGLE-CHUNK run
# bit-for-bit despite the float dtype
eng1 = MapReduceEngine(backend="hazelcast",
                       dispatcher=ElasticDispatcher(start_members=1))
out1 = np.asarray(eng1.run(word_weight_job(64), jnp.asarray(corpus)))
assert np.array_equal(base, out1)

# ---- auto_scale: EMA feeding scales out with NO on_chunk feeder --------
from repro.core.des_scan import scenario_grid_job
hc2 = dataclasses.replace(hc, max_instances=2)
d2 = ElasticDispatcher(health_cfg=hc2, start_members=1, auto_scale=True,
                       dispatch_ahead=2)
d2.calibrate_target(scenario_grid_job(cfg, False), 1e-9)  # everything is slow
r2 = run_scenario_grid(cfg, grid, dispatcher=d2, chunk=3)
assert d2.n_members == 2, d2.n_members
assert r2.dispatch["scale_events"] >= 1
assert r2.dispatch["ema_step_s"] > 0.0
assert np.array_equal(ref.finish_times, r2.finish_times)

# ---- non-divisor member count on the device path -----------------------
# pad_to_shards(chunk, m) is NOT monotone in m (pad(4,3)=6 > pad(4,4)=4):
# the one-time device-source pad must cover the widest reachable window or
# dynamic_slice would clamp and compute on the wrong rows
from repro.core.dispatch import DispatchJob
d3 = ElasticDispatcher(devices=jax.devices(), start_members=3)
d3.device_slice_min_bytes = 0
j = DispatchJob(name="rows", signature="rows",
                member_fn=lambda x, v, *_: x * 2.0)
x = jnp.arange(16.0, dtype=jnp.float32).reshape(8, 2)
out, rep = d3.submit(j, x, chunk=4)
assert rep.staged_device == rep.n_chunks == 2, rep
assert rep.staged_in_place == 0, rep     # three members: the copy path
assert np.array_equal(np.asarray(out), np.asarray(x) * 2.0)
# one member whose device does not hold the source: the copy path too
d4 = ElasticDispatcher(devices=jax.devices()[1:2], start_members=1)
d4.device_slice_min_bytes = 0
out, rep = d4.submit(j, x, chunk=4)
assert rep.staged_device == rep.n_chunks == 2, rep
assert rep.staged_in_place == 0, rep
assert np.array_equal(np.asarray(out), np.asarray(x) * 2.0)
print("OK")
"""], env=env, capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_cluster_auto_wires_exchange_load_into_rebalance():
    """ROADMAP exchange follow-on (c), retired: every ``scan_dist`` run
    feeds its measured per-VM exchange load into the dispatcher's
    ``observe_key_weights`` automatically, so the next scale event
    rebalances locality-aware with NO caller cooperation — and the sample
    is consumed by that event (one-shot)."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import numpy as np
from repro.core.cloudsim import ElasticSimulationCluster, SimulationConfig
from repro.core.health import HealthConfig

hc = HealthConfig(target_step_time=1.0, max_threshold=0.8, min_threshold=0.2,
                  time_between_scaling=1, window=1, max_instances=2)
cl = ElasticSimulationCluster(start_members=1, health_cfg=hc)
cfg = SimulationConfig(n_vms=16, n_cloudlets=64, core="scan_dist")
res = cl.simulate(cfg)
kw = cl.dispatcher._key_weights
assert kw is not None, "simulate() did not auto-feed key weights"
assert kw.sum() == cfg.n_cloudlets                 # one weight per cloudlet
counts = np.bincount(res.vm_assign, minlength=kw.shape[0])
assert np.array_equal(kw.astype(np.int64), counts), (kw, counts)
cl.observe_load(2.0)                               # scale out 1 -> 2
assert cl.n_members == 2
assert cl.dispatcher._key_weights is None          # one-shot: consumed
# the run after the event re-feeds a fresh observation, bit-identically
res2 = cl.simulate(cfg)
assert cl.dispatcher._key_weights is not None
assert np.array_equal(res.finish_times, res2.finish_times)
print("OK")
"""], env=env, capture_output=True, text=True, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_auto_block_cache_writes_only_on_measurement():
    """Steady-state auto-capacity hits must not rewrite the block cache:
    only the first call measures (one miss, one metadata write that does
    NOT count as an executable build), later calls hit — churn-free
    counters stay meaningful."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core import des_scan
    from repro.core.executor import DistributedExecutor

    des_scan.invalidate_dist_core()
    ex = DistributedExecutor(Mesh(np.array(jax.devices()[:1]), ("data",)))
    args = (jnp.zeros(16, jnp.int32), jnp.ones(16), jnp.ones(4),
            jnp.ones(16, bool))
    cache = des_scan._AUTO_BLOCK_CACHE
    b0, h0, m0 = cache.builds, cache.hits, cache.misses
    for _ in range(3):                      # 1 measurement + 2 cached hits
        des_scan.simulate_completion_distributed(*args, ex)
    assert cache.builds == b0                 # metadata, not an executable
    assert cache.misses == m0 + 1 and cache.hits == h0 + 2
    des_scan.invalidate_dist_core()


def test_start_members_beyond_the_pool_is_refused():
    """Asking for more members than devices is an error, never a silent
    clamp to the devices present."""
    import jax

    n = len(jax.devices())
    with pytest.raises(ValueError, match="start_members"):
        ElasticDispatcher(start_members=n + 1)
    with pytest.raises(ValueError, match="start_members"):
        ElasticDispatcher(start_members=0)
    assert ElasticDispatcher(start_members=n).n_members == n


def test_cluster_rejects_conflicting_topology_kwargs():
    from repro.core.cloudsim import ElasticSimulationCluster

    d = ElasticDispatcher(start_members=1)
    with pytest.raises(ValueError):
        ElasticSimulationCluster(dispatcher=d, start_members=2)
    with pytest.raises(ValueError):
        from repro.core.health import HealthConfig
        ElasticSimulationCluster(dispatcher=d, health_cfg=HealthConfig())


def test_elastic_cluster_is_thin_dispatcher_client():
    """The cluster owns NO topology of its own: table, controller, mesh,
    executor, grid, entity_pad and scale_events all live in the dispatcher."""
    from repro.core.cloudsim import ElasticSimulationCluster

    cl = ElasticSimulationCluster(start_members=1)
    d = cl.dispatcher
    assert isinstance(d, ElasticDispatcher)
    assert cl.table is d.table
    assert cl.controller is d.controller
    assert cl.mesh is d.mesh
    assert cl.executor is d.executor
    assert cl.entity_pad == d.entity_pad
    assert cl.scale_events is d.scale_events
    assert cl.n_members == d.n_members
    assert np.array_equal(np.asarray(cl.vm_owner(8)),
                          np.asarray(d.vm_owner(8)))
    # an externally-built dispatcher can be shared with the cluster
    cl2 = ElasticSimulationCluster(dispatcher=d)
    assert cl2.dispatcher is d


# ------------------------------------------------- async dispatch pipeline

def test_in_flight_drains_cleanly_on_exception():
    """A failing ``on_chunk`` mid-stream must not leak launched buffers:
    the in-flight queue is drained by the cleanup path and the dispatcher
    stays fully usable for the next stream (tier-1 smoke of the async
    pipeline's exception safety)."""
    import jax.numpy as jnp

    d = ElasticDispatcher(start_members=1, dispatch_ahead=3)
    job = DispatchJob(name="j", signature="j",
                      member_fn=lambda x, v, *_: x * 2.0, reduce="concat")
    seen_in_flight = []

    def boom(disp, ci, n):
        seen_in_flight.append(disp.in_flight)
        if ci == 2:
            raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        d.submit(job, np.ones((12, 2), np.float32), chunk=2, on_chunk=boom)
    assert max(seen_in_flight) >= 2          # the pipeline really was ahead
    assert d.in_flight == 0                  # nothing leaked
    out, rep = d.submit(job, np.ones((4, 2), np.float32), chunk=2)
    assert np.asarray(out).shape == (4, 2) and d.in_flight == 0
    # sum jobs drain too (partials queue through the same pipeline)
    sum_job = DispatchJob(
        name="s", signature="s", reduce="sum",
        member_fn=lambda x, v, *_: jnp.where(v[:, None], x, 0).sum(axis=0))
    with pytest.raises(RuntimeError, match="boom"):
        d.submit(sum_job, np.ones((12, 2), np.float32), chunk=2,
                 on_chunk=boom)
    assert d.in_flight == 0


def test_unarmed_stream_drops_its_tail_without_blocking(monkeypatch):
    """The lazy tail: a stream with nothing armed, no stats, no journal
    and no auto-scale sampling blocks only to hold the flight bound —
    ``n_chunks - dispatch_ahead`` retirements — and drops the rest of its
    flight queue, so the combine queues behind the last chunk."""
    import jax

    d = ElasticDispatcher(start_members=1, dispatch_ahead=2)
    job = DispatchJob(name="j", signature="j-lazy",
                      member_fn=lambda x, v, *_: x * 2.0, reduce="concat")
    x = np.arange(16, dtype=np.float32).reshape(8, 2)
    d.submit(job, x, chunk=1)                    # compile outside the count
    blocks = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda t: blocks.append(1) or real(t))
    out, rep = d.submit(job, x, chunk=1)
    assert len(blocks) == rep.n_chunks - rep.dispatch_ahead == 6
    assert d.in_flight == 0 and rep.max_in_flight == 3
    assert np.asarray(out).tobytes() == (x * 2.0).tobytes()


def test_device_resident_items_zero_host_copies(monkeypatch):
    """Device-resident item sets stay on device: chunks are cut by
    ``executor.slice_chunk`` (host staging is patched to FAIL), outputs are
    device arrays that chain into the next job, and a counting
    ``executor.put`` shim sees no host (numpy) operand on the global path."""
    import jax
    import jax.numpy as jnp

    d = ElasticDispatcher(start_members=1)
    d.device_slice_min_bytes = 0         # force device slicing at any size
    monkeypatch.setattr(
        ElasticDispatcher, "_stage_host",
        staticmethod(lambda *a: (_ for _ in ()).throw(
            AssertionError("host staging touched on the device path"))))

    job = DispatchJob(name="j", signature="j",
                      member_fn=lambda x, v, *_: x + 1.0, reduce="concat")
    items = jnp.arange(20.0, dtype=jnp.float32).reshape(10, 2)
    out, rep = d.submit(job, items, chunk=3)
    assert rep.staged_device == rep.n_chunks == 4 and rep.staged_host == 0
    leaf = jax.tree_util.tree_leaves(out)[0]
    assert isinstance(leaf, jax.Array)       # exposed lazily, still on device
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(items) + 1.0)

    # a previous job's device output feeds the next submit host-copy-free
    out2, rep2 = d.submit(job, out, chunk=4)
    assert rep2.staged_device == rep2.n_chunks and rep2.staged_host == 0
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(items) + 2.0)

    # global (auto-SPMD) path: the counting put shim must never see numpy
    host_puts = []
    orig_put = d.executor.put

    def counting_put(value, spec=None):
        if isinstance(value, np.ndarray):
            host_puts.append(value.shape)
        return orig_put(value, spec)

    monkeypatch.setattr(d.executor, "put", counting_put)
    gjob = DispatchJob(name="g", signature="g",
                       global_fn=lambda x, v, *_: x * 3.0, reduce="concat")
    out3, rep3 = d.submit(gjob, out2, chunk=5)
    assert rep3.staged_device == rep3.n_chunks and rep3.staged_host == 0
    assert host_puts == []                   # zero host copies end to end
    np.testing.assert_array_equal(np.asarray(out3),
                                  (np.asarray(items) + 2.0) * 3.0)


def _in_place_case(case):
    """A job and a 10-row device source for ``case`` (reduce-mode)."""
    import jax.numpy as jnp
    from repro.core.mapreduce import (dispatch_job_for, make_corpus,
                                      word_count_job)

    reduce, mode = case.split("-")
    if reduce == "sum":                  # word count, int32 partials
        backend = "hazelcast" if mode == "member" else "infinispan"
        corpus = make_corpus(10, 256, vocab=64, seed=3)
        return (dispatch_job_for(word_count_job(64), backend),
                jnp.asarray(corpus),
                np.bincount(corpus.reshape(-1), minlength=64))
    rows = np.arange(30.0, dtype=np.float32).reshape(10, 3)
    job = DispatchJob(name=f"rows_{mode}", signature=("rows", mode),
                      reduce="concat",
                      **{f"{mode}_fn": lambda x, v, *_: x * 2.0 + 1.0})
    return job, jnp.asarray(rows), rows * 2.0 + 1.0


@pytest.mark.parametrize("case", ["sum-member", "sum-global",
                                  "concat-member", "concat-global"])
def test_in_place_cut_matches_the_copy(case, monkeypatch):
    """One member over a device source on its own device: the job's
    executable cuts each window itself, bit-identical to the
    ``slice_chunk`` copy — the ragged last chunk included (10 rows in
    chunks of 4, over a source padded to 12).  The source reads back
    unchanged after the stream, and a second stream over it gives the
    same bytes from the cached executable."""
    job, items, want = _in_place_case(case)
    before = np.asarray(items)
    d = ElasticDispatcher(start_members=1)
    d.device_slice_min_bytes = 0
    out, rep = d.submit(job, items, chunk=4)
    assert rep.staged_in_place == rep.staged_device == rep.n_chunks == 3
    assert rep.staged_host == 0
    assert not items.is_deleted()
    np.testing.assert_array_equal(np.asarray(items), before)
    out2, rep2 = d.submit(job, items, chunk=4)
    assert rep2.compiles == 0 and rep2.staged_in_place == 3

    monkeypatch.setattr(ElasticDispatcher, "_reads_in_place",
                        lambda *a: False)
    dc = ElasticDispatcher(start_members=1)
    dc.device_slice_min_bytes = 0
    ref, rep_copy = dc.submit(job, items, chunk=4)
    assert rep_copy.staged_in_place == 0
    assert rep_copy.staged_device == rep_copy.n_chunks == 3
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(out), want)


@pytest.mark.parametrize("mode", ["member", "global"])
def test_in_place_source_is_never_donated(mode):
    """A source exactly one chunk long has the output's shape and dtype,
    so a donated source would be taken over by the output and deleted: it
    must read back after the stream, twice over."""
    import jax.numpy as jnp

    job, _, _ = _in_place_case(f"concat-{mode}")
    rows = np.arange(12.0, dtype=np.float32).reshape(4, 3)
    src = jnp.asarray(rows)
    d = ElasticDispatcher(start_members=1)
    d.device_slice_min_bytes = 0
    for _ in range(2):
        out, rep = d.submit(job, src, chunk=4)
        assert rep.staged_in_place == rep.n_chunks == 1
        assert not src.is_deleted()
        np.testing.assert_array_equal(np.asarray(src), rows)
        np.testing.assert_array_equal(np.asarray(out), rows * 2.0 + 1.0)


def test_in_place_replay_reads_the_same_window():
    """A chunk poisoned under the fault injector is launched again with
    its ``lo`` and reads the same window of the source: the stream comes
    out as the fault-free one, every launch cut in place."""
    import jax.numpy as jnp
    from repro.core.faults import FaultInjector, FaultSpec

    job = DispatchJob(name="rows_f", signature="rows_f", reduce="concat",
                      member_fn=lambda x, v, *_: x * 2.0 + 1.0)
    items = jnp.arange(30.0, dtype=jnp.float32).reshape(10, 3)
    d = ElasticDispatcher(start_members=1, fault_injector=FaultInjector(
        [FaultSpec("nan_poison", chunk=1, member=0)]))
    d.device_slice_min_bytes = 0
    out, rep = d.submit(job, items, chunk=4)
    assert [f["chunk"] for f in rep.failures] == [1] and rep.retries == 1
    assert rep.staged_in_place == rep.staged_device == rep.n_chunks + 1
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(items) * 2.0 + 1.0)


def test_deterministic_sum_requires_sum_reduce():
    with pytest.raises(ValueError):
        DispatchJob(name="x", signature="x", member_fn=lambda *a: a,
                    reduce="concat", deterministic=True)


def test_deterministic_float_sum_bit_identical_across_chunkings():
    """The fixed-arity pairwise tree keyed on chunk index: float sums are
    bit-identical across power-of-two chunk sizes (equal pow2 chunks form
    exact subtrees of the global row-aligned tree) and across host/device
    item staging — the int32 word-count guarantee, extended to floats."""
    import jax.numpy as jnp

    d = ElasticDispatcher(start_members=1)
    job = DispatchJob(name="det", signature="det", reduce="sum",
                      deterministic=True,
                      member_fn=lambda x, v, *_: x)
    rng = np.random.RandomState(0)
    x = (rng.randn(22, 5) * 10 ** rng.uniform(-3, 3, (22, 5))).astype(
        np.float32)
    outs = [np.asarray(d.submit(job, x, chunk=c)[0]) for c in (2, 4, 8, 16)]
    outs += [np.asarray(d.submit(job, jnp.asarray(x), chunk=c)[0])
             for c in (2, 8)]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)
    # a non-pow2 chunking is still deterministic run-to-run, but the stream
    # WARNS that the cross-chunking guarantee is forfeited (ROADMAP hygiene
    # note, now surfaced at submit instead of silently lost)
    with pytest.warns(NonPow2ChunkWarning):
        a = np.asarray(d.submit(job, x, chunk=3)[0])
    with pytest.warns(NonPow2ChunkWarning):
        b = np.asarray(d.submit(job, x, chunk=3)[0])
    np.testing.assert_array_equal(a, b)


def test_nonpow2_warning_exactly_once_per_submit():
    """NonPow2ChunkWarning fires EXACTLY once per offending submit — and
    never for pow2 chunkings, single-chunk streams, or non-deterministic
    jobs (their reduce order doesn't depend on the chunking)."""
    import warnings as _warnings

    d = ElasticDispatcher(start_members=1)
    det = DispatchJob(name="det", signature="detw", reduce="sum",
                      deterministic=True, member_fn=lambda x, v, *_: x)
    x = np.ones((12, 2), np.float32)

    def count(job, **kw):
        with _warnings.catch_warnings(record=True) as rec:
            _warnings.simplefilter("always")
            d.submit(job, x, **kw)
        return sum(issubclass(w.category, NonPow2ChunkWarning) for w in rec)

    assert count(det, chunk=3) == 1            # non-pow2, multi-chunk
    assert count(det, chunk=3) == 1            # once per submit, not once ever
    assert count(det, chunk=4) == 0            # pow2
    assert count(det, chunk=12) == 0           # single chunk: no cross-chunk
    plain = DispatchJob(name="p", signature="pw", reduce="concat",
                        member_fn=lambda x, v, *_: x * 2.0)
    assert count(plain, chunk=3) == 0          # non-deterministic job


def test_auto_scale_ema_and_target_calibration():
    """auto_scale feeds an EMA of retirement-to-retirement step times: the
    synchronous baseline still samples per chunk, compile chunks reset the
    timer instead of polluting the EMA, an explicit per-job-class target
    dominates, and an uncalibrated job class self-calibrates so its first
    sample lands at the neutral midpoint of the scaling thresholds."""
    d = ElasticDispatcher(start_members=1, auto_scale=True, dispatch_ahead=0)
    job = DispatchJob(name="j", signature="jsig",
                      member_fn=lambda x, v, *_: x * 2.0, reduce="concat",
                      target_step_time=1e9)
    d.submit(job, np.ones((12, 2), np.float32), chunk=2)
    assert d.job_targets == {}            # explicit target: no calibration
    assert d.controller.monitor.load() < 0.1      # huge target => tiny load

    job2 = DispatchJob(name="k", signature="ksig",
                       member_fn=lambda x, v, *_: x * 2.0, reduce="concat")
    _, rep = d.submit(job2, np.ones((12, 2), np.float32), chunk=2)
    assert rep.ema_step_s > 0.0
    target = d.job_targets.get("ksig")
    assert target is not None and target > 0.0    # self-calibrated
    # the calibrating sample itself lands at the neutral threshold midpoint
    mid = 0.5 * (d.health_cfg.max_threshold + d.health_cfg.min_threshold)
    assert d._job_target(job2, 1.0) == target     # sticky once calibrated
    d.calibrate_target(job2, 123.0)
    assert d.job_targets["ksig"] == 123.0         # explicit API overrides
    fresh = DispatchJob(name="f", signature="fsig",
                        member_fn=lambda x, v, *_: x, reduce="concat")
    assert d._job_target(fresh, 2.0) == pytest.approx(2.0 / mid)

    # PIPELINED short streams (n_chunks <= depth, nothing ever retires
    # mid-loop) still sample: the auto_scale end-drain falls back to
    # launch-to-completion walls, so the IAS is never starved
    d2 = ElasticDispatcher(start_members=1, auto_scale=True,
                           dispatch_ahead=2)
    sj = DispatchJob(name="s", signature="ssig", target_step_time=1e9,
                     member_fn=lambda x, v, *_: x * 2.0, reduce="concat")
    d2.submit(sj, np.ones((4, 2), np.float32), chunk=2)     # compile chunk
    _, rep2 = d2.submit(sj, np.ones((4, 2), np.float32), chunk=2)
    assert rep2.ema_step_s > 0.0 and rep2.max_in_flight == 2
    assert d2.in_flight == 0


# ------------------------------------------- locality-aware rebalance (seed)

def test_weighted_rebalance_spreads_hot_vm():
    """A hot VM (huge observed exchange_load) must not drag a full share of
    cold partitions onto its member: weighted leveling gives the hot
    partition's owner far FEWER partitions than the balanced count, while
    total weighted load stays near-balanced."""
    n_keys, n_members = DEFAULT_PARTITION_COUNT, 4
    key_w = np.ones(n_keys)
    hot_key = 17
    key_w[hot_key] = 300.0                 # one hot VM
    w = partition_weights_from_keys(key_w)
    assert w.shape == (DEFAULT_PARTITION_COUNT,)
    assert w[hot_key % DEFAULT_PARTITION_COUNT] == 300.0

    pt = PartitionTable(n_instances=1)
    moved = pt.rebalance(n_members, weights=w)
    assert moved > 0
    assert (pt.owner >= 0).all() and (pt.owner < n_members).all()
    hot_owner = pt.owner[hot_key % DEFAULT_PARTITION_COUNT]
    counts = np.bincount(pt.owner, minlength=n_members)
    balanced = DEFAULT_PARTITION_COUNT // n_members
    # the hot member carries far fewer partitions than a count-balanced table
    assert counts[hot_owner] < balanced // 2, counts
    # ... and weighted loads are leveled AROUND the irreducible hot
    # partition: the hot member takes almost nothing on top of it, while the
    # cold members split the remaining weight evenly
    loads = np.zeros(n_members)
    np.add.at(loads, pt.owner, w)
    assert loads[hot_owner] <= 300.0 * 1.1, loads
    cold = np.delete(loads, hot_owner)
    assert cold.max() - cold.min() <= 0.2 * cold.mean(), loads
    # unweighted rebalance (the default) still levels by COUNT
    pt2 = PartitionTable(n_instances=1)
    pt2.rebalance(n_members)
    c2 = pt2.load()
    assert c2.max() - c2.min() <= 1


def test_weighted_rebalance_validates_and_covers_departures():
    pt = PartitionTable(n_instances=4)
    with pytest.raises(ValueError):
        pt.rebalance(2, weights=np.ones(3))        # wrong shape
    w = np.ones(DEFAULT_PARTITION_COUNT)
    pt.rebalance(2, weights=w)                     # departed members re-home
    assert (pt.owner < 2).all()
    # uniform weights behave like count-leveling (spread stays tight)
    load = pt.load()
    assert load.max() - load.min() <= DEFAULT_PARTITION_COUNT // 20


def test_dispatcher_observe_key_weights_feeds_remesh():
    """After ``observe_key_weights``, the next scale event rebalances by
    weight: the hot key's member ends up with a small partition count."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import numpy as np
from repro.core.dispatch import ElasticDispatcher
from repro.core.health import HealthConfig

hc = HealthConfig(target_step_time=1.0, max_threshold=0.8, min_threshold=0.2,
                  time_between_scaling=1, window=1, max_instances=2)
d = ElasticDispatcher(start_members=1, health_cfg=hc)
key_w = np.ones(100)
key_w[3] = 500.0                                   # VM 3 is hot
d.observe_key_weights(key_w)
d.observe_load(2.0)                                # scale out 1 -> 2
assert d.n_members == 2, d.n_members
owner = np.asarray(d.vm_owner(100))
hot_member = owner[3]
loads = np.zeros(2)
np.add.at(loads, owner, key_w)
counts = np.bincount(owner, minlength=2)
# weighted load near-balanced => the hot member holds few other keys
assert counts[hot_member] < counts[1 - hot_member], (counts, loads)
print("OK")
"""], env=env, capture_output=True, text=True, timeout=600)
    assert "OK" in r.stdout, r.stdout + r.stderr
