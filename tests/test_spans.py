"""Spans and JAX counters inside the dispatcher (``core/spans.py``), and the
names its executables carry on the device trace."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dispatch import DispatchJob, ElasticDispatcher
from repro.core.mapreduce import dispatch_job_for, word_count_job
from repro.core.spans import jax_counts, span
from repro.core.stats import DispatchStats

CHUNK_SPANS = ("dispatch.stage", "dispatch.launch", "dispatch.retire")


def corpus(n_files=16, file_len=64, vocab=32, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (n_files, file_len)).astype(np.int32)


def count(job, files, chunk, **kw):
    d = ElasticDispatcher(start_members=1, **kw)
    d.device_slice_min_bytes = 0        # cut the chunks on the device
    return d.submit(dispatch_job_for(job), jnp.asarray(files), chunk=chunk,
                    deliver="host")


@pytest.mark.parametrize("depth", [2, 0])
def test_a_stream_records_each_stage_once_per_chunk(depth):
    files = corpus()
    out, rep = count(word_count_job(32), files, chunk=4, collect_stats=True,
                     dispatch_ahead=depth)
    assert np.array_equal(out, np.bincount(files.ravel(), minlength=32))
    spans = rep.stats["spans"]
    assert rep.n_chunks == 4
    for name in CHUNK_SPANS:
        assert spans[name]["n"] == rep.n_chunks, name
    assert spans["dispatch.combine"]["n"] == 1
    assert spans["dispatch.stream"]["n"] == 1
    for name, s in spans.items():
        assert 0 <= s["self_s"] <= s["total_s"] + 1e-12, name
    # the stream's own time is what its stages leave uncovered
    stages = sum(s["total_s"] for k, s in spans.items()
                 if k != "dispatch.stream")
    assert spans["dispatch.stream"]["self_s"] == pytest.approx(
        spans["dispatch.stream"]["total_s"] - stages, abs=1e-9)


def test_with_stats_off_nothing_is_recorded_and_outputs_are_identical():
    files = corpus(seed=1)
    job = word_count_job(32)
    off, rep_off = count(job, files, chunk=4)
    on, rep_on = count(job, files, chunk=4, collect_stats=True)
    assert rep_off.stats is None and "spans" in rep_on.stats
    assert np.asarray(off).tobytes() == np.asarray(on).tobytes()


def test_jax_counters_read_a_first_submit_and_not_its_repeat():
    job = DispatchJob(name="tests/triple", signature=("triple", object()),
                      member_fn=lambda x, v, *_: x * 3.0, reduce="concat")
    d = ElasticDispatcher(start_members=1)
    x = np.arange(32, dtype=np.float32)
    _, first = d.submit(job, x, chunk=8, deliver="host")
    assert first.jax_traces > 0
    assert first.jax_compiles + first.jax_cache_loads > 0
    _, again = d.submit(job, x, chunk=8, deliver="host")
    assert (again.jax_traces, again.jax_compiles, again.jax_cache_loads) \
        == (0, 0, 0)


def test_nested_spans_take_self_time_and_feed_a_collector():
    stats = DispatchStats()
    with span("outer", stats, stream=1) as outer:
        with span("inner", stats, stream=1, chunk=0) as inner:
            sum(range(20000))
        with span("untracked") as free:
            sum(range(20000))
    assert outer.seconds >= inner.seconds + free.seconds
    rec = stats.summary()["spans"]
    assert set(rec) == {"outer", "inner"}          # no collector, no record
    assert rec["inner"]["self_s"] == pytest.approx(inner.seconds)
    assert rec["outer"]["self_s"] == pytest.approx(
        outer.seconds - inner.seconds - free.seconds)


def test_a_span_lands_on_the_profilers_host_plane_with_its_args(tmp_path):
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    with span("dispatch.launch", stream=7, chunk=2) as s:
        s.annotate(built=1)
        jnp.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    found = [dict(e.stats) for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name == "dispatch.launch"]
    assert found and found[0] == {"stream": 7, "chunk": 2, "built": 1}


def compiled_names(fn):
    """The names of the programs JAX compiles while ``fn()`` runs."""
    names = []

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    return names


def test_member_executable_module_carries_the_job_name():
    d = ElasticDispatcher(start_members=1)
    job = dispatch_job_for(word_count_job(32))
    sl, valid = d._stage_host(corpus(), 0, 4, 4)
    text = d._executable(job, sl, (), 4).lower(sl, valid).as_text()
    assert "module @jit_dispatch_mapreduce_word_count " in text


@pytest.mark.parametrize("backend,stages", [
    ("hazelcast", ["dispatch_mapreduce_word_weight_rows",
                   "dispatch_mapreduce_word_weight_tree"]),
    ("infinispan", ["dispatch_mapreduce_word_weight",
                    "dispatch_mapreduce_word_weight_tree"]),
])
def test_deterministic_and_global_stages_carry_the_job_name(backend, stages):
    from repro.core.mapreduce import word_weight_job
    job = dispatch_job_for(word_weight_job(32), backend)
    d = ElasticDispatcher(start_members=1)
    names = compiled_names(lambda: d.submit(job, corpus(), chunk=8,
                                            deliver="host"))
    for stage in stages:
        assert f"jit({stage})" in names, names


def test_run_simulation_stages_land_on_the_profilers_host_plane(tmp_path):
    from jax.profiler import ProfileData
    from jax.sharding import Mesh
    from repro.core.cloudsim import SimulationConfig, run_simulation
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    jax.profiler.start_trace(str(tmp_path))
    try:
        run_simulation(SimulationConfig(n_vms=8, n_cloudlets=64), mesh)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    found = [e.name for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events if e.name.startswith("sim.")]
    assert sorted(found) == ["sim.core", "sim.create", "sim.schedule"]


def test_run_simulation_keeps_its_stage_timings():
    from jax.sharding import Mesh
    from repro.core.cloudsim import SimulationConfig, run_simulation
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    r = run_simulation(SimulationConfig(n_vms=8, n_cloudlets=64,
                                        is_loaded=True, workload_dim=4),
                       mesh)
    assert set(r.timings) == {"create", "schedule", "workload", "core_sim"}
    assert all(v > 0 for v in r.timings.values())


def test_counts_are_kept_per_thread():
    import threading
    before = jax_counts()
    t = threading.Thread(target=lambda: jax.jit(lambda x: x - 5.0)(
        jnp.ones(3)).block_until_ready())
    t.start()
    t.join(timeout=60)
    assert not t.is_alive()
    assert jax_counts() == before
