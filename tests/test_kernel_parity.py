"""v2 kernel parity: ``use_kernel=True`` is BIT-identical to the lax path.

The v2 position-gated kernel replicates ``_segmented_cumsum``'s
Hillis–Steele combine tree exactly (same step set {2^j : 2^j < C}, same
gate ``pos >= d``), so — unlike the tolerance-equivalent v1 matmul kernel
gated in ``test_kernels.py`` — its contract is bitwise equality, asserted
here in three layers:

  1. the kernel primitive vs the lax scan (emulation AND the real Pallas
     kernel under ``force_pallas``), across chunks / dtypes / sizes;
  2. the fused end-to-end scan core (sort + scan + scatter) vs the
     default path, jit-vs-jit (eager-vs-jit differs by pre-existing XLA
     fusion on BOTH paths equally, so like is compared with like);
  3. the distributed/elastic cores with ``use_kernel=True`` across member
     counts and a mid-stream scale event.

Plus the roofline autotuner's guarantees (never slower than the hand-
picked default on the measured harness; trace-time purity) and the
``kernel_path`` provenance satellite.
"""
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compat
from repro.core.des_scan import _segmented_cumsum, simulate_completion_scan_jit
from repro.kernels.seg_scan.v2 import scatter_finish_v2, seg_cumsum_v2
from repro.roofline import autotune

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
CHUNKS = (64, 128, 256)


def _case(rng, C, dtype):
    if np.issubdtype(dtype, np.integer):
        term = jnp.asarray(rng.integers(-50, 50, C).astype(dtype))
    else:
        term = jnp.asarray(rng.uniform(0.0, 5.0, C).astype(dtype))
    start = jnp.asarray(rng.uniform(size=C) < 0.1)
    return term, start


# ------------------------------------------------------- kernel primitive

@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_v2_emulation_bitwise_equals_lax(dtype):
    rng = np.random.default_rng(0)
    lax = jax.jit(_segmented_cumsum)
    for C in (1, 7, 64, 100, 257, 1000, 4096):
        term, start = _case(rng, C, dtype)
        want = np.asarray(lax(term, start))
        for chunk in CHUNKS:
            got = np.asarray(jax.jit(
                lambda t, s, c=chunk: seg_cumsum_v2(t, s, chunk=c,
                                                    interpret=True))(
                term, start))
            assert np.array_equal(want, got), (C, chunk, dtype)


@pytest.mark.parametrize("dtype", [np.float32, np.int32])
def test_v2_real_kernel_bitwise_equals_lax(dtype):
    """``force_pallas=True`` runs the ACTUAL kernel body under the Pallas
    interpreter (grid loop, VMEM carry scratch, @pl.when reset) — the same
    program a TPU compiles — and it must match bitwise too."""
    rng = np.random.default_rng(1)
    lax = jax.jit(_segmented_cumsum)
    for C in (64, 100, 257):
        term, start = _case(rng, C, dtype)
        want = np.asarray(lax(term, start))
        for chunk in (64, 128):
            got = np.asarray(seg_cumsum_v2(term, start, chunk=chunk,
                                           force_pallas=True))
            assert np.array_equal(want, got), (C, chunk, dtype)


def test_scatter_finish_v2_bitwise_both_paths():
    rng = np.random.default_rng(2)
    for C in (5, 64, 257, 1000):
        f = jnp.asarray(rng.uniform(0.0, 9.0, C).astype(np.float32))
        order = jnp.asarray(rng.permutation(C).astype(np.int32))
        sent = jnp.asarray(rng.uniform(size=C) < 0.2)
        want = np.zeros(C, np.float32)
        want[np.asarray(order)] = np.where(np.asarray(sent), 0.0,
                                           np.asarray(f))
        for kw in (dict(interpret=True), dict(force_pallas=True)):
            got = np.asarray(scatter_finish_v2(f, order, sent, **kw))
            assert np.array_equal(want, got), (C, kw)


# ------------------------------------------------- fused end-to-end core

def test_scan_use_kernel_bitwise_equals_default():
    """The full fused path (lax.sort gather + v2 scan + fused scatter) is
    bitwise identical to ``use_kernel=False`` under jit — per chunk AND at
    the autotuned default (kernel_chunk=None)."""
    rng = np.random.default_rng(3)
    for C, V in ((80, 12), (333, 7), (2048, 64)):
        assign = jnp.asarray(rng.integers(0, V, C).astype(np.int32))
        mi = jnp.asarray(rng.uniform(1.0, 200.0, C).astype(np.float32))
        mips = jnp.asarray(rng.uniform(5.0, 20.0, V).astype(np.float32))
        mips = mips.at[0].set(0.0)                 # zero-MIPS padded VM
        valid = jnp.asarray(rng.uniform(size=C) < 0.8)
        f0, m0 = simulate_completion_scan_jit(assign, mi, mips, valid)
        for chunk in (None,) + CHUNKS:
            f1, m1 = simulate_completion_scan_jit(
                assign, mi, mips, valid, use_kernel=True, kernel_chunk=chunk)
            assert np.array_equal(np.asarray(f0), np.asarray(f1)), (C, chunk)
            assert float(m0) == float(m1), (C, chunk)


def test_use_kernel_distributed_bitwise_across_member_counts():
    """scan_dist with use_kernel=True on 1/2/4 members == the kernel-free
    single-device scan, BITWISE — the kernel keeps the elasticity accuracy
    claim intact (the whole point of the position-gated redesign)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import dataclasses
import jax, numpy as np
from jax.sharding import Mesh
from repro.core.cloudsim import SimulationConfig, run_simulation
devs = jax.devices()
cfg = SimulationConfig(n_vms=40, n_cloudlets=80, broker="matchmaking",
                       core="scan_dist", use_kernel=True, kernel_chunk=64)
base = run_simulation(dataclasses.replace(cfg, core="scan",
                                          use_kernel=False),
                      Mesh(np.array(devs[:1]), ("data",)))
for n in (1, 2, 4, 8):
    r = run_simulation(cfg, Mesh(np.array(devs[:n]), ("data",)))
    assert np.array_equal(base.finish_times, r.finish_times), n
    assert base.makespan == r.makespan, n
print("OK")
"""], env=env, capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_use_kernel_elastic_scale_event_bitwise():
    """A mid-run scale-out (1→2) with use_kernel=True: finish vectors stay
    bit-identical to the fixed-mesh kernel-free run across the event."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import dataclasses
import jax, numpy as np
from jax.sharding import Mesh
from repro.core.cloudsim import (ElasticSimulationCluster, SimulationConfig,
                                 run_simulation)
from repro.core.health import HealthConfig
devs = jax.devices()
cfg = SimulationConfig(n_vms=40, n_cloudlets=80, broker="matchmaking",
                       core="scan_dist", use_kernel=True)
fixed = run_simulation(dataclasses.replace(cfg, core="scan",
                                           use_kernel=False),
                       Mesh(np.array(devs[:1]), ("data",)))
hc = HealthConfig(target_step_time=1.0, max_threshold=0.8, min_threshold=0.2,
                  time_between_scaling=1, window=1, max_instances=2)
cl = ElasticSimulationCluster(devices=devs, health_cfg=hc, start_members=1)
results = [cl.simulate(cfg)]
cl.observe_load(2.0)                                   # scale out 1 -> 2
assert cl.n_members == 2, cl.n_members
results.append(cl.simulate(cfg))
for i, r in enumerate(results):
    assert np.array_equal(fixed.finish_times, r.finish_times), i
    assert fixed.makespan == r.makespan, i
print("OK")
"""], env=env, capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr


# ------------------------------- deterministic-sum FMA fence (regression)

def test_deterministic_bare_product_bitwise_across_member_counts():
    """Regression for the M=1 FMA-fusion caveat: a deterministic sum job
    whose member_fn is a BARE product used to differ at M=1 because XLA
    fused ``xs * ws`` into the row reduction as an FMA (single executable)
    while M>1's exchange boundary kept them separate.  The row/tree split
    now compiles the tree in its own executable, so the bare product is
    bit-identical across member counts."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import numpy as np
from repro.core.dispatch import DispatchJob, ElasticDispatcher
rng = np.random.RandomState(0)
x = (rng.randn(24, 5) * 10 ** rng.uniform(-3, 3, (24, 5))).astype(np.float32)
w = (rng.randn(5) * 10 ** rng.uniform(-2, 2, 5)).astype(np.float32)
job = DispatchJob(name="prod", signature="prod", reduce="sum",
                  deterministic=True, member_fn=lambda xs, v, ws: xs * ws)
outs = []
for n in (1, 2, 4):
    d = ElasticDispatcher(start_members=n)
    out, _ = d.submit(job, x, replicated=(w,), chunk=4)
    outs.append(np.asarray(out))
assert np.array_equal(outs[0], outs[1]), "M=1 vs M=2"
assert np.array_equal(outs[0], outs[2]), "M=1 vs M=4"
print("OK")
"""], env=env, capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr


# ----------------------------------------------------- roofline autotuner

def test_candidate_chunks_clamped_and_default_present():
    assert autotune.candidate_chunks(32) == (32,)       # clamped default
    cands = autotune.candidate_chunks(1 << 20)
    assert autotune.DEFAULT_CHUNK in cands
    assert all(c & (c - 1) == 0 for c in cands)
    assert min(cands) >= 64 and max(cands) <= 1024


def test_analytic_ranking_models_both_kernels():
    # v2 is memory-bound at 1M: bigger L -> fewer tail passes -> wins
    v2 = autotune.rank_chunks(1 << 20, kind="v2", device_kind="cpu")
    assert v2[0].chunk == max(s.chunk for s in v2)
    assert v2[0].bottleneck == "memory"
    # v1's masked matmul makes FLOPs grow with L: smallest chunk wins
    v1 = autotune.rank_chunks(1 << 20, kind="v1", device_kind="cpu")
    assert v1[0].chunk == min(s.chunk for s in v1)
    # the measured HLO anchor parses real compiled traffic (the add-only
    # scan has no dot ops, so only HBM bytes are nonzero — memory-bound)
    costs = autotune.lax_scan_costs(1 << 20)
    assert costs.hbm_bytes > 0
    small = autotune.lax_scan_costs(1 << 12)
    assert costs.hbm_bytes > small.hbm_bytes    # element·step extrapolation
    # peaks are keyed by device_kind; a kind without published peaks is an
    # error, never a default
    assert autotune.rank_chunks(1 << 20, device_kind="TPU v5 lite")
    with pytest.raises(ValueError, match="no published peaks"):
        autotune.rank_chunks(1 << 20, device_kind="TPU v99")


@pytest.fixture
def fresh_tune_cache(monkeypatch):
    """Each autotuner test persists into its own empty cache."""
    from repro.core.dispatch import CompileCache
    monkeypatch.setattr(autotune, "TUNE_CACHE", CompileCache(max_entries=64))


def test_tuned_chunk_never_slower_than_default(fresh_tune_cache):
    """With measure=True the hand-picked default is ALWAYS in the measured
    set, so the returned chunk's measured time <= the default's."""
    fake = {64: 3e-3, 128: 2e-3, 256: 1e-3, 512: 4e-3, 1024: 5e-3}
    got = autotune.tuned_chunk(1 << 20, device_kind="cpu", measure=True,
                               bench=lambda c: fake[c], top_k=2)
    choice = autotune.tuning_report(1 << 20, device_kind="cpu")
    assert choice.source == "measured"
    assert autotune.DEFAULT_CHUNK in choice.measured_s
    assert choice.measured_s[got] <= choice.measured_s[autotune.DEFAULT_CHUNK]
    # when the default measures fastest, it IS the answer (ties included)
    got2 = autotune.tuned_chunk(1 << 19, device_kind="cpu", measure=True,
                                bench=lambda c: 1e-3 if c == 128 else 9e-3)
    assert got2 == autotune.DEFAULT_CHUNK


def test_tuned_chunk_trace_time_purity_and_cache(fresh_tune_cache):
    """measure=False never benches (a poisoned bench proves it) and the
    measured choice persists per (device_kind, kind, pow2 bucket)."""
    def boom(c):
        raise AssertionError("measure=False must not bench")

    got = autotune.tuned_chunk(1 << 18, device_kind="cpu", bench=boom)
    assert got == autotune.rank_chunks(1 << 18, device_kind="cpu")[0].chunk
    autotune.tuned_chunk(1 << 18, device_kind="cpu", measure=True,
                         bench=lambda c: {64: 9, 128: 9}.get(c, 1e-4))
    # cache hit: measured choice now wins even with a poisoned bench
    again = autotune.tuned_chunk(1 << 18, device_kind="cpu", bench=boom,
                                 measure=True)
    assert again == autotune.tuning_report(1 << 18, device_kind="cpu").chunk
    # same bucket, different size -> same cached entry
    assert autotune.tuned_chunk((1 << 18) - 3, device_kind="cpu",
                                bench=boom) == again
    # another chip generation keeps its own entry
    assert autotune.tuning_report(1 << 18,
                                  device_kind="TPU v5 lite") is None


def test_tuned_exchange_block_bounds():
    for C, M in ((100_000, 8), (4096, 4), (64, 16), (1, 1), (7, 32)):
        b = autotune.tuned_exchange_block(C, M)
        assert 1 <= b <= max(C // max(M, 1), 1), (C, M, b)
        assert b & (b - 1) == 0, (C, M, b)
    # the roofline view of an exchange returns finite positive seconds
    t, bottleneck = autotune.exchange_roofline(100_000, 8, 2048)
    assert t > 0 and bottleneck in ("compute", "memory", "collective")


# ------------------------------------------------- kernel_path provenance

def test_kernel_path_resolution():
    assert compat.kernel_path(False) is None
    assert compat.kernel_path(True, interpret=True) == "interpret"
    assert compat.kernel_path(True, interpret=False) == "compiled"
    on_cpu = "interpret" if jax.default_backend() != "tpu" else "compiled"
    assert compat.kernel_path(True) == on_cpu
    # a backend that can neither compile nor interpret-by-default is refused
    from unittest import mock
    with mock.patch.object(jax, "default_backend", return_value="gpu"):
        with pytest.raises(RuntimeError, match="no path on backend"):
            compat.resolve_kernel_interpret(None)


def test_interpret_fallback_warns_exactly_once(monkeypatch):
    monkeypatch.setattr(compat, "_warned_interpret_fallback", False)
    if jax.default_backend() == "tpu":
        pytest.skip("fallback warning only fires off-TPU")
    with pytest.warns(compat.KernelInterpretFallbackWarning):
        assert compat.resolve_kernel_interpret(None) is True
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # second call must be silent
        assert compat.resolve_kernel_interpret(None) is True
        # explicit interpret is a deliberate choice: never warns
        monkeypatch.setattr(compat, "_warned_interpret_fallback", False)
        assert compat.resolve_kernel_interpret(True) is True
        assert compat.resolve_kernel_interpret(False) is False


def test_dispatch_report_records_kernel_path():
    from repro.core.cloudsim import SimulationConfig
    from repro.core.des_scan import run_simulation_batch, scenario_grid_job
    from repro.core.dispatch import ElasticDispatcher

    cfg = SimulationConfig(n_vms=8, n_cloudlets=16, use_kernel=True)
    expect = "interpret" if jax.default_backend() != "tpu" else "compiled"
    assert scenario_grid_job(cfg).kernel_path == expect
    r = run_simulation_batch(cfg, np.arange(4),
                             dispatcher=ElasticDispatcher(start_members=1),
                             chunk=2)
    assert r.dispatch["kernel_path"] == expect
    # the lax path records None — no kernel involved
    lax_cfg = SimulationConfig(n_vms=8, n_cloudlets=16)
    assert scenario_grid_job(lax_cfg).kernel_path is None
    r2 = run_simulation_batch(lax_cfg, np.arange(4),
                              dispatcher=ElasticDispatcher(start_members=1),
                              chunk=2)
    assert r2.dispatch["kernel_path"] is None
