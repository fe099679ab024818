"""The plain references against the program at small sizes, and the same
computation in bfloat16 failing the comparison that decides ``correct``."""
import ml_dtypes
import numpy as np
import pytest

from reference import entities, matchmaking, timeshared, wordcount

LIMIT = 1e-4          # finish_rel_err's limit in the configurations
MIPS, MI = (500.0, 2000.0), (1000.0, 50000.0)
RULE = {"max_mi": 50000.0, "headroom": 0.9}


def rel(got, ref):
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref) / ref))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_closed_form_reference_equals_the_stepping_one(seed):
    rng = np.random.default_rng(seed)
    mi = rng.uniform(*MI, 3000)
    mips = rng.uniform(*MIPS, 40)
    assign = rng.integers(0, 40, 3000)
    every = timeshared.finish_times_all(assign, mi, mips)
    for v in range(40):
        rows = assign == v
        assert rel(every[rows], timeshared.finish_times(mi[rows],
                                                        mips[v])) < 1e-12


def test_stepping_reference_by_hand():
    # two cloudlets of 10 and 30 MI on a 10-MIPS VM: both run at 5 MIPS
    # until t=2, then the second runs alone at 10 MIPS for 20 MI more
    np.testing.assert_allclose(timeshared.finish_times([10.0, 30.0], 10.0),
                               [2.0, 4.0])


@pytest.fixture(scope="module")
def simulation():
    import jax
    from jax.sharding import Mesh

    from repro.core.cloudsim import SimulationConfig, run_simulation
    cfg = SimulationConfig(n_vms=64, n_cloudlets=4096, broker="matchmaking",
                           seed=123)
    res = run_simulation(cfg, Mesh(np.array(jax.devices()[:1]), ("data",)))
    mips, mi = entities.simulation(123, 64, 4096, MIPS, MI)
    return res, mips, mi


def test_program_broker_equals_the_reference_and_bf16_does_not(simulation):
    res, mips, mi = simulation
    want, also = matchmaking.matchmaking(mi, mips, **RULE)
    assert matchmaking.mismatches(res.vm_assign, want, also).size == 0
    low, _ = matchmaking.matchmaking(mi, mips, **RULE,
                                     dtype=ml_dtypes.bfloat16, band=0.0)
    assert matchmaking.mismatches(low, want, also).size > 0


def test_program_finish_times_equal_the_reference_and_bf16_do_not(simulation):
    res, mips, mi = simulation
    ref = timeshared.finish_times_all(res.vm_assign, mi, mips)
    assert rel(res.finish_times, ref) < LIMIT
    worst = 0.0
    for v in range(0, 64, 8):
        rows = res.vm_assign == v
        step = timeshared.finish_times(mi[rows], mips[v])
        assert rel(res.finish_times[rows], step) < LIMIT
        low = timeshared.finish_times(mi[rows], mips[v], ml_dtypes.bfloat16)
        worst = max(worst, rel(low, step))
    assert worst > LIMIT


def test_program_word_count_equals_bincount_and_bf16_does_not():
    import jax
    import jax.numpy as jnp

    from repro.core.dispatch import ElasticDispatcher
    from repro.core.mapreduce import MapReduceEngine, word_count_job
    tokens = np.random.default_rng(0).integers(0, 64, (16, 2048))
    files = jnp.asarray(tokens, jnp.int32)
    eng = MapReduceEngine(dispatcher=ElasticDispatcher(
        devices=jax.devices()[:1], start_members=1))
    got = np.asarray(eng.run(word_count_job(64), files, chunk=4))
    want = wordcount.counts(tokens, 64)
    assert np.array_equal(got, want)
    assert want.max() > 256          # where bfloat16 stops counting by ones
    assert not np.array_equal(wordcount.counts_bf16(files, 64), want)
