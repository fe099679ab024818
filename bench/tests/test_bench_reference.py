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
    _, order, lo, hi = matchmaking.matchmaking(mi, mips, **RULE)
    assert matchmaking.mismatches(res.vm_assign, order, lo, hi).size == 0
    low = matchmaking.matchmaking(mi, mips, **RULE,
                                  dtype=ml_dtypes.bfloat16, band=0.0)[0]
    assert matchmaking.mismatches(low, order, lo, hi).size > 0


# a rule whose requirement is the cloudlet's length itself, exactly
UNIT_RULE = {"max_mi": 2000.0, "headroom": 1.0}


def accepted(mips, n_cloudlets, **band):
    """The expected VM of cloudlets 0..n-1 of 1000 MI each, and the set of
    VMs the reference accepts for each of them (by default in the band that
    the check applies)."""
    mi = np.full(n_cloudlets, 1000.0)
    want, order, lo, hi = matchmaking.matchmaking(mi, mips, **UNIT_RULE,
                                                  **band)
    sets = [set() for _ in range(n_cloudlets)]
    for v in range(len(mips)):
        bad = set(matchmaking.mismatches(np.full(n_cloudlets, v), order,
                                         lo, hi).tolist())
        for c in range(n_cloudlets):
            if c not in bad:
                sets[c].add(v)
    return want, sets


# sorted by MIPS: VMs 2, 6, 5, 3, 1, 4, 0; VMs 3 and 1 lie inside the band
# of the 1000-MIPS requirement, VM 5 just below it
TWO_IN_BAND = np.array([2000.0, 1000.0 * (1 + 5e-7), 500.0,
                        1000.0 * (1 - 5e-7), 1500.0, 1000.0 * (1 - 2e-6),
                        700.0])


def test_two_vms_inside_the_band_accept_the_pick_of_each_first_position():
    # the first adequate position may be 3, 4 or 5 (VM 3, 1 or 4); cloudlet
    # c binds to the VM at position f + c mod (7 - f)
    want, sets = accepted(TWO_IN_BAND, 3)
    assert want.tolist() == [1, 4, 0]
    assert sets == [{3, 1, 4}, {1, 4, 0}, {4, 0}]


def test_a_vm_just_outside_the_band_is_refused():
    # VM 5 lies 2e-6 below the requirement: first for no requirement inside
    # a band of 1e-6, first for one inside a band of 3e-6
    _, sets = accepted(TWO_IN_BAND, 1)
    assert 5 not in sets[0]
    _, wider = accepted(TWO_IN_BAND, 1, band=3e-6)
    assert 5 in wider[0]


def test_with_no_vm_in_the_band_only_the_expected_vm_is_accepted():
    mips = np.array([2000.0, 500.0, 1500.0, 1000.0 * (1 + 1e-5), 700.0])
    want, sets = accepted(mips, 4)
    assert want.tolist() == [3, 2, 0, 3]
    assert sets == [{int(w)} for w in want]


def test_the_float32_boundary_the_chip_met_is_accepted():
    # simulation seed 1595435016: the TPU's float32 requirement of cloudlet
    # 498425 equals VM 649's MIPS, one float32 step below the float64 one,
    # and its broker picked VM 330; the first adequate position may be 618,
    # 619 or 620 (VM 55, 330 or 443), and nothing else is accepted
    mips, mi = entities.simulation(1595435016, 1024, 2 ** 20, MIPS, MI)
    want, order, lo, hi = matchmaking.matchmaking(mi, mips, **RULE)
    c = 498425
    assert (want[c], lo[c], hi[c]) == (443, 618, 620)
    got = want.copy()
    for vm, fair in ((330, True), (55, True), (443, True), (649, False),
                     (331, False)):
        got[c] = vm
        assert (matchmaking.mismatches(got, order, lo, hi).size == 0) == fair


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
