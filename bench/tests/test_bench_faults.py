"""A run with the timed path broken underneath: ``correct`` comes out false,
once for each fault the cells can have.  The harness's look for a chip is
skipped; everything else runs as on the chip, at tiny sizes."""
import io
import json
import time

import pytest

import harness
from benchtools import ROOT


def run_cell(tiny, workload):
    d, s = tiny
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(ROOT, s, workload, 2 ** 31 + 11, 0.2, False,
                     time.perf_counter(), require_chip=False, bench_dir=d,
                     out=out, err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_runs_are_correct(tiny):
    for workload in ("des_1m", "wordcount_large"):
        assert run_cell(tiny, workload)["correct"] is True


def test_a_finish_time_altered_where_it_is_produced(tiny, monkeypatch):
    from repro.core import des_scan
    core = des_scan.simulate_completion_scan_jit

    def altered(*a, **k):
        finish, makespan = core(*a, **k)
        return finish.at[7].multiply(1.001), makespan

    monkeypatch.setattr(des_scan, "simulate_completion_scan_jit", altered)
    line = run_cell(tiny, "des_1m")
    assert line["correct"] is False
    assert line["checks"]["finish_rel_err"]["value"] > 1e-4


def test_a_broker_answer_altered_where_it_is_produced(tiny, monkeypatch):
    from repro.core import cloudsim
    broker = cloudsim.matchmaking_assign

    def altered(ids, mi, vm_mips, n_vms):
        out = broker(ids, mi, vm_mips, n_vms)
        return out.at[0].set((out[0] + 1) % n_vms)

    monkeypatch.setattr(cloudsim, "matchmaking_assign", altered)
    line = run_cell(tiny, "des_1m")
    assert line["correct"] is False
    assert line["checks"]["assign_mismatch"]["value"] > 0


@pytest.mark.parametrize("fault", ["half_the_tokens", "a_count_altered"])
def test_word_count_faults(tiny, monkeypatch, fault):
    from repro.core import mapreduce
    make = mapreduce.word_count_job

    def broken(vocab, use_kernel=False):
        job = make(vocab, use_kernel)
        if fault == "half_the_tokens":
            fn = lambda chunk: job.map_fn(chunk[: chunk.shape[0] // 2])
        else:
            fn = lambda chunk: job.map_fn(chunk).at[0].add(1)
        return mapreduce.MapReduceJob(map_fn=fn, n_keys=vocab,
                                      name="word_count")

    monkeypatch.setattr(mapreduce, "word_count_job", broken)
    line = run_cell(tiny, "wordcount_large")
    assert line["correct"] is False
    assert line["checks"]["count_mismatch"]["value"] > 0
