"""Room for des_1m: the spec that adds the cell as data alone (one
configuration, one cell, and the six metrics that read a simulation, each
listing the cell, their files all in place) meets the whole contract, a
tiny run of it reports the simulation's metrics, the readers give what
they should on a hand-built context, and word count reports none of
them."""
import io
import json
import time

import pytest

import harness
import peaks
import spec_checks
import trace_reduce as tr
from benchtools import (DES_1M_CELL, DES_1M_CONFIG, DES_1M_END_TO_END,
                        DES_1M_METRICS, DES_1M_PER_LAYER, ROOT, spec,
                        tiny_bench, with_des_1m)
from trace_reduce import Event


def test_des_1m_enters_by_data_alone():
    base, added = spec(), with_des_1m(spec())
    assert added["configs"] == base["configs"] + [DES_1M_CONFIG]
    assert added["workloads"] == base["workloads"] + [DES_1M_CELL]
    assert added["end_to_end"] == base["end_to_end"] + DES_1M_END_TO_END
    assert added["per_layer"] == base["per_layer"] + DES_1M_PER_LAYER
    assert not {m["name"] for m in base["end_to_end"] + base["per_layer"]} \
        & set(DES_1M_METRICS)
    for k in ("command", "paths", "run_seconds"):
        assert added[k] == base[k]


@pytest.mark.parametrize("group", ["end_to_end", "per_layer"])
def test_a_metric_that_no_cell_reports_is_refused(group):
    s = with_des_1m(spec())
    [m for m in s[group] if m["name"] in DES_1M_METRICS][0]["workloads"] = []
    with pytest.raises(AssertionError):
        spec_checks.metrics(s)


@pytest.mark.parametrize("check", spec_checks.CHECKS,
                         ids=lambda c: c.__name__)
def test_the_spec_with_des_1m_meets_the_contract(check):
    check(with_des_1m(spec()))


@pytest.mark.parametrize("traced", [False, True])
def test_des_1m_finds_its_metric_readers(traced):
    s = with_des_1m(spec())
    wanted = harness.metrics_of(s, "des_1m", traced)
    names = {m["name"] for m in wanted}
    if traced:
        assert names == set(DES_1M_METRICS) - {"cloudlets_per_s"}
    else:
        assert names == {"cloudlets_per_s", "setup_s"}
    for m in wanted:
        mod = harness.reader(m["name"])
        assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"]
        if traced:
            assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]


def hand_context():
    """Two requests; the DES core's module runs 2 ms of a 10 ms window on
    one chip."""
    mod = "jit_simulate_completion_scan"
    trace = tr.Trace(
        ops={"/device:TPU:0": [Event("%sort = s32[8]{0} sort(s32[8] %p)",
                                     0, 2_000_000)]},
        async_ops={},
        modules={"/device:TPU:0": [Event(f"{mod}(1)", 0, 2_000_000)]},
        host=[Event("bench.window", 0, 10_000_000)])
    records = [{"work": {"cloudlets": 1024},
                "spans": {"schedule": 0.8, "core_sim": 0.06}},
               {"work": {"cloudlets": 1024},
                "spans": {"schedule": 0.9, "core_sim": 0.07}}]
    return harness.Context(
        cell={}, config={}, traffic={"n_cloudlets": 1024, "n_vms": 16},
        setup_s=1.0, window_start=0.0, records=records,
        trace=tr.reduce(trace), device_kind="TPU v5 lite",
        window_programs={"traces": 130, "compiles": 92, "cache_loads": 92})


def test_simulation_readers_on_a_hand_built_context():
    ctx = hand_context()
    read = lambda name: harness.reader(name).read(ctx)
    assert read("broker_ms") == pytest.approx(850.0)
    assert read("des_core_ms") == pytest.approx(65.0)
    assert read("jax_programs.cloudlets") == pytest.approx(111.0)
    assert read("idle_share.cloudlets") == pytest.approx(80.0)
    need = 2 * peaks.des_core_bytes(1024, 16)
    assert read("des_core_roofline") == pytest.approx(
        100.0 * need / 819e9 / 2e-3)


def test_without_a_counter_or_a_module_the_readers_report_nothing():
    ctx = hand_context()
    ctx.window_programs = {}
    assert harness.reader("jax_programs.cloudlets").read(ctx) is None
    ctx.trace.module_s = {"jit__unknown": 1.0}
    assert harness.reader("des_core_roofline").read(ctx) is None
    ctx.trace = None
    for name in ("des_core_roofline", "idle_share.cloudlets"):
        assert harness.reader(name).read(ctx) is None


def tiny_run(tmp_path, base, workload, traced):
    d = str(tmp_path / "bench")
    s = tiny_bench(d, base)
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(ROOT, s, workload, 2 ** 31 + 17, 0.2, traced,
                     time.perf_counter(), require_chip=False, bench_dir=d,
                     out=out, err=err)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    return line["metrics"]


@pytest.mark.parametrize("traced", [False, True])
def test_a_tiny_des_1m_reports_the_simulation_metrics(tmp_path, traced):
    metrics = tiny_run(tmp_path, with_des_1m(spec()), "des_1m", traced)
    if traced:
        # the CPU has no device plane: nothing is read from the device trace
        assert set(metrics) == {"broker_ms", "des_core_ms",
                                "jax_programs.cloudlets"}
        assert metrics["broker_ms"]["value"] > 0
        assert metrics["des_core_ms"]["value"] > 0
        assert metrics["jax_programs.cloudlets"]["value"] >= 0
    else:
        assert set(metrics) == {"cloudlets_per_s", "setup_s"}
        assert metrics["cloudlets_per_s"]["value"] > 0


@pytest.mark.parametrize("traced", [False, True])
def test_word_count_reports_none_of_the_simulation_metrics(tmp_path, traced):
    metrics = tiny_run(tmp_path, spec(), "wordcount_large", traced)
    assert not set(metrics) & set(DES_1M_METRICS)
    want = {m["name"] for m in harness.metrics_of(spec(), "wordcount_large",
                                                  traced)}
    if traced:     # on the CPU the device-trace readers report nothing
        want = {n for n in want if harness.reader(n).SOURCE != "device_trace"}
    assert set(metrics) == want
