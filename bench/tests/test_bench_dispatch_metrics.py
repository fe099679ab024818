"""The readers of the dispatcher's spans and counters, on a hand-built
context and trace, and in a traced run of the word count on the CPU."""
import io
import json
import time

import pytest

import harness
import trace_reduce as tr
from benchtools import ROOT
from trace_reduce import Event

NEW = ("dispatch_host_ms.wordcount", "map_device_ms.wordcount",
       "stream_compiles.wordcount")


def hand_trace():
    """One request in a window [0, 200) ns: the job's module runs two chunks
    over [0, 80) and [100, 180), a chunk cut over [90, 95).  Device gaps
    [80, 90) and [95, 100) fall in the dispatcher's stage and launch spans,
    nested in its stream and in the harness's request; the gap [180, 200)
    after the stream ends belongs to the request alone."""
    op = lambda a, b: Event(f"%fusion = s32[8]{{0}} fusion(s32[8] %p)", a,
                            b - a)
    mod = "jit_dispatch_mapreduce_word_count"
    return tr.Trace(
        ops={"/device:TPU:0": [op(0, 80), op(90, 95), op(100, 180)]},
        async_ops={},
        modules={"/device:TPU:0": [Event(f"{mod}(1)", 0, 80),
                                   Event("jit__slice_chunk(2)", 90, 5),
                                   Event(f"{mod}(1)", 100, 80)]},
        host=[Event("bench.window", 0, 200), Event("bench.request", 0, 190),
              Event("dispatch.stream", 0, 185),
              Event("dispatch.retire", 50, 31),
              Event("dispatch.stage", 81, 15),
              Event("dispatch.launch", 96, 5)])


def record(spans=True, compiles=0, loads=0):
    dispatch = {"n_chunks": 2, "stats": {}}
    if spans:
        dispatch.update(jax_compiles=compiles, jax_cache_loads=loads)
        dispatch["stats"]["spans"] = {
            "dispatch.stage": {"n": 2.0, "total_s": 0.002, "self_s": 0.002},
            "dispatch.launch": {"n": 2.0, "total_s": 0.004, "self_s": 0.004},
            "dispatch.stream": {"n": 1.0, "total_s": 0.5, "self_s": 0.001}}
    return {"work": {"tokens": 100}, "dispatch": dispatch}


def context(records, trace=True):
    return harness.Context(
        cell={}, config={}, traffic={}, setup_s=1.0, window_start=0.0,
        records=records, trace=tr.reduce(hand_trace()) if trace else None,
        device_kind="TPU v5 lite")


def read(name, ctx):
    return harness.reader(name).read(ctx)


def test_gaps_go_to_the_innermost_dispatcher_span():
    r = tr.reduce(hand_trace())
    assert r.gap_s["dispatch.stage"] == pytest.approx(10e-9)
    assert r.gap_s["dispatch.launch"] == pytest.approx(5e-9)
    assert r.gap_s["bench.request"] == pytest.approx(20e-9)
    assert "dispatch.stream" not in r.gap_s


def test_readers_on_a_hand_built_context():
    ctx = context([record(), record(compiles=2, loads=1)])
    assert read("dispatch_host_ms.wordcount", ctx) == pytest.approx(3.0)
    # 160 ns of the job's module over 4 chunks
    assert read("map_device_ms.wordcount", ctx) == pytest.approx(40e-6)
    assert read("stream_compiles.wordcount", ctx) == pytest.approx(1.5)


def test_a_program_without_spans_or_counters_reports_none_of_them():
    ctx = context([record(spans=False)])
    for name in ("dispatch_host_ms.wordcount", "stream_compiles.wordcount"):
        assert read(name, ctx) is None, name
    ctx.trace.module_s = {"jit_call": 1.0}       # executables without names
    assert read("map_device_ms.wordcount", ctx) is None


def test_without_a_trace_the_device_readers_report_nothing():
    ctx = context([record()], trace=False)
    assert read("map_device_ms.wordcount", ctx) is None
    assert read("dispatch_host_ms.wordcount", ctx) == pytest.approx(3.0)


def test_a_traced_word_count_on_the_cpu_reports_the_program_metrics(tiny):
    d, s = tiny
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(ROOT, s, "wordcount_large", 2 ** 31 + 13, 0.3, True,
                     time.perf_counter(), require_chip=False, bench_dir=d,
                     out=out, err=err)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    metrics = line["metrics"]
    assert metrics["dispatch_host_ms.wordcount"]["value"] > 0
    assert metrics["stream_compiles.wordcount"]["value"] == 0
    assert metrics["chunk_wait_ms.wordcount"]["value"] >= 0
    # the CPU has no device plane: nothing is read from the device trace
    for name in ("map_device_ms.wordcount", "wordcount_roofline",
                 "idle_share.tokens"):
        assert name not in metrics
    assert set(NEW) <= {m["name"] for m in
                        harness.metrics_of(s, "wordcount_large", True)}
