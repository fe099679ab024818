"""The trace reduction, on traces built by hand and on one recorded here."""
import pytest

import trace_reduce as tr
from trace_reduce import Event


def hand_trace():
    """One window [0, 100) ns.  Device 0 runs ops over [0, 20) and [30, 40)
    inside modules ``jit_a`` and ``jit_b``; device 1 runs [0, 50).  The
    host compiles over [20, 30) and runs Python over [40, 100)."""
    op = lambda name, a, b: Event(f"%{name} = f32[8]{{0}} fusion(f32[8] %p)",
                                  a, b - a)
    return tr.Trace(
        ops={"/device:TPU:0": [op("fusion.1", 0, 10), op("fusion.2", 5, 20),
                               op("sort.3", 30, 40)],
             "/device:TPU:1": [op("fusion.1", 0, 50)]},
        async_ops={"/device:TPU:1": [op("all-to-all-start.4", 10, 30)]},
        modules={"/device:TPU:0": [Event("jit_a(123)", 0, 25),
                                   Event("jit_b(7)", 28, 17)],
                 "/device:TPU:1": [Event("jit_a(123)", 0, 50)]},
        host=[Event("bench.window", 0, 100), Event("bench.request", 0, 100),
              Event("backend_compile", 20, 10),
              Event("PjitFunction(f)", 40, 60)])


def test_busy_union_idle_share_and_window():
    r = tr.reduce(hand_trace())
    assert r.n_devices == 2
    assert r.window_s == pytest.approx(100e-9)
    # device 0 busy 30 ns (overlapping ops counted once), device 1 50 ns
    assert r.busy_s == pytest.approx(40e-9)
    assert r.idle_share == pytest.approx(0.6)


def test_module_and_op_times_are_per_device_means():
    r = tr.reduce(hand_trace())
    assert r.module_s["jit_a"] == pytest.approx((25 + 50) / 2 * 1e-9)
    assert r.module_s["jit_b"] == pytest.approx(17 / 2 * 1e-9)
    assert r.op_s["jit_a/%fusion.1"] == pytest.approx((10 + 50) / 2 * 1e-9)
    assert r.op_s["jit_b/%sort.3"] == pytest.approx(10 / 2 * 1e-9)
    assert r.op_s["jit_a/%all-to-all-start.4"] == pytest.approx(20 / 2 * 1e-9)
    assert r.module_time(r"^jit_a$") == pytest.approx(37.5e-9)
    assert r.top_ops[0][0] == "jit_a/%fusion.1"


def test_gaps_go_to_the_host_event_that_covers_them():
    r = tr.reduce(hand_trace())
    # device 0: [20,30) compile, [40,100) python; device 1: [50,100) python
    assert r.gap_s["backend_compile"] == pytest.approx(10 / 2 * 1e-9)
    assert r.gap_s["PjitFunction(f)"] == pytest.approx((60 + 50) / 2 * 1e-9)
    assert "bench.request" not in r.gap_s
    assert r.idle_gaps[0][0] == "PjitFunction(f)"


def test_ops_outside_the_window_are_clipped():
    t = hand_trace()
    t.ops["/device:TPU:0"].append(Event("%late = f32[1] copy(f32[1] %x)",
                                        90, 50))
    r = tr.reduce(t)
    assert r.busy_s == pytest.approx((30 + 10 + 50) / 2 * 1e-9)


def test_a_window_span_is_required():
    t = hand_trace()
    t.host = [e for e in t.host if e.name != "bench.window"]
    with pytest.raises(ValueError):
        tr.reduce(t)


def test_a_recorded_trace_loads(tmp_path):
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        jax.jit(lambda x: x * 2)(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    r = tr.reduce(tr.load(str(tmp_path)))
    assert r.window_s > 0
    assert r.n_devices == 0                  # the CPU has no device plane
