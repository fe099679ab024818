#!/usr/bin/env python3
"""Chip smoke test: drive the simulation middleware's main path once on a TPU.

    python chip_smoke.py            # one chip: DES core, scenario sweep,
                                    # MapReduce word count, tenant front end
    python chip_smoke.py --chips 4  # four chips: scan_dist at M=4 vs M=1,
                                    # and a 1->2->4 grid stream vs M=1

Every phase runs through the entry points a user calls, at the sizes users
run, and checks its output against the repository's own guarantees and a
plain reference.  Each phase prints one JSON line: its sizes, the members
used, the kernel path, the wall seconds of each first call (compile
included), of a warm call and their difference (``compile_s``), and its
check.  The last line of standard output is
``{"ok": true, "device": {...}}``; it is printed only when every phase ran
on a TPU and every check held.  Anything else exits non-zero with no ``ok``
line.  Everything runs in this one process, which holds the chip(s).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

C_DES, V_DES = 1 << 20, 1024          # largest core size in BENCH_dist.json
SWEEP_B, SWEEP_C, SWEEP_V = 512, 2000, 128   # BENCH_batch.json's largest
VOCAB = 65536
N_FILES, FILE_LEN = 1024, 65536       # 2**26 int32 tokens: 256 MiB


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=float), flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def first_and_warm(fn):
    """Call ``fn`` twice: the first call compiles, the second runs what was
    compiled.  Returns both outputs and the seconds of each; their
    difference is the compile time."""
    first, t_first = timed(fn)
    warm, t_warm = timed(fn)
    return first, warm, {"first_s": t_first, "warm_s": t_warm,
                         "compile_s": t_first - t_warm}


def same_bytes(a, b) -> bool:
    import jax
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.asarray(x).dtype == np.asarray(y).dtype
        and np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(la, lb))


# ------------------------------------------------------- plain reference

def timeshared_finish_ref(mi: np.ndarray, mips: float) -> np.ndarray:
    """Finish times of the cloudlets of ONE time-shared VM, in float64, by
    stepping from completion to completion: every active cloudlet runs at
    ``mips / n_active`` until the shortest remaining one ends."""
    rem = mi.astype(np.float64).copy()
    fin = np.zeros_like(rem)
    active = rem > 0.0
    tol = 1e-9 * float(rem.max(initial=1.0))
    now = 0.0
    while active.any():
        rate = float(mips) / int(active.sum())
        dt = rem[active].min() / rate
        now += dt
        rem[active] -= rate * dt
        done = active & (rem <= tol)
        fin[done] = now
        active &= ~done
    return fin


# ------------------------------------------------------------- phases

def phase_des_core(devs) -> dict:
    """1M cloudlets x 1024 VMs, matchmaking broker, through run_simulation:
    the lax path, then the v2 kernel path."""
    from jax.sharding import Mesh

    from repro.core import compat
    from repro.core.cloudsim import (SimulationConfig, create_entities,
                                     run_simulation)
    from repro.core.grid import DataGrid
    from repro.roofline import autotune

    mesh = Mesh(np.array(devs[:1]), ("data",))
    base = SimulationConfig(n_vms=V_DES, n_cloudlets=C_DES,
                            broker="matchmaking", core="scan")
    runs, lines = {}, []
    for use_kernel in (False, True):
        cfg = dataclasses.replace(base, use_kernel=use_kernel)
        first, warm, secs = first_and_warm(lambda: run_simulation(cfg, mesh))
        check(same_bytes(first.finish_times, warm.finish_times),
              "repeated run_simulation differs")
        runs[use_kernel] = warm
        lines.append(dict(use_kernel=use_kernel,
                          kernel_path=compat.kernel_path(use_kernel),
                          kernel_chunk=(autotune.tuning_report(C_DES).chunk
                                        if use_kernel else None),
                          **secs, warm_stages_s=warm.timings))
    path = compat.kernel_path(True)
    check(path == "compiled", f"kernel path is {path!r}, not 'compiled'")
    lax_f, ker_f = runs[False].finish_times, runs[True].finish_times
    diff = np.nonzero(lax_f.view(np.int32) != ker_f.view(np.int32))[0]
    check(diff.size == 0,
          f"kernel finish times differ from the lax path's at {diff.size} "
          f"rows (first {diff[:5].tolist()}: "
          f"{lax_f[diff[:5]].tolist()} vs {ker_f[diff[:5]].tolist()})")
    check(runs[False].makespan == runs[True].makespan, "makespans differ")

    # float64 reference, VM by VM, on a projection of 16 VMs
    ents = create_entities(base, DataGrid(mesh))
    mi = np.asarray(ents["cloudlet_mi"])
    mips = np.asarray(ents["vm_mips"])
    assign = runs[True].vm_assign
    worst, n_ref = 0.0, 0
    for v in np.linspace(0, V_DES - 1, 16).astype(int):
        rows = np.nonzero(assign == v)[0]
        if rows.size == 0:
            continue
        ref = timeshared_finish_ref(mi[rows], mips[v])
        got = ker_f[rows].astype(np.float64)
        worst = max(worst, float(np.max(np.abs(got - ref) / ref)))
        n_ref += rows.size
    check(n_ref > 0, "the reference projection holds no cloudlets")
    check(worst <= 1e-5, f"max relative error {worst} > 1e-5 vs float64")
    check(float(runs[True].makespan) == float(ker_f.max()),
          "makespan is not the largest finish time")
    return dict(cloudlets=C_DES, vms=V_DES, broker="matchmaking", members=1,
                kernel_path=path, runs=lines, kernel_bitwise_equal_lax=True,
                ref_cloudlets=n_ref, ref_max_rel_err=worst)


def _sweep_grid():
    from repro.core.des_scan import make_scenario_grid
    return make_scenario_grid(seeds=range(SWEEP_B // 16),
                              mi_scales=[0.7, 1.3],
                              brokers=["round_robin", "matchmaking"],
                              vm_counts=[SWEEP_V // 2, SWEEP_V],
                              mips_dists=["uniform", "bimodal"])


def phase_sweep(devs) -> dict:
    """512 variants x 2000 cloudlets streamed through the dispatcher in 4
    chunks, pipelined with donated chunk buffers, vs the synchronous
    stream."""
    from repro.core.cloudsim import SimulationConfig
    from repro.core.des_scan import run_scenario_grid
    from repro.core.dispatch import ElasticDispatcher

    cfg = SimulationConfig(n_vms=SWEEP_V, n_cloudlets=SWEEP_C)
    grid = _sweep_grid()
    out, lines = {}, []
    for ahead in (2, 0):
        d = ElasticDispatcher(devices=devs[:1], start_members=1)
        first, warm, secs = first_and_warm(lambda: run_scenario_grid(
            cfg, grid, dispatcher=d, chunk=SWEEP_B // 4,
            dispatch_ahead=ahead))
        check(same_bytes(first.finish_times, warm.finish_times),
              "repeated sweep differs")
        out[ahead] = warm
        rep = warm.dispatch
        check(rep["n_chunks"] >= 4, f"{rep['n_chunks']} chunks < 4")
        lines.append(dict(dispatch_ahead=ahead, chunks=rep["n_chunks"],
                          max_in_flight=rep["max_in_flight"], **secs))
    a, s = out[2], out[0]
    check(same_bytes((a.vm_assign, a.finish_times, a.makespans),
                     (s.vm_assign, s.finish_times, s.makespans)),
          "pipelined sweep is not bitwise equal to dispatch_ahead=0")
    check(out[2].dispatch["max_in_flight"] >= 2, "the stream never pipelined")
    return dict(variants=len(grid["seeds"]), cloudlets=SWEEP_C, vms=SWEEP_V,
                members=1, kernel_path=a.dispatch["kernel_path"], runs=lines,
                pipelined_bitwise_equal_sync=True)


def phase_mapreduce(devs) -> dict:
    """Word count over a Zipf(1.3) corpus of 2**26 tokens held on the
    device, streamed in 8 chunks, with and without the histogram kernel."""
    import jax
    import jax.numpy as jnp

    from repro.core import compat
    from repro.core.dispatch import ElasticDispatcher
    from repro.core.mapreduce import (MapReduceEngine, make_corpus,
                                      word_count_job)

    corpus, t_make = timed(lambda: make_corpus(N_FILES, FILE_LEN, VOCAB,
                                               seed=11))
    expect = np.bincount(corpus.reshape(-1), minlength=VOCAB)
    files = jax.device_put(jnp.asarray(corpus), devs[0])
    eng = MapReduceEngine(dispatcher=ElasticDispatcher(devices=devs[:1],
                                                       start_members=1))
    lines = []
    for use_kernel in (False, True):
        job = word_count_job(VOCAB, use_kernel=use_kernel)
        first, warm, secs = first_and_warm(
            lambda: np.asarray(eng.run(job, files, chunk=N_FILES // 8)))
        rep = eng.last_report
        check(np.array_equal(first, expect) and np.array_equal(warm, expect),
              f"word count (use_kernel={use_kernel}) != np.bincount")
        check(rep.staged_device == rep.n_chunks,
              "the corpus was not chunked on the device")
        lines.append(dict(use_kernel=use_kernel,
                          kernel_path=compat.kernel_path(use_kernel),
                          chunks=rep.n_chunks, **secs))
    path = compat.kernel_path(True)
    check(path == "compiled", f"histogram kernel path is {path!r}")
    return dict(tokens=N_FILES * FILE_LEN, vocab=VOCAB, files=N_FILES,
                zipf_a=1.3, members=1, kernel_path=path,
                corpus_make_s=t_make, runs=lines, equals_bincount=True)


def phase_frontend(devs) -> dict:
    """16 tenants mixing scenario-grid and word-count requests on one
    dispatcher; a chunk of tenant t4's first (scenario-grid) request is
    NaN-poisoned once and its retry policy recovers it.  Every result must equal the same request served
    alone."""
    import jax
    import jax.numpy as jnp

    from repro.core.cloudsim import SimulationConfig
    from repro.core.des_scan import make_scenario_grid
    from repro.core.dispatch import ElasticDispatcher
    from repro.core.faults import FaultInjector, FaultSpec, RetryPolicy
    from repro.core.mapreduce import make_corpus, word_count_job
    from repro.serve.frontend import (TenantFrontEnd, grid_request,
                                      mapreduce_request)

    cfg = SimulationConfig(n_vms=64, n_cloudlets=1000, broker="matchmaking")
    wc = word_count_job(4096)                # one job object: one executable
    poisoned = "t4"                          # even tenants start with a grid
    reqs = []
    for i in range(16):
        name = f"t{i}"
        for k in range(2):
            seed = 100 * i + k
            if (i + k) % 2 == 0:
                grid = make_scenario_grid(seeds=range(seed, seed + 8),
                                          mi_scales=[0.8, 1.2],
                                          brokers=["round_robin",
                                                   "matchmaking"])
                reqs.append(grid_request(name, cfg, grid, chunk=8))
            else:
                files = jax.device_put(jnp.asarray(
                    make_corpus(64, 4096, 4096, seed=seed)), devs[0])
                reqs.append(mapreduce_request(name, wc, files, chunk=16))

    alone = ElasticDispatcher(devices=devs[:1], start_members=1)
    ref, t_alone = timed(lambda: [alone.submit(r.job, r.items,
                                               chunk=r.chunk)[0]
                                  for r in reqs])

    inj = FaultInjector([FaultSpec(kind="nan_poison", chunk=1, times=1,
                                   tenant=poisoned)])
    fe = TenantFrontEnd(ElasticDispatcher(devices=devs[:1], start_members=1),
                        backlog_max=64, fault_injector=inj)
    for i in range(16):
        fe.register_tenant(f"t{i}", weight=1.0 + i % 3,
                           retry_policy=RetryPolicy(max_attempts=3,
                                                    check_finite=True))
    decisions = [fe.submit(r) for r in reqs]
    outs, t_serve = timed(fe.run)
    check(all(d.admitted for d in decisions), "a request was refused")
    check(len(outs) == len(reqs) and all(o["ok"] for o in outs),
          "not every admitted request completed")
    fired = [r for r in inj.fired if r["kind"] == "nan_poison"]
    check(len(fired) == 1 and fired[0].get("tenant") == poisoned,
          f"nan_poison did not fire once for {poisoned}: {inj.fired}")
    st = fe.tenants[poisoned]
    retries = sum(rep.retries for rep in st.reports.values())
    check(st.completed == 2 and not st.failures and retries >= 1,
          f"{poisoned} did not recover through its retry")
    for r, want in zip(reqs, ref):
        got = fe.tenants[r.tenant].results[r.req_id]
        check(same_bytes(got, want),
              f"{r.tenant} request {r.req_id} differs from its run alone")
    return dict(tenants=16, requests=len(reqs), members=1,
                grid_cloudlets=cfg.n_cloudlets, grid_vms=cfg.n_vms,
                poisoned_tenant=poisoned, poisoned_retries=retries,
                alone_s=t_alone, serve_s=t_serve,
                bystanders_bitwise_equal=True)


def phase_four_chips(devs) -> dict:
    """scan_dist (exchange) at M=4 vs M=1 on 1M x 1024, and a scenario-grid
    stream that scales 1 -> 2 -> 4 mid-stream vs the stream at M=1."""
    from jax.sharding import Mesh

    from repro.core.cloudsim import SimulationConfig, run_simulation
    from repro.core.des_scan import run_scenario_grid
    from repro.core.dispatch import ElasticDispatcher
    from repro.core.grid import DataGrid
    from repro.core.health import HealthConfig

    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    check(len({d.id for d in devs[:4]}) == 4, "the 4 devices are not distinct")
    cfg = SimulationConfig(n_vms=V_DES, n_cloudlets=C_DES,
                           broker="matchmaking", core="scan_dist",
                           dist_method="exchange")
    res, lines = {}, []
    for m in (4, 1):
        mesh = Mesh(np.array(devs[:m]), ("data",))
        grid = DataGrid(mesh)
        first, t_first = timed(lambda: run_simulation(cfg, mesh, grid=grid))
        placed = grid.get("cloudlet_mi").sharding.device_set
        check(len(placed) == m, f"cloudlets placed on {len(placed)} of {m}")
        warm, t_warm = timed(lambda: run_simulation(cfg, mesh))
        check(same_bytes(first.finish_times, warm.finish_times),
              "repeated scan_dist differs")
        res[m] = warm
        lines.append(dict(members=m, devices=sorted(d.id for d in placed),
                          first_s=t_first, warm_s=t_warm,
                          compile_s=t_first - t_warm,
                          warm_stages_s=warm.timings))
    check(same_bytes(res[4].finish_times, res[1].finish_times),
          "scan_dist M=4 is not bitwise equal to M=1")
    check(res[4].makespan == res[1].makespan, "scan_dist makespans differ")

    scfg = SimulationConfig(n_vms=SWEEP_V, n_cloudlets=SWEEP_C)
    sgrid = _sweep_grid()
    alone = ElasticDispatcher(devices=devs[:1], start_members=1)
    ref, t_ref = timed(lambda: run_scenario_grid(
        scfg, sgrid, dispatcher=alone, chunk=SWEEP_B // 8))
    hc = HealthConfig(target_step_time=1.0, max_threshold=0.8,
                      min_threshold=0.2, time_between_scaling=1, window=1,
                      max_instances=4)
    d = ElasticDispatcher(devices=devs[:4], health_cfg=hc, start_members=1)
    loads = iter([2.0, 2.0])

    def on_chunk(disp, ci, n):
        load = next(loads, None)
        if load is not None:
            disp.observe_load(load)

    got, t_got = timed(lambda: run_scenario_grid(
        scfg, sgrid, dispatcher=d, chunk=SWEEP_B // 8, on_chunk=on_chunk))
    members = got.dispatch["members_per_chunk"]
    check(members[:3] == [1, 2, 4] and members[-1] == 4,
          f"stream did not scale 1 -> 2 -> 4: {members}")
    used = {dev.id for dev in d.executor.device_list}
    check(len(used) == 4, f"the 4-member mesh spans {len(used)} devices")
    check(same_bytes((got.vm_assign, got.finish_times, got.makespans),
                     (ref.vm_assign, ref.finish_times, ref.makespans)),
          "the 1->2->4 stream is not bitwise equal to M=1")
    return dict(cloudlets=C_DES, vms=V_DES, broker="matchmaking",
                method="exchange", scan_dist=lines,
                scan_dist_bitwise_equal=True,
                stream_variants=len(sgrid["seeds"]),
                stream_cloudlets=SWEEP_C, stream_members=members,
                stream_devices=sorted(used), stream_s=t_got,
                stream_m1_s=t_ref, stream_bitwise_equal_m1=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    emit("start", device_kind=devs[0].device_kind, count=len(devs),
         compile_cache=cache)
    t0 = time.perf_counter()
    if args.chips == 4:
        phases = (phase_four_chips,)
    else:
        phases = (phase_des_core, phase_sweep, phase_mapreduce,
                  phase_frontend)
    failed = []
    for phase in phases:
        name = phase.__name__[len("phase_"):]
        t = time.perf_counter()
        try:
            fields, verdict = phase(devs), "passed"
        except Exception as e:              # report, run the next phase
            failed.append(name)
            print(f"chip_smoke: phase {name} failed:", file=sys.stderr)
            traceback.print_exc()
            fields, verdict = {"error": f"{type(e).__name__}: {e}"}, "failed"
        emit(name, **fields, wall_s=time.perf_counter() - t, check=verdict)
    if failed:
        print(f"chip_smoke: {len(failed)} phase(s) failed: {failed} "
              f"({time.perf_counter() - t0:.1f} s)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
