"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV.  Runs on 8 emulated host devices
(the thesis's research-lab-cluster analogue); set BEFORE jax import.

``--check`` FAILS (exit 1) if any ``scan_s`` entry regressed by more than
20% against the committed BENCH files — the committed files are left
untouched.  A suspect module is RE-MEASURED best-of-N (N ≥ 3, via
``BENCH_CHECK_BEST_OF``) before a regression is declared, because single-
shot timings on a shared-CPU box are noisy; every surviving problem names
the BENCH file and entry that tripped.

``--smoke`` runs EVERY benchmark module at toy sizes on 2 emulated devices
without writing any BENCH file — the tier-1 suite invokes it so benchmark
scripts can't silently bit-rot.
"""
import os
import sys

SMOKE = "--smoke" in sys.argv
if SMOKE and "--check" in sys.argv:
    # toy-size labels never join against the committed full-size entries, so
    # the regression gate would pass vacuously with zero comparisons
    sys.exit("--smoke and --check are mutually exclusive: smoke sizes can't "
             "be compared against the committed BENCH files")
if SMOKE:
    # toy sizes everywhere: modules consult benchmarks.common.smoke()
    os.environ["BENCH_SMOKE"] = "1"
    os.environ.setdefault("BENCH_CORE_WAVE_BUDGET_S", "0")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=2")
elif "--one-device" not in sys.argv:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
if "--check" in sys.argv:
    # regression checks only compare scan_s: skip the slow wave-loop replays
    os.environ.setdefault("BENCH_CORE_WAVE_BUDGET_S", "0")

import json
import traceback

# make `python benchmarks/run.py` work from anywhere (repo root + src)
_root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, _root)
sys.path.insert(0, os.path.join(_root, "src"))

REGRESSION_TOLERANCE = 0.20
BEST_OF_N = max(3, int(os.environ.get("BENCH_CHECK_BEST_OF", "3")))
# entry fields that identify a scan_s measurement across runs
_ID_KEYS = ("chunk", "core", "n_cloudlets", "n_members", "n_scenarios",
            "n_vms")


def _scan_entries(obj, out):
    """Collect every ``scan_s`` in a payload, labelled by its identifying
    sibling fields — the committed-vs-fresh join key for ``--check``."""
    if isinstance(obj, dict):
        if "scan_s" in obj:
            label = tuple((k, obj[k]) for k in _ID_KEYS if k in obj)
            out[label] = float(obj["scan_s"])
        for v in obj.values():
            _scan_entries(v, out)
    elif isinstance(obj, list):
        for v in obj:
            _scan_entries(v, out)
    return out


def _compare(committed, fresh, path):
    """Problems for every committed scan_s the fresh (best-of) run exceeds."""
    problems = []
    for label, old in sorted(committed.items()):
        new = fresh.get(label)
        if new is None:
            continue                     # shrunk sweep: nothing to compare
        if new > old * (1.0 + REGRESSION_TOLERANCE):
            name = ",".join(f"{k}={v}" for k, v in label) or "scan"
            problems.append(f"{os.path.basename(path)}[{name}]: scan_s "
                            f"{old:.4f}s -> {new:.4f}s "
                            f"(+{(new / old - 1) * 100:.0f}%)")
    return problems


def _check_payload(mod, payload, path):
    """Compare fresh scan_s timings against the committed BENCH file,
    re-measuring best-of-N before declaring any regression real."""
    if not os.path.exists(path):
        return [f"{mod.__name__}: no committed {os.path.basename(path)} "
                f"to check against"]
    with open(path) as f:
        committed = _scan_entries(json.load(f), {})
    best = _scan_entries(payload, {})
    problems = _compare(committed, best, path)
    attempts = 1
    while problems and attempts < BEST_OF_N:
        # noisy shared-CPU timing: re-run the module and keep the per-entry
        # minimum before believing a regression
        attempts += 1
        print(f"# re-measuring {mod.__name__} "
              f"(attempt {attempts}/{BEST_OF_N}): "
              f"{len(problems)} suspect entr{'y' if len(problems) == 1 else 'ies'}",
              flush=True)
        fresh = _scan_entries(mod.main(), {})
        for label, v in fresh.items():
            best[label] = min(best.get(label, v), v)
        problems = _compare(committed, best, path)
    return [p + f" [best of {attempts}]" for p in problems]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (batch_grid, checkpoint_resume, core_scaling,
                            dist_scaling, fault_recovery, fig_5_1_scaling,
                            fig_5_4_matchmaking, fig_5_9_mapreduce,
                            kernel_tuning, queue_stats, serve_brokers,
                            serve_load, speedup_model, table_5_1,
                            table_5_2_elastic)
    check = "--check" in sys.argv
    mods = (table_5_1, core_scaling, batch_grid, dist_scaling,
            fig_5_1_scaling, fig_5_4_matchmaking, fig_5_9_mapreduce,
            table_5_2_elastic, speedup_model, serve_brokers, fault_recovery,
            queue_stats, checkpoint_resume, kernel_tuning, serve_load)
    if check:
        # only modules whose COMMITTED artifact holds scan_s entries can be
        # compared — skip the rest (e.g. batch_grid's throughput-only JSON)
        # instead of re-running their sweeps for nothing
        def checkable(m):
            path = os.path.join(_root, getattr(m, "BENCH_JSON", "") or "")
            if not getattr(m, "BENCH_JSON", None):
                return False
            if not os.path.exists(path):
                return True          # surfaces the "no committed file" error
            with open(path) as f:
                return bool(_scan_entries(json.load(f), {}))

        mods = [m for m in mods if checkable(m)]
    print("name,us_per_call,derived")
    problems = []
    for mod in mods:
        try:
            payload = mod.main()
            # modules that declare a JSON artifact get it written here
            # (core_scaling -> BENCH_core.json, dist_scaling ->
            # BENCH_dist.json, ...), anchored at the repo root regardless of
            # the invoking CWD; in --check mode the files are compared (not
            # rewritten) and --smoke never writes at all
            if payload is not None and getattr(mod, "BENCH_JSON", None):
                path = os.path.join(_root, mod.BENCH_JSON)
                if check:
                    problems += _check_payload(mod, payload, path)
                elif not SMOKE:
                    with open(path, "w") as f:
                        json.dump(payload, f, indent=2)
                    print(f"# wrote {path}", flush=True)
        except Exception:
            print(f"{mod.__name__},FAILED,", flush=True)
            traceback.print_exc()
            sys.exit(1)
    if check:
        if problems:
            print(f"# REGRESSION: {len(problems)} scan_s timing(s) exceeded "
                  f"the {REGRESSION_TOLERANCE:.0%} budget after best-of-"
                  f"{BEST_OF_N} re-measurement", flush=True)
            for p in problems:
                print(f"#   {p}", flush=True)
            sys.exit(1)
        print("# check OK: no scan_s regression > "
              f"{REGRESSION_TOLERANCE:.0%} (best-of-{BEST_OF_N})", flush=True)
    if SMOKE:
        print("# smoke OK: every benchmark module ran at toy sizes "
              "(no BENCH files written)", flush=True)


if __name__ == "__main__":
    main()
