"""Helpers of the benchmark's CPU tests: the harness's modules on the path,
and the cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds,
written into a temporary directory of their own."""
import copy
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]

# tiny sizes of each configuration and traffic mix, by file name
TINY = {
    "configs/hibench_wordcount_large.json": {
        "corpus": {"vocab": 64, "bytes_per_word": 10.7, "n_files": 16,
                   "file_len": 8192, "chunks": 4}},
    "traffic/one_sim_2e20x1024.json": {
        "n_cloudlets": 4096, "n_vms": 64, "check": {"requests": 2, "vms": 8}},
}


# every cell whose files the benchmark holds: (configuration, traffic,
# chips).  A cell not (or not yet) in BENCHMARK.json keeps its tests.
CELLS = {"des_1m": ("cloudsim_timeshared", "one_sim_2e20x1024", 1),
         "wordcount_large": ("hibench_wordcount_large", "wordcount_corpus", 1)}


# what a change that brings des_1m into BENCHMARK.json adds: one
# configuration, one cell, the simulation's end-to-end rate with its bound
# (PERF.md §2) and the per-layer metrics that read a simulation, each listing
# the cell; every file they name is already under bench/
DES_1M_CONFIG = {
    "name": "cloudsim_timeshared",
    "source": "CloudSim time-shared cloudlet scheduler (arXiv 0903.2525) "
              "with Cloud2Sim's matchmaking broker (arXiv 1601.03980, ch. 4-5)",
    "file": "bench/configs/cloudsim_timeshared.json",
    "reduced": [],
    "why": "one large simulated cloud on one chip: the broker and the scan "
           "DES core on the lax path, the default users get"}
DES_1M_CELL = {
    "name": "des_1m", "config": "cloudsim_timeshared",
    "traffic": "one_sim_2e20x1024", "chips": 1,
    "why": "one simulation per request, back to back: 2**20 cloudlets over "
           "1,024 VMs, matchmaking broker then the DES core"}
DES_1M_END_TO_END = [
    {"name": "cloudlets_per_s", "unit": "cloudlets/s", "better": "higher",
     "bound": 0.25, "source": "host_clock", "workloads": ["des_1m"]}]
DES_1M_PER_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "cloudlets_per_s", "workloads": ["des_1m"]}
    for name, unit, better, source, layer in (
        ("broker_ms", "ms", "lower", "program_span", "broker"),
        ("des_core_ms", "ms", "lower", "program_span", "DES core"),
        ("des_core_roofline", "%", "higher", "device_trace",
         "DES core on the device"),
        ("idle_share.cloudlets", "%", "lower", "device_trace", "device"),
        ("jax_programs.cloudlets", "programs", "lower", "program_counter",
         "host: JAX tracing and compiling"))]
DES_1M_METRICS = tuple(m["name"] for m in DES_1M_END_TO_END
                       + DES_1M_PER_LAYER)


def with_des_1m(s: dict) -> dict:
    """A copy of the spec ``s`` with des_1m added as data alone."""
    s = copy.deepcopy(s)
    s["configs"].append(copy.deepcopy(DES_1M_CONFIG))
    s["workloads"].append(copy.deepcopy(DES_1M_CELL))
    s["end_to_end"].extend(copy.deepcopy(DES_1M_END_TO_END))
    s["per_layer"].extend(copy.deepcopy(DES_1M_PER_LAYER))
    return s


def load(path):
    with open(path) as f:
        return json.load(f)


def spec():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def tiny_bench(dest: str, base=None) -> dict:
    """Copy the benchmark's data, entry and metric files under ``dest``, cut
    to tiny sizes; return a spec (``base``, by default ``BENCHMARK.json``)
    whose configuration files point there."""
    for sub in ("configs", "traffic", "entries", "metrics"):
        shutil.copytree(os.path.join(BENCH_DIR, sub),
                        os.path.join(dest, sub))
    for rel, small in TINY.items():
        path = os.path.join(dest, rel)
        data = load(path)
        data.update(small)
        with open(path, "w") as f:
            json.dump(data, f)
    s = copy.deepcopy(spec() if base is None else base)
    have = {w["name"] for w in s["workloads"]}
    configs = {c["name"] for c in s["configs"]}
    for name, (config, traffic, chips) in CELLS.items():
        if name not in have:
            s["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "a cell of the tests"})
        if config not in configs:
            configs.add(config)
            s["configs"].append({"name": config, "source": "tests",
                                 "file": f"bench/configs/{config}.json",
                                 "reduced": [], "why": "tests"})
    for c in s["configs"]:
        c["file"] = os.path.join(dest, "configs",
                                 os.path.basename(c["file"]))
    return s
