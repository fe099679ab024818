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


def load(path):
    with open(path) as f:
        return json.load(f)


def spec():
    return load(os.path.join(ROOT, "BENCHMARK.json"))


def tiny_bench(dest: str) -> dict:
    """Copy the benchmark's data, entry and metric files under ``dest``, cut
    to tiny sizes; return a spec whose configuration files point there."""
    for sub in ("configs", "traffic", "entries", "metrics"):
        shutil.copytree(os.path.join(BENCH_DIR, sub),
                        os.path.join(dest, sub))
    for rel, small in TINY.items():
        path = os.path.join(dest, rel)
        data = load(path)
        data.update(small)
        with open(path, "w") as f:
            json.dump(data, f)
    s = copy.deepcopy(spec())
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
