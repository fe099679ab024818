"""The harness: no result off the chip, files found by name, and a new
configuration, traffic mix and metric picked up from files alone."""
import io
import json
import os
import subprocess
import sys
import time

import pytest

import harness
from benchtools import BENCH_DIR, ROOT, load, spec


def test_off_the_chip_the_command_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH_DIR, "run.py"),
                        "--workload", spec()["workloads"][0]["name"],
                        "--seed", str(2 ** 31 + 3),
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_an_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.cell_files(ROOT, spec(), "no_such_cell")


@pytest.mark.parametrize("workload", [w["name"] for w in spec()["workloads"]])
def test_each_cell_finds_its_files_and_metric_readers(workload):
    s = spec()
    cell, config, traffic = harness.cell_files(ROOT, s, workload)
    assert config["name"] == cell["config"]
    entry = __import__("generator").entry_class(traffic["entry"], BENCH_DIR)
    assert callable(entry.setup) and callable(entry.check)
    assert config["limits"]
    for traced in (False, True):
        wanted = harness.metrics_of(s, workload, traced)
        assert wanted
        for m in wanted:
            mod = harness.reader(m["name"])
            assert mod.UNIT == m["unit"] and mod.SOURCE == m["source"]
            if traced:
                assert mod.LAYER == m["layer"] and mod.MOVES == m["moves"]


ENTRY = """
import numpy as np
import generator


class Entry(generator.Base):
    def setup(self):
        self.sums = []

    def request(self, i):
        x = np.arange(self.traffic["n"]) * (i + 1)
        self.sums.append(int(x.sum()))
        return {"work": {"items": self.traffic["n"]}}

    def check(self, rng, dtype=np.float64):
        n = self.traffic["n"]
        want = [n * (n - 1) // 2 * (i + 1) for i in range(len(self.sums))]
        return {"sum_mismatch": sum(a != b for a, b in zip(self.sums, want))}
"""


def test_an_unknown_entry_is_refused(tiny):
    with pytest.raises(KeyError):
        __import__("generator").entry_class("no_such_entry", tiny[0])


def test_new_config_traffic_and_metric_come_from_files_alone(tiny):
    """A cell added by files and entries only: nothing the harness or the
    generator holds is edited."""
    d, s = tiny
    config = load(os.path.join(d, "configs", "cloudsim_timeshared.json"))
    config["name"] = "cloudsim_narrow"
    config["simulation"]["vm_mips_range"] = [900.0, 1100.0]
    with open(os.path.join(d, "configs", "cloudsim_narrow.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(d, "traffic", "one_sim_rr.json"), "w") as f:
        json.dump({"entry": "run_simulation", "n_cloudlets": 2048,
                   "n_vms": 32, "broker": "round_robin",
                   "check": {"requests": 1, "vms": 4}}, f)
    with open(os.path.join(d, "metrics", "requests_done.py"), "w") as f:
        f.write('LAYER, UNIT, SOURCE, MOVES = "end to end", "requests", '
                '"host_clock", None\n\n\ndef read(ctx):\n'
                '    return len(ctx.records)\n')
    s["configs"].append({"name": "cloudsim_narrow", "source": "test",
                         "file": os.path.join(d, "configs",
                                              "cloudsim_narrow.json"),
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "narrow_rr", "config": "cloudsim_narrow",
                           "traffic": "one_sim_rr", "chips": 1, "why": "t"})
    s["end_to_end"].append({"name": "requests_done", "unit": "requests",
                            "better": "higher", "bound": 0.01,
                            "source": "host_clock",
                            "workloads": ["narrow_rr"]})
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(ROOT, s, "narrow_rr", 2 ** 31 + 5, 0.2, False,
                     time.perf_counter(), require_chip=False, bench_dir=d,
                     out=out, err=err)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["requests_done"]["value"] >= 1
    assert set(line["metrics"]) == {"requests_done", "setup_s"}
    assert list(line)[-1] == "checks"
    assert err.getvalue().strip().splitlines()[-1].startswith("check ")


def test_a_new_entry_comes_from_its_file_alone(tiny):
    """A mix that drives an entry the benchmark did not have: the entry is
    one new file under ``entries/``, found by the name the mix gives."""
    d, s = tiny
    with open(os.path.join(d, "entries", "arange_sum.py"), "w") as f:
        f.write(ENTRY)
    with open(os.path.join(d, "traffic", "sums.json"), "w") as f:
        json.dump({"entry": "arange_sum", "n": 1000,
                   "check": {"requests": 0}}, f)
    with open(os.path.join(d, "configs", "plain.json"), "w") as f:
        json.dump({"name": "plain", "limits": {"sum_mismatch": 0}}, f)
    with open(os.path.join(d, "metrics", "items_per_s.py"), "w") as f:
        f.write('LAYER, UNIT, SOURCE, MOVES = "end to end", "items/s", '
                '"host_clock", None\n\n\ndef read(ctx):\n'
                '    return ctx.rate("items")\n')
    s["configs"].append({"name": "plain", "source": "test",
                         "file": os.path.join(d, "configs", "plain.json"),
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "sums", "config": "plain",
                           "traffic": "sums", "chips": 1, "why": "t"})
    s["end_to_end"].append({"name": "items_per_s", "unit": "items/s",
                            "better": "higher", "bound": 0.01,
                            "source": "host_clock", "workloads": ["sums"]})
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(ROOT, s, "sums", 2 ** 31 + 7, 0.2, False,
                     time.perf_counter(), require_chip=False, bench_dir=d,
                     out=out, err=err)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["items_per_s"]["value"] > 0
    assert line["checks"]["sum_mismatch"] == {"value": 0, "limit": 0}
