"""The lower-precision control at a size a test run holds: the program's
readings pass every limit, and the control's fail at least one in each
cell.  The same code, at the cells' own sizes on the chip, gave the
readings the limits were set from (PERF.md)."""
import pytest

import control
from benchtools import ROOT, load


@pytest.mark.parametrize("workload", ["des_1m", "wordcount_large"])
def test_program_passes_and_control_fails(tiny, workload):
    d, s = tiny
    cfg = {c["name"]: c for c in s["configs"]}[
        {w["name"]: w for w in s["workloads"]}[workload]["config"]]
    limits = load(cfg["file"])["limits"]
    for r in control.readings(ROOT, s, workload, [2 ** 31 + 17, 5],
                              require_chip=False, bench_dir=d):
        assert all(r["program"][k] <= limits[k] for k in r["program"]), r
        assert any(r["control"][k] > limits[k] for k in r["control"]), r
