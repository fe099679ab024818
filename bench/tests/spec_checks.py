"""The benchmark's contract as checks of a spec: each function takes a spec
(the dict of a ``BENCHMARK.json``) and asserts one part of the contract, so
that the committed file and a spec a later change would commit are held to
the same checks."""
import json
import os
import re

from benchtools import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def line_text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def top_level_and_size(s):
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(s, indent=1).encode()) <= 64 * 1024
    assert 1 <= len(s["paths"]) <= 16
    for p in s["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(s["command"]) <= 32
    assert all(line_text(w) and not w.startswith("/") and ".." not in w
               for w in s["command"])
    assert os.path.isfile(os.path.join(ROOT, s["command"][1]))
    assert any(s["command"][1].startswith(p + "/") for p in s["paths"])


def run_seconds_fit_a_full_check_of_24_cells(s):
    rs = s["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def entries_keys_and_names(s):
    for group, keys in KEYS.items():
        entries = s[group]
        names = [e["name"] for e in entries]
        assert len(names) == len(set(names))
        for e in entries:
            extra = set(e) - keys
            assert set(e) >= keys and extra <= ({"workloads"} if group in (
                "end_to_end", "per_layer") else set()), e
            assert NAME.match(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                                 "higher")
    metric_names = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def configs_and_cells(s):
    assert 1 <= len(s["configs"]) <= 24 and 1 <= len(s["workloads"]) <= 24
    files = [c["file"] for c in s["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in s["workloads"]}
    for c in s["configs"]:
        assert c["name"] in used
        assert line_text(c["source"]) and line_text(c["why"])
        assert len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in s["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(pairs) == len(set(pairs))
    fours = sum(w["chips"] == 4 for w in s["workloads"])
    assert fours <= max(1, len(s["workloads"]) // 2)
    for w in s["workloads"]:
        assert w["chips"] in (1, 4) and line_text(w["why"])
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert os.path.isfile(os.path.join(BENCH_DIR, "traffic",
                                           w["traffic"] + ".json"))


def metrics(s):
    cells = {w["name"] for w in s["workloads"]}
    e2e = {m["name"]: m for m in s["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line_text(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved)
    for m in s["end_to_end"] + s["per_layer"]:
        # a metric no cell reports cannot be listed: no empty list
        listed = m.get("workloads", sorted(cells))
        assert isinstance(listed, list) and listed, m["name"]
        assert len(listed) == len(set(listed)) and set(listed) <= cells
        assert os.path.isfile(os.path.join(BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    for c in cells:
        reports = [m["name"] for m in s["end_to_end"]
                   if c in m.get("workloads", cells)]
        assert "setup_s" in reports and len(reports) >= 2
        assert any(c in m.get("workloads", cells) for m in s["per_layer"])


CHECKS = (top_level_and_size, run_seconds_fit_a_full_check_of_24_cells,
          entries_keys_and_names, configs_and_cells, metrics)
