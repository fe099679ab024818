"""``BENCHMARK.json`` against the benchmark's contract: its keys, names,
limits and the files it names (the checks are ``spec_checks``')."""
import os

import spec_checks
from benchtools import ROOT, spec


def test_top_level_and_size():
    spec_checks.top_level_and_size(spec())
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells():
    spec_checks.run_seconds_fit_a_full_check_of_24_cells(spec())


def test_entries_keys_and_names():
    spec_checks.entries_keys_and_names(spec())


def test_configs_and_cells():
    spec_checks.configs_and_cells(spec())


def test_metrics():
    spec_checks.metrics(spec())
