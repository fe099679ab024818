#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's and the
lower-precision control's, seed by seed, in one process.

    python bench/control.py --workload <name> --seeds 1 2 3 ...

For each seed it builds the cell's traffic as a run would, serves as many
requests through the timed path as a run's check samples, and prints one
JSON line with the compared numbers twice: ``program`` (the timed path's
outputs against the float64 references) and ``control`` (the references
computed in bfloat16 standing in for the program).  The limits in
a configuration's file lie between the largest ``program`` reading and the
smallest ``control`` reading.  Like ``run.py`` it needs the chips the cell
asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def readings(root, spec, workload, seeds, require_chip=True,
             bench_dir=BENCH_DIR):
    """Yield ``{"seed", "program", "control"}`` for each seed."""
    import ml_dtypes
    import numpy as np

    import generator
    import harness

    cell, config, traffic = harness.cell_files(root, spec, workload,
                                               bench_dir)
    devices = harness._devices(int(cell["chips"]), require_chip)
    entry = generator.entry_class(traffic["entry"], bench_dir)
    n = max(int(traffic["check"]["requests"]), 1)
    for seed in seeds:
        gen = entry(config, traffic, seed, devices, collect_stats=False)
        gen.setup()
        for i in range(n):
            gen.request(i)
        gen.release()
        rng = lambda: np.random.default_rng(generator.derive(seed, 8))
        program = gen.check(rng())
        control = gen.check(rng(), dtype=ml_dtypes.bfloat16)
        yield {"seed": seed, "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness
    harness.use_compile_cache()
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        for line in readings(ROOT, spec, args.workload, args.seeds):
            print(json.dumps(line), flush=True)
    except harness.NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
