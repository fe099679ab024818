#!/usr/bin/env python3
"""Run one benchmark cell once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, draws its inputs from ``--seed``,
warms every shape it uses (set-up), serves requests back to back for
``--seconds``, checks the sampled outputs against the plain references and
prints one JSON result line last.  ``--trace 1`` profiles the window and
reports the cell's per-layer metrics instead of its end-to-end ones.  Off a
TPU, or with fewer chips than the cell asks for, it exits 2 and prints no
result.  JAX's persistent compilation cache lives in ``bench/.jax_cache``
inside the checkout, so only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    sys.path[:0] = [BENCH_DIR, os.path.join(ROOT, "src")]
    import harness
    harness.use_compile_cache()
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return harness.run(ROOT, spec, args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)


if __name__ == "__main__":
    sys.exit(main())
