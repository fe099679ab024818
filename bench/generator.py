"""The one general traffic generator: what every entry shares, and the entry
a traffic mix names, found by its name.

A traffic mix is a data file, ``traffic/<mix>.json``.  It names its
``entry``, the program entry point it drives, and gives the sizes of one
request.  The entry is the file ``entries/<entry>.py``, whose class
``Entry`` (a ``Base``) has

- ``setup()``: build what the requests share and warm every shape they use;
  it may note the seconds of its parts in ``self.phases``;
- ``request(i)``: serve request ``i`` to its end and return what it did
  (``work`` units, the program's own ``spans``, its ``dispatch`` report);
- ``release()``: drop the program's state;
- ``check(rng, dtype)``: after the window, the kept outputs against the
  plain references, ``{name: value}``; below float64 the reference in
  ``dtype`` stands in for the program (the lower-precision control).

Every request is drawn from the run's seed and its own index, so the same
seed gives the same requests, and every seed gives requests of the same
sizes.  A new mix of an existing entry is a data file alone; a new entry is
a new file, and nothing here changes.
"""
from __future__ import annotations

import importlib.util
import os

import numpy as np


def derive(seed: int, *path: int, n: int = 1) -> np.ndarray:
    """``n`` 31-bit seeds for the draw at ``path`` under the run's ``seed``
    (any non-negative whole number, however large)."""
    ss = np.random.SeedSequence([seed % (1 << 64), *path])
    return (ss.generate_state(n, np.uint32) >> 1).astype(np.int64)


class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn with ``rng``."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.seen, self.items = k, rng, 0, []

    def offer(self, make):
        """Offer the next item; ``make()`` builds it only if it is kept."""
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(make())
            return
        j = int(self.rng.integers(self.seen))
        if j < self.k:
            self.items[j] = make()


class Base:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 collect_stats: bool):
        self.config, self.traffic = config, traffic
        self.seed, self.devices = seed, list(devices)
        self.collect_stats = collect_stats
        self.kept = Reservoir(int(traffic["check"]["requests"]),
                              np.random.default_rng(derive(seed, 9)))
        self.phases = {}

    def release(self):
        pass


def entry_class(name: str, bench_dir: str):
    """The class ``Entry`` of ``entries/<name>.py`` under ``bench_dir``."""
    return entry_module(name, bench_dir).Entry


def entry_module(name: str, bench_dir: str):
    """The module ``entries/<name>.py`` under ``bench_dir``."""
    path = os.path.join(bench_dir, "entries", f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no entry {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_entry_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
