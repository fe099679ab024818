"""``cloudsim.run_simulation``: one whole simulation per request.

Each request draws its entities from the run's seed and its index.  After
the window, the kept requests are compared with the plain references:

- ``assign_mismatch``: cloudlets whose VM differs from the broker
  reference's, over every cloudlet of every kept request;
- ``finish_rel_err``: the largest relative gap between a finish time (or the
  makespan) and the float64 reference: the closed-form reference over every
  cloudlet, and the per-VM stepping reference over sampled VMs.

Below float64 the references computed in that precision stand in for the
program (the lower-precision control), where the stepping reference runs,
on the sampled VMs.
"""
from __future__ import annotations

import dataclasses
import sys

import numpy as np

import generator
from reference import entities, matchmaking, timeshared


def _broker(name: str, got, mi, mips, rule: dict, dtype):
    """(expected VM, compared VM, ids of the cloudlets whose compared VM the
    reference does not accept) per cloudlet.  The compared VM is the
    program's ``got``, or, below float64, the reference in ``dtype`` (the
    control)."""
    if name != "matchmaking":
        want = matchmaking.round_robin(mi.size, mips.size)
        got = np.asarray(got) if dtype is np.float64 else want
        return want, got, np.nonzero(got != want)[0]
    want, order, lo, hi = matchmaking.matchmaking(
        mi, mips, max_mi=rule["max_mi"], headroom=rule["headroom"])
    if dtype is not np.float64:
        got = matchmaking.matchmaking(
            mi, mips, max_mi=rule["max_mi"], headroom=rule["headroom"],
            dtype=dtype, band=0.0)[0]
    got = np.asarray(got)
    return want, got, matchmaking.mismatches(got, order, lo, hi)


def _requirement(mi, mips, rule: dict):
    """A cloudlet's least adequate MIPS under the matchmaking rule, in
    float64 and in float32 arithmetic, and the VM whose MIPS lies nearest
    the float64 one with its relative distance: a distance within float32
    rounding tells a boundary case from a fault of the broker's logic."""
    top = np.max(mips)
    f64 = float(mi) / rule["max_mi"] * (rule["headroom"] * float(top))
    f32 = (np.float32(mi) / np.float32(rule["max_mi"])
           * (np.float32(top) * np.float32(rule["headroom"])))
    dist = np.abs(np.asarray(mips, np.float64) - f64) / f64
    near = int(np.argmin(dist))
    return f64, f32, near, float(dist[near])


def _mismatches(bad, got, want, mi, mips, rule: dict, where: str) -> int:
    """The broker's mismatches ``bad`` counted, the first few named on
    standard error with the cloudlet's requirement and its distance to the
    nearest VM's MIPS."""
    for b in bad[:3]:
        f64, f32, near, dist = _requirement(mi[b], mips, rule)
        print(f"broker mismatch {where} cloudlet {b} mi {mi[b]!r} got VM "
              f"{got[b]} ({mips[got[b]]!r} MIPS) want VM {want[b]} "
              f"({mips[want[b]]!r} MIPS); requires {f64!r} MIPS (float64), "
              f"{f32!r} (float32); nearest VM {near} ({mips[near]!r} MIPS) "
              f"at relative distance {dist!r}", file=sys.stderr)
    return int(bad.size)


def _rel(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    live = ref > 0
    if not live.any():
        return 0.0
    return float(np.max(np.abs(got[live] - ref[live]) / ref[live]))


def _stepped(assign, finish, mi, mips, vms, dtype) -> float:
    """Largest relative gap on the VMs ``vms`` between the program's finish
    times (or, below float64, the stepping reference in ``dtype``) and the
    float64 stepping reference."""
    worst = 0.0
    for v in vms:
        rows = np.nonzero(assign == v)[0]
        if rows.size == 0:
            continue
        ref = timeshared.finish_times(mi[rows], mips[v])
        got = (finish[rows] if dtype is np.float64
               else timeshared.finish_times(mi[rows], mips[v], dtype))
        worst = max(worst, _rel(got, ref))
    return worst


def compare(samples, sim: dict, rng: np.random.Generator,
            vms_per_sample: int, dtype=np.float64) -> dict:
    """One large simulation per sample: the broker's whole assignment and
    every finish time and the makespan against the references, and the
    finish times of ``vms_per_sample`` VMs drawn from ``rng`` plus the VM
    that holds the makespan against the stepping reference.  A sample is a
    dict of the request's ``seed``, ``n_vms``, ``n_cloudlets``, ``broker``
    and the program's ``assign``, ``finish`` and ``makespan``."""
    mism, worst = 0, 0.0
    for s in samples:
        mips, mi = entities.simulation(s["seed"], s["n_vms"], s["n_cloudlets"],
                                       sim["vm_mips_range"],
                                       sim["cloudlet_mi_range"])
        assign = np.asarray(s["assign"])
        finish = np.asarray(s["finish"])
        want, got, bad = _broker(s["broker"], assign, mi, mips,
                                 sim["matchmaking"], dtype)
        mism += _mismatches(bad, got, want, mi, mips, sim["matchmaking"],
                            f"seed {s['seed']}")
        if dtype is np.float64:
            ref = timeshared.finish_times_all(assign, mi, mips)
            worst = max(worst, _rel(finish, ref),
                        _rel([s["makespan"]], [ref.max()]))
        vms = set(rng.choice(s["n_vms"], size=min(vms_per_sample, s["n_vms"]),
                             replace=False).tolist())
        vms.add(int(assign[np.argmax(finish)]))
        worst = max(worst, _stepped(assign, finish, mi, mips, sorted(vms),
                                    dtype))
    return {"assign_mismatch": mism, "finish_rel_err": worst}


class Entry(generator.Base):

    def setup(self):
        import time

        from jax.sharding import Mesh

        from repro.core.cloudsim import SimulationConfig

        sim, t = self.config["simulation"], self.traffic
        self.mesh = Mesh(np.array(self.devices), ("data",))
        self.base = SimulationConfig(
            n_vms=t["n_vms"], n_cloudlets=t["n_cloudlets"], broker=t["broker"],
            core=sim["core"], use_kernel=sim["use_kernel"],
            vm_mips_range=tuple(sim["vm_mips_range"]),
            cloudlet_mi_range=tuple(sim["cloudlet_mi_range"]))
        t0 = time.perf_counter()
        self._run(int(generator.derive(self.seed, 0)[0]))
        self.phases["warm-up request"] = time.perf_counter() - t0

    def _run(self, s: int):
        from repro.core.cloudsim import run_simulation
        return run_simulation(dataclasses.replace(self.base, seed=s),
                              self.mesh)

    def request(self, i: int) -> dict:
        s = int(generator.derive(self.seed, 1, i)[0])
        res = self._run(s)
        t = self.traffic
        self.kept.offer(lambda: dict(
            seed=s, n_vms=t["n_vms"], n_cloudlets=t["n_cloudlets"],
            broker=t["broker"], assign=res.vm_assign,
            finish=res.finish_times, makespan=res.makespan))
        return {"work": {"cloudlets": t["n_cloudlets"]},
                "spans": dict(res.timings)}

    def release(self):
        self.mesh = None

    def check(self, rng, dtype=np.float64) -> dict:
        return compare(self.kept.items, self.config["simulation"], rng,
                       int(self.traffic["check"]["vms"]), dtype)
