"""Concurrent & distributed cloud (DES) simulator — the thesis's CloudSim side.

Entity model (struct-of-arrays, stored in the DataGrid as the thesis stores
them in Hazelcast IMaps): Datacenters ⊃ Hosts ⊃ VMs ⊂ Cloudlets.  Brokers:

  * RoundRobinBroker      — cloudlet i → VM (i mod V)           (§5.1.1)
  * MatchmakingBroker     — fair matchmaking (Raman et al.): each cloudlet
    requires a minimal VM size f(length); it binds to an adequate VM while
    *not* overloading the large VMs — among adequate candidates the broker
    round-robins by cloudlet index (§5.1.2).

Execution phases, mirroring §3.4.1.2 / Fig 3.10:
  1. create entities          (distributed: partitions created shard-locally)
  2. schedule (broker)        (distributed: matchmaking over local partitions,
                               VM table replicated — executeOnKeyOwner)
  3. cloudlet workloads       (distributed: the ``isLoaded`` real compute)
  4. core event simulation    (distributed: the closed-form segmented-scan
                               core in ``des_scan`` re-homes each cloudlet
                               to its VM-owner member with one owner-keyed
                               all-to-all and each member sorts + scans only
                               its own ~C/M cloudlets — the thesis left this
                               phase master-only because "tightly coupled
                               core fragments are not distributed", §4; the
                               closed form decouples them and the exchange
                               makes phase 4 COMPUTE-partitioned end-to-end)
``SimulationConfig.core`` selects the phase-4 engine: "scan" (default,
O(C log C) closed form), "scan_dist" (scan partitioned over members;
``dist_method`` picks the owner-keyed "exchange" pipeline or the PR-2
"replicated" baseline), "wave" (the original master-only event loop — kept
as the equivalence oracle).  Outputs are identical regardless of the number
of members (tests assert the thesis's accuracy claim).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.executor import DistributedExecutor
from repro.core.grid import DataGrid
from repro.core.partition import pad_to_shards
from repro.core.spans import span
from repro.core import des_scan


@dataclasses.dataclass(frozen=True)
class SimulationConfig:
    n_datacenters: int = 15
    n_hosts: int = 60
    n_vms: int = 200
    n_cloudlets: int = 400
    vm_mips_range: tuple = (500.0, 2000.0)
    cloudlet_mi_range: tuple = (1000.0, 50000.0)   # million instructions
    broker: str = "round_robin"                    # | "matchmaking"
    core: str = "scan"                             # | "scan_dist" | "wave"
    dist_method: str = "exchange"                  # | "replicated" (PR-2 core)
    exchange_slack: Optional[float] = None         # None = exact auto capacity
    use_kernel: bool = False                       # Pallas seg-scan kernel
    kernel_chunk: Optional[int] = None             # None = roofline-autotuned
    is_loaded: bool = False                        # attach a real workload
    workload_dim: int = 64                         # loaded-matmul size
    workload_iters_per_gmi: float = 2.0            # iterations per 1000 MI
    seed: int = 42


# ----------------------------------------------------------------- entities

def create_entities(cfg: SimulationConfig, grid: DataGrid,
                    pad_multiple: int = 1) -> Dict[str, jax.Array]:
    """Create datacenters/hosts/VMs/cloudlets into the data grid (padded so
    every member owns an equal partition, per PartitionUtil).

    ``pad_multiple`` additionally pads entity array sizes to a multiple of
    that value: the elastic cluster passes the LCM of every member count its
    IAS can reach, so padded shapes — and hence the PRNG draws — are
    IDENTICAL across scale events without requiring the LIVE entity counts
    to be divisible by anything.  Padding rows are inert (0-MIPS VMs,
    ``valid=False`` cloudlets) and never scheduled onto."""
    n = math.lcm(grid.n_members, max(pad_multiple, 1))
    key = jax.random.PRNGKey(cfg.seed)
    k1, k2 = jax.random.split(key)
    V = pad_to_shards(cfg.n_vms, n)
    C = pad_to_shards(cfg.n_cloudlets, n)

    lo, hi = cfg.vm_mips_range
    vm_mips = jax.random.uniform(k1, (V,), minval=lo, maxval=hi)
    vm_mips = jnp.where(jnp.arange(V) < cfg.n_vms, vm_mips, 0.0)
    vm_host = jnp.arange(V, dtype=jnp.int32) % max(cfg.n_hosts, 1)

    lo, hi = cfg.cloudlet_mi_range
    cl_mi = jax.random.uniform(k2, (C,), minval=lo, maxval=hi)
    cl_valid = jnp.arange(C) < cfg.n_cloudlets
    cl_mi = jnp.where(cl_valid, cl_mi, 0.0)

    grid.put("vm_mips", vm_mips)
    grid.put("vm_host", vm_host)
    grid.put("cloudlet_mi", cl_mi)
    grid.put("cloudlet_valid", cl_valid)
    return {"vm_mips": vm_mips, "vm_host": vm_host, "cloudlet_mi": cl_mi,
            "cloudlet_valid": cl_valid, "n_vms": cfg.n_vms,
            "n_cloudlets": cfg.n_cloudlets}


# ------------------------------------------------------------------ brokers

def round_robin_assign(local_ids, n_vms: int):
    return (local_ids % n_vms).astype(jnp.int32)


def matchmaking_assign(local_ids, local_mi, vm_mips, n_vms: int):
    """Fair matchmaking over the (replicated) VM table for a local partition.

    required(cl) = mi-proportional minimal MIPS; candidates = VMs with
    mips >= required; bind to the (id mod n_candidates)-th smallest adequate
    VM — best-fit with round-robin fairness (no overloading the largest VMs).
    """
    return matchmaking_assign_masked(local_ids, local_mi, vm_mips[:n_vms],
                                     jnp.ones((n_vms,), bool))


def matchmaking_assign_masked(local_ids, local_mi, vm_mips, vm_valid):
    """``matchmaking_assign`` with the VM count TRACED: padded VMs are masked
    by ``vm_valid`` instead of sliced off, so scenario-grid variants with
    heterogeneous VM counts batch into one vmap.  Equals the static version
    exactly when every VM is valid (padded VMs sort to +inf, past every
    candidate window)."""
    n_vms = vm_valid.sum().astype(jnp.int32)
    keyed = jnp.where(vm_valid, vm_mips, jnp.inf)
    order = jnp.argsort(keyed)                           # valid ascending first
    sorted_mips = keyed[order]
    max_mi = 50000.0
    max_mips = jnp.max(jnp.where(vm_valid, vm_mips, -jnp.inf))
    required = local_mi / max_mi * (max_mips * 0.9)
    first_ok = jnp.searchsorted(sorted_mips, required)   # (c,)
    first_ok = jnp.minimum(first_ok, n_vms - 1)
    n_cand = n_vms - first_ok
    pick = first_ok + (local_ids % n_cand)
    return order[pick].astype(jnp.int32)


def schedule(cfg: SimulationConfig, grid: DataGrid,
             executor: DistributedExecutor) -> jax.Array:
    """Distributed scheduling: each member matches its cloudlet partition."""
    C = grid.get("cloudlet_mi").shape[0]
    ids = jnp.arange(C, dtype=jnp.int32)
    mi = grid.get("cloudlet_mi")
    vm_mips = grid.replicate("vm_mips")                  # near-cache the VM table

    if cfg.broker == "round_robin":
        fn = lambda data, vm: round_robin_assign(data[0], cfg.n_vms)
    else:
        fn = lambda data, vm: matchmaking_assign(data[0], data[1], vm,
                                                 cfg.n_vms)
    assign = executor.execute_on_key_owners(fn, (ids, mi),
                                            replicated_args=(vm_mips,))
    grid.put("cloudlet_vm", assign)
    return assign


# ----------------------------------------------------------------- workloads

def _one_workload(mi, dim: int, iters: int):
    """The ``isLoaded`` cloudlet payload: real (distributable) compute whose
    size scales with the cloudlet length."""
    a = (jnp.ones((dim, dim), jnp.float32) * (mi / 50000.0) +
         jnp.eye(dim, dtype=jnp.float32))

    def body(_, m):
        return jnp.tanh(m @ a) * 0.5 + a * 0.1

    out = jax.lax.fori_loop(0, iters, body, a)
    return jnp.sum(out)


def workload_iters(cfg: SimulationConfig) -> int:
    """The ``isLoaded`` payload's iteration count — ONE definition shared by
    the per-simulation path (``run_workloads``) and the scenario grid's
    ``is_loaded`` axis, so both report the same checksum for a config."""
    return int(cfg.workload_iters_per_gmi * (cfg.cloudlet_mi_range[1] / 1000.0))


def run_workloads(cfg: SimulationConfig, grid: DataGrid,
                  executor: DistributedExecutor) -> jax.Array:
    mi = grid.get("cloudlet_mi")
    iters = workload_iters(cfg)

    def member(local_mi):
        return jax.vmap(lambda m: _one_workload(m, cfg.workload_dim, iters))(
            local_mi)

    checks = executor.execute_on_key_owners(member, mi)
    grid.put("workload_checksum", checks)
    return checks


# ------------------------------------------------- core DES (master instance)

def simulate_completion(vm_assign, cloudlet_mi, vm_mips, valid):
    """Time-shared completion waves (CloudletSchedulerTimeShared).

    Event loop: between consecutive completions every active cloudlet on VM v
    progresses at mips_v / active_v.  Returns (finish_times, makespan).
    Pure JAX while_loop — one iteration per completion wave.

    O(waves × C × V): kept as the equivalence ORACLE for the O(C log C)
    closed-form core in ``repro.core.des_scan`` (the production path).

    Dtype-generic: the arithmetic runs in the dtype of ``cloudlet_mi``, so
    under ``jax.experimental.enable_x64`` the oracle accumulates ``now`` in
    f64 and the equivalence tolerance measures only the scan's own f32
    error, not the oracle's sequential drift (~eps·|t|·√waves in f32).
    """
    C = cloudlet_mi.shape[0]
    V = vm_mips.shape[0]
    dtype = cloudlet_mi.dtype if jnp.issubdtype(cloudlet_mi.dtype,
                                                jnp.floating) else jnp.float32
    remaining = jnp.where(valid, cloudlet_mi, 0.0).astype(dtype)
    vm_mips = vm_mips.astype(dtype)
    finish = jnp.zeros((C,), dtype)
    onehot_vm = jax.nn.one_hot(vm_assign, V, dtype=dtype)

    def cond(state):
        remaining, _, _ = state
        return jnp.any(remaining > 1e-6)

    def body(state):
        remaining, finish, now = state
        active = remaining > 1e-6
        counts = (active.astype(dtype))[None, :] @ onehot_vm  # (1,V)
        counts = counts[0]
        rate_vm = jnp.where(counts > 0, vm_mips / jnp.maximum(counts, 1.0), 0.0)
        rate = (onehot_vm @ rate_vm) * active                        # (C,)
        tte = jnp.where(active & (rate > 0), remaining / rate, jnp.inf)
        dt = jnp.min(tte)
        dt = jnp.where(jnp.isfinite(dt), dt, 0.0)
        new_remaining = jnp.maximum(remaining - rate * dt, 0.0)
        just_done = active & (new_remaining <= 1e-6)
        finish = jnp.where(just_done, now + dt, finish)
        # guard: if nothing progresses (all rates 0), zero out to terminate
        stalled = (dt <= 0) & active & (rate <= 0)
        new_remaining = jnp.where(stalled, 0.0, new_remaining)
        return new_remaining, finish, now + dt

    _, finish, makespan = jax.lax.while_loop(
        cond, body, (remaining, finish, jnp.zeros((), dtype)))
    return finish, makespan


_simulate_completion_jit = jax.jit(simulate_completion)


# ----------------------------------------------------------------- full run

@dataclasses.dataclass
class SimulationResult:
    vm_assign: np.ndarray
    finish_times: np.ndarray
    makespan: float
    workload_checksum: Optional[np.ndarray]
    timings: Dict[str, float]

    def summary(self) -> Dict[str, float]:
        return {"makespan": float(self.makespan),
                "mean_finish": float(self.finish_times.mean()),
                **{f"t_{k}": v for k, v in self.timings.items()}}


def run_simulation(cfg: SimulationConfig, mesh: Mesh,
                   backup_count: int = 0, *, grid: Optional[DataGrid] = None,
                   executor: Optional[DistributedExecutor] = None,
                   vm_owner=None, pad_multiple: int = 1,
                   weight_observer=None) -> SimulationResult:
    """One full simulation on ``mesh``.  ``grid``/``executor`` may be
    supplied by an elastic cluster that re-homes them across scale events
    (caller-owned grids are NOT cleared at the end); ``vm_owner`` is the
    PartitionTable-backed VM→member map for ``core="scan_dist"``;
    ``pad_multiple`` additionally pads entity sizes (see
    ``create_entities``) so elastic runs keep identical shapes across
    member counts; ``weight_observer`` receives the scan core's measured
    per-VM exchange load (see ``simulate_completion_distributed``) — the
    elastic cluster passes its dispatcher's ``observe_key_weights`` so the
    next rebalance is locality-aware with no caller cooperation."""
    own_grid = grid is None
    grid = grid if grid is not None else DataGrid(mesh,
                                                 backup_count=backup_count)
    executor = executor if executor is not None else DistributedExecutor(mesh)
    timings = {}

    with span("sim.create") as stage:
        create_entities(cfg, grid, pad_multiple)
        jax.block_until_ready(grid.get("cloudlet_mi"))
    timings["create"] = stage.seconds

    with span("sim.schedule") as stage:
        assign = schedule(cfg, grid, executor)
        jax.block_until_ready(assign)
    timings["schedule"] = stage.seconds

    checks = None
    if cfg.is_loaded:
        with span("sim.workload") as stage:
            checks = run_workloads(cfg, grid, executor)
            jax.block_until_ready(checks)
        timings["workload"] = stage.seconds

    with span("sim.core") as stage:
        core_args = (assign, grid.get("cloudlet_mi"), grid.get("vm_mips"),
                     grid.get("cloudlet_valid"))
        if cfg.core == "wave":
            finish, makespan = _simulate_completion_jit(*core_args)
        elif cfg.core == "scan_dist":
            finish, makespan = des_scan.simulate_completion_distributed(
                *core_args, executor, vm_owner=vm_owner,
                method=cfg.dist_method, slack=cfg.exchange_slack,
                use_kernel=cfg.use_kernel, kernel_chunk=cfg.kernel_chunk,
                weight_observer=weight_observer)
        elif cfg.core == "scan":
            finish, makespan = des_scan.simulate_completion_scan_jit(
                *core_args, use_kernel=cfg.use_kernel,
                kernel_chunk=cfg.kernel_chunk)
        else:
            raise ValueError(f"unknown core {cfg.core!r}")
        jax.block_until_ready(finish)
    timings["core_sim"] = stage.seconds

    if own_grid:
        grid.clear()   # clearDistributedObjects()
    return SimulationResult(
        vm_assign=np.asarray(assign), finish_times=np.asarray(finish),
        makespan=float(makespan),
        workload_checksum=None if checks is None else np.asarray(checks),
        timings=timings)


# ------------------------------------------------- elastic simulation cluster

class ElasticSimulationCluster:
    """Elastic mesh for ``core="scan_dist"`` — a THIN CLIENT of the
    ``ElasticDispatcher`` middleware (``core/dispatch.py``).

    All the moving parts live in the dispatcher now: the 271-virtual-
    partition ``PartitionTable``, the ``ElasticController``→IAS wiring, the
    remesh callback (rebalance table → retire exactly the outgoing
    geometry's executables → rebuild mesh → re-home the ``DataGrid``), and
    the compile cache.  This class only binds a simulation config to the
    dispatcher's current geometry: it pads entities to the dispatcher's
    ``entity_pad`` (the LCM of every member count the IAS can reach) and
    ships the table-backed VM→member map as the distributed core's runtime
    operand, so finish vectors are BIT-identical before and after any scale
    event (PAPER §4.1.3 / §4.3).
    """

    def __init__(self, devices=None, axis: str = "data",
                 health_cfg: Optional["HealthConfig"] = None,
                 start_members: int = 1,
                 partition_count: Optional[int] = None,
                 dispatcher=None):
        from repro.core.dispatch import ElasticDispatcher

        if dispatcher is not None:
            # the dispatcher IS the topology: silently dropping conflicting
            # kwargs would run a differently-configured cluster
            if (devices is not None or axis != "data"
                    or health_cfg is not None or start_members != 1
                    or partition_count is not None):
                raise ValueError(
                    "pass either a dispatcher OR topology kwargs (devices/"
                    "axis/health_cfg/start_members/partition_count), not "
                    "both — the dispatcher already owns the topology")
            self.dispatcher = dispatcher
        else:
            self.dispatcher = ElasticDispatcher(
                devices=devices, axis=axis, health_cfg=health_cfg,
                start_members=start_members, partition_count=partition_count)

    # ------------------------------------------- dispatcher-backed topology
    @property
    def devices(self):
        return self.dispatcher.devices

    @property
    def axis(self) -> str:
        return self.dispatcher.axis

    @property
    def table(self):
        return self.dispatcher.table

    @property
    def controller(self):
        return self.dispatcher.controller

    @property
    def mesh(self):
        return self.dispatcher.mesh

    @property
    def executor(self) -> DistributedExecutor:
        return self.dispatcher.executor

    @property
    def grid(self) -> Optional[DataGrid]:
        return self.dispatcher.grid

    @property
    def entity_pad(self) -> int:
        return self.dispatcher.entity_pad

    @property
    def scale_events(self):
        return self.dispatcher.scale_events

    @property
    def n_members(self) -> int:
        return self.dispatcher.n_members

    def vm_owner(self, n_vms: int) -> jnp.ndarray:
        """Current VM→member map (the runtime operand of the scan core)."""
        return self.dispatcher.vm_owner(n_vms)

    # ------------------------------------------------------------- scaling
    def observe_load(self, load: float):
        """Feed one load sample (observed/target, the paper's process-CPU
        analogue) to the monitor→probe→IAS chain; a threshold crossing
        triggers the dispatcher's remesh callback at this step boundary."""
        return self.dispatcher.observe_load(load)

    # ----------------------------------------------------------- simulation
    def simulate(self, cfg: SimulationConfig) -> SimulationResult:
        """Run one simulation on the CURRENT member count with table-backed
        VM ownership.  Entity sizes are auto-padded to the LCM of every
        member count the IAS can reach (``self.entity_pad``), so padded
        shapes — and hence PRNG draws and finish vectors — are BIT-identical
        across scale events for ARBITRARY ``n_vms``/``n_cloudlets``; no
        divisibility requirement.  Results are trimmed back to the
        configured live entity counts.

        Each run also AUTO-feeds its measured per-VM exchange load into the
        dispatcher's ``observe_key_weights``, so the next IAS scale event
        rebalances locality-aware (hot VMs spread across members) with no
        caller cooperation."""
        if cfg.core != "scan_dist":
            cfg = dataclasses.replace(cfg, core="scan_dist")
        grid = self.dispatcher.ensure_grid()
        V = pad_to_shards(cfg.n_vms, math.lcm(self.n_members,
                                              self.entity_pad))
        r = run_simulation(cfg, self.mesh, grid=grid,
                           executor=self.executor,
                           vm_owner=self.vm_owner(V),
                           pad_multiple=self.entity_pad,
                           weight_observer=(
                               self.dispatcher.observe_key_weights))
        C = cfg.n_cloudlets
        return dataclasses.replace(
            r, vm_assign=r.vm_assign[:C], finish_times=r.finish_times[:C],
            workload_checksum=(None if r.workload_checksum is None
                               else r.workload_checksum[:C]))

    def simulate_grid(self, cfg: SimulationConfig, grid, *,
                      chunk: Optional[int] = None, on_chunk=None,
                      dispatch_ahead: Optional[int] = None,
                      checkpoint=None):
        """Stream a ``make_scenario_grid`` product through this cluster's
        elastic dispatcher — the cloudsim face of the scenario-grid batch
        path (``des_scan.run_scenario_grid``), with mid-stream IAS scale
        events and, via ``checkpoint`` (a ``core.journal.CheckpointPolicy``),
        DURABLE dispatch: the campaign's chunk stream is journaled and
        checkpointed so a killed coordinator resumes bit-identically
        (``resume_grid``).  Returns a ``BatchSimulationResult`` whose
        ``dispatch`` field carries the ``DispatchReport`` summary."""
        from repro.core.des_scan import run_scenario_grid
        return run_scenario_grid(cfg, grid, dispatcher=self.dispatcher,
                                 chunk=chunk, on_chunk=on_chunk,
                                 dispatch_ahead=dispatch_ahead,
                                 checkpoint=checkpoint)

    def resume_grid(self, path: str, cfg: SimulationConfig, grid, *,
                    chunk: Optional[int] = None, on_chunk=None):
        """Continue a journaled ``simulate_grid`` after a coordinator
        crash/drain: rebuild the scenario job + operand stack exactly as
        ``simulate_grid`` would (the journal's environment signature is
        verified against it), then hand off to
        ``ElasticDispatcher.resume``.  Returns the same tuple-of-arrays
        payload the scenario job produces, bit-identical to an
        uninterrupted ``simulate_grid``."""
        from repro.core.des_scan import grid_batch_args
        args, job, _ = grid_batch_args(cfg, grid)
        return self.dispatcher.resume(path, job, args, chunk=chunk,
                                      on_chunk=on_chunk)
