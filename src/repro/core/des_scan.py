"""Closed-form DES core — sort + segmented scan replaces the event loop.

The wave-loop reference (``cloudsim.simulate_completion``) replays the
CloudSim event loop: one ``lax.while_loop`` iteration per completion wave,
each wave a dense (C,V) one-hot matmul — O(waves × C × V) and inherently
master-only ("tightly coupled core fragments are not distributed", §4).

Time-shared scheduling has a closed form that collapses the loop.  On a VM
with MIPS μ running the cloudlets sorted ascending by length m_1 ≤ … ≤ m_k,
the shortest finishes first and every completion frees capacity for the
rest, so

    finish_j = finish_{j-1} + (m_j − m_{j-1}) · (k − j + 1) / μ

— a per-VM prefix sum.  Globally: sort cloudlets by (vm, length), take
first differences within each VM segment, weight by the number of still-
active sharers, and run ONE segmented prefix scan.  O(C log C) total, no
while_loop, no (C,V) one-hot, trivially vmappable (batched sweeps) and
partitionable by VM ownership (distributed phase 4).

Execution paths:
  * ``simulate_completion_scan``        — pure-jnp sort + segmented cumsum
  * ``use_kernel=True``                 — the v2 position-gated fused kernel
                                          (``kernels/seg_scan/v2``): the
                                          chunked Pallas scan reproduces the lax
                                          addition tree BIT-exactly, and the
                                          sentinel mask + result scatter are
                                          fused into the epilogue kernel.
                                          On the CPU the kernel runs as a
                                          bit-exact jnp emulation (one-time
                                          ``KernelInterpretFallbackWarning``);
                                          ``kernel_chunk=None`` resolves via
                                          the roofline autotuner
                                          (``roofline/autotune``).
  * ``simulate_completion_distributed`` — COMPUTE-partitioned phase 4: an
                                          owner-keyed exchange re-homes each
                                          cloudlet to the member owning its
                                          VM, and each member sorts+scans
                                          only its own ~C/M cloudlets
  * ``run_simulation_batch``            — one jit over a multi-axis scenario
                                          GRID (seeds × mi_scale × broker ×
                                          VM-count × MIPS-distribution),
                                          heterogeneous shapes padded so all
                                          variants stack; optionally sharded
                                          across mesh members (vmap of the
                                          scenario fn inside the partitioned
                                          member_fn).

The exchange protocol (``method="exchange"``, the default distributed core):

  1. Each member buckets its cloudlet shard (C/M contiguous rows) by
     ``vm_owner[vm_assign]`` — the ``PartitionTable`` map, a RUNTIME operand,
     so IAS rebalances re-home VMs without recompiling.
  2. One padded all-to-all ships each cloudlet's ``(orig, assign, mi, valid)``
     to the owner member.  Per-(src, dst) capacity is ``block`` entries
     (static, part of the compile-cache key): heuristically
     ``ceil(shard * slack / M)`` or, by default, the exact observed
     ``exchange_load(...).max()`` rounded up to a power of two.  Unused
     capacity is ``valid=False`` fill, which the scan maps to the sentinel
     segment — padding contributes exactly 0.0.  Capacity violations are
     counted on-device and raised as ``ExchangeCapacityError`` — loud, never
     silent truncation.
  3. The owner sorts + scans only its own cloudlets: per-member work drops
     from O(C log C), replicated M times, to O((C/M) log(C/M)) each.
  4. Finish partials are scattered back to global row positions and psum-med;
     partials are disjoint (each cloudlet has exactly one owner) and
     x + 0.0 == x, so the sum is exact.

Bit-identity argument (the thesis's accuracy claim, preserved from PR 2):
every per-element quantity in the scan depends only on the element's segment
(its VM's cloudlet multiset) and its in-segment position p — the sort key
(vm, mi), first differences, sharer counts (exact small-int f32 sums), and
the segmented prefix sum, which ``_segmented_cumsum`` computes with a
position-gated Hillis–Steele doubling scan whose addition tree is a function
of p ALONE (never of the element's global offset or the array length).  A
member's exchanged sub-array therefore reproduces the full array's finish
values bit-for-bit, for any member count, ownership map, slack, or mid-run
rebalance.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import dispatch
from repro.core.dispatch import CompileCache, DispatchJob

_EPS = 1e-6   # same "still running" threshold as the wave-loop reference


def _segment_start_index(start, block: int = 1024):
    """Index of each element's segment start: the running max of
    ``where(start, idx, 0)``.  Integer max is exact, so this is bitwise
    ``lax.cummax`` of that array; it runs blocked, as a cummax along
    ``block``-wide rows plus one over the row maxima, because the TPU
    compiler takes ~40 s for a single 1-D cummax of 2**20 elements and
    well under a second for the blocked form."""
    C = start.shape[0]
    idx = jnp.arange(C, dtype=jnp.int32)
    marks = jnp.where(start, idx, 0)
    if C <= block:
        return jax.lax.cummax(marks)
    pad = (-C) % block
    rows = jnp.concatenate([marks, jnp.zeros((pad,), jnp.int32)]).reshape(
        -1, block)
    within = jax.lax.cummax(rows, axis=1)
    before = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jax.lax.cummax(within[:-1, -1])])
    return jnp.maximum(within, before[:, None]).reshape(-1)[:C]


def _sort_by_segment_and_length(seg, mi):
    """The stable (seg, mi) order of the rows: ``(seg_s, mi_s, order)``.

    Two stable one-key sorts, the minor key first, each carrying only a row
    index.  Runnable lengths are positive f32s, whose bit patterns order
    like the floats, so the length is compared as an int32.  The TPU
    compiler builds this pair in about the time of one such sort (~30 s at
    2**20 rows), where a single sort on (seg, mi, row) keys takes it
    minutes."""
    idx = jnp.arange(seg.shape[0], dtype=jnp.int32)
    mi_bits = jax.lax.bitcast_convert_type(mi, jnp.int32)
    _, by_mi = jax.lax.sort((mi_bits, idx), num_keys=1, is_stable=True)
    seg_s, q = jax.lax.sort((seg[by_mi], idx), num_keys=1, is_stable=True)
    order = by_mi[q]
    return seg_s, mi[order], order


def _segmented_cumsum(term, start):
    """Segmented inclusive prefix sum, position-gated Hillis–Steele.

    log2(C) doubling steps; step ``d`` adds the value ``d`` slots back iff
    that slot is in the same segment (in-segment position ``p >= d``).  The
    value at p is therefore combined by a fixed tree determined by p ALONE:
    x_d(p) = x_{d-1}(p) + [p >= d] * x_{d-1}(p - d).  Unlike
    ``lax.associative_scan`` (whose combine tree follows GLOBAL offsets),
    this makes the result layout-invariant — a segment scanned inside an
    owner-keyed sub-array of any length reproduces the full array's values
    BIT-exactly, which is what lets the distributed exchange core stay
    bit-identical to the single-member scan.  Extra steps past a segment's
    length are gated no-ops, so differing array lengths don't perturb it.
    Rounding error stays proportional to per-SEGMENT magnitudes, as with the
    segmented-operator scan this replaces."""
    C = term.shape[0]
    idx = jnp.arange(C, dtype=jnp.int32)
    pos = idx - _segment_start_index(start)                # in-segment p
    x = term
    d = 1
    while d < C:
        shifted = jnp.concatenate([jnp.zeros((d,), x.dtype), x[:-d]])
        x = x + jnp.where(pos >= d, shifted, jnp.zeros((), x.dtype))
        d *= 2
    return x


# ------------------------------------------------------------- the scan core

def simulate_completion_scan(vm_assign, cloudlet_mi, vm_mips, valid, *,
                             use_kernel: bool = False,
                             interpret: Optional[bool] = None,
                             kernel_chunk: Optional[int] = None):
    """Closed-form time-shared completion: sort by (vm, mi) + segmented scan.

    Numerically equivalent to ``cloudsim.simulate_completion`` (atol 1e-3):
    returns (finish_times (C,), makespan).  Cloudlets that never run —
    invalid padding rows, zero-length cloudlets, cloudlets bound to
    zero-MIPS (padded) VMs — keep finish time 0, exactly like the wave loop.

    ``use_kernel=True`` runs the v2 kernel path, BIT-identical to the
    default path: after the same sort, ``seg_cumsum_v2`` reproduces
    ``_segmented_cumsum``'s exact position-gated addition tree, and the
    sentinel mask + scatter fuse into the epilogue.  ``kernel_chunk`` (power of two, static) picks the
    in-kernel level split; ``None`` asks the roofline autotuner for the
    persisted/analytic choice.  ``interpret=None`` resolves to the backend
    default — compiled on TPU, bit-exact jnp emulation elsewhere (a
    one-time ``KernelInterpretFallbackWarning`` flags the fallback)."""
    C = cloudlet_mi.shape[0]
    V = vm_mips.shape[0]
    mi = jnp.where(valid, cloudlet_mi, 0.0).astype(jnp.float32)
    mips = vm_mips.astype(jnp.float32)

    # segment id = owning VM; everything that never runs goes to sentinel V
    runnable = valid & (mi > _EPS) & (mips[vm_assign] > 0.0)
    seg = jnp.where(runnable, vm_assign, V).astype(jnp.int32)

    seg_s, mi_s, order = _sort_by_segment_and_length(seg, mi)
    idx = jnp.arange(C, dtype=jnp.int32)

    prev_seg = jnp.concatenate([jnp.full((1,), -1, jnp.int32), seg_s[:-1]])
    start = seg_s != prev_seg                       # segment boundaries
    pos = (idx - _segment_start_index(start)).astype(jnp.float32)  # j-1

    # sharers count k per segment, gathered back per element
    counts = jax.ops.segment_sum(jnp.ones((C,), jnp.float32), seg_s,
                                 num_segments=V + 1)
    k = counts[seg_s]

    prev_mi = jnp.concatenate([jnp.zeros((1,), jnp.float32), mi_s[:-1]])
    delta = jnp.where(start, mi_s, mi_s - prev_mi)  # m_j − m_{j-1}
    seg_mips = jnp.concatenate([mips, jnp.zeros((1,), jnp.float32)])[seg_s]
    inv_mips = jnp.where(seg_mips > 0.0,
                         1.0 / jnp.maximum(seg_mips, 1e-30), 0.0)
    term = delta * (k - pos) * inv_mips             # (m_j−m_{j-1})(k−j+1)/μ

    if use_kernel:
        from repro.core.compat import resolve_kernel_interpret
        from repro.kernels.seg_scan.v2 import scatter_finish_v2, seg_cumsum_v2
        interpret = resolve_kernel_interpret(interpret)
        if kernel_chunk is None:
            from repro.roofline.autotune import tuned_chunk
            kernel_chunk = tuned_chunk(int(C))
        f_s = seg_cumsum_v2(term, start, chunk=kernel_chunk,
                            interpret=interpret)
        sentinel = seg_s == V                       # sentinel never finishes
        finish = scatter_finish_v2(f_s, order, sentinel,
                                   interpret=interpret)
        f_s = jnp.where(sentinel, 0.0, f_s)
    else:
        f_s = _segmented_cumsum(term, start)
        f_s = jnp.where(seg_s == V, 0.0, f_s)       # sentinel never finishes
        finish = jnp.zeros((C,), jnp.float32).at[order].set(f_s)
    makespan = jnp.max(f_s, initial=0.0)
    return finish, makespan


# jitted entry point with the flags static, shared so repeated calls (e.g.
# run_simulation) hit the compile cache instead of re-wrapping in jax.jit
simulate_completion_scan_jit = jax.jit(
    simulate_completion_scan,
    static_argnames=("use_kernel", "interpret", "kernel_chunk"))


# ------------------------------------------------- distributed phase 4

def default_vm_owner(n_vms: int, n_members: int) -> jnp.ndarray:
    """VM→member map from a freshly-balanced ``PartitionTable`` — the
    ownership an elastic cluster starts from before any scale event."""
    from repro.core.partition import PartitionTable
    table = PartitionTable(n_instances=n_members)
    return jnp.asarray(table.owners_of_range(n_vms))


class ExchangeCapacityError(RuntimeError):
    """The owner-keyed all-to-all's per-(src, dst) ``block`` capacity was
    exceeded: some cloudlets could not be shipped to their VM's owner and the
    finish vector would be silently wrong.  Raise ``block``/``slack`` (the
    exception message carries the observed requirement) or use the default
    auto capacity, which sizes ``block`` from the exact ``exchange_load``."""


# Compiled distributed cores, keyed on (mesh, axis, method, shapes, capacity).
# A ``CompileCache`` (the dispatcher's generalized LRU executable cache, which
# grew out of this dict) so a scale event can retire exactly the executables
# built for the mesh it replaces while every other member count's core stays
# warm; LRU-bounded (hits move to the back, the FRONT is evicted) so long
# grid sweeps over many (mesh, V, capacity) combinations don't accumulate
# executables forever — and don't evict the hottest mesh.
_DIST_CORE_CACHE = CompileCache()
_DIST_CORE_CACHE_MAX = 32

# Auto-sized exchange capacities, keyed (mesh, axis, V, C_pad): steady-state
# calls reuse the measured block instead of re-histogramming the ownership
# map on the host every call; overflow triggers an exact-requirement retry
# that updates the entry (see ``simulate_completion_distributed``).
_AUTO_BLOCK_CACHE = CompileCache()

# a dispatcher scale event retires the outgoing mesh's entries from both
# caches automatically (the auto-block capacities are metadata, not
# executables, so they don't count toward the event's retired-core tally)
dispatch.register_geometry_cache("dist_core", _DIST_CORE_CACHE)
dispatch.register_geometry_cache("auto_block", _AUTO_BLOCK_CACHE,
                                 counts_as_core=False)


def _cache_put(key, fn):
    # the cap stays a module global (not CompileCache(max_entries=...)) so
    # tests can monkeypatch _DIST_CORE_CACHE_MAX around a shared cache
    _DIST_CORE_CACHE.put(key, fn, max_entries=_DIST_CORE_CACHE_MAX)


def invalidate_dist_core(mesh=None, axis: Optional[str] = None) -> int:
    """Drop compiled distributed cores.  With a mesh (and optionally an
    axis), only that mesh's executables are invalidated — the elastic
    controller calls this on SCALE_OUT/IN so the retired member count's
    cores are freed but all other cached cores survive the event.  With no
    arguments, clears everything.  Returns the number of entries dropped."""
    def match(k):
        return ((mesh is None or k[0] == mesh)
                and (axis is None or k[1] == axis))

    n = _DIST_CORE_CACHE.invalidate(match)
    _AUTO_BLOCK_CACHE.invalidate(match)
    return n


def _dist_core_replicated(mesh, axis, V, use_kernel, interpret,
                          kernel_chunk=None):
    """The PR-2 distributed core, kept as the benchmark baseline: every
    member runs the IDENTICAL full O(C log C) scan and masks the finish
    entries of the VMs it doesn't own — result-partitioned, not
    compute-partitioned."""
    key = (mesh, axis, "replicated", V, use_kernel, interpret, kernel_chunk)
    cached = _DIST_CORE_CACHE.get(key)
    if cached is not None:
        return cached

    from repro.core.executor import DistributedExecutor

    executor = DistributedExecutor(mesh, axis)
    members = jnp.arange(executor.n_members, dtype=jnp.int32)

    def member_fn(mid, owner, assign, mi, mips, val):
        # Masking the *output* rather than the validity keeps each element's
        # value bit-identical to the single-member scan for ANY ownership map
        # and member count: partials are disjoint, and x + 0.0 == x exactly.
        f, _ = simulate_completion_scan(assign, mi, mips, val,
                                        use_kernel=use_kernel,
                                        interpret=interpret,
                                        kernel_chunk=kernel_chunk)
        mine = owner[assign] == mid[0]
        return jnp.where(mine, f, 0.0)[None, :]     # (1, C) partial

    def call(vm_owner, vm_assign, cloudlet_mi, vm_mips, valid):
        parts = executor.execute_on_key_owners(
            member_fn, members,
            replicated_args=(vm_owner, vm_assign, cloudlet_mi, vm_mips,
                             valid),
            out_specs=P(axis, None))
        finish = parts.sum(axis=0)
        return finish, jnp.max(finish, initial=0.0)

    fn = jax.jit(call)
    _cache_put(key, fn)
    return fn


def _dist_core_exchange(mesh, axis, V, C_pad, block, use_kernel, interpret,
                        kernel_chunk=None):
    """Compute-partitioned distributed core: bucket by VM owner, all-to-all,
    then each member sorts + scans ONLY its own cloudlets.  ``C_pad`` and
    ``block`` (the per-(src, dst) exchange capacity) are static — part of
    this cache key — while the VM→member ownership map stays a RUNTIME
    operand, so rebalancing the partition table never recompiles."""
    key = (mesh, axis, "exchange", V, C_pad, block, use_kernel, interpret,
           kernel_chunk)
    cached = _DIST_CORE_CACHE.get(key)
    if cached is not None:
        return cached

    from repro.core.executor import DistributedExecutor

    executor = DistributedExecutor(mesh, axis)
    M = executor.n_members
    S = C_pad // M                       # local cloudlet shard
    R = M * block                        # per-member receive capacity

    def member_fn(local, owner, mips):
        assign, mi, val = local                               # (S,) each
        mid = executor.member_id()
        orig = (mid * S + jnp.arange(S, dtype=jnp.int32))     # global rows
        # --- 1. bucket the local shard by destination owner --------------
        dest = jnp.where(val, owner[assign], M).astype(jnp.int32)
        order = jnp.argsort(dest)                 # group rows by destination
        dest_s = dest[order]
        idx = jnp.arange(S, dtype=jnp.int32)
        prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), dest_s[:-1]])
        rank = idx - _segment_start_index(dest_s != prev)  # within bucket
        live = dest_s < M                         # invalid rows don't ship
        # overflowed rows land OUT of range and are dropped — but counted,
        # so the caller can fail loudly instead of returning wrong results
        slot = jnp.where(live & (rank < block), dest_s * block + rank, R)
        overflow = jnp.sum(live & (rank >= block)).astype(jnp.int32)
        need = jnp.max(jnp.where(live, rank, -1), initial=-1) + 1
        # fill: assign 0, orig C_pad (dropped at scatter-back), valid False
        fill = jnp.broadcast_to(jnp.array([0, C_pad, 0], jnp.int32), (R, 3))
        ints = fill.at[slot].set(
            jnp.stack([assign[order], orig[order],
                       val[order].astype(jnp.int32)], axis=-1), mode="drop")
        s_mi = jnp.zeros((R,), jnp.float32).at[slot].set(
            mi[order].astype(jnp.float32), mode="drop")
        # --- 2. one padded all-to-all re-homes the triples ---------------
        r_ints = executor.all_to_all(ints.reshape(M, block, 3)).reshape(R, 3)
        r_mi = executor.all_to_all(s_mi.reshape(M, block)).reshape(R)
        r_assign = r_ints[:, 0]
        r_orig, r_val = r_ints[:, 1], r_ints[:, 2] == 1
        # --- 3. sort + scan ONLY the ~C/M cloudlets this member owns -----
        f_loc, _ = simulate_completion_scan(r_assign, r_mi, mips, r_val,
                                            use_kernel=use_kernel,
                                            interpret=interpret,
                                            kernel_chunk=kernel_chunk)
        # --- 4. scatter finishes back to global rows; disjoint partials --
        part = jnp.zeros((C_pad,), jnp.float32).at[r_orig].set(
            f_loc, mode="drop")
        return (executor.psum(part), executor.psum(overflow),
                executor.pmax(need))

    def call(vm_owner, vm_assign, cloudlet_mi, vm_mips, valid):
        finish, overflow, need = executor.execute_on_key_owners(
            member_fn, (vm_assign, cloudlet_mi, valid),
            replicated_args=(vm_owner, vm_mips),
            out_specs=(P(), P(), P()))
        return finish, jnp.max(finish, initial=0.0), overflow, need

    fn = jax.jit(call)
    _cache_put(key, fn)
    return fn


def _pow2_ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def simulate_completion_distributed(vm_assign, cloudlet_mi, vm_mips, valid,
                                    executor, vm_owner=None, *,
                                    method: str = "exchange",
                                    block: Optional[int] = None,
                                    slack: Optional[float] = None,
                                    use_kernel: bool = False,
                                    interpret: Optional[bool] = None,
                                    kernel_chunk: Optional[int] = None,
                                    weight_observer: Optional[
                                        Callable] = None):
    """Phase 4 distributed: per-VM completion segments are independent, so
    each member owns the finish entries of its VMs — ownership given by a
    ``PartitionTable``-backed VM→member map (``vm_owner``, a (V,) int32
    runtime array; defaults to a freshly-balanced table).

    ``method="exchange"`` (default) is COMPUTE-partitioned: an owner-keyed
    all-to-all re-homes each cloudlet to its VM's owner and each member
    sorts + scans only its own ~C/M cloudlets (see the module docstring for
    the protocol and padding invariants).  ``method="replicated"`` keeps the
    PR-2 baseline (every member scans the full problem, masks its output).

    Exchange capacity: ``block`` fixes the per-(src, dst) all-to-all block;
    ``slack`` sizes it heuristically (``exchange_block_size``).  Both fail
    LOUDLY (``ExchangeCapacityError``) when violated — never a silently-
    truncated result.  With neither, capacity is automatic and adaptive: the
    exact requirement is measured once from the concrete ownership map
    (``exchange_load``), rounded up to a power of two, and cached per
    (mesh, axis, V, C) so steady-state calls skip the host-side histogram
    entirely; if a later call's skew outgrows the cached block, the core's
    on-device overflow counter reports the exact new requirement and the
    call transparently retries once at that capacity (one recompile, still
    never a wrong result).

    ``weight_observer`` (optional) AUTO-wires the run's measured per-VM
    exchange load into locality-aware rebalancing: it is called with the
    (V,) count of valid cloudlets bound to each VM — exactly the per-key
    column mass of ``exchange_load`` — so passing a dispatcher's
    ``observe_key_weights`` makes the NEXT scale event spread hot VMs
    across members with no caller cooperation (the elastic simulation
    cluster wires this automatically).

    The per-member partials are disjoint and their sum is the full finish
    vector — BIT-identical to ``simulate_completion_scan`` for any member
    count, ownership map, and capacity (the thesis's accuracy claim), so an
    IAS scale event mid-run cannot perturb results."""
    from repro.core.partition import (exchange_block_size, exchange_load,
                                      pad_to_shards)

    V = vm_mips.shape[0]
    M = executor.n_members
    if vm_owner is None:
        vm_owner = default_vm_owner(V, M)
    vm_owner = jnp.asarray(vm_owner, jnp.int32)
    if use_kernel:
        from repro.core.compat import resolve_kernel_interpret
        interpret = resolve_kernel_interpret(interpret)
    if weight_observer is not None:
        a = np.asarray(vm_assign)
        live = np.asarray(valid).astype(bool)
        weight_observer(np.bincount(a[live], minlength=V).astype(np.float64))

    if method == "replicated":
        fn = _dist_core_replicated(executor.mesh, executor.axis, V,
                                   use_kernel, interpret, kernel_chunk)
        return fn(vm_owner, vm_assign, cloudlet_mi, vm_mips, valid)
    if method != "exchange":
        raise ValueError(f"unknown distributed method {method!r}")

    C = int(cloudlet_mi.shape[0])
    C_pad = pad_to_shards(max(C, 1), M)
    shard = C_pad // M
    auto = block is None and slack is None
    measured = False        # only a fresh measurement updates the cache
    if block is None:
        if slack is not None:
            block = exchange_block_size(C, M, slack)
        else:       # auto: exact requirement, cached per core geometry
            bkey = (executor.mesh, executor.axis, V, C_pad)
            block = _AUTO_BLOCK_CACHE.get(bkey)
            if block is None:
                need = int(exchange_load(vm_owner, vm_assign, valid, M).max())
                block = _pow2_ceil(max(need, 1))
                measured = True
    block = max(1, min(int(block), shard))

    vm_assign = jnp.asarray(vm_assign, jnp.int32)
    cloudlet_mi = jnp.asarray(cloudlet_mi, jnp.float32)
    valid = jnp.asarray(valid, bool)
    if C_pad != C:      # pad to whole shards; fill never runs nor ships
        pad = C_pad - C
        vm_assign = jnp.concatenate([vm_assign, jnp.zeros((pad,), jnp.int32)])
        cloudlet_mi = jnp.concatenate([cloudlet_mi, jnp.zeros((pad,))])
        valid = jnp.concatenate([valid, jnp.zeros((pad,), bool)])

    while True:
        fn = _dist_core_exchange(executor.mesh, executor.axis, V, C_pad,
                                 block, use_kernel, interpret, kernel_chunk)
        finish, makespan, overflow, need = fn(vm_owner, vm_assign,
                                              cloudlet_mi, vm_mips, valid)
        if int(overflow) == 0:
            break
        if not auto:
            raise ExchangeCapacityError(
                f"{int(overflow)} cloudlet(s) exceeded the exchange block "
                f"capacity {block} (observed per-(src,dst) requirement: "
                f"{int(need)}); raise block/slack or use the default auto "
                f"capacity")
        # adaptive retry at the device-reported exact requirement; clamped
        # to the shard size, so the second attempt cannot overflow
        block = min(_pow2_ceil(int(need)), shard)
        measured = True
    if auto and measured:   # steady-state hits don't rewrite (or churn) it
        _AUTO_BLOCK_CACHE[bkey] = block
    return finish[:C], makespan


# ------------------------------------------------- batched scenario sweeps

BROKER_IDS = {"round_robin": 0, "matchmaking": 1}
MIPS_DIST_IDS = {"uniform": 0, "fixed": 1, "bimodal": 2}


@dataclasses.dataclass
class BatchSimulationResult:
    """One jit, B scenario variants (a multi-axis grid)."""
    vm_assign: np.ndarray        # (B, C)
    finish_times: np.ndarray     # (B, C)
    makespans: np.ndarray        # (B,)
    timings: Dict[str, float]
    broker: Optional[np.ndarray] = None      # (B,) broker id per variant
    n_vms: Optional[np.ndarray] = None       # (B,) live VMs per variant
    n_cloudlets: Optional[np.ndarray] = None  # (B,) live cloudlets per variant
    mips_dist: Optional[np.ndarray] = None   # (B,) MIPS-distribution id
    n_datacenters: Optional[np.ndarray] = None  # (B,) topology (0 = flat)
    is_loaded: Optional[np.ndarray] = None   # (B,) workload attached?
    workload_checksum: Optional[np.ndarray] = None  # (B,) isLoaded checksum
    dispatch: Optional[Dict] = None          # ElasticDispatcher report

    @property
    def n_scenarios(self) -> int:
        return int(self.makespans.shape[0])

    def summary(self) -> Dict[str, float]:
        return {"n_scenarios": self.n_scenarios,
                "mean_makespan": float(self.makespans.mean()),
                "min_makespan": float(self.makespans.min()),
                "max_makespan": float(self.makespans.max()),
                **{f"t_{k}": v for k, v in self.timings.items()}}


def grid_scenario_inputs(cfg, seed, mi_scale, n_vms, n_cloudlets, mips_dist,
                         n_datacenters=None):
    """Entities for ONE grid variant at the padded (cfg.n_vms, cfg.n_cloudlets)
    shape — pure and vmappable.  Shape padding: VMs beyond ``n_vms`` get
    0 MIPS and cloudlets beyond ``n_cloudlets`` get ``valid=False``, so
    heterogeneous variants stack into one batch and padded rows keep finish
    time exactly 0 (the scan core's sentinel-segment invariant).

    ``mips_dist`` selects the VM-capacity distribution family: 0 = uniform
    over ``vm_mips_range``, 1 = fixed at the range midpoint, 2 = bimodal
    (each VM at the low or high end, fair coin).

    ``n_datacenters`` (optional, traced) is the datacenter-topology axis:
    VMs are struck round-robin across that many datacenters, each datacenter
    carrying a seed-deterministic capacity factor in [0.5, 1.5], so the same
    VM population performs differently under different topologies.  The
    sentinel 0 (and ``None``) means FLAT topology — a bit-exact ×1.0 no-op,
    so pre-axis results are unchanged.  Padded VMs stay at exactly 0 MIPS.
    """
    V, C = cfg.n_vms, cfg.n_cloudlets
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    lo, hi = cfg.vm_mips_range
    mips_u = jax.random.uniform(k1, (V,), minval=lo, maxval=hi)
    mips_f = jnp.full((V,), (lo + hi) / 2.0, jnp.float32)
    mips_b = jnp.where(jax.random.bernoulli(k3, 0.5, (V,)), hi, lo)
    vm_mips = jnp.select([mips_dist == 0, mips_dist == 1],
                         [mips_u, mips_f], mips_b)
    vm_valid = jnp.arange(V) < n_vms
    vm_mips = jnp.where(vm_valid, vm_mips, 0.0)

    if n_datacenters is not None:
        n_dc = jnp.asarray(n_datacenters, jnp.int32)
        kd = jax.random.fold_in(key, 3)    # independent of k1/k2/k3 draws
        D = max(int(cfg.n_datacenters), 1)
        dc_factor = jax.random.uniform(kd, (D,), minval=0.5, maxval=1.5)
        vm_dc = jnp.arange(V, dtype=jnp.int32) % jnp.maximum(n_dc, 1)
        factor = jnp.where(n_dc > 0, dc_factor[vm_dc], 1.0)
        vm_mips = vm_mips * factor         # flat: ×1.0, bit-exact no-op

    lo, hi = cfg.cloudlet_mi_range
    mi = jax.random.uniform(k2, (C,), minval=lo, maxval=hi) * mi_scale
    valid = jnp.arange(C) < n_cloudlets
    mi = jnp.where(valid, mi, 0.0)
    return vm_mips, vm_valid, mi, valid


def _grid_workload(cfg, mi, valid, is_loaded):
    """Per-variant ``isLoaded`` checksum: every live cloudlet runs the real
    workload payload (``cloudsim._one_workload``) and the sum is the
    variant's checksum — 0.0 when the variant's ``is_loaded`` flag is off
    (padded/invalid cloudlets contribute exactly 0 either way)."""
    from repro.core.cloudsim import _one_workload, workload_iters

    iters = workload_iters(cfg)
    per = jax.vmap(lambda m: _one_workload(m, cfg.workload_dim, iters))(
        jnp.where(valid, mi, 0.0))
    total = jnp.where(valid, per, 0.0).sum()
    return jnp.where(is_loaded > 0, total, 0.0)


def _grid_scenario(cfg, with_workload, seed, mi_scale, broker, n_vms,
                   n_cloudlets, mips_dist, n_datacenters, is_loaded):
    """One full scenario — entities + broker + workload + scan core — pure-
    functionally (no DataGrid side effects) with every grid axis a traced
    scalar, so the whole pipeline vmaps over a heterogeneous variant stack.
    ``with_workload`` is STATIC: grids without an ``is_loaded`` axis never
    trace the workload payload at all."""
    from repro.core.cloudsim import matchmaking_assign_masked

    vm_mips, vm_valid, mi, valid = grid_scenario_inputs(
        cfg, seed, mi_scale, n_vms, n_cloudlets, mips_dist,
        n_datacenters=n_datacenters)
    ids = jnp.arange(cfg.n_cloudlets, dtype=jnp.int32)
    rr = (ids % n_vms).astype(jnp.int32)
    mm = matchmaking_assign_masked(ids, mi, vm_mips, vm_valid)
    assign = jnp.where(broker == BROKER_IDS["round_robin"], rr, mm)
    workload = (_grid_workload(cfg, mi, valid, is_loaded) if with_workload
                else jnp.zeros((), jnp.float32))
    finish, makespan = simulate_completion_scan(
        assign, mi, vm_mips, valid, use_kernel=cfg.use_kernel,
        kernel_chunk=cfg.kernel_chunk)
    return assign, finish, makespan, workload


@functools.lru_cache(maxsize=32)
def _batch_fn(cfg, with_workload):
    """Jitted vmap of the grid-scenario pipeline, cached per (hashable,
    frozen) config so repeated sweeps with the same cfg and batch shape
    reuse the compiled executable."""
    return jax.jit(jax.vmap(
        functools.partial(_grid_scenario, cfg, with_workload)))


@functools.lru_cache(maxsize=32)
def _batch_dist_fn(cfg, mesh, axis, with_workload):
    """Batch-sharded grid: the scenario vmap INSIDE the partitioned
    member_fn, so a grid of B variants shards B/n-per-member across the
    mesh — CloudSim-scale scenario throughput from data-parallel members."""
    from repro.core.executor import DistributedExecutor

    executor = DistributedExecutor(mesh, axis)

    def member_fn(local):
        return jax.vmap(
            functools.partial(_grid_scenario, cfg, with_workload))(*local)

    def call(*axes):
        return executor.execute_on_key_owners(member_fn, axes,
                                              out_specs=P(axis))

    return jax.jit(call)


def scenario_grid_job(cfg, with_workload: bool = False) -> DispatchJob:
    """The scenario grid as a dispatcher job: chunk items are the per-variant
    axis arrays, each member vmaps the scenario pipeline over its local
    variants, rows concatenate in submission order.  The signature is fully
    determined by the (frozen, hashable) config + the static workload gate,
    so every chunk of a geometry reuses one executable."""
    fn = functools.partial(_grid_scenario, cfg, with_workload)

    def member_fn(local, valid, *_):
        del valid                          # concat path: pad rows trimmed off
        return jax.vmap(fn)(*local)

    from repro.core.compat import kernel_path

    return DispatchJob(name="scenario_grid",
                       signature=("scenario_grid", cfg, with_workload),
                       member_fn=member_fn, reduce="concat",
                       kernel_path=kernel_path(cfg.use_kernel))


def _axis_array(value, B, dtype, name, id_map=None):
    """Normalize one grid axis to a (B,) array: scalars broadcast, str
    entries map through ``id_map`` (broker / MIPS-distribution names)."""
    if value is None:
        return None
    if isinstance(value, str) or np.isscalar(value):
        value = [value] * B
    vals = value if hasattr(value, "dtype") else np.asarray(value)
    if getattr(vals.dtype, "kind", "") in "USO":   # names -> ids
        vals = np.asarray([id_map[str(v)] for v in np.asarray(vals).ravel()])
    arr = jnp.asarray(vals, dtype)
    if arr.shape != (B,):
        raise ValueError(f"{name} must have shape ({B},), got {arr.shape}")
    return arr


def _batch_axis_args(cfg, seeds, *, mi_scale=None, broker=None, n_vms=None,
                     n_cloudlets=None, mips_dist=None, n_datacenters=None,
                     is_loaded=None):
    """Normalize the grid axes of a scenario batch into the positional
    operand stack ``_grid_scenario`` consumes: ``(seeds, scale, broker,
    n_vms, n_cloudlets, mips_dist, n_datacenters, is_loaded)``, each a (B,)
    array, plus the STATIC workload gate.  Shared by ``run_simulation_batch``
    and the resume path (``grid_batch_args``) so a restarted coordinator
    rebuilds bit-identical operands from the same cfg + grid."""
    if cfg.core != "scan":
        raise ValueError(
            f"run_simulation_batch only supports core='scan', got {cfg.core!r}")
    seeds = jnp.asarray(seeds, jnp.int32)
    B = seeds.shape[0]

    def default(arr, fill, dtype):
        return jnp.full((B,), fill, dtype) if arr is None else arr

    scale = default(_axis_array(mi_scale, B, jnp.float32, "mi_scale"),
                    1.0, jnp.float32)
    broker = default(_axis_array(broker, B, jnp.int32, "broker", BROKER_IDS),
                     BROKER_IDS[cfg.broker], jnp.int32)
    n_vms = default(_axis_array(n_vms, B, jnp.int32, "n_vms"),
                    cfg.n_vms, jnp.int32)
    n_cl = default(_axis_array(n_cloudlets, B, jnp.int32, "n_cloudlets"),
                   cfg.n_cloudlets, jnp.int32)
    n_dc = default(_axis_array(n_datacenters, B, jnp.int32, "n_datacenters"),
                   0, jnp.int32)
    with_workload = is_loaded is not None      # STATIC workload gate
    loaded = default(_axis_array(is_loaded, B, jnp.int32, "is_loaded"),
                     0, jnp.int32)
    # live counts must fit the padded shapes — JAX's clamping gather would
    # otherwise turn an oversized variant into silently-wrong results
    for name, arr, low, cap in (
            ("n_vms", n_vms, 1, cfg.n_vms),
            ("n_cloudlets", n_cl, 1, cfg.n_cloudlets),
            ("n_datacenters", n_dc, 0, cfg.n_datacenters),
            ("is_loaded", loaded, 0, 1)):
        if B == 0:
            break                        # nothing to validate (or run)
        lo, hi = int(arr.min()), int(arr.max())
        if lo < low or hi > cap:
            raise ValueError(f"{name} axis must lie in [{low}, {cap}] "
                             f"(the padded cfg shape), got [{lo}, {hi}]")
    mips_dist = default(_axis_array(mips_dist, B, jnp.int32, "mips_dist",
                                    MIPS_DIST_IDS),
                        MIPS_DIST_IDS["uniform"], jnp.int32)
    args = (seeds, scale, broker, n_vms, n_cl, mips_dist, n_dc, loaded)
    return args, with_workload


def run_simulation_batch(cfg, seeds, *, mi_scale=None, broker=None,
                         n_vms=None, n_cloudlets=None, mips_dist=None,
                         n_datacenters=None, is_loaded=None,
                         executor=None, dispatcher=None, chunk=None,
                         on_chunk=None, dispatch_ahead=None,
                         checkpoint=None) -> BatchSimulationResult:
    """Execute a multi-axis scenario GRID in a SINGLE jitted vmap.

    seeds: (B,) int array — one PRNG stream per scenario.  The optional grid
    axes are each a (B,) per-variant array (or a scalar applied to all):

      mi_scale      — float multiplier on cloudlet lengths (workload sweep)
      broker        — "round_robin" | "matchmaking" (names or BROKER_IDS ints)
      n_vms         — live VM count ≤ cfg.n_vms; the rest are 0-MIPS padding
      n_cloudlets   — live cloudlet count ≤ cfg.n_cloudlets; rest valid=False
      mips_dist     — "uniform" | "fixed" | "bimodal" (or MIPS_DIST_IDS ints)
      n_datacenters — datacenter-topology axis: VMs round-robin over that
                      many datacenters with seed-deterministic capacity
                      factors; 0 = flat topology (bit-exact no-op)
      is_loaded     — 0/1: attach the real ``isLoaded`` workload payload and
                      report its per-variant checksum (finish times are
                      untouched; padded rows keep finish exactly 0)

    The closed-form core has no data-dependent loop and every axis is a
    traced scalar, so B heterogeneous variants cost one XLA dispatch; ≥96
    variants per jit is the intended operating point.  With ``executor``
    (a multi-member mesh) the grid is sharded B/n-per-member: the scenario
    vmap runs inside the partitioned member_fn.  With ``dispatcher`` (an
    ``ElasticDispatcher``) the grid is submitted as a STREAMING job: cut
    into ``chunk``-variant chunks (grids larger than device memory), one
    compile per (geometry, job-signature), surviving IAS scale events
    between chunks (``on_chunk`` can feed ``observe_load``); the stream is
    ASYNC double-buffered — ``dispatch_ahead`` overrides the dispatcher's
    pipeline depth (0 = synchronous baseline), and the grid axes (jnp
    arrays) are chunked on DEVICE, never round-tripping to host.  ``cfg.
    use_kernel`` is honored; only the vmappable ``core="scan"`` is
    supported (the wave loop doesn't batch).
    """
    args, with_workload = _batch_axis_args(
        cfg, seeds, mi_scale=mi_scale, broker=broker, n_vms=n_vms,
        n_cloudlets=n_cloudlets, mips_dist=mips_dist,
        n_datacenters=n_datacenters, is_loaded=is_loaded)
    (seeds, scale, broker, n_vms, n_cl, mips_dist, n_dc, loaded) = args
    B = seeds.shape[0]

    report = None
    t0 = time.perf_counter()
    if dispatcher is not None and executor is not None:
        raise ValueError("pass either executor= (fixed mesh-sharded batch) "
                         "or dispatcher= (elastic chunk streaming), not "
                         "both — the dispatcher owns its own geometry")
    if dispatcher is not None:
        job = scenario_grid_job(cfg, with_workload)
        # deliver="host": the result dataclass materializes to numpy right
        # below, so the reduce lands on host directly — one gather, not a
        # sharded device concat plus a gather
        # checkpoint= journals the scenario stream (durable dispatch): a
        # long campaign killed mid-sweep resumes bit-identically via
        # ElasticDispatcher.resume with the same cfg/grid/chunking
        (assign, finish, makespans, workload), report = dispatcher.submit(
            job, args, chunk=chunk, on_chunk=on_chunk,
            dispatch_ahead=dispatch_ahead, deliver="host",
            checkpoint=checkpoint)
    elif executor is not None and executor.n_members > 1:
        n = executor.n_members
        pad = (-B) % n                   # round B up to a whole shard each
        if pad:
            args = tuple(jnp.concatenate([a, a[-1:].repeat(pad)])
                         for a in args)
        fn = _batch_dist_fn(cfg, executor.mesh, executor.axis, with_workload)
        assign, finish, makespans, workload = (o[:B] for o in fn(*args))
    else:
        assign, finish, makespans, workload = _batch_fn(cfg, with_workload)(
            *args)
    jax.block_until_ready(makespans)
    wall = time.perf_counter() - t0
    return BatchSimulationResult(
        vm_assign=np.asarray(assign), finish_times=np.asarray(finish),
        makespans=np.asarray(makespans),
        timings={"batch_total": wall, "per_scenario": wall / max(B, 1)},
        broker=np.asarray(broker), n_vms=np.asarray(n_vms),
        n_cloudlets=np.asarray(n_cl), mips_dist=np.asarray(mips_dist),
        n_datacenters=np.asarray(n_dc), is_loaded=np.asarray(loaded),
        workload_checksum=(np.asarray(workload) if with_workload else None),
        dispatch=(report.summary() if report is not None else None))


def make_scenario_grid(seeds: Sequence[int],
                       mi_scales: Sequence[float] = (1.0,),
                       brokers: Sequence[Union[str, int]] = ("round_robin",),
                       vm_counts: Sequence[int] = (0,),
                       cloudlet_counts: Sequence[int] = (0,),
                       mips_dists: Sequence[Union[str, int]] = ("uniform",),
                       dc_counts: Sequence[int] = (0,),
                       loaded: Sequence[int] = (0,),
                       ) -> Dict[str, np.ndarray]:
    """Cartesian product of grid axes → per-variant (B,) arrays, B = the
    product of axis lengths.  A 0 in ``vm_counts``/``cloudlet_counts`` means
    "the config's full count"; a 0 in ``dc_counts`` means flat datacenter
    topology; ``loaded`` entries are 0/1 ``isLoaded`` flags.  The sentinels
    are resolved against a config by ``run_scenario_grid(cfg, grid)``, the
    intended way to execute the product."""
    brokers = [BROKER_IDS[b] if isinstance(b, str) else int(b)
               for b in brokers]
    mips_dists = [MIPS_DIST_IDS[d] if isinstance(d, str) else int(d)
                  for d in mips_dists]
    axes = np.meshgrid(np.asarray(seeds, np.int32),
                       np.asarray(mi_scales, np.float32),
                       np.asarray(brokers, np.int32),
                       np.asarray(vm_counts, np.int32),
                       np.asarray(cloudlet_counts, np.int32),
                       np.asarray(mips_dists, np.int32),
                       np.asarray(dc_counts, np.int32),
                       np.asarray([int(v) for v in loaded], np.int32),
                       indexing="ij")
    flat = [a.ravel() for a in axes]
    return {"seeds": flat[0], "mi_scale": flat[1], "broker": flat[2],
            "n_vms": flat[3], "n_cloudlets": flat[4], "mips_dist": flat[5],
            "n_datacenters": flat[6], "is_loaded": flat[7]}


def _resolve_grid(cfg, grid: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Resolve a ``make_scenario_grid`` product against a config: 0-valued
    VM/cloudlet counts become the config's full counts, and an all-zero
    ``is_loaded`` axis is dropped so the workload payload is never traced
    for grids that don't use it (the STATIC gate)."""
    g = dict(grid)
    g["n_vms"] = np.where(np.asarray(g["n_vms"]) == 0, cfg.n_vms,
                          g["n_vms"]).astype(np.int32)
    g["n_cloudlets"] = np.where(np.asarray(g["n_cloudlets"]) == 0,
                                cfg.n_cloudlets,
                                g["n_cloudlets"]).astype(np.int32)
    if "is_loaded" in g and not np.asarray(g["is_loaded"]).any():
        g.pop("is_loaded")                # static gate: skip workload tracing
    return g


def grid_batch_args(cfg, grid: Dict[str, np.ndarray]):
    """Rebuild the (operand stack, dispatch job) of a scenario-grid stream
    from its cfg + grid — the resume-path counterpart of
    ``run_scenario_grid``.  ``ElasticDispatcher.resume`` needs the SAME
    args and job the original coordinator journaled so the environment
    signature verifies and replayed chunks are bit-identical; going through
    the same ``_resolve_grid`` + ``_batch_axis_args`` normalization
    guarantees that.  Returns ``(args, job, with_workload)``."""
    g = _resolve_grid(cfg, grid)
    seeds = g.pop("seeds")
    args, with_workload = _batch_axis_args(cfg, seeds, **g)
    return args, scenario_grid_job(cfg, with_workload), with_workload


def run_scenario_grid(cfg, grid: Dict[str, np.ndarray], *,
                      executor=None, dispatcher=None, chunk=None,
                      on_chunk=None, dispatch_ahead=None,
                      checkpoint=None) -> BatchSimulationResult:
    """Run a ``make_scenario_grid`` product through ``run_simulation_batch``
    (0-valued VM/cloudlet counts resolve to the config's full counts).
    With ``dispatcher``, the grid streams through the elastic dispatch
    middleware in ``chunk``-sized dispatches (see ``run_simulation_batch``).
    An ``is_loaded`` axis that is all-zero is dropped so the workload
    payload is never traced for grids that don't use it."""
    g = _resolve_grid(cfg, grid)
    seeds = g.pop("seeds")
    return run_simulation_batch(cfg, seeds, executor=executor,
                                dispatcher=dispatcher, chunk=chunk,
                                on_chunk=on_chunk,
                                dispatch_ahead=dispatch_ahead,
                                checkpoint=checkpoint, **g)
