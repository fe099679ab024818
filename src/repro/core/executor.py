"""DistributedExecutor — ``IExecutorService`` over a mesh.

``execute_on_key_owners(fn, data)`` ships ``fn`` to every shard and runs it on
the locally-resident partition (the paper's ``executeOnKeyOwner`` data-locality
principle): implemented with ``shard_map``, so *logic moves to the data* and no
operand crosses the interconnect.  ``submit`` mirrors plain ExecutorService
round-robin task submission (a vmapped task batch partitioned over members).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def cut_chunk(src, lo, n_live, length: int):
    """Fixed-shape chunk cut: rows [lo, lo+length) of every leaf of ``src``
    plus the live-row mask ``arange(length) < n_live``.  ``length`` is
    static; ``lo``/``n_live`` may be traced, so one program serves every
    chunk of a stream.  Traced inside the dispatcher's in-place executable
    (the window fuses into its consumer) and inside ``_slice_chunk``."""
    sl = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, lo, length, axis=0), src)
    valid = jnp.arange(length, dtype=jnp.int32) < n_live
    return sl, valid


@functools.partial(jax.jit, static_argnames=("length",))
def _slice_chunk(src, lo, n_live, *, length):
    """``cut_chunk`` as its own program: the chunk is written out as a fresh
    buffer (one executable per chunk shape)."""
    return cut_chunk(src, lo, n_live, length)


class DistributedExecutor:
    def __init__(self, mesh: Mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis

    @classmethod
    def for_devices(cls, devices, axis: str = "data") -> "DistributedExecutor":
        """Executor over an explicit device list — the elastic cluster
        rebuilds one per scale event from its (fixed) device pool."""
        import numpy as np
        return cls(Mesh(np.array(devices), (axis,)), axis)

    @property
    def n_members(self) -> int:
        return self.mesh.shape[self.axis]

    @property
    def device_list(self):
        """The devices backing this executor's mesh in axis order — the
        member-slot → device map the dispatcher's fault-injection launch
        hook consumes (slot i of the mesh is device_list[i])."""
        return list(self.mesh.devices.ravel())

    def sharding(self, spec: P) -> NamedSharding:
        """A NamedSharding on this executor's mesh — the placement vocabulary
        the dispatcher's auto-SPMD (global_fn) path speaks."""
        return NamedSharding(self.mesh, spec)

    def put(self, value, spec: P = None):
        """Place ``value`` on the mesh: partitioned on dim 0 by default
        (scalars replicate — there is no dim to partition), replicated with
        ``P()``, or any explicit spec."""
        value = jnp.asarray(value)
        if spec is None:
            spec = (P() if value.ndim == 0
                    else P(self.axis, *([None] * (value.ndim - 1))))
        return jax.device_put(value, self.sharding(spec))

    def slice_chunk(self, src, lo: int, length: int, n_live: int):
        """Copy a fixed-shape ``length``-row chunk starting at row ``lo`` out
        of a DEVICE-resident item source, entirely on device (``lax.
        dynamic_slice`` + a valid mask for the first ``n_live`` rows) — a
        corpus produced by a previous job never round-trips to host just to
        be re-chunked.  The dispatcher takes this path only where handing
        the whole source to the job's executable would move it: a multi-
        member mesh, a source on another device, or a deterministic job's
        rows stage; a one-member stream over a co-located source has the
        job's executable cut its window in place (``cut_chunk``) instead.
        The caller must guarantee ``lo + length`` does not exceed the
        source's rows (the dispatcher pads the source once, at stream
        start); ``dynamic_slice`` would otherwise clamp ``lo`` and silently
        shift the window.  ``lo``/``n_live`` ride into the jit as weak-typed
        scalars — no per-chunk eager device_put."""
        return _slice_chunk(src, lo, n_live, length=length)

    def execute_on_key_owners(self, fn: Callable, data, *, out_specs=None,
                              replicated_args=()):
        """Run ``fn(local_shard, *replicated_args)`` on each member's partition.

        data: array (or pytree) partitioned on dim 0 over the executor axis.
        fn must be shape-polymorphic in dim 0 (it receives 1/n of the rows).
        """
        in_spec = P(self.axis)
        out_specs = out_specs if out_specs is not None else P(self.axis)
        rep = P()

        f = jax.shard_map(
            lambda d, *r: fn(d, *r), mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: in_spec, data),
                      *[jax.tree_util.tree_map(lambda _: rep, a)
                        for a in replicated_args]),
            out_specs=out_specs, check_vma=False)
        return f(data, *replicated_args)

    def map_reduce(self, map_fn: Callable, reduce_kind: str, data,
                   *, replicated_args=()):
        """map per shard then a collective reduce ('sum'|'max'|'concat')."""
        axis = self.axis

        def body(local, *rep):
            mapped = map_fn(local, *rep)
            if reduce_kind == "sum":
                return jax.lax.psum(mapped, axis)
            if reduce_kind == "max":
                return jax.lax.pmax(mapped, axis)
            if reduce_kind == "concat":
                return jax.lax.all_gather(mapped, axis, tiled=True)
            raise ValueError(reduce_kind)

        f = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(axis), data),
                      *[jax.tree_util.tree_map(lambda _: P(), a)
                        for a in replicated_args]),
            out_specs=P(), check_vma=False)
        return f(data, *replicated_args)

    # -- member-side collectives: only valid INSIDE an execute_on_key_owners
    #    (or map_reduce) body, where the executor axis is bound by shard_map.

    def member_id(self):
        """This member's index on the executor axis (0..n_members-1)."""
        return jax.lax.axis_index(self.axis)

    def all_to_all(self, x, split_axis: int = 0, concat_axis: int = 0):
        """Exchange: scatters ``split_axis`` (length n_members) across the
        members and gathers the received blocks along ``concat_axis`` — the
        owner-keyed cloudlet re-home of the distributed scan core."""
        return jax.lax.all_to_all(x, self.axis, split_axis, concat_axis)

    def psum(self, x):
        return jax.lax.psum(x, self.axis)

    def pmax(self, x):
        return jax.lax.pmax(x, self.axis)

    def submit(self, task_fn: Callable, args_batch):
        """ExecutorService.submit of a task batch: tasks are round-robin
        partitioned over members and vmapped locally."""
        def local(batch):
            return jax.vmap(task_fn)(batch)
        return self.execute_on_key_owners(local, args_batch)
