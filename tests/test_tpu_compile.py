"""The main path's kernels compile for a TPU v5e chip.

No chip is attached: ``jax.experimental.topologies`` describes a v5e:2x2
host and XLA's TPU compiler compiles for it, refusing what the chip would
refuse (blocks off the (8, 128) tiling, scalar reads from vector memory,
more VMEM than a kernel may use).  Nothing runs, so these tests say nothing
about results or times; ``test_kernel_parity.py`` pins the results under the
interpreter.  ``interpret=False`` is passed explicitly because the default
backend here is the CPU.  The topology is described inside a fixture, never
at import: only one process may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

C = 1 << 20
V = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("chunk", [64, 1024])
def test_seg_cumsum_v2_compiles(one_chip, chunk):
    from repro.kernels.seg_scan.v2 import seg_cumsum_v2

    hlo = _compile(lambda t, s: seg_cumsum_v2(t, s, chunk=chunk,
                                              interpret=False),
                   _shape(one_chip, (C,), jnp.float32),
                   _shape(one_chip, (C,), jnp.bool_))
    assert "tpu_custom_call" in hlo


def test_scatter_finish_v2_compiles(one_chip):
    from repro.kernels.seg_scan.v2 import scatter_finish_v2

    hlo = _compile(lambda f, o, s: scatter_finish_v2(f, o, s,
                                                     interpret=False),
                   _shape(one_chip, (C,), jnp.float32),
                   _shape(one_chip, (C,), jnp.int32),
                   _shape(one_chip, (C,), jnp.bool_))
    assert "tpu_custom_call" in hlo


def test_histogram_kernel_compiles(one_chip):
    from repro.kernels.histogram.kernel import histogram_kernel

    hlo = _compile(lambda t: histogram_kernel(t, 65536, interpret=False),
                   _shape(one_chip, (C,), jnp.int32))
    assert "tpu_custom_call" in hlo


def test_des_scan_core_with_kernels_compiles(one_chip):
    """The jitted DES core on 1M cloudlets x 1024 VMs, kernels compiled."""
    from repro.core.des_scan import simulate_completion_scan_jit

    args = (_shape(one_chip, (C,), jnp.int32),
            _shape(one_chip, (C,), jnp.float32),
            _shape(one_chip, (V,), jnp.float32),
            _shape(one_chip, (C,), jnp.bool_))
    hlo = simulate_completion_scan_jit.lower(
        *args, use_kernel=True, interpret=False,
        kernel_chunk=128).compile().as_text()
    assert hlo.count("tpu_custom_call") >= 2       # scan + scatter


def test_exchange_core_compiles_on_four_chips(topo):
    """The owner-keyed exchange core over a 4-chip mesh: one program, with
    the all-to-all the compiler must place between the chips."""
    from repro.core.des_scan import _dist_core_exchange

    n = 1 << 16
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    rep, part = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    fn = _dist_core_exchange(mesh, "data", V, n, n // 4, False, None)
    hlo = fn.lower(_shape(rep, (V,), jnp.int32),
                   _shape(part, (n,), jnp.int32),
                   _shape(part, (n,), jnp.float32),
                   _shape(rep, (V,), jnp.float32),
                   _shape(part, (n,), jnp.bool_)).compile().as_text()
    assert "all-to-all" in hlo


@pytest.mark.parametrize("vocab, path", [(1000, "convolution"),
                                         (65537, "scatter")])
def test_word_count_map_lowers_per_vocab(one_chip, vocab, path):
    """Lowered for the chip, word count's map is the int8 one-hot
    contraction (a convolution on the MXU, no scatter) up to
    ``ONEHOT_MAX_VOCAB``, and the scatter-add above it; vmapped over a
    chunk's files and masked as the dispatch job does."""
    from repro.core.mapreduce import word_count_job

    job = word_count_job(vocab)

    def counts(files, valid):
        c = jax.vmap(job.map_fn)(files)
        return jnp.where(valid[:, None], c, 0).sum(axis=0)

    hlo = _compile(counts, _shape(one_chip, (8, 4096), jnp.int32),
                   _shape(one_chip, (8,), jnp.bool_))
    other = {"convolution": "scatter", "scatter": "convolution"}[path]
    assert path in hlo and other not in hlo


def _entry_arrays(hlo):
    """(dtype, elements) of each array an instruction of ``hlo``'s ENTRY
    computation writes.  Parameters are read, not written, and the values
    inside a fusion's body never leave the chip's fast memory."""
    body = hlo[hlo.index("\nENTRY"):]
    body = body[:body.index("\n}")]
    arrays = []
    for line in body.splitlines()[1:]:
        rhs = line.partition(" = ")[2]
        if not rhs or " parameter(" in rhs:
            continue
        if rhs.startswith("("):          # a tuple: up to its closing paren
            depth = 0
            for end, ch in enumerate(rhs):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            typ = rhs[:end + 1]
        else:
            typ = rhs.split(" ", 1)[0]
        for dtype, dims in re.findall(r"([a-z]+\d*)\[([\d,]*)\]", typ):
            arrays.append((dtype, int(np.prod(
                [int(d) for d in dims.split(",") if d]))))
    return arrays


def test_word_count_cuts_its_chunk_in_place(topo, one_chip):
    """The dispatcher's in-place executable for word count, compiled for
    the chip with a chunk of 8 files of 65,536 ids out of a source of 32:
    the window's dynamic slice fuses into the pass that narrows the ids,
    so nothing writes an int32 array of the chunk's size.  The copy
    program (``_slice_chunk``) is the control: it writes one."""
    from repro.core.dispatch import ElasticDispatcher
    from repro.core.executor import _slice_chunk
    from repro.core.mapreduce import dispatch_job_for, word_count_job

    rows, width, L = 32, 65536, 8
    src = _shape(one_chip, (rows, width), jnp.int32)
    d = ElasticDispatcher(devices=topo.devices[:1], start_members=1)
    job = dispatch_job_for(word_count_job(1000))
    assert d._reads_in_place(job, src)
    fn = d._executable(job, src, (), L, in_place=True)
    hlo = fn.lower(src, 0, L).compile().as_text()
    copy = _slice_chunk.lower(src, 0, L, length=L).compile().as_text()

    def chunk_sized(text):
        return [a for a in _entry_arrays(text)
                if a[0] == "s32" and a[1] >= L * width]

    assert hlo.startswith("HloModule jit_dispatch_mapreduce_word_count")
    assert "convolution" in hlo               # the MXU map, as on the chip
    assert chunk_sized(hlo) == []
    assert ("s32", L * width) in chunk_sized(copy)
