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
