"""MapReduce correctness — word count vs a numpy oracle, backend parity.

The thesis's dual-backend design promises the SAME job result from the
Hazelcast-style (member-local map + collective reduce) and Infinispan-style
(global auto-SPMD) execution models.  Word count reduces in int32, so the
contract here is exact: both backends BIT-identical to ``np.bincount`` and
to each other, across member counts {1, 2, 4}, chunked streaming included,
and through the Pallas histogram-kernel path (interpret mode off-TPU).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core.mapreduce import MapReduceEngine, make_corpus, word_count_job

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


@pytest.mark.parametrize("backend", ["hazelcast", "infinispan"])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_word_count_vs_numpy_oracle(backend, use_kernel):
    # file_len a multiple of the histogram kernel's 256-token block
    corpus = make_corpus(6, 512, vocab=48, seed=7)
    oracle = np.bincount(corpus.reshape(-1), minlength=48)
    eng = MapReduceEngine(mesh1(), backend=backend)
    out = eng.run(word_count_job(48, use_kernel=use_kernel),
                  jnp.asarray(corpus))
    np.testing.assert_array_equal(np.asarray(out), oracle)


@pytest.mark.parametrize("backend", ["hazelcast", "infinispan"])
def test_word_count_chunked_streaming_exact(backend):
    """Streaming the corpus in chunks (including a ragged last chunk) is
    bit-identical to the one-dispatch run — padding rows are masked out of
    the int32 reduction, never counted."""
    corpus = make_corpus(7, 256, vocab=32, seed=1)      # 7 % chunk != 0
    oracle = np.bincount(corpus.reshape(-1), minlength=32)
    eng = MapReduceEngine(mesh1(), backend=backend)
    for chunk in (1, 2, 3, 7):
        out = eng.run(word_count_job(32), jnp.asarray(corpus), chunk=chunk)
        np.testing.assert_array_equal(np.asarray(out), oracle), chunk
    assert eng.last_report.n_chunks == 1                # chunk=7: one go


def test_word_count_empty_and_degenerate():
    # single file, vocab larger than any token
    corpus = np.zeros((1, 16), np.int32)
    eng = MapReduceEngine(mesh1(), backend="hazelcast")
    out = np.asarray(eng.run(word_count_job(8), jnp.asarray(corpus)))
    assert out[0] == 16 and out[1:].sum() == 0


def test_backends_bit_identical_across_member_counts():
    """{1, 2, 4} members × both backends × kernel path: every run equals the
    numpy oracle EXACTLY (int32 reduction ⇒ bit-identity), including a file
    count (10) that no member count divides evenly."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core.mapreduce import MapReduceEngine, make_corpus, word_count_job

devs = jax.devices()
corpus = make_corpus(10, 512, vocab=64, seed=3)    # 10 files: ragged shards
oracle = np.bincount(corpus.reshape(-1), minlength=64)
outs = {}
for M in (1, 2, 4):
    mesh = Mesh(np.array(devs[:M]), ("data",))
    for backend in ("hazelcast", "infinispan"):
        for use_kernel in (False, True):
            out = np.asarray(MapReduceEngine(mesh, backend=backend).run(
                word_count_job(64, use_kernel=use_kernel),
                jnp.asarray(corpus)))
            assert np.array_equal(out, oracle), (M, backend, use_kernel)
            outs[(M, backend, use_kernel)] = out
# all configurations agree bit-for-bit with each other
base = outs[(1, "hazelcast", False)]
for k, v in outs.items():
    assert np.array_equal(base, v), k
# chunked streaming on 4 members, ragged chunks, kernel path
eng = MapReduceEngine(Mesh(np.array(devs), ("data",)), backend="hazelcast")
out = np.asarray(eng.run(word_count_job(64, use_kernel=True),
                         jnp.asarray(corpus), chunk=3))
assert np.array_equal(out, oracle)
assert eng.last_report.n_chunks == 4
print("OK")
"""], env=env, capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout + r.stderr


def _bincount_like_scatter(files, valid, vocab):
    """np.bincount with ``.at[ids].add(mode="drop")``'s index semantics: an
    id in [-vocab, 0) counts at id + vocab, any other id outside
    [0, vocab) is dropped; files with ``valid`` False count nothing."""
    ids = np.asarray(files, np.int64)[np.asarray(valid)].reshape(-1)
    ids = np.where(ids < 0, ids + vocab, ids)
    return np.bincount(ids[(ids >= 0) & (ids < vocab)],
                       minlength=vocab).astype(np.int32)


def _ids(case, vocab, n_files, n, rng):
    if case == "in_range":
        return rng.integers(0, vocab, (n_files, n))
    if case == "wrapped_negative":
        return rng.integers(-vocab, 0, (n_files, n))
    if case == "out_of_range":
        ids = rng.integers(-vocab - 70, vocab + 70, (n_files, n))
        ids[0, :4] = [np.iinfo(np.int32).min, np.iinfo(np.int32).max,
                      -vocab - 1, vocab]
        return ids
    assert case == "one_repeated_id"
    return np.full((n_files, n), vocab // 2)


@pytest.mark.parametrize("case", ["in_range", "wrapped_negative",
                                  "out_of_range", "one_repeated_id"])
@pytest.mark.parametrize("vocab", [1, 8, 48, 300, 1000, 4096, 20000])
def test_onehot_contraction_matches_bincount_and_scatter(vocab, case):
    """The TPU's map (``onehot_counts``: int8 one-hots of each id's high and
    low part, contracted with int32 accumulation) counts exactly what the
    scatter-add counts, bit for bit, vmapped over files and masked as the
    dispatch job masks them.  300 and 48 factor into non-square H x L, and
    20000 (157 x 128) takes int16 parts.  The
    last file is padding (``valid`` False) and must not count.  The CPU
    engine lowers the scatter, so this is what guards the TPU branch."""
    from repro.core.mapreduce import onehot_counts, scatter_counts

    rng = np.random.default_rng(vocab)
    files = _ids(case, vocab, 5, 1000, rng).astype(np.int32)
    valid = np.array([True] * 4 + [False])

    def masked_sum(fn):
        counts = jax.vmap(lambda f: fn(f, vocab))(jnp.asarray(files))
        return np.asarray(jnp.where(jnp.asarray(valid)[:, None], counts,
                                    0).sum(axis=0))

    got = masked_sum(onehot_counts)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, _bincount_like_scatter(files, valid,
                                                              vocab))
    np.testing.assert_array_equal(got, masked_sum(scatter_counts))


def test_word_count_reports_its_map_path():
    """The dispatch summary names the path that counted: the scatter on the
    CPU, the Pallas kernel with ``use_kernel``; the word-count job picks the
    one-hot contraction on the TPU up to ``ONEHOT_MAX_VOCAB``."""
    from repro.core.mapreduce import ONEHOT_MAX_VOCAB, word_weight_job

    corpus = jnp.asarray(make_corpus(4, 256, vocab=16, seed=2))
    eng = MapReduceEngine(mesh1(), backend="hazelcast")
    for job, path in ((word_count_job(16), "scatter"),
                      (word_count_job(16, use_kernel=True), "kernel"),
                      (word_weight_job(16), "scatter")):
        eng.run(job, corpus)
        assert eng.last_report.summary()["map_path"] == path
    assert word_count_job(16).map_paths == {"tpu": "mxu_onehot",
                                            "default": "scatter"}
    assert word_count_job(ONEHOT_MAX_VOCAB + 1).map_paths == {
        "default": "scatter"}
