"""Seeded traffic: the same seed gives the same bytes, ids stay in range,
the corpus follows the uniform pmf, and the configuration's corpus is the
source's datasize in words."""
import os

import numpy as np
import pytest

import generator
from benchtools import BENCH_DIR, load

BIG = 2 ** 31 + 12345          # seeds beyond 32 signed bits


def test_derive_is_deterministic_and_31_bit():
    a = generator.derive(BIG, 1, 5, n=32)
    assert np.array_equal(a, generator.derive(BIG, 1, 5, n=32))
    assert not np.array_equal(a, generator.derive(BIG, 1, 6, n=32))
    assert not np.array_equal(a, generator.derive(BIG + 1, 1, 5, n=32))
    assert a.min() >= 0 and a.max() < 2 ** 31


def test_corpus_same_seed_same_bytes_ids_in_range():
    wc = generator.entry_module("wordcount", BENCH_DIR)
    a = np.asarray(wc.corpus(7, 8, 1024, 100))
    b = np.asarray(wc.corpus(7, 8, 1024, 100))
    c = np.asarray(wc.corpus(8, 8, 1024, 100))
    assert a.dtype == np.int32 and a.shape == (8, 1024)
    assert a.tobytes() == b.tobytes()
    assert a.tobytes() != c.tobytes()
    assert a.min() >= 0 and a.max() < 100


def test_corpus_histogram_is_uniform_over_the_vocabulary():
    wc = generator.entry_module("wordcount", BENCH_DIR)
    vocab, n = 1000, 1 << 20
    tok = np.asarray(wc.corpus(3, 4, n // 4, vocab)).ravel()
    freq = np.bincount(tok, minlength=vocab) / tok.size
    p = 1.0 / vocab
    sigma = np.sqrt(p * (1 - p) / tok.size)
    assert np.all(np.abs(freq - p) < 6 * sigma)


def test_large_corpus_is_the_profile_datasize_in_words():
    cfg = load(os.path.join(BENCH_DIR, "configs",
                            "hibench_wordcount_large.json"))
    c = cfg["corpus"]
    words = cfg["profile"]["hibench.wordcount.large.datasize"] \
        / c["bytes_per_word"]
    assert c["n_files"] * c["file_len"] == pytest.approx(words, rel=1e-3)
    assert c["file_len"] % 128 == 0 and c["n_files"] % c["chunks"] == 0


@pytest.mark.parametrize("request_index", [0, 1, 17])
def test_simulation_requests_depend_on_seed_and_index_only(request_index):
    a = generator.derive(BIG, 1, request_index)
    assert np.array_equal(a, generator.derive(BIG, 1, request_index))
    assert not np.array_equal(a, generator.derive(BIG, 1,
                                                  request_index + 1))
    assert not np.array_equal(a, generator.derive(BIG, 0))
