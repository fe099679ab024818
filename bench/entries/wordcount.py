"""``MapReduceEngine.run`` over a device-resident corpus: one whole word count
per request.

The corpus is the configuration's ``corpus``: ``n_files`` files of
``file_len`` int32 word ids, each drawn uniformly over ``vocab`` words, as
RandomTextWriter draws every word of its sentences uniformly from its word
list.  One jitted call makes the whole corpus on the device from the seed.
The engine streams it through the map in ``chunks`` chunks, cut on the
device.  Every request counts the same corpus, and every request's counts
are checked against ``np.bincount``.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import generator
from reference import wordcount


@functools.lru_cache(maxsize=None)
def _draw(n_files: int, file_len: int, vocab: int):
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda key: jax.random.randint(
        key, (n_files, file_len), 0, vocab, jnp.int32))


def corpus(seed: int, n_files: int, file_len: int, vocab: int, device=None):
    """The (n_files, file_len) int32 corpus of ``seed`` (a 31-bit int), on
    ``device``."""
    import jax
    key = jax.device_put(jax.random.PRNGKey(seed), device)
    return _draw(n_files, file_len, vocab)(key)


def count_mismatch(results, tokens, vocab: int) -> dict:
    """Every result against ``np.bincount`` of the corpus: the most
    vocabulary bins that any result got wrong."""
    want = wordcount.counts(tokens, vocab)
    worst = 0
    for got in results:
        got = np.asarray(got)
        wrong = (np.count_nonzero(got != want) if got.shape == want.shape
                 else vocab)
        worst = max(worst, int(wrong))
    return {"count_mismatch": worst}


class Entry(generator.Base):

    def setup(self):
        from repro.core.dispatch import ElasticDispatcher
        from repro.core.mapreduce import MapReduceEngine, word_count_job

        c = self.config["corpus"]
        t = time.perf_counter()
        self.files = corpus(int(generator.derive(self.seed, 0)[0]),
                            c["n_files"], c["file_len"], c["vocab"],
                            self.devices[0]).block_until_ready()
        self.phases["corpus"] = time.perf_counter() - t
        t = time.perf_counter()
        self.engine = MapReduceEngine(dispatcher=ElasticDispatcher(
            devices=self.devices, start_members=1,
            collect_stats=self.collect_stats))
        self.job = word_count_job(c["vocab"],
                                  use_kernel=self.config["use_kernel"])
        self.phases["engine"] = time.perf_counter() - t
        t = time.perf_counter()
        self._run()
        self.phases["warm-up request"] = time.perf_counter() - t
        self.results = []

    def _run(self):
        c = self.config["corpus"]
        out = self.engine.run(self.job, self.files,
                              chunk=c["n_files"] // c["chunks"])
        return np.asarray(out)

    def request(self, i: int) -> dict:
        self.results.append(self._run())
        c = self.config["corpus"]
        return {"work": {"tokens": c["n_files"] * c["file_len"]},
                "dispatch": self.engine.last_report.summary()}

    def release(self):
        self.tokens = np.asarray(self.files)
        self.files = self.engine = self.job = None

    def check(self, rng, dtype=np.float64) -> dict:
        vocab = self.config["corpus"]["vocab"]
        if dtype is not np.float64:
            return count_mismatch([wordcount.counts_bf16(self.tokens, vocab)],
                                  self.tokens, vocab)
        return count_mismatch(self.results, self.tokens, vocab)
