"""Plain reference of word count: ``np.bincount`` over every token."""
from __future__ import annotations

import numpy as np


def counts(tokens: np.ndarray, vocab: int) -> np.ndarray:
    """Occurrences of each word id in ``tokens``, exact, int64."""
    return np.bincount(np.asarray(tokens).reshape(-1), minlength=vocab)


def counts_bf16(tokens, vocab: int) -> np.ndarray:
    """The same count accumulated in bfloat16 on the default device: the
    lower-precision control."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def count(t):
        t = t.reshape(-1)
        return jnp.zeros((vocab,), jnp.bfloat16).at[t].add(
            jnp.ones(t.shape, jnp.bfloat16))

    return np.asarray(count(tokens)).astype(np.float64)
