"""The simulated cloud's entities, drawn from a seed as the configuration
states, independently of the program.

A simulation of ``seed`` draws its VMs' MIPS and its cloudlets' lengths from
``PRNGKey(seed)`` split in two: ``uniform(k1, (V,))`` over the MIPS range and
``uniform(k2, (C,))`` over the length range.
"""
from __future__ import annotations

import jax
import numpy as np


def simulation(seed: int, n_vms: int, n_cloudlets: int, mips_range,
               mi_range):
    """(VM MIPS (V,), cloudlet lengths (C,)) of one simulation, float32."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    mips = jax.random.uniform(k1, (n_vms,), minval=mips_range[0],
                              maxval=mips_range[1])
    mi = jax.random.uniform(k2, (n_cloudlets,), minval=mi_range[0],
                            maxval=mi_range[1])
    return np.asarray(mips), np.asarray(mi)

