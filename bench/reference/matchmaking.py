"""Plain references of Cloud2Sim's two brokers (arXiv 1601.03980, ch. 4-5).

Matchmaking: a cloudlet of length ``mi`` needs a VM of at least
``mi / max_mi * headroom * (largest MIPS)``; among the adequate VMs, taken
in ascending MIPS order (ties by VM index), it binds to the
``(cloudlet id mod number of candidates)``-th.  A cloudlet that no VM can
hold goes to the largest VM.  Round robin: cloudlet ``i`` goes to VM
``i mod n_vms``.

The reference runs in float64 (or, for the lower-precision control, in
bfloat16).  Where a cloudlet's requirement lies within ``band`` (relative)
of a VM's MIPS, float32 rounding in the program may fairly put the
boundary on either side, so the reference returns both answers.
"""
from __future__ import annotations

import numpy as np


def matchmaking(mi: np.ndarray, mips: np.ndarray, *, max_mi: float,
                headroom: float, dtype=np.float64, band: float = 1e-6):
    """(expected VM, also-accepted VM) per cloudlet, for cloudlets ``mi``
    (ids 0..C-1) over the live VMs ``mips``.  The second array equals the
    first wherever the requirement is not within ``band`` of a boundary."""
    mips_d = np.asarray(mips).astype(dtype)
    order = np.argsort(mips_d, kind="stable")
    sorted_mips = mips_d[order]
    n = mips_d.shape[0]
    need = (np.asarray(mi).astype(dtype) / dtype(max_mi)
            * dtype(dtype(headroom) * mips_d.max())).astype(dtype)
    ids = np.arange(need.shape[0], dtype=np.int64)

    def pick(req):
        first = np.minimum(np.searchsorted(sorted_mips, req, side="left"),
                           n - 1)
        return order[first + ids % (n - first)]

    want = pick(need)
    if band <= 0:
        return want, want
    lo = pick((need.astype(np.float64) * (1 - band)).astype(dtype))
    hi = pick((need.astype(np.float64) * (1 + band)).astype(dtype))
    return want, np.where(lo != want, lo, hi)


def round_robin(n_cloudlets: int, n_vms: int) -> np.ndarray:
    return np.arange(n_cloudlets, dtype=np.int64) % n_vms


def mismatches(got: np.ndarray, want: np.ndarray,
               also: np.ndarray) -> np.ndarray:
    """Ids of the cloudlets whose VM is neither the expected nor the
    accepted one."""
    got = np.asarray(got)
    return np.nonzero((got != want) & (got != also))[0]
