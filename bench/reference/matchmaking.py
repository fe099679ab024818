"""Plain references of Cloud2Sim's two brokers (arXiv 1601.03980, ch. 4-5).

Matchmaking: a cloudlet of length ``mi`` needs a VM of at least
``mi / max_mi * headroom * (largest MIPS)``; among the adequate VMs, taken
in ascending MIPS order (ties by VM index), it binds to the
``(cloudlet id mod number of candidates)``-th.  A cloudlet that no VM can
hold goes to the largest VM.  Round robin: cloudlet ``i`` goes to VM
``i mod n_vms``.

The reference runs in float64 (or, for the lower-precision control, in
bfloat16).  The program computes the requirement in float32, which may land
it anywhere within ``band`` (relative) of the float64 one, and so past any
VM whose MIPS lies in that band.  The reference therefore accepts a VM
exactly when the rule picks it for some requirement inside the band: for
every first adequate position ``f`` (in ascending MIPS order) that such a
requirement gives, the VM at position ``f + id mod (n - f)``.  Where no VM's
MIPS lies in the band, that is the expected VM alone.
"""
from __future__ import annotations

import numpy as np


def _pick(order: np.ndarray, first: np.ndarray, ids: np.ndarray):
    """The rule's VM for each cloudlet ``ids`` whose first adequate position
    in ``order`` (the VMs in ascending MIPS, ties by index) is ``first``."""
    n = order.shape[0]
    return order[first + ids % (n - first)]


def matchmaking(mi: np.ndarray, mips: np.ndarray, *, max_mi: float,
                headroom: float, dtype=np.float64, band: float = 1e-6):
    """(expected VM per cloudlet, the VMs in ascending MIPS order, lowest and
    highest first adequate position per cloudlet), for cloudlets ``mi`` (ids
    0..C-1) over the live VMs ``mips``.  The positions span the requirements
    within ``band`` of the one computed in ``dtype``; both equal the
    expected VM's first position where no VM's MIPS lies in the band."""
    mips_d = np.asarray(mips).astype(dtype)
    order = np.argsort(mips_d, kind="stable")
    sorted_mips = mips_d[order]
    n = mips_d.shape[0]
    need = (np.asarray(mi).astype(dtype) / dtype(max_mi)
            * dtype(dtype(headroom) * mips_d.max())).astype(dtype)
    ids = np.arange(need.shape[0], dtype=np.int64)

    def first(req):
        return np.minimum(np.searchsorted(sorted_mips, req, side="left"),
                          n - 1)

    f = first(need)
    want = _pick(order, f, ids)
    if band <= 0:
        return want, order, f, f
    wide = need.astype(np.float64)
    return (want, order, first((wide * (1 - band)).astype(dtype)),
            first((wide * (1 + band)).astype(dtype)))


def round_robin(n_cloudlets: int, n_vms: int) -> np.ndarray:
    return np.arange(n_cloudlets, dtype=np.int64) % n_vms


def mismatches(got: np.ndarray, order: np.ndarray, first_lo: np.ndarray,
               first_hi: np.ndarray) -> np.ndarray:
    """Ids of the cloudlets whose VM ``got`` the rule picks for no first
    adequate position from ``first_lo`` to ``first_hi`` in ``order``, as
    ``matchmaking`` gives them in float64."""
    got = np.asarray(got)
    ids = np.arange(got.shape[0], dtype=np.int64)
    ok = np.zeros(got.shape[0], bool)
    for k in range(int(np.max(first_hi - first_lo, initial=0)) + 1):
        ok |= got == _pick(order, np.minimum(first_lo + k, first_hi), ids)
    return np.nonzero(~ok)[0]
