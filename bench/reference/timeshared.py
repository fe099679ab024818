"""Plain reference of CloudSim's time-shared cloudlet scheduler, one VM at a
time.

Every active cloudlet of a VM runs at ``mips / n_active``; the reference
steps from one completion to the next.  It knows nothing of sorting, scans
or segments.  Copied from the float64 per-VM reference of ``chip_smoke.py``,
with two changes: the arithmetic runs in a given dtype (float64 for the
reference, bfloat16 for the lower-precision control), and a step completes
the cloudlets whose remaining length equals the smallest one, a test that is
exact in any precision.
"""
from __future__ import annotations

import numpy as np


def finish_times(mi: np.ndarray, mips: float, dtype=np.float64) -> np.ndarray:
    """Finish times (seconds) of the cloudlets of ONE time-shared VM of
    ``mips`` million instructions per second, for cloudlet lengths ``mi``
    (million instructions), computed in ``dtype``.  Cloudlets of length 0
    never run and keep finish time 0."""
    rem = np.asarray(mi).astype(dtype)
    fin = np.zeros(rem.shape, dtype)
    live = np.nonzero(rem > 0)[0]
    rem = rem[live]
    mips = dtype(mips)
    now = dtype(0)
    while live.size:
        rate = dtype(mips / dtype(live.size))
        low = rem.min()
        now = dtype(now + dtype(low / rate))
        done = rem <= low
        fin[live[done]] = now
        keep = ~done
        live = live[keep]
        rem = (rem[keep] - low).astype(dtype)
    return fin


def finish_times_all(assign: np.ndarray, mi: np.ndarray,
                     mips: np.ndarray) -> np.ndarray:
    """Finish times of every cloudlet of every VM at once, in float64.

    On a time-shared VM whose k cloudlets sorted by length are
    m_1 <= ... <= m_k, the j-th finishes at
    sum_{i<=j} (m_i - m_{i-1}) * (k - i + 1) / mips: while the i-th
    shortest runs out, k - i + 1 cloudlets share the VM.  This is the same
    time-shared semantics as ``finish_times``, summed in closed form so
    that a million cloudlets take a second; the tests hold the two to each
    other.  Cloudlets of length 0 or on a VM of 0 MIPS keep finish time 0.
    """
    assign = np.asarray(assign, np.int64)
    mi = np.asarray(mi, np.float64)
    mips = np.asarray(mips, np.float64)
    live = (mi > 0) & (mips[assign] > 0)
    seg = np.where(live, assign, mips.size)
    order = np.lexsort((mi, seg))
    seg_s, mi_s = seg[order], np.where(live, mi, 0.0)[order]
    start = np.r_[True, seg_s[1:] != seg_s[:-1]]
    first = np.maximum.accumulate(np.where(start, np.arange(seg_s.size), 0))
    counts = np.bincount(seg_s, minlength=mips.size + 1)
    k = counts[seg_s]
    pos = np.arange(seg_s.size) - first
    prev = np.r_[0.0, mi_s[:-1]]
    delta = np.where(start, mi_s, mi_s - prev)
    rate = np.r_[mips, 1.0][seg_s]
    term = delta * (k - pos) / rate
    csum = np.cumsum(term)
    fin_s = csum - np.r_[0.0, csum[:-1]][first]
    fin = np.zeros(mi.shape, np.float64)
    fin[order] = np.where(seg_s < mips.size, fin_s, 0.0)
    return fin
