"""The yardstick's chip peaks and the least bytes each measured layer must move.

Peaks are keyed by ``device_kind`` as JAX reports it.  Source: Google Cloud
documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
819 GB/s).  A kind missing from the table is an error, never a default.

The byte counts are what a layer has to read and write, from the shapes of
its inputs and outputs alone.  They do not depend on how the program
computes the answer, so a later change of algorithm cannot move them, and a
layer that moves at least these bytes can never read above 100% of its
roofline.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12,
                    "int8_ops_per_s": 393e12, "hbm_bytes": 16e9},
}


def peak(device_kind: str, key: str) -> float:
    """One peak of ``device_kind``; raises for a chip the table lacks."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind][key]


def des_core_bytes(n_cloudlets: int, n_vms: int) -> int:
    """The DES core reads each cloudlet's VM (int32), length (f32) and valid
    flag (bool) and writes its finish time (f32): 13 bytes a cloudlet; it
    reads each VM's MIPS (f32): 4 bytes a VM."""
    return 13 * n_cloudlets + 4 * n_vms


def wordcount_bytes(n_tokens: int, vocab: int) -> int:
    """Word count reads every int32 token once and writes one int32 count
    per vocabulary word."""
    return 4 * n_tokens + 4 * vocab
