"""The DES core's share of its memory roofline: the least bytes the core
must move (``peaks.des_core_bytes``) at the chip's HBM bandwidth, over the
device time of the core's module (``jit_simulate_completion_scan``)."""
import peaks

LAYER, UNIT, SOURCE, MOVES = ("DES core on the device", "%", "device_trace",
                              "cloudlets_per_s")
MODULE = r"^jit_simulate_completion_scan$"


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.module_time(MODULE)
    if busy <= 0:
        return None
    t = ctx.traffic
    need = len(ctx.records) * peaks.des_core_bytes(t["n_cloudlets"],
                                                   t["n_vms"])
    bw = peaks.peak(ctx.device_kind, "hbm_bytes_per_s")
    return 100.0 * need / bw / busy
