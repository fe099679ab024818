"""Word count's share of its memory roofline: the least bytes it must move
(``peaks.wordcount_bytes``) at the chip's HBM bandwidth, over the time the
device was busy in the window.  Every device op of this cell is word-count
work (chunk cuts, map, reduce), so the busy time is the layer's time, under
any module names."""
import peaks

LAYER, UNIT, SOURCE, MOVES = ("map/reduce on the device", "%", "device_trace",
                              "tokens_per_s")


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    c = ctx.config["corpus"]
    need = len(ctx.records) * peaks.wordcount_bytes(
        c["n_files"] * c["file_len"], c["vocab"])
    bw = peaks.peak(ctx.device_kind, "hbm_bytes_per_s")
    return 100.0 * need / bw / ctx.trace.busy_s
