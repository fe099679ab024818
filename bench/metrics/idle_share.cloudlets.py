"""Share of the window in which no op ran on the device, mean over chips."""
LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", "cloudlets_per_s"


def read(ctx):
    if ctx.trace is None or ctx.trace.n_devices == 0:
        return None
    return 100.0 * ctx.trace.idle_share
