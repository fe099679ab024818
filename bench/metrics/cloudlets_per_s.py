"""Valid cloudlets simulated per second over the whole window."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "cloudlets/s", "host_clock", None


def read(ctx):
    return ctx.rate("cloudlets")
