"""Set-up: from the start of the process to the start of the window."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "s", "host_clock", None


def read(ctx):
    return ctx.setup_s
