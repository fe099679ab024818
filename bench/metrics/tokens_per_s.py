"""Corpus tokens word-counted per second over the whole window."""
LAYER, UNIT, SOURCE, MOVES = "end to end", "tokens/s", "host_clock", None


def read(ctx):
    return ctx.rate("tokens")
