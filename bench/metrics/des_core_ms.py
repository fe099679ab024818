"""Mean per request of the program's ``core_sim`` stage: the DES core."""
LAYER, UNIT, SOURCE, MOVES = "DES core", "ms", "program_span", "cloudlets_per_s"


def read(ctx):
    return ctx.span_ms("core_sim")
