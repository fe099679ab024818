"""Mean per request of the program's ``schedule`` stage
(``SimulationResult.timings``): the broker, host clock around work that
ends in ``block_until_ready``."""
LAYER, UNIT, SOURCE, MOVES = "broker", "ms", "program_span", "cloudlets_per_s"


def read(ctx):
    return ctx.span_ms("schedule")
