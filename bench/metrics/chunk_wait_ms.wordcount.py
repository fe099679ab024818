"""Mean time a chunk of the word count waited in the dispatcher's queue
(``DispatchStats``), over every chunk of the window."""
LAYER, UNIT, SOURCE, MOVES = "dispatcher", "ms", "program_span", "tokens_per_s"


def read(ctx):
    return ctx.chunk_wait_ms()
