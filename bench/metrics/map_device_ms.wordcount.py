"""Device time per chunk of the word count's map: the device time of the
dispatcher's executable for the job (module ``jit_dispatch_mapreduce_
word_count``) over the chunks of the window's requests."""
LAYER, UNIT, SOURCE, MOVES = ("map/reduce on the device", "ms",
                              "device_trace", "tokens_per_s")
MODULE = r"^jit_dispatch_mapreduce_word_count"


def read(ctx):
    if ctx.trace is None:
        return None
    busy = ctx.trace.module_time(MODULE)
    chunks = sum(int((r.get("dispatch") or {}).get("n_chunks", 0))
                 for r in ctx.records)
    if busy <= 0 or not chunks:
        return None
    return 1e3 * busy / chunks
