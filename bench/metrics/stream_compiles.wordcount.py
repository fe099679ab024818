"""Programs the dispatcher's word-count stream compiled or loaded from the
persistent cache, mean per request of the window (``DispatchReport.
jax_compiles + jax_cache_loads``): 0 once set-up has warmed every shape."""
LAYER, UNIT, SOURCE, MOVES = ("dispatcher", "programs", "program_counter",
                              "tokens_per_s")


def read(ctx):
    counts = [d["jax_compiles"] + d["jax_cache_loads"]
              for d in (r.get("dispatch") or {} for r in ctx.records)
              if "jax_compiles" in d]
    return sum(counts) / len(counts) if counts else None
