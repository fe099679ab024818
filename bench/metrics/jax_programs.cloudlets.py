"""JAX traces and XLA compiles in the window (the harness's
``CompileCounter``, the ``window`` line), per completed request: the host's
work of turning the request into programs, 0 once every stage of a
simulation is one program that set-up has warmed.  A persistent-cache load
fires JAX's compile event too, so each loaded program counts once, as a
compile, and the ``cache_loads`` count is not added."""
LAYER, UNIT, SOURCE, MOVES = ("host: JAX tracing and compiling", "programs",
                              "program_counter", "cloudlets_per_s")


def read(ctx):
    if not ctx.records or not ctx.window_programs:
        return None
    return ((ctx.window_programs["traces"] + ctx.window_programs["compiles"])
            / len(ctx.records))
