"""JAX traces, XLA compiles and persistent-cache loads in the window (the
harness's ``CompileCounter``, the ``window`` line), per completed request:
the host's work of turning the request into programs, 0 once every stage
of a simulation is one program that set-up has warmed."""
LAYER, UNIT, SOURCE, MOVES = ("host: JAX tracing and compiling", "programs",
                              "program_counter", "cloudlets_per_s")


def read(ctx):
    if not ctx.records or not ctx.window_programs:
        return None
    return sum(ctx.window_programs.values()) / len(ctx.records)
