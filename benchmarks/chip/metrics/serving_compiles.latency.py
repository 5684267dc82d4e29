"""XLA backend compiles inside the window, in the cells that report a
latency: the program's `jit.compiles` counter (JAX's
`/jax/core/compile/backend_compile_duration` events), which a tracing
telemetry keeps from its start.  None where the program has no such
counter."""
LAYER = "JAX runtime (jit dispatch, compile cache)"
UNIT, SOURCE, BETTER, MOVES = "count", "program_counter", "lower", \
    "latency_p50_s"


def read(run):
    return run.counters.get("jit.compiles")
