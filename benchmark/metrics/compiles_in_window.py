"""``compiles_in_window``: XLA backend compiles (JAX's
``/jax/core/compile/backend_compile_duration`` monitoring event, which
sees module-level jits and the engine's own alike) between the start and
the close of the window. Warm-up should leave none."""


def read(run):
    return float(run.compiles_in_window)
