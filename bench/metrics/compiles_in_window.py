"""compiles_in_window: backend compiles between the window's start and its
end, from jax.monitoring's backend_compile_duration events."""


def read(run):
    return run.compiles_in_window
