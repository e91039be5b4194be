"""Executables JAX built, by compiling or from its cache, inside the
window (``jax.monitoring``'s backend-compile event); 0 when set-up warmed
every shape the traffic uses."""


def read(run):
    return run.compiles_in_window
