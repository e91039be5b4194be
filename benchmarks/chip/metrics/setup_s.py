"""From the moment JAX has found the chip to the window's first request:
the program's imports, the served stack, weights, PBQP solves, compiles
or compile-cache loads, warm-up.  The process's start before it (Python
and JAX imports, the TPU runtime's start) is reported apart, in the
result's ``setup_phases_s``."""


def read(run):
    return run.setup_s
