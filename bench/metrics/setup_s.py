"""From the process's start to the window's start: JAX and the chip
coming up, the weights, programs from the compile cache (or compiled),
warm-up, and the steady-state fill (host clock)."""


def read(run):
    return run.setup_s
