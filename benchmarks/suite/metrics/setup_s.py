"""setup_s: process start to the window's start (host clock): loading JAX
and the program, making the data, and one warm-up fit that compiles, or
loads from the persistent cache, every program the window runs."""


def read(run):
    return run.setup_s
