"""setup_s: seconds from the process's start to the window's start: JAX's
start, data, the build, compilation or cache loads, warm-up and lead-in."""


def read(run):
    return run.setup_s
