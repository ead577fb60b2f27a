"""Process start to the window's start (s): imports and backend, pool
build, lap rendering, the chunk program's trace and compile (or cache
load) and the warm-up drive."""


def read(run):
    return run.setup_s
