"""Host gather into the pool's staging buffers, ``engine.decomp
["stage"]``: total over the window per dispatched chunk (ms)."""


def read(run):
    s = run.decomp["stage"]
    return sum(s) / len(s) * 1e3 if s else None
