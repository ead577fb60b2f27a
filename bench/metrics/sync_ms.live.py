"""The blocking pose sync, ``engine.decomp["sync"]``, per drained chunk
(ms)."""


def read(run):
    s = run.decomp["sync"]
    return sum(s) / len(s) * 1e3 if s else None
