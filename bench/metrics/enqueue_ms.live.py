"""The jitted chunk call, the ``fleet.enqueue`` span inside the
dispatch (``engine.decomp["enqueue"]``), per dispatched chunk (ms).
None where the program keeps no such span."""


def read(run):
    s = run.decomp.get("enqueue")
    return sum(s) / len(s) * 1e3 if s else None
