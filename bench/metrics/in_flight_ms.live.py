"""Median over the window's frames of the time in flight, from the
dispatch of a frame's chunk to the sync of its pose (ms):
``engine.decomp["in_flight"]``, one sample per frame. None where the
program keeps no such samples."""
import numpy as np


def read(run):
    s = run.decomp.get("in_flight")
    return float(np.median(s)) * 1e3 if s else None
