"""Median over the window's frames of the engine's queue wait, from a
frame's arrival to the dispatch of its chunk (ms):
``engine.decomp["queue_wait"]``, one sample per frame. None where the
program keeps no such samples."""
import numpy as np


def read(run):
    s = run.decomp.get("queue_wait")
    return float(np.median(s)) * 1e3 if s else None
