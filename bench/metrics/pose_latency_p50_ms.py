"""Median due-to-pose latency over every frame of the window (ms)."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 50)) * 1e3 if lat.size else None
