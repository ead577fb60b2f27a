"""95th percentile of due-to-pose latency over every frame of the
window (ms), not a median of chunks."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return float(np.percentile(lat, 95)) * 1e3 if lat.size else None
