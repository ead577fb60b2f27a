"""Poses handed back inside the window, of any frame (also those due
before it: the traffic runs on from the warm-up), over the window's
seconds. While the chip keeps up this is the offered load."""


def read(run):
    w = run.window
    return w.returned / (w.t_end - w.t0)
