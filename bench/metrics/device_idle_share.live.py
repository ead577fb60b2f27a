"""Share of the traced window in which no op ran on the device (%):
1 - union of the device's op intervals / window."""
import devtrace


def read(run):
    if run.trace is None:
        return None
    s = devtrace.idle_share(run.trace)
    return None if s is None else 100.0 * s
