"""Device time of one execution of the fleet chunk program (ms): the
durations of the trace's module events named after the jitted
``FleetLocalizer._traced_chunk``, over their number in the traced
window. Finding none in a trace of the device is an error: the chunk
program runs in every window, so its name in the trace has changed."""
import devtrace

# the jitted chunk is a callable object without __name__, so the trace
# names its module "jit__unknown(<fingerprint>)"; in the window no other
# unnamed program runs
MODULE = "jit__unknown("


def read(run):
    if run.trace is None or not run.trace.modules:
        return None                      # no device plane in the trace
    t, n = devtrace.time_of(run.trace.modules,
                            lambda name: name.startswith(MODULE),
                            run.trace.window())
    if n == 0:
        raise LookupError(f"no module event named {MODULE}* in the trace")
    return t / n * 1e3
