"""Share of its roofline that the ``cov_update`` Pallas kernel reaches
(%).

The trace names a Pallas call after the transform around it
(``%vmap__.N``), not after the kernel, so the kernel is told by its
instruction: a ``tpu_custom_call`` whose result is the (d, d)
covariance (one per robot of the batch) and which reads the (ipf, 15,
15) transitions, d = 15 + 6 x window. Its operations and bytes come
from those shapes (``roofline.cov_update_cost``), once per robot.
Returns nothing where no such kernel ran."""
import re

import devtrace
import roofline


def kernels(trace, d: int, ipf: int):
    """{op name: robots per call} of the cov_update calls in ``trace``."""
    out = {}
    result = re.compile(rf"^\S+ = f32\[(?:(\d+),)?{d},{d}\]")
    for name, text in trace.texts.items():
        m = result.match(text)
        if (m and 'custom_call_target="tpu_custom_call"' in text
                and f"f32[{ipf},15,15]" in text):
            out[name] = int(m.group(1) or 1)
    return out


def read(run):
    if run.trace is None:
        return None
    d = 15 + 6 * run.window_size
    found = kernels(run.trace, d, run.ipf)
    if not found:
        return None
    w = run.trace.window()
    ops1, bytes1 = roofline.cov_update_cost(d, run.ipf)
    t = work_ops = work_bytes = 0.0
    for es in run.trace.ops.values():
        for e in es:
            if e.name in found:
                iv = devtrace.clip([e], w)
                if iv:
                    t += devtrace.length(iv)
                    work_ops += ops1 * found[e.name]
                    work_bytes += bytes1 * found[e.name]
    if t <= 0:
        return None
    share, _ = roofline.roofline_share(work_ops, work_bytes, t,
                                       roofline.peaks(run.device_kind))
    return share
