"""Where a cell's time goes inside the program, on the chip: device time
by primitive, the device's idle time by program span, the engine's
latency split and the chunk program's work counters, from one traced
window per run, in one process.

    python3 bench/inside.py --workload car-live-vio --seconds 42 \
        --runs 101,101:blind --arrival due --out chiprun_out/inside.json \
        --record chiprun_out/scoped_trace.json

Each run of ``--runs`` is a seed, optionally with the control
``:blind`` (featureless images, as in ``readings.py``). A run prepares
the cell as ``run.py`` does and records a profiler trace around its
window (``scopes.load``). ``--arrival due`` hands the engine each
frame's due time as its arrival (``submit_frame``'s ``t``), so that the
engine's queue wait counts the wait behind a blocked ``run_chunk`` too;
``submit`` (the default) leaves it at the submit, as the benchmark's
driver does. ``--record`` keeps one chunk execution of the first run's
trace, with its scopes and program spans, as a small JSON trace.
Standard error gets the lines ``device by primitive:``, ``idle by
program span:``, ``latency:`` and ``counters:`` per run.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

import devtrace
import run
import scopes

SPANS = ("stage", "dispatch", "build", "h2d", "enqueue", "feedback",
         "sync", "host_stage")


def due_arrivals(bench):
    """``bench._source`` with each frame's due time appended, which the
    driver's ``submit_frame(id, *frame)`` then passes as ``t``."""
    source, drv = bench._source, bench.drv

    def src(r, n):
        return source(r, n) + (drv._due(r),)
    return src


class ChunkArgs:
    """Wraps the fleet's jitted chunk to keep the abstract arguments of
    its first call: with them the chunk program's compiled HLO, which
    holds each op's scope, can be had again."""

    def __init__(self, fleet):
        self.real, self.args = fleet._fused_fleet_chunk, None
        self._hlo = None
        fleet._fused_fleet_chunk = self

    def __call__(self, *args):
        import jax
        if self.args is None:
            self.args = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=x.sharding), args)
        return self.real(*args)

    def hlo(self) -> str:
        if self._hlo is None:
            self._hlo = self.real.lower(*self.args).compile().as_text()
        return self._hlo


def counter_delta(after, before):
    return {k: v - before[k] for k, v in after.items()
            if isinstance(v, int)}


def inside(bench, capture: ChunkArgs, seconds: float, t0: float,
           record=None):
    """One traced window of ``bench`` (prepared): its numbers."""
    import jax
    before = bench.engine.counters()
    tmp = tempfile.mkdtemp(prefix="bench-inside-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        r = bench.measure(seconds, False, t0)
        jax.profiler.stop_trace()
        tr = scopes.load(tmp, [capture.hlo()])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    checks = bench.check(r)
    w = tr.window()
    n = scopes.chunk_executions(tr, w)
    prim = scopes.primitive_self_times(tr, w)
    whole = [e for es in tr.modules.values() for e in es
             if e.name.startswith(scopes.CHUNK_MODULE)
             and w[0] <= e.start and e.end <= w[1]]
    if record and whole:
        # the end of one execution, the next one's start and the host
        # spans of its dispatch
        mid = whole[len(whole) // 2]
        scopes.write_json(scopes.cut(tr, (mid.end - 4e-3,
                                          mid.end + 16e-3)), record)
    lat = r.latencies_s()
    ms = {k: float(np.mean(r.decomp[k]) * 1e3) if r.decomp[k] else 0.0
          for k in SPANS}
    # as ``chunk_device_ms.live`` reads it: the executions' time cut to
    # the window over their number, the two cut at its edges included
    t_read, n_read = devtrace.time_of(
        tr.modules, lambda s: s.startswith(scopes.CHUNK_MODULE), w)
    execution = sum(e.dur for e in whole) / len(whole) if whole else 0.0
    share = scopes.shares(prim)
    n_ops = sum(len(v) for v in tr.ops.values())
    n_scoped = sum(1 for v in tr.scopes.values() for s in v if s)
    return {
        "chunk_executions": n,
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "chunk_execution_ms": execution * 1e3,
        "chunk_device_ms_as_read": t_read / n_read * 1e3 if n_read else None,
        "primitives_ms_as_read": sum(prim.values()) / n * 1e3 if n else None,
        "device_ms_by_primitive": {k: share[k] / 100 * execution * 1e3
                                   for k in sorted(share)},
        "device_share_by_primitive": share,
        "ops_with_scope": [n_scoped, n_ops],
        "idle_ms_by_program_span": {
            k: v * 1e3 for k, v in sorted(
                scopes.idle_by_span(tr, w).items(), key=lambda kv: -kv[1])},
        "span_ms_per_chunk": ms,
        "span_ms_summed_per_chunk": ms["stage"] + ms["dispatch"]
        + ms["sync"] + ms["host_stage"],
        "pose_latency_p50_ms": float(np.percentile(lat, 50) * 1e3),
        "pose_latency_p95_ms": float(np.percentile(lat, 95) * 1e3),
        "queue_wait_p50_ms": float(np.median(r.decomp["queue_wait"])) * 1e3,
        "in_flight_p50_ms": float(np.median(r.decomp["in_flight"])) * 1e3,
        "dispatches": r.dispatches,
        "counters": counter_delta(bench.engine.counters(), before),
        "program_spans": len(tr.program),
    }


def report(tag: str, res, out=sys.stderr) -> None:
    def fmt(d):
        return ", ".join(f"{k} {v:.3f}" for k, v in d.items())
    print(f"[{tag}] device by primitive (ms per chunk execution of "
          f"{res['chunk_execution_ms']} ms; as read "
          f"{res['chunk_device_ms_as_read']} ms, primitives "
          f"{res['primitives_ms_as_read']} ms): "
          f"{fmt(res['device_ms_by_primitive'])}", file=out)
    print(f"[{tag}] idle by program span (ms in the window): "
          f"{fmt(res['idle_ms_by_program_span'])}", file=out)
    print(f"[{tag}] latency: p50 {res['pose_latency_p50_ms']:.2f} ms = "
          f"queue wait p50 {res['queue_wait_p50_ms']:.2f} + in flight p50 "
          f"{res['in_flight_p50_ms']:.2f}; spans per chunk "
          f"{fmt(res['span_ms_per_chunk'])}", file=out)
    c = res["counters"]
    per = {k: c[k] / c["frames"] for k in c if k not in ("frames",
                                                         "flushes")} \
        if c.get("frames") else {}
    print(f"[{tag}] counters: {json.dumps(c)} per frame {fmt(per)}",
          file=out)
    out.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", required=True)
    ap.add_argument("--arrival", choices=("submit", "due"),
                    default="submit")
    ap.add_argument("--out", required=True)
    ap.add_argument("--record", default=None)
    a = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("inside: no TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t0 = time.perf_counter()
    bench = run.Bench(run.Cell.load(run.ROOT, a.workload))
    capture = ChunkArgs(bench.pool.fleet)
    out = {"workload": a.workload, "arrival": a.arrival, "runs": {}}
    record = a.record
    for spec in a.runs.split(","):
        seed, _, control = spec.partition(":")
        bench.blind = control == "blind"
        bench.prepare(int(seed))
        saved = bench._source
        if a.arrival == "due":
            bench._source = due_arrivals(bench)
        try:
            res = inside(bench, capture, a.seconds, t0, record)
        finally:
            bench._source = saved
        record = None
        out["runs"][spec] = res
        report(spec, res)
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
