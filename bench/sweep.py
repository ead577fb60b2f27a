"""Find a cell's knee once, on the chip: the highest offered load at
which the backlog does not grow over the window.

    python3 bench/sweep.py --workload car-live-vio --points 1x20,2x20,4x20 \
        --seconds 20 --seed 5 --out chiprun_out/sweep.json

Each point ``RxF`` runs the cell's traffic with R robots (pool capacity
R) at F frames per second per robot, in this one process. For each it
reports p50 and p95 due-to-pose latency, frames posed per second and
the backlog (frames due and not yet posed) at each quarter of the
window; a backlog that grows quarter by quarter means the chip does not
keep up.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

import run


def backlog(win, t) -> int:
    due = sum(1 for f in win.frames if f.due <= t)
    done = sum(1 for f in win.frames
               if f.posed_at is not None and f.posed_at <= t)
    return due - done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--points", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t0 = time.perf_counter()
    rows = []
    bench = None
    for point in a.points.split(","):
        robots, rate = point.split("x")
        robots, rate = int(robots), float(rate)
        if bench is None or bench.pool.capacity != robots:
            # a new capacity is a new chunk program; a new rate is not
            bench = None
            gc.collect()
            jax.clear_caches()
            cell = run.Cell.load(run.ROOT, a.workload)
            cell.traffic = dict(cell.traffic, robots=robots,
                                capacity=robots)
            bench = run.Bench(cell)
        bench.cell.traffic = dict(bench.cell.traffic, rate_hz=rate)
        bench.prepare(a.seed)
        r = bench.measure(a.seconds, False, t0)
        lat = r.latencies_s()
        w = r.window
        q = [backlog(w, w.t0 + a.seconds * k / 4) for k in (1, 2, 3, 4)]
        rows.append({
            "robots": robots, "rate_hz": rate,
            "offered_frames_per_s": robots * rate,
            "posed_frames_per_s": w.returned / a.seconds,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "backlog_quarters": q, "frames": len(w.frames),
            "dispatches": r.dispatches,
            "sync_ms": float(np.mean(r.decomp["sync"]) * 1e3)})
        print(json.dumps(rows[-1]), flush=True)
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
