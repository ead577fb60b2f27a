"""Readings for the limits of a cell's correctness check, on the chip:
sound runs on many seeds and the controls, in one process.

    python3 bench/readings.py --workload car-live-vio --seconds 30 \
        --seeds 101,102,... --controls blind,float8_e4m3fn,matmul:default \
        --control-seeds 201,202,203 --out chiprun_out/readings.json

Every sound run also gives two controls that need no run of their own:
``stale`` answers each frame with its robot's previous pose, and
``raw_gps`` with its GPS fix (inside an outage, the last fix before
it). Controls that run the served path, on ``--control-seeds``:
``blind`` sends featureless images, so that no visual update happens;
``bfloat16`` and ``float8_e4m3fn`` feed it inputs rounded to that type;
``matmul:default`` runs the program at XLA's default matmul precision
(one bfloat16 pass) in place of the configuration's. Give precision
controls last: each builds its own pool. ``--traffic`` takes a JSON
object whose keys replace the cell's traffic mix, to try a variant.
The benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

import reference
import run


def numbers(bench, r, window=None):
    window = window or r.window
    checks = reference.compare(window, r.truth,
                               [sc["name"] for sc in bench.scen],
                               bench.cell.limits)
    lat = r.latencies_s()
    per_robot = {}
    for rr in range(len(bench.ids)):
        fr = [(f, t) for f, t in zip(window.frames, r.truth)
              if f.robot == rr]
        d = reference.distances([f for f, _ in fr], [t for _, t in fr])
        per_robot[bench.scen[rr]["name"] + f"#{rr}"] = {
            "rmse_m": float(np.sqrt(np.mean(d ** 2))) if d.size else None,
            "err_max_m": float(d.max()) if d.size else None}
    return {"checks": {k: v["value"] for k, v in checks.items()},
            "per_robot": per_robot,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p95_ms": float(np.percentile(lat, 95) * 1e3),
            "frames": len(window.frames)}


def held_fix(bench):
    """``answer(robot, frame)`` for the raw-GPS control: the frame's fix,
    else the robot's last fix before it, else where it joined."""
    def answer(r, n):
        for m in range(n, -1, -1):
            g = bench.gps(r, m)
            if np.isfinite(g).all():
                return g
        return bench.truth(r, 0)
    return answer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--traffic", default="{}")
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t0 = time.perf_counter()

    def cell():
        c = run.Cell.load(run.ROOT, a.workload)
        c.traffic = dict(c.traffic, **json.loads(a.traffic))
        # a variant's scenarios that the cell has no limits for yet are
        # read with none
        for sc in c.traffic["scenarios"]:
            c.limits.setdefault(sc["name"], {
                "err_max_m": {"limit": float("inf")},
                "rmse_m": {"limit": float("inf")}})
        return c
    bench = run.Bench(cell())
    seeds = [int(s) for s in a.seeds.split(",")]
    cseeds = [int(s) for s in a.control_seeds.split(",") if s]
    out = {"workload": a.workload, "seconds": a.seconds,
           "traffic": bench.cell.traffic, "sound": {}, "controls": {}}

    def save():
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(out, indent=1))

    for seed in seeds + [s for s in cseeds if s not in seeds]:
        bench.prepare(seed)
        r = bench.measure(a.seconds, False, t0)
        out["sound"][seed] = numbers(bench, r)
        print("sound", seed, json.dumps(out["sound"][seed]["checks"]),
              flush=True)
        out["controls"].setdefault("stale", {})[seed] = numbers(
            bench, r, reference.stale(r.window))
        out["controls"].setdefault("raw_gps", {})[seed] = numbers(
            bench, r, reference.raw_gps(r.window, held_fix(bench)))
        save()
    for ctl in [c for c in a.controls.split(",") if c]:
        if ctl.startswith("matmul:"):
            # the program at another matmul precision: a new pool, traced
            # under that precision
            c = cell()
            c.config["precision"]["matmul"] = ctl.split(":", 1)[1]
            bench = run.Bench(c)
        elif ctl == "blind":
            bench.blind = True
        else:
            bench.quantize = ctl
        for seed in cseeds:
            bench.prepare(seed)
            r = bench.measure(a.seconds, False, t0)
            out["controls"].setdefault(ctl, {})[seed] = numbers(bench, r)
            print("control", ctl, seed,
                  json.dumps(out["controls"][ctl][seed]["checks"]),
                  flush=True)
            save()
        bench.quantize = None
        bench.blind = False
    for name, group in [("sound", out["sound"])] + list(
            out["controls"].items()):
        for key in sorted(next(iter(group.values()))["checks"]):
            vals = [v["checks"][key] for v in group.values()]
            print(f"{name} {key}: min {min(vals)} max {max(vals)}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
