"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file it names (``bench/configs/<config>.json``), the
traffic mix (``bench/traffic/<traffic>.json``), the limits of its
correctness check (``bench/limits/<cell>.json``) and one reader per
metric (``bench/metrics/<metric>.py``, a ``read(run)`` function that
returns a number, or None when it finds nothing to read).

A run builds the program's ``RobotStatePool`` and ``ServingEngine`` for
the configuration, renders one lap of the circuit from the seed
(``lap.py``), admits the robots and warms up every shape the window
uses, then drives ``ServingEngine.run_chunk`` with the traffic's load
(``driver.py``): first for the traffic's ``warmup_s``, then, with no
pause or drain between them, for the window of ``--seconds``. Set-up
is everything before the window. A compile or a chunk retrace inside the window fails the run.
After the window, the frames still in flight are drained and every
pose of the window is compared with the circuit's ground truth
(``reference.py``); each number compared is printed beside its limit.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
records a profiler trace of the window and reports its per-layer
metrics, the device's busy time and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(BENCH), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import devtrace  # noqa: E402
import lap  # noqa: E402
import reference  # noqa: E402
from driver import Driver, Window  # noqa: E402

# events of JAX's own monitoring that mean a trace, a lowering or a
# compile (or a load from the persistent cache) happened
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
GRACE_S = 60.0
# a configuration's "matmul" precision -> jax_default_matmul_precision
# (Mosaic refuses precision=HIGH inside Pallas, so "high" cannot run)
MATMUL_PRECISION = {"default": None, "highest": "highest"}


# --------------------------------------------------------------------------
# the cell as BENCHMARK.json and its files describe it
# --------------------------------------------------------------------------

@dataclass
class Cell:
    root: Path
    bench: Path                        # the benchmark's own directory
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @classmethod
    def load(cls, root: Path, workload: str) -> "Cell":
        spec = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        w = cells[workload]
        conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
        bench = root / spec["paths"][0]

        def mine(metrics):
            return [m for m in metrics
                    if workload in m.get("workloads", [workload])]
        return cls(
            root=root, bench=bench, name=workload, chips=int(w["chips"]),
            config=json.loads((root / conf["file"]).read_text()),
            traffic=json.loads(
                (bench / "traffic" / f"{w['traffic']}.json").read_text()),
            limits=json.loads(
                (bench / "limits" / f"{workload}.json").read_text()),
            end_to_end=mine(spec["end_to_end"]),
            per_layer=mine(spec["per_layer"]))

    def reader(self, metric: str):
        """The ``read`` function of ``bench/metrics/<metric>.py``."""
        path = self.bench / "metrics" / f"{metric}.py"
        sp = importlib.util.spec_from_file_location(f"metric:{metric}",
                                                    path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        return mod.read


def build_config(c: Dict[str, Any]):
    """The program's ``EudoxusConfig`` from a configuration file. Every
    field is stated in the file; a missing or unknown key raises."""
    import dataclasses
    from repro.configs.eudoxus import (BackendConfig, EudoxusConfig,
                                       FrontendConfig)

    def make(klass, values):
        names = {f.name for f in dataclasses.fields(klass)}
        missing = names - set(values)
        if missing:
            raise ValueError(f"{klass.__name__}: the configuration file "
                             f"leaves out {sorted(missing)}")
        return klass(**values)
    top = {k: c[k] for k in ("matrix_block", "scheduler_enabled",
                              "frame_pipelining")}
    return make(EudoxusConfig, dict(
        name=c["name"], frontend=make(FrontendConfig, c["frontend"]),
        backend=make(BackendConfig, c["backend"]), **top))


# --------------------------------------------------------------------------
# compile accounting
# --------------------------------------------------------------------------

class GcLog:
    """Start and length of each collection of Python's garbage collector
    while ``on`` is set: a pause of the served path's own process."""

    def __init__(self):
        self.on = False
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)

    def _cb(self, phase: str, info: Dict[str, int]) -> None:
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))


class CompileLog:
    """Counts and times JAX's trace / lower / compile events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds: Dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in COMPILE_EVENTS:
            self.count += 1
        if event in COMPILE_EVENTS or event == CACHE_LOAD_EVENT:
            key = event.rsplit("/", 1)[-1]
            self.seconds[key] = self.seconds.get(key, 0.0) + duration


# --------------------------------------------------------------------------
# what a metric reader sees
# --------------------------------------------------------------------------

@dataclass
class Run:
    """Everything one run measured; a metric's ``read(run)`` takes this."""
    cell: Cell
    window: Window
    seconds: float
    setup_s: float
    decomp: Dict[str, List[float]]     # engine host spans in the window
    dispatches: int
    capacity: int
    chunk: int
    window_size: int                   # MSCKF clones
    ipf: int                           # IMU samples per frame
    device_kind: str
    truth: List[np.ndarray]            # ground truth of each window frame
    trace: Optional[devtrace.Trace] = None
    gc_pauses: List[tuple] = None      # (start, seconds, generation)

    def latencies_s(self) -> np.ndarray:
        """Due-to-pose latency of every frame of the window that was
        posed (frames never posed are counted by the check)."""
        return np.array([f.posed_at - f.due for f in self.window.frames
                         if f.posed_at is not None])


# --------------------------------------------------------------------------
# one process, one cell
# --------------------------------------------------------------------------

class Bench:
    """The program's pool and engine for one cell, reused across seeds."""

    def __init__(self, cell: Cell):
        import jax
        from repro.data.frames import CameraModel
        from repro.serve import RobotStatePool, ServingEngine
        self.cell = cell
        # the matmul precision the configuration states, set for the
        # whole process before anything is traced (the jit cache is keyed
        # on it, so it must not change between warm-up and window)
        jax.config.update("jax_default_matmul_precision",
                          MATMUL_PRECISION[cell.config["precision"]["matmul"]])
        self.compiles = CompileLog()
        self.gcs = GcLog()
        # controls (``readings.py``): the input type of ``reference.
        # quantize``, and ``blind``, which sends featureless images so
        # that no visual update happens; off in the benchmark's own runs
        self.quantize: Optional[str] = None
        self.blind = False
        t = cell.traffic
        self.cfg = build_config(cell.config)
        fe, be = self.cfg.frontend, self.cfg.backend
        self.ipf = be.imu_rate_hz // be.cam_rate_hz
        self.cam = lap.camera(fe.height, fe.width)
        self.pool = RobotStatePool(
            self.cfg, CameraModel(**vars(self.cam)), capacity=t["capacity"],
            window=be.msckf_window, staging_depth=max(2, t["inflight"]))
        self.engine = ServingEngine(
            self.pool, chunk=t["chunk"], inflight=t["inflight"],
            dt_imu=1.0 / be.imu_rate_hz, overflow="reject")
        self.ids: List[str] = []
        self.device = jax.devices()[0]
        self.setup: Dict[str, float] = {}

    # -- inputs ---------------------------------------------------------
    def _source(self, r: int, n: int):
        """Frame ``n`` of robot ``r``: its lap frame, with the sensor
        noise of that (robot, lap)."""
        P = self.lap.frames
        j = (int(self.offsets[r]) + n) % P
        k = (int(self.offsets[r]) + n) // P
        key = (r, k)
        if key not in self._sensors:
            sc = self.scen[r]
            self._sensors = {kk: v for kk, v in self._sensors.items()
                             if kk[0] != r or kk[1] >= k - 1}
            self._sensors[key] = self.lap.sensors(
                r, k, sc.get("gps_sigma_m"), sc.get("gps_drop", 0.0))
        ac, gy, gps = self._sensors[key]
        frame = (self.lap.images_left[j], self.lap.images_right[j],
                 ac[j], gy[j], self.fix(r, n, gps[j]))
        if self.blind:
            frame = (self._blank, self._blank) + frame[2:]
        if self.quantize is not None:
            frame = reference.quantize(frame, self.quantize)
        return frame

    def fix(self, r: int, n: int, gps: np.ndarray) -> np.ndarray:
        """Robot ``r``'s GPS fix for its frame ``n``: none (NaN) inside
        the scenario's outages, ``gps_outage: [on, off]`` frames counted
        from the robot's first frame."""
        out = self.scen[r].get("gps_outage")
        if out and n % (out[0] + out[1]) >= out[0]:
            return np.full(3, np.nan, np.float32)
        return gps

    def gps(self, r: int, n: int) -> np.ndarray:
        """The fix that robot ``r``'s frame ``n`` carries."""
        return self._source(r, n)[4]

    def truth(self, r: int, n: int) -> np.ndarray:
        return self.lap.positions[(int(self.offsets[r]) + n)
                                  % self.lap.frames]

    def _driver(self, next_frame) -> Driver:
        import jax
        t = self.cell.traffic
        kw = dict(loop=t["loop"], span=jax.profiler.TraceAnnotation)
        if t["loop"] == "open":
            kw.update(rate_hz=t["rate_hz"], phases=self.phases)
        else:
            kw.update(outstanding=t["outstanding"])
        return Driver(self.engine, self.ids,
                      lambda r, n: self._source(r, n), next_frame, **kw)

    # -- set-up -----------------------------------------------------------
    def prepare(self, seed: int) -> None:
        """Render the lap, (re)admit the robots at their seeded phases,
        warm up every shape the window will use, and leave the traffic
        running: the window continues its schedule."""
        t = self.cell.traffic
        K = t["chunk"]
        t0 = time.perf_counter()
        fe, be = self.cfg.frontend, self.cfg.backend
        self.lap = lap.generate(seed=seed, H=fe.height, W=fe.width,
                                cam_rate_hz=be.cam_rate_hz, ipf=self.ipf,
                                **t["scene"])
        self._blank = np.full((fe.height, fe.width), lap.BACKGROUND,
                              np.float32)
        t1 = time.perf_counter()
        self.setup["lap_s"] = t1 - t0
        R = int(t["robots"])
        rng = np.random.default_rng([seed, 2])
        self.offsets = rng.integers(0, self.lap.frames, R)
        # the same arrivals for every seed, robots in another order
        self.phases = (rng.permutation(R) / R / t["rate_hz"]
                       if t["loop"] == "open" else None)
        mix = t["scenarios"]
        self.scen = [mix[r % len(mix)] for r in range(R)]
        self._sensors: Dict = {}
        for rid in self.ids:
            self.engine.submit_leave(rid)
        self.ids = [f"s{seed}r{r}" for r in range(R)]
        for r, rid in enumerate(self.ids):
            j = int(self.offsets[r])
            self.engine.submit_join(
                rid, self.scen[r]["name"],
                p0=self.lap.positions[j].astype(np.float32),
                v0=self.lap.velocities[j].astype(np.float32),
                q0=lap.yaw_quaternion(self.lap.headings[j]).astype(
                    np.float32))
        # one full chunk per robot, then one ragged chunk, then the
        # traffic itself: every program and shape the window drives
        nxt = [0] * R
        for per_robot in (K, 1):
            for r, rid in enumerate(self.ids):
                for _ in range(per_robot):
                    self.engine.submit_frame(rid, *self._source(r, nxt[r]))
                    nxt[r] += 1
            self.engine.run_chunk()
            self.engine.flush()
        t2 = time.perf_counter()
        self.drv = self._driver(nxt)
        self.drv.run(t["warmup_s"])
        self.setup["first_chunks_s"] = t2 - t1
        self.setup["warmup_drive_s"] = time.perf_counter() - t2

    # -- the window -------------------------------------------------------
    def measure(self, seconds: float, trace: bool, t_process: float) -> Run:
        import jax
        eng, pool = self.engine, self.pool
        n0 = {k: len(v.samples) for k, v in eng.decomp.items()}
        traces0 = pool.chunk_trace_count()
        comp0 = self.compiles.count
        tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        drv = self.drv
        self.gcs.pauses = []
        try:
            if trace:
                # host spans come from TraceAnnotation; the Python
                # function tracer would only slow the driver
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(tmp, profiler_options=opts)
            self.gcs.on = True
            with jax.profiler.TraceAnnotation("window"):
                win = drv.run(seconds)
            self.gcs.on = False
            compiled = self.compiles.count - comp0
            retraced = pool.chunk_trace_count() - traces0
            # the frames still in flight are posed before the trace is
            # written, which takes longer than the grace they have
            drv.drain(GRACE_S)
            if trace:
                jax.profiler.stop_trace()
            if compiled or retraced:
                raise RuntimeError(
                    f"{compiled} compile events and {retraced} chunk "
                    "retraces inside the measured window: the warm-up "
                    "missed a program or a shape")
            tr = devtrace.load(tmp) if trace else None
        finally:
            if tmp is not None:
                shutil.rmtree(tmp, ignore_errors=True)
        eng.flush()
        decomp = {k: list(v.samples[n0[k]:]) for k, v in eng.decomp.items()}
        return Run(cell=self.cell, window=win, seconds=seconds,
                   setup_s=win.t0 - t_process, decomp=decomp,
                   dispatches=len(decomp["stage"]),
                   capacity=pool.capacity, chunk=eng.chunk,
                   window_size=pool.fleet.window, ipf=self.ipf,
                   device_kind=self.device.device_kind,
                   truth=[self.truth(f.robot, f.frame) for f in win.frames],
                   trace=tr, gc_pauses=list(self.gcs.pauses))

    def check(self, run: Run) -> Dict[str, Dict[str, float]]:
        return reference.compare(run.window, run.truth,
                                 [sc["name"] for sc in self.scen],
                                 run.cell.limits)


# --------------------------------------------------------------------------
# the result line
# --------------------------------------------------------------------------

def result(bench: Bench, run: Run, checks, trace: bool) -> Dict[str, Any]:
    cell = bench.cell
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.reader(m["name"])(run)
        if v is None:
            continue
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} read {v}")
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = bench.device
    import jax
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(
                  (dev.memory_stats() or {}).get("peak_bytes_in_use", 0))}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(run.window.frames),
           "failed": int(checks["unposed"]["value"]),
           "metrics": metrics, "device": device}
    if trace:
        w = run.trace.window()
        device["busy_s"] = devtrace.busy_s(run.trace)
        device["window_s"] = w[1] - w[0]
        out["breakdown"] = {"device_ops": devtrace.top_ops(run.trace),
                            "idle_gaps": devtrace.idle_gaps(run.trace)}
    out["checks"] = checks
    return out


def report(bench: Bench, run: Run, res: Dict[str, Any], out=sys.stderr):
    """Set-up split, load-generator lateness, then each number compared
    beside its limit as the last lines of standard error."""
    w = run.window
    late = np.asarray(w.wake_late or [0.0])
    print(f"setup_s {run.setup_s} split {json.dumps(bench.setup)} "
          f"compile events {json.dumps(bench.compiles.seconds)}", file=out)
    print(f"window: {len(w.frames)} frames due, {w.returned} poses "
          f"returned, {run.dispatches} dispatches, driver woke late p50 "
          f"{float(np.median(late)) * 1e3} ms max {float(late.max()) * 1e3}"
          " ms", file=out)
    print(f"stalls: {stalls(run)}", file=out)
    for k, c in res["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=out)
    out.flush()


def stalls(run: Run) -> str:
    """Where a slow window lost its time: the longest ``run_chunk``
    calls (ms, and seconds into the window), the engine's longest host
    spans, and Python's garbage collections."""
    w = run.window
    calls = sorted(((e - s, s - w.t0) for s, e, _ in w.calls),
                   reverse=True)
    walls = [d for d, _ in calls]
    longest = ", ".join(f"{d * 1e3:.1f} at {o:.2f} s" for d, o in calls[:3])
    spans = " ".join(f"{k} {max(v) * 1e3:.1f}" for k, v in
                     sorted(run.decomp.items()) if v)
    gcs = run.gc_pauses or []
    gmax = max((p for _, p, _ in gcs), default=0.0)
    return (f"run_chunk p50 {float(np.median(walls or [0])) * 1e3:.1f} ms, "
            f"longest {longest}; longest engine spans (ms) {spans}; "
            f"{len(gcs)} gc collections, longest {gmax * 1e3:.2f} ms")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell.load(ROOT, args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s). Nothing "
              "was measured.", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t_import = time.perf_counter()
    bench = Bench(cell)
    bench.setup["import_and_backend_s"] = t_import - T_PROCESS
    bench.setup["pool_s"] = time.perf_counter() - t_import
    bench.prepare(args.seed)
    run = bench.measure(args.seconds, bool(args.trace), T_PROCESS)
    checks = bench.check(run)
    res = result(bench, run, checks, bool(args.trace))
    report(bench, run, res)
    sys.stdout.write(json.dumps(res) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
