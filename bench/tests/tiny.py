"""A tiny cell for CPU tests of the harness: a copy of ``bench/`` in a
temporary root whose BENCHMARK.json names a 120x160 configuration and a
short open-loop mix of two vio robots. Nothing here is a measurement."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_CONFIG = json.loads((BENCH / "configs" / "edx-car.json").read_text())
TINY_CONFIG.update(name="tiny", matrix_block=128)
TINY_CONFIG["frontend"].update(height=120, width=160, max_features=128,
                               stereo_max_disparity=32)
TINY_CONFIG["backend"].update(msckf_window=12, imu_rate_hz=400,
                              max_map_points=2048, ba_window=3,
                              ba_landmarks=8, lm_iters=2)

TINY_TRAFFIC = json.loads(
    (BENCH / "traffic" / "live-car-vio.json").read_text())
TINY_TRAFFIC.update(robots=2, capacity=2, warmup_s=0.5, rate_hz=20.0,
                    scenarios=[{"name": "vio", "gps_sigma_m": 0.05,
                                "gps_drop": 0.0}])
TINY_TRAFFIC["scene"].update(landmarks=600)

# at this size vio robots read err_max 0.03-0.1 m and rmse 0.01-0.05 m
# on the CPU; answering each frame with the previous pose reads rmse
# about 0.2 m (4.2 m/s over 50 ms)
TINY_LIMITS = {"vio": {"err_max_m": {"limit": 0.3},
                       "rmse_m": {"limit": 0.15}}}


def make_root(dst: Path, traffic=None, limits=None) -> Path:
    """A checkout-like root at ``dst`` holding the benchmark's files and
    a BENCHMARK.json with the one cell ``tiny-live``."""
    shutil.copytree(BENCH, dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    (dst / "bench" / "traffic" / "tiny-open.json").write_text(
        json.dumps(traffic or TINY_TRAFFIC))
    (dst / "bench" / "limits" / "tiny-live.json").write_text(
        json.dumps(limits or TINY_LIMITS))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tiny-live", "config": "tiny",
                          "traffic": "tiny-open", "chips": 1,
                          "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst
