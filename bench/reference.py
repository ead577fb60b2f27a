"""The check that decides ``correct``: every pose of the window against
the circuit's ground truth.

The reference is the closed-form trajectory of ``lap.Circuit``; it
imports nothing of the program and takes nothing the program made. A
localization service's answer for a frame is where the robot was, so
each pose is judged by its distance from where the robot truly was at
that frame. The numbers compared, each with its limit:

* ``unposed``: frames of the window that never got a pose (limit 0);
* ``unexpected``: poses returned for no frame waiting (limit 0);
* ``nonfinite``: poses with a NaN or an infinity (limit 0);
* ``err_max_m``: the widest distance of any pose from the truth;
* ``rmse_m``: the root mean square of those distances.

The last two are taken per scenario (``err_max_m.vio``, ...): robots
on GPS and robots without it err by different amounts, and a limit
over both would be set by the worse. Their limits are per cell, in
``bench/limits/<cell>.json``, each with the readings it was set from.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def distances(frames, truth: Sequence[np.ndarray]) -> np.ndarray:
    """Position error (m) of each posed frame with a finite pose."""
    d = [np.linalg.norm(f.pose - t) for f, t in zip(frames, truth)
         if f.pose is not None and np.isfinite(f.pose).all()]
    return np.asarray(d, np.float64)


def compare(window, truth: Sequence[np.ndarray], scenario: Sequence[str],
            limits: Dict[str, Dict]) -> Dict[str, Dict[str, float]]:
    """``scenario[r]`` names robot ``r``'s scenario; ``limits`` maps a
    scenario to its ``err_max_m`` and ``rmse_m`` entries."""
    frames = window.frames
    posed = [f for f in frames if f.pose is not None]
    nonfinite = sum(1 for f in posed if not np.isfinite(f.pose).all())
    out = {
        "unposed": {"value": len(frames) - len(posed), "limit": 0},
        "unexpected": {"value": window.unexpected, "limit": 0},
        "nonfinite": {"value": nonfinite, "limit": 0},
    }
    for name in sorted(set(scenario)):
        mine = [(f, t) for f, t in zip(frames, truth)
                if scenario[f.robot] == name]
        d = distances([f for f, _ in mine], [t for _, t in mine])
        lim = limits[name]
        out[f"err_max_m.{name}"] = {
            "value": float(d.max()) if d.size else 0.0,
            "limit": float(lim["err_max_m"]["limit"])}
        out[f"rmse_m.{name}"] = {
            "value": float(np.sqrt(np.mean(d ** 2))) if d.size else 0.0,
            "limit": float(lim["rmse_m"]["limit"])}
    return out


def quantize(frame, dtype: str):
    """The control's inputs: each array of ``(img_l, img_r, accel, gyro,
    gps)`` rounded to ``dtype`` (``bfloat16``, ``float8_e4m3fn``) and
    back to float32. NaN (no GPS fix) stays NaN."""
    import ml_dtypes
    t = getattr(ml_dtypes, dtype)
    return tuple(np.asarray(a, np.float32).astype(t).astype(np.float32)
                 for a in frame)


def stale(window):
    """The control that breaks the guarantee that a pose fuses its own
    frame: each robot's frame is answered with the pose of its previous
    frame (the first keeps its own), as a server that answers from the
    last state before fusing the new frame would. Returns a copy."""
    import copy
    out = copy.copy(window)
    out.frames = [copy.copy(f) for f in window.frames]
    last = {}
    for f in out.frames:
        prev = last.get(f.robot)
        last[f.robot] = f.pose
        if prev is not None and f.pose is not None:
            f.pose = prev
    return out


def raw_gps(window, answer):
    """The control that ignores the camera and the IMU: each frame is
    answered with ``answer(robot, frame)``, its GPS fix or, inside an
    outage, the robot's last fix before it. Returns a copy."""
    import copy
    out = copy.copy(window)
    out.frames = [copy.copy(f) for f in window.frames]
    for f in out.frames:
        f.pose = np.asarray(answer(f.robot, f.frame), np.float64)
    return out
