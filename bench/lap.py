"""Periodic synthetic drive for the benchmark: a closed circuit.

Adapted from the program's ``repro.data.frames.generate`` (landmark
world, blob rendering, IMU and GPS noise), with two changes that a
served fleet needs:

* The trajectory is a closed circuit whose lap is ``lap_frames`` frames.
  Position, velocity, acceleration, heading and yaw rate are closed-form
  and periodic, so the IMU stream is continuous across the lap's wrap
  (the program's generator differentiates an interpolated forward path).
* Landmarks fill an annulus around the circuit, so the forward camera
  sees them all the way round.

One lap of stereo images is rendered once per run; every robot drives
laps from its own phase. IMU and GPS noise are drawn fresh for every
(robot, lap) from the seed, so no two laps of a robot repeat a noise
draw while the work per frame stays the same.

The module imports nothing of the program.
"""
from __future__ import annotations

import os
import statistics
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import numpy as np

GRAVITY = np.array([0.0, -9.81, 0.0])
BACKGROUND = 24.0       # grey level of the sky and ground, no landmark


@dataclass(frozen=True)
class Camera:
    """Pinhole stereo camera; the same fields as the program's
    ``CameraModel`` (its constructor takes these by name)."""
    fx: float
    fy: float
    cx: float
    cy: float
    baseline: float = 0.12


@dataclass(frozen=True)
class Circuit:
    """Closed-form periodic path. ``phi = 2 pi t / period``; radius
    ``radius + wobble sin(5 phi)``; height ``sway sin(3 phi)``; the
    camera looks along the horizontal tangent."""
    period: float
    radius: float
    wobble: float
    sway: float

    def kinematics(self, t) -> Dict[str, np.ndarray]:
        """Position (n,3), world velocity and acceleration (n,3),
        heading (n,) and yaw rate (n,) at times ``t`` (n,)."""
        t = np.asarray(t, np.float64)
        w = 2.0 * np.pi / self.period
        ph = w * t
        kr, ky = 5.0, 3.0
        r = self.radius + self.wobble * np.sin(kr * ph)
        r1 = self.wobble * kr * np.cos(kr * ph)
        r2 = -self.wobble * kr * kr * np.sin(kr * ph)
        s, c = np.sin(ph), np.cos(ph)
        x, z = r * s, r * c
        x1 = r1 * s + r * c
        z1 = r1 * c - r * s
        x2 = r2 * s + 2.0 * r1 * c - r * s
        z2 = r2 * c - 2.0 * r1 * s - r * c
        y = self.sway * np.sin(ky * ph)
        y1 = self.sway * ky * np.cos(ky * ph)
        y2 = -self.sway * ky * ky * np.sin(ky * ph)
        heading = np.arctan2(x1, z1)
        yaw_rate = w * (z1 * x2 - x1 * z2) / (x1 * x1 + z1 * z1)
        return {"p": np.stack([x, y, z], -1),
                "v": w * np.stack([x1, y1, z1], -1),
                "a": w * w * np.stack([x2, y2, z2], -1),
                "heading": heading, "yaw_rate": yaw_rate}


def camera(H: int, W: int) -> Camera:
    """The program generator's intrinsics for an H x W image."""
    return Camera(fx=0.9 * W, fy=0.9 * W, cx=W / 2, cy=H / 2)


def yaw_matrix(theta) -> np.ndarray:
    """(..., 3, 3) rotation about +y: camera-to-world for heading theta
    (the program's generator uses the same convention)."""
    c, s = np.cos(theta), np.sin(theta)
    o, z = np.ones_like(c), np.zeros_like(c)
    return np.stack([np.stack([c, z, s], -1), np.stack([z, o, z], -1),
                     np.stack([-s, z, c], -1)], -2)


def yaw_quaternion(theta) -> np.ndarray:
    """wxyz quaternion of ``yaw_matrix(theta)``."""
    h = 0.5 * np.asarray(theta, np.float64)
    return np.stack([np.cos(h), np.zeros_like(h), np.sin(h),
                     np.zeros_like(h)], -1)


def make_landmarks(rng, n: int, radius: float, keep_out: float,
                   reach: float, height: float) -> np.ndarray:
    """Landmarks uniform over an annulus around the circuit's centre,
    outside a corridor of ``keep_out`` metres round the track."""
    inner = np.sqrt(rng.uniform(0.0, max(radius - keep_out, 0.0) ** 2, n))
    outer = np.sqrt(rng.uniform((radius + keep_out) ** 2,
                                (radius + reach) ** 2, n))
    # share of landmarks inside the circuit, by area
    a_in = max(radius - keep_out, 0.0) ** 2
    a_out = (radius + reach) ** 2 - (radius + keep_out) ** 2
    rho = np.where(rng.uniform(size=n) < a_in / (a_in + a_out), inner, outer)
    ang = rng.uniform(0.0, 2.0 * np.pi, n)
    return np.stack([rho * np.sin(ang), rng.uniform(-height, height, n),
                     rho * np.cos(ang)], -1)


def _noise_table(sigma: float) -> np.ndarray:
    """Gaussian pixel noise by table: the normal quantiles at the 256
    byte midpoints, scaled by ``sigma`` (a byte per pixel is several
    times cheaper to draw than a normal deviate)."""
    nd = statistics.NormalDist()
    return np.array([nd.inv_cdf((k + 0.5) / 256) for k in range(256)],
                    np.float32) * np.float32(sigma)


_RR = 4
_GY, _GX = np.mgrid[-_RR:_RR + 1, -_RR:_RR + 1]


def render_view(landmarks, brightness, sizes, R, t, cam: Camera,
                H: int, W: int) -> np.ndarray:
    """Landmarks as Gaussian blobs on a dim background (the program
    generator's ``render_view``, vectorised over landmarks)."""
    pw = (landmarks - t) @ R                     # world -> camera
    z = pw[:, 2]
    zc = np.maximum(z, 1e-6)
    u = cam.fx * pw[:, 0] / zc + cam.cx
    v = cam.fy * pw[:, 1] / zc + cam.cy
    vis = (z > 0.5) & (u > 4) & (u < W - 5) & (v > 4) & (v < H - 5)
    img = np.full((H, W), BACKGROUND, np.float32)
    idx = np.nonzero(vis)[0]
    if idx.size:
        sig = sizes[idx] * np.clip(8.0 / z[idx], 0.4, 2.0)
        blob = brightness[idx, None, None] * np.exp(
            -(_GY ** 2 + _GX ** 2)[None] / (2.0 * sig[:, None, None] ** 2))
        vi = np.rint(v[idx]).astype(np.int64)
        ui = np.rint(u[idx]).astype(np.int64)
        rows = vi[:, None, None] + _GY[None]
        cols = ui[:, None, None] + _GX[None]
        np.add.at(img, (rows.ravel(), cols.ravel()),
                  blob.ravel().astype(np.float32))
    return img


@dataclass
class Lap:
    """One rendered lap plus the closed-form ground truth.

    Frame ``j`` of the lap is at sensor time ``j * dt``. ``imu_*[j]``
    holds the ``ipf`` samples of the interval that ends at frame ``j``
    (frame 0's interval starts at the previous lap's last frame), noise
    free; ``Lap.sensors`` adds the per-(robot, lap) noise."""
    images_left: np.ndarray     # (P, H, W) float32
    images_right: np.ndarray
    positions: np.ndarray       # (P, 3) ground truth
    velocities: np.ndarray      # (P, 3)
    headings: np.ndarray        # (P,)
    imu_accel: np.ndarray       # (P, ipf, 3) body accel incl. gravity
    imu_gyro: np.ndarray        # (P, ipf, 3) body angular rate
    cam: Camera
    dt: float
    ipf: int
    seed: int
    accel_sigma: float
    gyro_sigma: float

    @property
    def frames(self) -> int:
        return self.positions.shape[0]

    def sensors(self, robot: int, lap: int, gps_sigma: Optional[float],
                gps_drop: float = 0.0
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Noisy (accel (P,ipf,3), gyro (P,ipf,3), gps (P,3)) for lap
        number ``lap`` of robot ``robot``, drawn from (seed, robot, lap).
        ``gps_sigma=None`` gives no fixes (NaN); ``gps_drop`` is the
        share of frames whose fix is missing."""
        rng = np.random.default_rng([self.seed, 1, robot, lap])
        P = self.frames
        ac = (self.imu_accel + rng.standard_normal(self.imu_accel.shape)
              * self.accel_sigma).astype(np.float32)
        gy = (self.imu_gyro + rng.standard_normal(self.imu_gyro.shape)
              * self.gyro_sigma).astype(np.float32)
        if gps_sigma is None:
            gps = np.full((P, 3), np.nan, np.float32)
        else:
            gps = (self.positions + rng.standard_normal((P, 3))
                   * gps_sigma).astype(np.float32)
            gps[rng.uniform(size=P) < gps_drop] = np.nan
        return ac, gy, gps


def generate(*, seed: int, H: int, W: int, cam_rate_hz: float, ipf: int,
             lap_frames: int, radius_m: float, wobble_m: float,
             sway_m: float, landmarks: int, keep_out_m: float,
             reach_m: float, height_m: float, accel_sigma: float,
             gyro_sigma: float, pixel_sigma: float) -> Lap:
    """Render one lap of the circuit. The world (landmarks) is fixed by
    the scene; ``seed`` draws the image noise and, through
    ``Lap.sensors``, the IMU and GPS noise."""
    world = np.random.default_rng(0)
    lms = make_landmarks(world, landmarks, radius_m, keep_out_m, reach_m,
                         height_m)
    bright = world.uniform(120, 230, landmarks)
    sizes = world.uniform(0.9, 1.6, landmarks)

    dt = 1.0 / cam_rate_hz
    circuit = Circuit(period=lap_frames * dt, radius=radius_m,
                      wobble=wobble_m, sway=sway_m)
    cam = camera(H, W)
    kin = circuit.kinematics(np.arange(lap_frames) * dt)
    Rs = yaw_matrix(kin["heading"])
    il = np.empty((lap_frames, H, W), np.float32)
    ir = np.empty((lap_frames, H, W), np.float32)
    base = np.array([cam.baseline, 0.0, 0.0])
    table = _noise_table(pixel_sigma)

    def frame(j):
        noise = np.random.default_rng([seed, 0, j])
        R, t = Rs[j], kin["p"][j]
        for img, origin in ((il[j], t), (ir[j], t + R @ base)):
            img[...] = render_view(lms, bright, sizes, R, origin, cam, H, W)
            img += table[noise.integers(0, 256, (H, W), np.uint8)]
            np.clip(img, 0, 255, out=img)

    # frames are independent and numpy releases the interpreter lock in
    # the bulk work, so threads cut the set-up's rendering time
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        list(ex.map(frame, range(lap_frames)))

    # IMU: sample m of frame j's interval is at (j - 1 + m / ipf) * dt
    ts = ((np.arange(lap_frames)[:, None] - 1)
          + np.arange(ipf)[None, :] / ipf) * dt
    ik = circuit.kinematics(ts.ravel())
    Ri = yaw_matrix(ik["heading"])
    accel = np.einsum("nji,nj->ni", Ri, ik["a"] - GRAVITY)
    gyro = np.zeros_like(accel)
    gyro[:, 1] = ik["yaw_rate"]
    return Lap(images_left=il, images_right=ir, positions=kin["p"],
               velocities=kin["v"], headings=kin["heading"],
               imu_accel=accel.reshape(lap_frames, ipf, 3),
               imu_gyro=gyro.reshape(lap_frames, ipf, 3), cam=cam, dt=dt,
               ipf=ipf, seed=seed, accel_sigma=accel_sigma,
               gyro_sigma=gyro_sigma)
