"""The periodic generator: closed-form kinematics, IMU continuity across
the lap's wrap, determinism from the seed."""
import numpy as np
import pytest

import lap

SCENE = dict(H=48, W=64, cam_rate_hz=20.0, ipf=10, lap_frames=240,
             radius_m=8.0, wobble_m=0.4, sway_m=0.15, landmarks=300,
             keep_out_m=3.0, reach_m=40.0, height_m=6.0, accel_sigma=0.05,
             gyro_sigma=0.002, pixel_sigma=1.5)


@pytest.fixture(scope="module")
def one():
    return lap.generate(seed=2 ** 31 + 12345, **SCENE)


def test_kinematics_are_derivatives_and_periodic():
    c = lap.Circuit(period=3.0, radius=8.0, wobble=0.4, sway=0.15)
    t = np.linspace(0.0, 3.0, 2001)
    k = c.kinematics(t)
    h = t[1] - t[0]
    v_num = np.gradient(k["p"], h, axis=0)
    a_num = np.gradient(k["v"], h, axis=0)
    yr_num = np.gradient(np.unwrap(k["heading"]), h)
    assert np.abs(v_num - k["v"])[1:-1].max() < 1e-3
    assert np.abs(a_num - k["a"])[1:-1].max() < 1e-2
    assert np.abs(yr_num - k["yaw_rate"])[1:-1].max() < 1e-3
    k0, k1 = c.kinematics([0.0]), c.kinematics([3.0])
    for key in ("p", "v", "a", "yaw_rate"):
        assert np.allclose(k0[key], k1[key], atol=1e-9)


def test_imu_continuous_across_the_wrap(one):
    acc = one.imu_accel.reshape(-1, 3)
    gyr = one.imu_gyro.reshape(-1, 3)
    for s in (acc, gyr):
        # the stream of laps: ... frame P-1's samples, then frame 0's
        step = np.abs(np.diff(np.concatenate([s, s[:1]]), axis=0))
        inside = step[:-1].max(axis=0)
        wrap = step[-1]
        assert (wrap <= 1.5 * inside + 1e-9).all()


def test_imu_matches_ground_truth_motion(one):
    """Integrating the noise-free IMU of one frame interval in the world
    frame reproduces the change of ground-truth velocity."""
    dt = one.dt / one.ipf
    for j in (1, 97, 239):
        th0 = one.headings[j - 1]
        dth = one.imu_gyro[j, :, 1] * dt
        th = th0 + np.concatenate([[0.0], np.cumsum(dth)[:-1]])
        a_w = np.einsum("nij,nj->ni", lap.yaw_matrix(th),
                        one.imu_accel[j]) + lap.GRAVITY
        dv = a_w.sum(0) * dt
        assert np.allclose(dv, one.velocities[j] - one.velocities[j - 1],
                           atol=0.05)


def test_deterministic_from_the_seed(one):
    again = lap.generate(seed=2 ** 31 + 12345, **SCENE)
    assert np.array_equal(one.images_left, again.images_left)
    assert np.array_equal(one.images_right, again.images_right)
    for a, b in zip(one.sensors(3, 7, 0.05, 0.5),
                    again.sensors(3, 7, 0.05, 0.5)):
        assert np.array_equal(a, b, equal_nan=True)
    other = lap.generate(seed=5, **SCENE)
    assert not np.array_equal(one.images_left, other.images_left)
    # the ground truth is the scene's, not the seed's
    assert np.array_equal(one.positions, other.positions)


def test_noise_is_fresh_per_lap_and_robot(one):
    a = one.sensors(0, 0, 0.05)
    assert not np.array_equal(a[2], one.sensors(0, 1, 0.05)[2])
    assert not np.array_equal(a[2], one.sensors(1, 0, 0.05)[2])
    gps = one.sensors(0, 0, None)[2]
    assert np.isnan(gps).all()
    dropped = one.sensors(0, 0, 0.05, gps_drop=0.5)[2]
    assert 0 < np.isnan(dropped[:, 0]).sum() < one.frames


def test_landmarks_visible_all_the_way_round():
    big = lap.generate(seed=1, **dict(SCENE, H=120, W=160))
    bright = (big.images_left > 60).reshape(big.frames, -1).sum(1)
    assert bright.min() > 0.2 * bright.mean()


def test_yaw_quaternion_matches_matrix():
    th = 0.7
    w, x, y, z = lap.yaw_quaternion(th)
    R = np.array([[1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
                   2 * (x * z + w * y)],
                  [2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
                   2 * (y * z - w * x)],
                  [2 * (x * z - w * y), 2 * (y * z + w * x),
                   1 - 2 * (x * x + y * y)]])
    assert np.allclose(R, lap.yaw_matrix(th))
