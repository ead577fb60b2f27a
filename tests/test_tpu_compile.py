"""The main-path Pallas kernels and the served chunk program compile for a
TPU v5e at deployment shapes.

Interpret mode proves numerics, not that Mosaic can lower a kernel: the
TPU compiler is installed here and compiles for a described (not
attached) v5e chip. Nothing runs, so these tests say nothing about
results or speed. The topology is described inside a fixture — never
while a module is imported — and every compile happens in the test's
own process.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.eudoxus import EDX_CAR, EDX_DRONE


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# (config, clone window -> d = 15 + 6W, IMU samples per frame)
DEPLOYMENTS = {"car": (EDX_CAR, 30, 10), "drone": (EDX_DRONE, 12, 20)}


@pytest.mark.parametrize("name", sorted(DEPLOYMENTS))
def test_cov_update_compiles(one_chip, name):
    from repro.kernels import cov_update
    _, w, k = DEPLOYMENTS[name]
    d = 15 + 6 * w
    _compile(lambda P, F, Q, g: cov_update.fused_update(P, F, Q, g,
                                                        interpret=False),
             _spec(one_chip, (d, d)), _spec(one_chip, (k, 15, 15)),
             _spec(one_chip, (15, 15)), _spec(one_chip, (), jnp.int32))


@pytest.mark.parametrize("m,double_buffer", [(64, False), (64, True),
                                             (256, True)])
def test_marg_schur_compiles(one_chip, m, double_buffer):
    """The BA window (K=10) at the deployments' 64-landmark budget, and a
    two-tile budget on which the double-buffered DMA pipeline runs."""
    from repro.kernels import marg_schur
    k = EDX_CAR.backend.ba_window
    _compile(lambda r, jx, jl: marg_schur.accumulate_normal(
                 r, jx, jl, double_buffer=double_buffer,
                 interpret=False),
             _spec(one_chip, (k, m, 2)), _spec(one_chip, (k, m, 2, 6)),
             _spec(one_chip, (k, m, 2, 3)))


@pytest.mark.parametrize("double_buffer", [False, True])
@pytest.mark.parametrize("name", sorted(DEPLOYMENTS))
def test_frontend_kernel_a_compiles(one_chip, name, double_buffer):
    from repro.kernels import frontend_fused
    fe = DEPLOYMENTS[name][0].frontend
    _compile(lambda img: frontend_fused.fe_cells(
                 img, fe, double_buffer=double_buffer, interpret=False),
             _spec(one_chip, (fe.height, fe.width)))


def test_frontend_kernel_c_compiles(one_chip):
    from repro.kernels import frontend_fused
    fe = EDX_CAR.frontend
    n = fe.max_features
    pk = _spec(one_chip, (n, 8), jnp.uint32)
    yx = _spec(one_chip, (n, 2), jnp.int32)
    v = _spec(one_chip, (n,), jnp.bool_)
    _compile(lambda a, ya, va, b, yb, vb: frontend_fused.match_packed(
                 a, ya, va, b, yb, vb,
                 max_disparity=fe.stereo_max_disparity,
                 hamming_budget=fe.stereo_hamming_budget, interpret=False),
             pk, yx, v, pk, yx, v)


def test_blocked_matmul_compiles(one_chip):
    from repro.kernels import blocked_matmul
    _compile(lambda a, b: blocked_matmul.matmul(a, b, interpret=False),
             _spec(one_chip, (256, 256)), _spec(one_chip, (256, 256)))


def test_served_chunk_program_compiles(one_chip, monkeypatch):
    """The serving pool's one fleet chunk program at EDX-CAR shapes, with
    the kernel plan a TPU resolves (no fitted models): cov_update and
    marg_schur on Pallas, frontend_fused on XLA (its tpu_blocker). The
    Pallas calls carry their kernels' names and the op metadata every
    primitive's named scope."""
    from repro.data import frames
    from repro.kernels import cov_update, frontend_fused, marg_schur, registry
    from repro.serve import RobotStatePool
    # steer the code that asks jax for its backend (it sees the CPU here)
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    monkeypatch.delenv("REPRO_KERNELS", raising=False)
    for mod in (cov_update, frontend_fused, marg_schur):
        monkeypatch.setattr(mod, "default_interpret", lambda: False)

    cfg = EDX_CAR
    fe, be = cfg.frontend, cfg.backend
    B, K, ipf = 2, 2, be.imu_rate_hz // be.cam_rate_hz
    cam = frames.CameraModel(fx=0.9 * fe.width, fy=0.9 * fe.width,
                             cx=fe.width / 2, cy=fe.height / 2)
    pool = RobotStatePool(cfg, cam, capacity=B)
    fleet = pool.fleet
    mode = np.array([fleet.scenarios.id_of("vio"),
                     fleet.scenarios.id_of("slam")], np.int32)
    z = lambda *s: np.zeros(s, np.float32)
    inputs = fleet._build_chunk(z(K, B, fe.height, fe.width),
                                z(K, B, fe.height, fe.width),
                                z(K, B, ipf, 3), z(K, B, ipf, 3),
                                z(K, B, 3), mode, np.ones((K, B), bool))
    plan = fleet._chunk_plan(K)
    assert [plan.get(k) for k in ("cov_update", "marg_schur",
                                  "frontend_fused")] == [True, True, False]
    flags = fleet._fleet_flags(plan, mode)
    args = jax.tree_util.tree_map(
        lambda x: _spec(one_chip, np.shape(x), x.dtype),
        (pool.states, inputs, flags, jnp.float32(1.0 / be.imu_rate_hz)))
    compiled = fleet._fused_fleet_chunk.lower(*args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    # each Pallas call is named after its kernel in the compiled program
    # (and so in a device trace)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    head = re.compile(r"\s*(?:ROOT )?%([a-z_]+)\.\d+ = ")
    names = sorted(head.match(line).group(1) for line in calls)
    assert names == ["cov_update", "marg_schur_normal"]
    # every primitive's named scope survives into the op metadata
    scopes = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(re.findall(r"[A-Za-z_]\w*", path))
    assert {"frontend", "track_ring", "imu_propagate", "msckf_update",
            "gps_fusion"} <= scopes
