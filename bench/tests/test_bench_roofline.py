"""Operation and byte count of ``cov_update`` against a hand count, and
the table of peaks."""
import json

import pytest

import roofline


def test_cov_update_cost_hand_count():
    # d = 21 (one clone), ipf = 2. Per sample: F Pii F^T = two 15x15x15
    # matmuls = 2 * 2 * 3375 = 13500; F Pic = 15x15x6 -> 2 * 1350 = 2700;
    # + Q and the symmetrising average: 3 * 225 = 675. Total 16875.
    ops, nbytes = roofline.cov_update_cost(21, 2)
    assert ops == 2 * 16875
    # P in and out (2 * 441), two F blocks (450), Q (225), the gate (1)
    assert nbytes == 4 * (2 * 441 + 450 + 225 + 1)


def test_cov_update_cost_matches_xla_matmul_count():
    """XLA's own count of the reference composition agrees with the hand
    count (one IMU sample: XLA counts a loop's body once)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import cov_update
    d, ipf = 33, 1
    args = (jnp.eye(d), jnp.ones((ipf, 15, 15)), jnp.eye(15), jnp.int32(1))
    ca = jax.jit(cov_update.update_ref).lower(*args).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    ops, _ = roofline.cov_update_cost(d, ipf)
    matmul_ops = ipf * (2 * 2 * 15 ** 3 + 2 * 15 * 15 * (d - 15))
    assert matmul_ops <= ca["flops"] <= ops * 1.5


def test_peaks_known_and_unknown(tmp_path):
    p = roofline.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    f = tmp_path / "peaks.json"
    f.write_text(json.dumps({"devices": {}}))
    with pytest.raises(KeyError):
        roofline.peaks("TPU v5 lite", f)


def test_roofline_share_picks_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = roofline.roofline_share(ops=100, nbytes=50, seconds=10,
                                           peak=peak)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = roofline.roofline_share(ops=1000, nbytes=1, seconds=20,
                                           peak=peak)
    assert bound == "compute" and share == pytest.approx(50.0)
