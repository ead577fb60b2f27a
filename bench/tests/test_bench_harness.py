"""The harness end to end on the CPU at a tiny size: discovery by name,
the correctness check against sound runs, planted faults and the
control, and the refusals (no chip, a compile inside the window).
Nothing here is a measurement."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import reference
import run
import tiny

T0 = time.perf_counter()
SECONDS = 1.5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("root"))


@pytest.fixture(scope="module")
def bench(root):
    import jax
    before = jax.config.jax_default_matmul_precision
    b = run.Bench(run.Cell.load(root, "tiny-live"))
    yield b
    b.compiles.close()
    b.gcs.close()
    jax.config.update("jax_default_matmul_precision", before)


def one_run(bench, seed, trace=False):
    bench.prepare(seed)
    r = bench.measure(SECONDS, trace, T0)
    return r, run.result(bench, r, bench.check(r), trace)


def test_sound_run_is_correct(bench):
    r, res = one_run(bench, 2 ** 33 + 7)
    assert res["correct"], res["checks"]
    assert res["attempted"] == len(r.window.frames) > 0
    assert res["failed"] == 0
    assert list(res)[-1] == "checks"
    names = {m["name"] for m in bench.cell.end_to_end}
    assert set(res["metrics"]) == names
    assert res["checks"]["rmse_m.vio"]["value"] > 0
    assert res["device"]["platform"] == "cpu"


def test_traced_run_reports_host_spans_and_no_device_metric(bench):
    r, res = one_run(bench, 11, trace=True)
    # a CPU trace has no device plane: no device metric, no busy time
    assert "device_idle_share.live" not in res["metrics"]
    assert "stage_ms.live" in res["metrics"]
    assert res["device"]["busy_s"] == 0.0
    assert {e.name for e in r.trace.host} >= {"window", "run_chunk",
                                              "submit"}


# ----------------------------------------------------------------------
# planted faults: each must turn ``correct`` false
# ----------------------------------------------------------------------

def _state_unchanged(real):
    import jax.numpy as jnp

    def chunk(states, inputs, flags, dt):
        import jax
        _, outs = real(jax.tree_util.tree_map(jnp.copy, states), inputs,
                       flags, dt)
        p = jnp.broadcast_to(states.filt.p[None], outs.p.shape)
        return states, outs._replace(p=p)
    return chunk


def _half_batch(real):
    def chunk(states, inputs, flags, dt):
        act = inputs.active.at[:, 1::2].set(False)
        return real(states, inputs._replace(active=act), flags, dt)
    return chunk


def _answer_altered(real):
    def chunk(states, inputs, flags, dt):
        states, outs = real(states, inputs, flags, dt)
        return states, outs._replace(p=outs.p.at[:, 0, 0].add(1.0))
    return chunk


def _poses_swapped(real):
    def chunk(states, inputs, flags, dt):
        states, outs = real(states, inputs, flags, dt)
        return states, outs._replace(p=outs.p[:, ::-1])
    return chunk


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered, _poses_swapped])
def test_planted_fault_is_not_correct(bench, monkeypatch, fault):
    fleet = bench.pool.fleet
    monkeypatch.setattr(fleet, "_fused_fleet_chunk",
                        fault(fleet._fused_fleet_chunk))
    _, res = one_run(bench, 12345)
    assert not res["correct"], res["checks"]


def test_control_stale_pose_is_not_correct(bench):
    bench.prepare(2 ** 31 + 99)
    r = bench.measure(SECONDS, False, T0)
    assert run.result(bench, r, bench.check(r), False)["correct"]
    r.window = reference.stale(r.window)
    assert not run.result(bench, r, bench.check(r), False)["correct"]


def test_raw_gps_control_holds_the_last_fix():
    import readings
    from driver import Posed, Window

    class Fixes:
        def gps(self, r, n):
            return (np.array([n, 0.0, 0.0]) if n % 4 == 0
                    else np.full(3, np.nan))

        def truth(self, r, n):
            return np.zeros(3)
    answer = readings.held_fix(Fixes())
    win = Window(t0=0.0, t_end=1.0, frames=[
        Posed(0, n, 0.0, 0.5, np.full(3, -1.0)) for n in range(3, 9)])
    out = reference.raw_gps(win, answer)
    assert [f.pose[0] for f in out.frames] == [0, 4, 4, 4, 4, 8]
    assert all(f.pose[0] == -1.0 for f in win.frames)   # a copy


def test_gps_outage_leaves_frames_without_a_fix(bench):
    saved = bench.scen
    bench.scen = [{"name": "vio", "gps_sigma_m": 0.05,
                   "gps_outage": [2, 3]}]
    try:
        got = [bool(np.isfinite(bench.fix(0, n, np.ones(3))).all())
               for n in range(10)]
    finally:
        bench.scen = saved
    assert got == [True, True, False, False, False] * 2


def test_blind_control_sends_featureless_images(bench):
    bench.blind = True
    try:
        r, res = one_run(bench, 31)
        img_l, img_r = bench._source(0, 3)[:2]
    finally:
        bench.blind = False
    assert res["checks"]["unposed"]["value"] == 0
    assert np.all(img_l == run.lap.BACKGROUND)
    assert np.all(img_r == run.lap.BACKGROUND)
    assert not np.all(bench._source(0, 3)[0] == run.lap.BACKGROUND)


def test_compile_inside_the_window_fails_the_run(bench, monkeypatch):
    import jax
    real = bench._source

    def source(r, n):
        jax.jit(lambda x: x + n)(np.ones(2))      # a new program
        return real(r, n)
    bench.prepare(3)
    monkeypatch.setattr(bench, "_source", source)
    with pytest.raises(RuntimeError, match="inside the measured window"):
        bench.measure(0.3, False, T0)


# ----------------------------------------------------------------------
# discovery by name and refusals
# ----------------------------------------------------------------------

def test_new_metric_traffic_and_config_found_by_name(root, bench):
    """Files dropped into the tree and named in BENCHMARK.json are found
    with no edit of any existing file of the harness."""
    b = root / "bench"
    (b / "metrics" / "frames_due.py").write_text(
        "def read(run):\n    return float(len(run.window.frames))\n")
    traffic = dict(tiny.TINY_TRAFFIC, robots=1, capacity=1)
    (b / "traffic" / "tiny-closed.json").write_text(json.dumps(
        dict(traffic, loop="closed", outstanding=2)))
    (b / "configs" / "tiny2.json").write_text(json.dumps(
        dict(tiny.TINY_CONFIG, name="tiny2")))
    (b / "limits" / "tiny-closed.json").write_text(
        json.dumps(tiny.TINY_LIMITS))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny2", "source": "test",
                            "file": "bench/configs/tiny2.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-closed", "config": "tiny2",
                              "traffic": "tiny-closed", "chips": 1,
                              "why": "test"})
    spec["end_to_end"].append({"name": "frames_due", "unit": "frames",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny-live"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    try:
        cell = run.Cell.load(root, "tiny-closed")
        assert cell.traffic["loop"] == "closed"
        assert run.build_config(cell.config).name == "tiny2"
        assert "frames_due" not in {m["name"] for m in cell.end_to_end}
        live = run.Cell.load(root, "tiny-live")
        assert "frames_due" in {m["name"] for m in live.end_to_end}
        bench.cell = live
        r, res = one_run(bench, 5)
        assert res["metrics"]["frames_due"]["value"] == len(r.window.frames)
    finally:
        bench.cell = run.Cell.load(root, "tiny-live")


def test_config_file_must_state_every_field():
    c = json.loads(json.dumps(tiny.TINY_CONFIG))
    del c["backend"]["lm_iters"]
    with pytest.raises(ValueError, match="lm_iters"):
        run.build_config(c)


def test_no_chip_no_result(capsys):
    assert run.main(["--workload", "car-live-vio", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_bare_tree_exits_nonzero(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ (no program)."""
    tiny.make_root(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "tiny-live", "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
