"""The trace reduction, on small traces whose answers are known by hand
and on a trace recorded on a v5e chip (``recorded_trace.json``)."""
import json
from pathlib import Path

import pytest

import devtrace
from devtrace import Event, Trace

HERE = Path(__file__).resolve().parent


def hand_trace():
    # window [0, 10); device busy [1,3) u [2,4) u [6,7) = 4 s
    return Trace(
        ops={"/device:TPU:0": [Event("fusion.1", 1, 2), Event("fusion.2", 2, 2),
                               Event("_cov_kernel", 6, 1),
                               Event("late", 9.5, 2)]},
        modules={"/device:TPU:0": [Event("jit__unknown(123)", 1, 3),
                                   Event("jit__unknown(123)", 6, 1),
                                   Event("jit_other", 9.5, 2)]},
        host=[Event("window", 0, 10), Event("run_chunk", 0.5, 4),
              Event("wait", 4.2, 1.7), Event("submit", 7.0, 0.1)])


def test_union_and_gaps():
    u = devtrace.union([(1, 3), (2, 4), (6, 7), (7, 8)])
    assert u == [(1, 4), (6, 8)]
    assert devtrace.gaps(u, (0, 10)) == [(0, 1), (4, 6), (8, 10)]
    assert devtrace.length(u) == 5


def test_busy_idle_window():
    tr = hand_trace()
    assert tr.window() == (0, 10)
    # the op running past the window's close counts up to the close
    assert devtrace.busy_s(tr) == pytest.approx(4.5)
    assert devtrace.idle_share(tr) == pytest.approx(0.55)


def test_module_and_kernel_time():
    tr = hand_trace()
    t, n = devtrace.time_of(tr.modules,
                            lambda s: s.startswith("jit__unknown("),
                            tr.window())
    assert (t, n) == (pytest.approx(4.0), 2)
    t, n = devtrace.time_of(tr.ops, lambda s: "_cov_kernel" in s,
                            tr.window())
    assert (t, n) == (pytest.approx(1.0), 1)


def test_breakdown_names_gaps_by_host_span():
    tr = hand_trace()
    gaps = devtrace.idle_gaps(tr)
    # gaps [7,9.5) (a submit span inside), [4,6) (mostly "wait"), [0,1)
    assert gaps[0] == ["submit", pytest.approx(2.5)]
    assert gaps[1] == ["wait", pytest.approx(2.0)]
    assert gaps[2] == ["run_chunk", pytest.approx(1.0)]
    top = devtrace.top_ops(tr)
    # overlapping ops: each instant goes to the op that started last
    assert top[0] == ["fusion.2", pytest.approx(2.0)]
    assert sorted(v for _, v in top) == [pytest.approx(0.5), 1.0, 1.0, 2.0]
    assert sum(v for _, v in top) == pytest.approx(devtrace.busy_s(tr))


def test_no_device_ops_is_no_idle_share():
    tr = Trace(host=[Event("window", 0, 1)])
    assert devtrace.idle_share(tr) is None
    assert devtrace.idle_gaps(tr) == []


def test_json_round_trip(tmp_path):
    tr = hand_trace()
    devtrace.write_json(tr, tmp_path / "t.json")
    back = devtrace.read_json(tmp_path / "t.json")
    assert back.to_json() == tr.to_json()


def test_window_span_is_required():
    with pytest.raises(ValueError):
        Trace(host=[]).window()


def test_load_reads_host_spans_of_a_real_profile(tmp_path):
    """``load`` on an .xplane.pb written here (CPU: host spans only)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("run_chunk"):
                f(jnp.ones(8)).block_until_ready()
    tr = devtrace.load(str(tmp_path))
    names = [e.name for e in tr.host]
    assert names.count("window") == 1 and "run_chunk" in names
    w = tr.window()
    rc = [e for e in tr.host if e.name == "run_chunk"][0]
    assert w[0] <= rc.start and rc.end <= w[1]


# ----------------------------------------------------------------------
# a trace recorded on one v5e chip: 20 ms of the car cell around the
# boundary between two executions of the chunk program
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return devtrace.read_json(HERE / "recorded_trace.json")


def grid_busy(intervals, w, step=1e-7):
    """Busy time by sampling the window on a fine grid: an independent
    check of the interval union."""
    import numpy as np
    t = np.arange(w[0] + step / 2, w[1], step)
    busy = np.zeros(t.shape, bool)
    for s, e in intervals:
        busy |= (t >= s) & (t < e)
    return busy.sum() * step


def test_recorded_busy_and_idle(recorded):
    w = recorded.window()
    dev = sorted(recorded.ops)[0]
    ivs = devtrace.clip(recorded.ops[dev], w)
    assert devtrace.busy_s(recorded) == pytest.approx(
        grid_busy(ivs, w), abs=2e-6)
    share = devtrace.idle_share(recorded)
    assert 0.0 < share < 1.0
    # the host stages and dispatches the next chunk between the two
    # executions: the longest gap lies under run_chunk
    gaps = devtrace.idle_gaps(recorded)
    assert gaps[0][0] == "run_chunk" and gaps[0][1] > 1e-3
    assert sum(g for _, g in devtrace.idle_gaps(recorded, n=10 ** 6)) \
        == pytest.approx((w[1] - w[0]) * share)


def test_recorded_chunk_module_time(recorded):
    w = recorded.window()
    t, n = devtrace.time_of(recorded.modules,
                            lambda s: s.startswith("jit__unknown("), w)
    assert n == 2
    mods = [e for e in recorded.modules[sorted(recorded.modules)[0]]
            if e.name.startswith("jit__unknown(")]
    by_hand = sum(max(0.0, min(e.end, w[1]) - max(e.start, w[0]))
                  for e in mods)
    assert t == pytest.approx(by_hand)


def test_recorded_self_time_adds_up(recorded):
    """Self times of the ops partition the time some op ran."""
    w = recorded.window()
    dev = sorted(recorded.ops)[0]
    st = devtrace.self_times(recorded.ops[dev], w)
    assert sum(st.values()) == pytest.approx(devtrace.busy_s(recorded),
                                             rel=1e-6)
    assert min(st.values()) >= -1e-12


def test_recorded_kernel_told_by_its_instruction(recorded):
    import importlib.util
    path = HERE.parent / "metrics" / "cov_update_roofline.live.py"
    sp = importlib.util.spec_from_file_location("cov_reader", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    # EDX-CAR: window 30 -> d = 195, 10 IMU samples per frame; the
    # other Pallas call in the trace (marg_schur) is not taken for it
    assert mod.kernels(recorded, 195, 10) == {"%vmap__.28": 1}
    assert mod.kernels(recorded, 87, 20) == {}
