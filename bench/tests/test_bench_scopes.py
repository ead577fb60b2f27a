"""Device time by primitive and idle time by program span
(``scopes.py``), and the readers of the engine's spans and waits, on
small traces whose answers are known by hand."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace
import scopes
from devtrace import Event
from scopes import Scoped

HERE = Path(__file__).resolve().parent
DEV = "/device:TPU:0"


def hand_trace():
    """Window [0, 20): two chunk executions [1, 9) and [11, 19).

    First execution: a scan op over [1, 9) (unscoped), a frontend op
    over [2, 6) with a track_ring op nested in [3, 4), an msckf_update
    op over [6, 8); the scan alone runs [1, 2) and [8, 9). Second: one
    frontend op over [11, 19). An op of another program at [9.5, 10)."""
    fe = "jit(<unknown>)/while/body/vmap(frontend)/add"
    ops = [("%while.1", 1, 8, "jit(<unknown>)/while"),
           ("%fusion.1", 2, 4, fe),
           ("%fusion.2", 3, 1, "vmap(track_ring)/concatenate"),
           ("%fusion.3", 6, 2, "jit(<unknown>)/vmap(msckf_update)/dot"),
           ("%convert.1", 9.5, 0.5, ""),
           ("%fusion.1", 11, 8, fe)]
    return Scoped(
        ops={DEV: [Event(n, s, d) for n, s, d, _ in ops]},
        scopes={DEV: [sc for *_, sc in ops]},
        modules={DEV: [Event("jit__unknown(7)", 1, 8),
                       Event("jit_convert(3)", 9.5, 0.5),
                       Event("jit__unknown(7)", 11, 8)]},
        host=[Event("window", 0, 20), Event("run_chunk", 0, 11)],
        program=[Event("engine.dispatch", 0.2, 0.6),
                 Event("fleet.enqueue", 0.5, 0.2),
                 Event("pool.sync", 9, 1.8)])


def test_primitive_of_reads_the_innermost_scope():
    assert scopes.primitive_of(
        "jit(<unknown>)/while/body/closed_call/vmap(frontend)/add") \
        == "frontend"
    assert scopes.primitive_of("vmap(ba_marginalize)/vmap(msckf_update)/x") \
        == "msckf_update"
    # a token must match whole: a kernel's name is not its primitive's
    assert scopes.primitive_of("jit(frontend_fused_cells)/x") \
        == scopes.UNSCOPED
    assert scopes.primitive_of("") == scopes.UNSCOPED


def test_primitive_self_times_innermost_op_wins():
    tr = hand_trace()
    st = scopes.primitive_self_times(tr)
    # frontend [2,3) u [4,6) and [11,19); track_ring [3,4);
    # msckf_update [6,8); the scan alone [1,2) and [8,9); the op of the
    # other program at [9.5, 10) lies in no execution
    assert st == {"unscoped": pytest.approx(2.0),
                  "frontend": pytest.approx(11.0),
                  "track_ring": pytest.approx(1.0),
                  "msckf_update": pytest.approx(2.0)}
    assert scopes.chunk_executions(tr) == 2
    # the split adds up to the chunk program's busy time
    assert sum(st.values()) == pytest.approx(16.0)
    assert sum(scopes.shares(st).values()) == pytest.approx(100.0)


def test_primitive_self_times_cut_to_the_window():
    st = scopes.primitive_self_times(hand_trace(), (0.0, 5.0))
    assert st == {"unscoped": pytest.approx(1.0),
                  "frontend": pytest.approx(2.0),
                  "track_ring": pytest.approx(1.0)}


def test_device_without_scopes_is_left_out():
    tr = hand_trace()
    tr.scopes = {}
    assert scopes.primitive_self_times(tr) == {}


def test_idle_by_span_books_gaps_to_the_innermost_span():
    tr = hand_trace()
    idle = scopes.idle_by_span(tr)
    # gaps: [0,1) under engine.dispatch (0.6) and fleet.enqueue (0.2):
    # the dispatch overlaps most; [9,9.5) and [10,11) under pool.sync;
    # [19,20) under no span
    assert idle == {"engine.dispatch": pytest.approx(1.0),
                    "pool.sync": pytest.approx(1.5),
                    "none": pytest.approx(1.0)}
    assert sum(idle.values()) == pytest.approx(
        20.0 - devtrace.busy_s(tr))
    # a gap wholly inside the inner span goes to it, not to its parent
    tr.ops[DEV].append(Event("%pad", 0.0, 0.5))
    tr.scopes[DEV].append("")
    tr.ops[DEV].append(Event("%pad", 0.7, 0.3))
    tr.scopes[DEV].append("")
    assert scopes.idle_by_span(tr)["fleet.enqueue"] == pytest.approx(0.2)


def test_hlo_scopes_from_compiled_text():
    text = (
        '  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
        'calls=%fused_computation.7, metadata={op_name="jit(<unknown>)/'
        'while/body/vmap(imu_propagate)/mul" source_file="x.py" '
        'source_line=3}\n'
        '  ROOT %tuple.2 = (f32[4]{0}) tuple(%fusion.7)\n'
        '  %cov_update.13 = f32[195,195]{1,0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name="vmap('
        'imu_propagate)/pallas_call"}\n')
    got = scopes.hlo_scopes(text)
    assert got == {
        "%fusion.7": "jit(<unknown>)/while/body/vmap(imu_propagate)/mul",
        "%cov_update.13": "vmap(imu_propagate)/pallas_call"}


def test_json_round_trip_and_cut(tmp_path):
    tr = hand_trace()
    scopes.write_json(tr, tmp_path / "t.json")
    back = scopes.read_json(tmp_path / "t.json")
    assert back.to_json() == tr.to_json()
    part = scopes.cut(tr, (10.5, 20.0))
    assert part.window() == (10.5, 20.0)
    assert [e.name for e in part.ops[DEV]] == ["%fusion.1"]
    assert part.scopes[DEV] == [tr.scopes[DEV][-1]]
    assert [e.name for e in part.program] == ["pool.sync"]
    assert scopes.primitive_self_times(part) == {
        "frontend": pytest.approx(8.0)}


def test_load_keeps_the_program_spans(tmp_path):
    """``load`` on an .xplane.pb written here (CPU: host spans only):
    the program's spans beside the harness's, and no device scope."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("engine.dispatch", chunk=3):
                with jax.profiler.TraceAnnotation("fleet.enqueue",
                                                  chunk=3):
                    f(jnp.ones(8)).block_until_ready()
            with jax.profiler.TraceAnnotation("pool.sync", chunk=2):
                pass
    tr = scopes.load(str(tmp_path))
    assert sorted(e.name for e in tr.program) == [
        "engine.dispatch", "fleet.enqueue", "pool.sync"]
    assert [e.name for e in tr.host] == ["window"]
    assert tr.scopes == {}
    w = tr.window()
    assert all(w[0] <= e.start and e.end <= w[1] for e in tr.program)
    back = Scoped.from_json(tr.to_json())
    assert back.to_json() == tr.to_json()


# ----------------------------------------------------------------------
# readers of the engine's spans and per-frame waits
# ----------------------------------------------------------------------

def reader(metric):
    path = HERE.parent / "metrics" / f"{metric}.py"
    sp = importlib.util.spec_from_file_location(f"m:{metric}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("metric,key,want", [
    ("queue_wait_ms.live", "queue_wait", 20.0),     # median
    ("in_flight_ms.live", "in_flight", 20.0),       # median
    ("enqueue_ms.live", "enqueue", 40.0)])          # mean per chunk
def test_span_readers(metric, key, want):
    read = reader(metric)
    run = SimpleNamespace(decomp={key: [0.01, 0.02, 0.09]})
    assert read(run) == pytest.approx(want)
    # a program without the span or wait (an older one) reads nothing
    assert read(SimpleNamespace(decomp={"stage": [0.1]})) is None
    assert read(SimpleNamespace(decomp={key: []})) is None


# ----------------------------------------------------------------------
# a trace recorded on one v5e chip (``recorded_scoped_trace.json``, by
# ``inside.py --record``): 20 ms of the car cell around the boundary of
# two executions of the chunk program, with each op's scope and the
# program's spans of the next chunk's dispatch
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return scopes.read_json(HERE / "recorded_scoped_trace.json")


def test_recorded_split_adds_up_to_the_chunk_program(recorded):
    """Primitives plus ``unscoped`` partition the chunk program's
    device time, and the named scopes cover most of it."""
    w = recorded.window()
    st = scopes.primitive_self_times(recorded)
    t, n = devtrace.time_of(recorded.modules,
                            lambda s: s.startswith("jit__unknown("), w)
    assert n == 2 == scopes.chunk_executions(recorded)
    assert sum(st.values()) == pytest.approx(t, rel=1e-3)
    assert set(st) <= set(scopes.PRIMITIVES) | {scopes.UNSCOPED}
    assert {"frontend", "imu_propagate"} <= set(st)
    assert st[scopes.UNSCOPED] < 0.1 * t


def test_recorded_program_spans_nest(recorded):
    """The next chunk's dispatch and its three host steps, in order and
    inside it, on the profiler's clock."""
    spans = {e.name: e for e in recorded.program}
    d = spans["engine.dispatch"]
    parts = [spans[k] for k in ("fleet.build", "fleet.h2d",
                                "fleet.enqueue")]
    assert all(d.start <= p.start and p.end <= d.end for p in parts)
    assert parts[0].end <= parts[1].start and parts[1].end \
        <= parts[2].start
    assert spans["engine.stage"].end <= d.start
    idle = scopes.idle_by_span(recorded)
    w = recorded.window()
    assert sum(idle.values()) == pytest.approx(
        (w[1] - w[0]) - devtrace.busy_s(recorded), abs=1e-9)


def test_recorded_kernels_carry_their_names(recorded):
    """The Pallas calls are named after their kernels in the trace, and
    the roofline reader still tells ``cov_update`` by its shapes."""
    import importlib.util
    names = {e.name.rsplit(".", 1)[0] for es in recorded.ops.values()
             for e in es}
    assert "%cov_update" in names
    path = HERE.parent / "metrics" / "cov_update_roofline.live.py"
    sp = importlib.util.spec_from_file_location("cov_reader", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    found = mod.kernels(recorded, 195, 10)
    assert len(found) == 1 and next(iter(found)).startswith("%cov_update.")
