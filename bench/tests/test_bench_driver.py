"""The load generator's due-time stamping, on a fake clock and a fake
engine."""
import numpy as np
import pytest

from driver import Driver


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakeEngine:
    """Each ``run_chunk`` takes ``cost`` seconds of the fake clock and
    returns, as poses, the frame numbers submitted before it began."""

    def __init__(self, clock, cost):
        self.clock, self.cost = clock, cost
        self.queue = {}
        self.calls = 0

    def submit_frame(self, rid, n):
        self.queue.setdefault(rid, []).append(n)

    def pending_frames(self):
        return sum(len(q) for q in self.queue.values())

    def inflight_chunks(self):
        return 0

    def run_chunk(self):
        self.calls += 1
        out = {rid: np.array([[n, 0.0, 0.0] for n in q])
               for rid, q in self.queue.items() if q}
        self.queue = {}
        self.clock.t += self.cost
        return out


def source(r, n):
    return (n,)


def drive(cost, seconds, **kw):
    clock = Clock()
    eng = FakeEngine(clock, cost)
    d = Driver(eng, ["a", "b"], source, [5, 9], clock=clock,
               sleep=clock.sleep, **kw)
    win = d.run(seconds)
    d.drain()
    return win, eng


def test_open_loop_fast_engine_stamps_from_due_time():
    win, _ = drive(0.01, 1.0, rate_hz=10.0, phases=[0.0, 0.05])
    assert [f.frame for f in win.frames if f.robot == 0] == list(range(5, 15))
    assert [f.frame for f in win.frames if f.robot == 1] == list(range(9, 19))
    for f in win.frames:
        assert f.due == pytest.approx(win.t0 + [0.0, 0.05][f.robot]
                                      + (f.frame - [5, 9][f.robot]) / 10)
        assert f.posed_at - f.due == pytest.approx(0.01)
        assert f.pose[0] == f.frame          # routed to its own frame
    assert max(win.wake_late) == pytest.approx(0.0)


def test_open_loop_blocked_engine_counts_the_wait():
    win, eng = drive(0.25, 1.0, rate_hz=10.0, phases=[0.0, 0.05])
    # the frame due at t0 + 0.1 arrives while the first call (t0, t0+0.25)
    # blocks; it is submitted after it and posed at t0 + 0.5
    f = [f for f in win.frames if f.robot == 0 and f.frame == 6][0]
    assert f.due == pytest.approx(win.t0 + 0.1)
    assert f.posed_at - f.due == pytest.approx(0.4)
    assert all(f.posed_at is not None for f in win.frames)
    assert len(win.frames) == 20          # due frames, not served ones


def test_window_continues_the_schedule_without_a_drain():
    clock = Clock()
    eng = FakeEngine(clock, 0.25)
    d = Driver(eng, ["a"], source, [0], clock=clock, sleep=clock.sleep,
               rate_hz=10.0, phases=[0.0])
    warm = d.run(1.0)
    # the warm-up closes with frames still waiting: no drain follows
    assert any(f.posed_at is None for f in warm.frames)
    win = d.run(2.0)
    d.drain()
    assert win.t0 == warm.t_end
    frames = [f.frame for f in warm.frames + win.frames]
    assert frames == list(range(len(frames)))        # one schedule
    assert all(win.t0 <= f.due < win.t_end for f in win.frames)
    for f in win.frames:
        assert f.due == pytest.approx(warm.t0 + f.frame / 10)
    # poses of warm-up frames handed back inside the window count
    inside = [f for f in warm.frames + win.frames
              if win.t0 < f.posed_at <= win.t_end]
    assert any(f in warm.frames for f in inside)
    assert win.returned == len(inside)
    assert win.calls and all(win.t0 < e <= win.t_end
                             for _, e, _ in win.calls)


def test_returned_is_the_offered_load_while_the_engine_keeps_up():
    clock = Clock()
    eng = FakeEngine(clock, 0.25)
    d = Driver(eng, ["a", "b"], source, [0, 0], clock=clock,
               sleep=clock.sleep, rate_hz=10.0, phases=[0.0, 0.05])
    d.run(1.0)
    win = d.run(4.0)
    assert win.returned / 4.0 == pytest.approx(20.0, rel=0.05)


def test_closed_loop_keeps_outstanding():
    win, eng = drive(0.1, 1.0, loop="closed", outstanding=3)
    per_call = len(win.frames) / eng.calls
    assert per_call == pytest.approx(6, abs=1)
    for f in win.frames:
        assert f.posed_at - f.due == pytest.approx(0.1)


def test_poses_for_no_waiting_frame_are_counted():
    clock = Clock()
    eng = FakeEngine(clock, 0.01)
    d = Driver(eng, ["a"], source, [0], clock=clock, sleep=clock.sleep,
               rate_hz=10.0, phases=[0.0])
    eng.queue["a"] = [-1]                  # a pose nobody asked for
    win = d.run(0.05)
    d.drain()
    assert win.unexpected == 1


def test_bad_loop_arguments():
    with pytest.raises(ValueError):
        Driver(None, [], source, [], loop="open", rate_hz=0, phases=[])
    with pytest.raises(ValueError):
        Driver(None, [], source, [], loop="closed", outstanding=0)
    with pytest.raises(ValueError):
        Driver(None, [], source, [], loop="bursty")
