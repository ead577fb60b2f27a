"""Load generator: drives ``ServingEngine.run_chunk`` from one thread.

Open loop (``loop: "open"``): robot ``r``'s ``k``-th frame is due at
``origin + phase[r] + k / rate_hz``, whatever the engine does. The
driver submits every frame that is due, calls ``run_chunk`` while the
engine holds frames or chunks, and otherwise sleeps until the next
frame is due. Each pose is stamped against the due time of its frame,
so a frame that waited behind a blocked ``run_chunk`` counts its wait.

Closed loop (``loop: "closed"``): every robot keeps ``outstanding``
frames submitted and not yet posed; a frame is due when it is
submitted.

One driver runs one schedule: a second ``run`` (the measured window
after the warm-up) continues it with no drain, from the instant the
first closed, so the window starts on a pipeline already in its steady
state. A window holds the frames due inside it; the poses it counts as
returned are all those handed back inside it, also those of frames due
before it.

A robot's poses come back in frame order (the engine drains chunks in
dispatch order), so each returned pose is matched to the oldest frame
of that robot still waiting.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import numpy as np


@dataclass
class Posed:
    """One frame of the window: who, which frame, when due, when its
    pose came back (None if it never did) and the pose."""
    robot: int
    frame: int
    due: float
    posed_at: Optional[float] = None
    pose: Optional[np.ndarray] = None


@dataclass
class Window:
    """What one measured window produced."""
    t0: float
    t_end: float
    frames: List[Posed]
    # how late the driver woke after each sleep (s): a starved
    # generator shows here, not as a fast server
    wake_late: List[float] = field(default_factory=list)
    unexpected: int = 0      # poses returned for no waiting frame
    returned: int = 0        # poses of any frame handed back inside it
    # (start, end, poses) of each run_chunk call that returned inside it
    calls: List[Tuple[float, float, int]] = field(default_factory=list)


class Driver:
    """One thread; ``source(robot, frame)`` returns the frame's
    ``(img_l, img_r, accel, gyro, gps)``; ``ids[r]`` is robot ``r``'s
    id in the engine; ``next_frame[r]`` is the first frame index the
    schedule submits for it."""

    def __init__(self, engine, ids: Sequence[Any],
                 source: Callable[[int, int], tuple],
                 next_frame: Sequence[int], *, loop: str = "open",
                 rate_hz: Optional[float] = None,
                 phases: Optional[Sequence[float]] = None,
                 outstanding: Optional[int] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 span: Callable[[str], Any] = lambda name:
                 contextlib.nullcontext()):
        if loop == "open":
            if not rate_hz or rate_hz <= 0 or phases is None:
                raise ValueError("an open loop needs rate_hz > 0 and "
                                 "phases")
        elif loop == "closed":
            if not outstanding or outstanding < 1:
                raise ValueError("a closed loop needs outstanding >= 1")
        else:
            raise ValueError(f"unknown loop {loop!r}")
        self.engine = engine
        self.ids = list(ids)
        self._index = {rid: r for r, rid in enumerate(self.ids)}
        self.source = source
        self.next_frame = [int(n) for n in next_frame]
        self.loop = loop
        self.rate_hz = rate_hz
        self.phases = None if phases is None else [float(p) for p in phases]
        self.outstanding = outstanding
        self.clock = clock
        self.sleep = sleep
        self.span = span
        self._waiting: List[Deque[Posed]] = [deque() for _ in self.ids]
        self._origin: Optional[float] = None   # the schedule's start
        self._k = [0] * len(self.ids)          # frames scheduled per robot
        self._win: Optional[Window] = None
        self._calls: List[Tuple[float, float, int]] = []

    # ------------------------------------------------------------------
    def _due(self, r: int) -> float:
        return self._origin + self.phases[r] + self._k[r] / self.rate_hz

    def _submit(self, r: int, due: float, win: Window) -> None:
        n = self.next_frame[r]
        with self.span("submit"):
            self.engine.submit_frame(self.ids[r], *self.source(r, n))
        rec = Posed(robot=r, frame=n, due=due)
        self._waiting[r].append(rec)
        win.frames.append(rec)
        self.next_frame[r] = n + 1
        self._k[r] += 1

    def _stamp(self, out: Dict[Any, np.ndarray], now: float,
               win: Window) -> int:
        n = 0
        for rid, poses in out.items():
            q = self._waiting[self._index[rid]]
            for p in np.asarray(poses):
                n += 1
                if not q:
                    win.unexpected += 1
                    continue
                rec = q.popleft()
                rec.posed_at = now
                rec.pose = np.asarray(p, np.float64)
        return n

    def _busy(self) -> bool:
        return bool(self.engine.pending_frames()
                    or self.engine.inflight_chunks())

    def _run_chunk(self, win: Window) -> None:
        start = self.clock()
        with self.span("run_chunk"):
            out = self.engine.run_chunk()
        now = self.clock()
        self._calls.append((start, now, self._stamp(out, now, win)))

    def run(self, seconds: float) -> Window:
        """Drive the schedule for ``seconds`` and return that window.
        Frames still waiting when it closes are posed by the next
        ``run`` or by ``drain``."""
        R = len(self.ids)
        if self._win is None:
            t0 = self._origin = self.clock()
        else:
            t0 = self._win.t_end
        t_end = t0 + seconds
        win = Window(t0=t0, t_end=t_end, frames=[])
        self._win = win
        while True:
            now = self.clock()
            # frames that fell due while the last call blocked are still
            # the window's load, even when it closed meanwhile
            if self.loop == "open":
                for r in range(R):
                    while True:
                        due = self._due(r)
                        if due > now or due >= t_end:
                            break
                        self._submit(r, due, win)
            elif now < t_end:
                for r in range(R):
                    while len(self._waiting[r]) < self.outstanding:
                        self._submit(r, now, win)
            if now >= t_end:
                break
            if self._busy():
                self._run_chunk(win)
                continue
            if self.loop == "closed":
                continue
            nxt = min(self._due(r) for r in range(R))
            wake = min(nxt, t_end)
            with self.span("wait"):
                self.sleep(max(0.0, wake - now))
            if nxt < t_end:
                win.wake_late.append(self.clock() - nxt)
        win.calls = [c for c in self._calls if t0 < c[1] <= t_end]
        win.returned = sum(c[2] for c in win.calls)
        return win

    def drain(self, grace: float = 60.0) -> None:
        """Submitting nothing more, run the engine until every frame
        submitted is posed or ``grace`` seconds past the last window's
        close have gone. Poses that come back here count their whole
        wait."""
        win = self._win
        deadline = win.t_end + grace
        while (any(self._waiting) and self._busy()
               and self.clock() < deadline):
            self._run_chunk(win)
