"""Continuous-batching admission engine over the paged state pool.

The serving discipline in one sentence: ALL mutation happens at chunk
boundaries. Robot sessions submit joins, leaves, scenario swaps and
frames at any time; the engine queues them, and ``run_chunk`` — the
single drain point — applies the queued requests as one batched
slot-table update, gathers each bound robot's staged frames (ragged,
up to ``chunk`` each, high-priority robots first), and advances the
whole pool in ONE fleet dispatch.

Since the pipelined drain (PR 9) ``run_chunk`` is split into a
dispatch FRONT and a drain BACK around a bounded in-flight deque::

    gather -> stage -> dispatch chunk N+1   ||   chunk N executes
                 sync chunk N's poses one behind -> host stage

The gather writes robot frames straight into the pool's ping-pong
host staging buffers (zero per-chunk allocation), the dispatch
returns un-synced device arrays plus a slot->robot manifest
(``pool.dispatch_staged``), and poses sync one chunk behind at the
drain point (``pool.drain_chunk``) — ``inflight=`` bounds the depth
(default 2; 1 degenerates to the synchronous reference drain).
Chunks whose scenario contract demands feedback before the next
dispatch (Registration chunk-flush, the host-Kalman fallback) force
an immediate drain, mirroring ``FleetLocalizer.run``'s per-robot
flush policy; ``flush()`` drains the tail.

Per-chunk wall time rides ``launch.watchdog.StepTimeTracker``
(``snapshot()`` reports without resetting), decomposed by the host
spans of ``serve.spans`` (stage, dispatch and its build/h2d/enqueue/
feedback parts, sync, host stage) so ``latency_report`` says where the
time lives; each span is also marked in a profiler trace. Per-pose
latency runs from the frame's arrival (``submit_frame``'s ``t``, by
default the submit) to the drain — stamped when the pose is actually
synced, NOT when its chunk was dispatched — and splits into the queue
wait (arrival-to-dispatch) and the in-flight remainder (dispatch-to-
sync). The chunk program's per-frame work counters (tracks, consumed
tracks, MSCKF updates applied in the program or shipped to the host)
are summed per robot and reported with the flush count.

Overflow policy is explicit: ``overflow="resize"`` grows the pool
(the slow, retrace-counting path — the pipeline is flushed first),
``overflow="reject"`` refuses the join and counts it.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core.environment import MODE_VIO
from repro.core.step import COUNTS
from repro.launch.watchdog import StepTimeTracker
from repro.serve.pool import (InFlightChunk, PoolFull, RobotStatePool,
                              SlotTicket, UnknownRobot)
from repro.serve.spans import Spans


class ServingEngine:
    """Chunk-boundary request drain over a ``RobotStatePool``.

    Parameters
    ----------
    pool: the paged state pool to serve.
    chunk: fixed frames-per-dispatch K (every drain reuses the one
        compiled K-frame trace; ragged arrival fills a prefix).
    dt_imu: IMU sample period handed to the fleet dispatch.
    overflow: ``"resize"`` (double capacity, carry state — slow path)
        or ``"reject"`` (count and drop the join).
    tracker: optional ``StepTimeTracker`` for per-chunk drain wall
        time (a fresh one is created by default).
    clock: the engine's clock (seconds): frame arrivals, latencies
        and the host spans are read on it.
    inflight: max chunks dispatched but not yet drained (the pipeline
        depth). 2 (default) gathers/stages/dispatches chunk N+1 while
        chunk N executes and syncs poses one chunk behind; 1 is the
        synchronous reference. Bounded by ``pool.staging_depth`` —
        each in-flight chunk owns one host staging set.
    gather_budget: optional cap on total frames gathered per chunk
        (bounds the host staging time of one boundary). When more
        frames are queued than the budget drains, high-``priority``
        robots are served first; the rest wait, FIFO per robot.
    shrink_after: shrink-on-idle trigger — after this many CONSECUTIVE
        chunk boundaries with occupancy at or below ``shrink_low_water
        * capacity``, halve the pool (never below the highest bound
        slot + 1 or ``shrink_min_capacity``; bound slots never
        relocate). The inverse of the overflow resize and just as
        explicit: the pipeline is flushed first and the retrace is
        counted. Default None = never shrink.
    shrink_low_water: occupancy fraction that counts as idle (default
        0.25 — a pool more than 4x over-provisioned for ``shrink_after``
        chunks gives the memory back).
    shrink_min_capacity: floor the shrink never crosses (default 1).
    """

    def __init__(self, pool: RobotStatePool, chunk: int = 8,
                 dt_imu: float = 0.005, overflow: str = "resize",
                 tracker: Optional[StepTimeTracker] = None,
                 clock=time.perf_counter, inflight: int = 2,
                 gather_budget: Optional[int] = None,
                 shrink_after: Optional[int] = None,
                 shrink_low_water: float = 0.25,
                 shrink_min_capacity: int = 1):
        if overflow not in ("resize", "reject"):
            raise ValueError(f"unknown overflow policy {overflow!r}")
        if not 1 <= inflight <= pool.staging_depth:
            raise ValueError(
                f"inflight={inflight} outside [1, {pool.staging_depth}] "
                "(each in-flight chunk owns one of the pool's "
                "staging_depth host staging sets)")
        if gather_budget is not None and gather_budget < 1:
            raise ValueError("gather_budget must be >= 1 (or None)")
        if shrink_after is not None and shrink_after < 1:
            raise ValueError("shrink_after must be >= 1 (or None)")
        if not 0.0 < shrink_low_water < 1.0:
            raise ValueError("shrink_low_water must be in (0, 1)")
        if shrink_min_capacity < 1:
            raise ValueError("shrink_min_capacity must be >= 1")
        self.pool = pool
        self.chunk = int(chunk)
        self.dt_imu = float(dt_imu)
        self.overflow = overflow
        self.inflight = int(inflight)
        self.gather_budget = gather_budget
        self.tracker = tracker if tracker is not None else StepTimeTracker()
        self._clock = clock
        # FIFO control queue: ("join"|"leave"|"assign", robot_id, arg)
        self._requests: Deque[Tuple[str, Any, Any]] = deque()
        # robot id -> deque of (arrival time, frame tuple) single frames
        self._streams: Dict[Any, Deque[Tuple[float, Tuple]]] = {}
        self._priority: Dict[Any, int] = {}
        # dispatched-but-undrained chunks, oldest first (drain is FIFO)
        self._inflight: Deque[InFlightChunk] = deque()
        self.peak_inflight = 0
        self.tickets: Dict[Any, SlotTicket] = {}
        # arrival-to-pose latency (stamped at the DRAIN point) and its
        # queue-wait component (arrival-to-dispatch), per robot
        self.latencies: Dict[Any, List[float]] = {}
        self.queue_waits: Dict[Any, List[float]] = {}
        # where one chunk boundary's wall time lives: the host spans
        # (``serve.spans``: stage, dispatch = build + h2d + enqueue +
        # feedback, sync, host_stage), one sample per chunk each; and,
        # one sample per frame, its queue wait and its in-flight time
        # (dispatch-to-sync)
        self._spans = Spans(clock)
        self.decomp = dict(self._spans.trackers,
                           queue_wait=StepTimeTracker(),
                           in_flight=StepTimeTracker())
        self.dispatches = 0          # chunk sequence numbers handed out
        # the chunk program's work counters (``step.COUNTS``) summed over
        # every real frame drained, per robot and in total, and the
        # chunks that forced a feedback flush
        self.counts = np.zeros(len(COUNTS), np.int64)
        self.robot_counts: Dict[Any, np.ndarray] = {}
        self.flushes = 0
        self.chunks = 0
        self.frames_served = 0
        self.rejected = 0
        # shrink-on-idle: consecutive low-occupancy boundaries seen,
        # and downward resizes taken
        self.shrink_after = shrink_after
        self.shrink_low_water = float(shrink_low_water)
        self.shrink_min_capacity = int(shrink_min_capacity)
        self._low_chunks = 0
        self.shrinks = 0

    # ------------------------------------------------------------------
    # submission surface: NEVER touches the pool
    # ------------------------------------------------------------------
    def submit_join(self, robot_id, scenario=MODE_VIO, p0=None, v0=None,
                    q0=None, priority: int = 0) -> None:
        self._requests.append(
            ("join", robot_id, (scenario, p0, v0, q0, priority)))

    def submit_leave(self, robot_id) -> None:
        self._requests.append(("leave", robot_id, None))

    def submit_assign(self, robot_id, scenario) -> None:
        self._requests.append(("assign", robot_id, scenario))

    def submit_frame(self, robot_id, img_l, img_r, imu_accel, imu_gyro,
                     gps=None, t: Optional[float] = None) -> None:
        """Queue one frame for ``robot_id`` (joined, or join queued).
        Frames submitted before the join drains are held and served in
        the robot's first chunk after admission. ``t`` is the frame's
        arrival on the engine's clock (a sensor's capture time, say;
        default now): its queue wait and latency count from there."""
        self._streams.setdefault(robot_id, deque()).append(
            (self._clock() if t is None else float(t),
             (img_l, img_r, imu_accel, imu_gyro, gps)))

    def pending_requests(self) -> int:
        return len(self._requests)

    def pending_frames(self, robot_id=None) -> int:
        if robot_id is not None:
            return len(self._streams.get(robot_id, ()))
        return sum(len(q) for q in self._streams.values())

    def inflight_chunks(self) -> int:
        return len(self._inflight)

    # ------------------------------------------------------------------
    # the drain point
    # ------------------------------------------------------------------
    def _admit(self, rid, scenario, p0, v0, q0, priority, poses) -> None:
        try:
            tk = self.pool.admit(rid, scenario, p0=p0, v0=v0, q0=q0)
        except PoolFull:
            if self.overflow == "reject":
                self.rejected += 1
                self._streams.pop(rid, None)
                return
            # the pool cannot grow under in-flight chunks (their
            # staging sets and outputs belong to the old program):
            # drain the pipeline, then take the explicitly-slow path
            while self._inflight:
                self._drain_oldest(poses)
            self.pool.resize(max(2 * self.pool.capacity,
                                 self.pool.capacity + 1))
            tk = self.pool.admit(rid, scenario, p0=p0, v0=v0, q0=q0)
        self.tickets[rid] = tk
        self._priority[rid] = int(priority)
        self.latencies.setdefault(rid, [])

    def _mutates_inflight_slot(self) -> bool:
        """True when a queued leave or scenario swap targets a slot
        some in-flight chunk still references (manifest slots cover
        every active slot, so a deferred SLAM replay's slots are a
        subset). A leave pops the map the replay owes; a swap to
        Registration would read it at the next dispatch, pre-replay."""
        touched = {s for fl in self._inflight for _, s, _ in fl.manifest}
        for kind, rid, _ in self._requests:
            if kind == "join":
                continue
            try:
                if self.pool.slot_of(rid) in touched:
                    return True
            except UnknownRobot:
                pass    # surfaces properly in _drain_requests
        return False

    def _drain_requests(self, poses) -> None:
        """Apply every queued control request in FIFO order — one
        batched slot-table update between dispatches."""
        while self._requests:
            kind, rid, arg = self._requests.popleft()
            if kind == "join":
                self._admit(rid, *arg, poses)
            elif kind == "leave":
                self.pool.retire(rid)
                self.tickets.pop(rid, None)
                self._streams.pop(rid, None)
                self._priority.pop(rid, None)
            else:
                self.pool.assign_scenario(rid, arg)

    def _gather_order(self) -> List[Any]:
        """Bound robots with staged frames, high priority first (stable:
        admission order breaks ties) — the order the gather serves them
        when ``gather_budget`` can't drain everything."""
        order = [rid for rid in self.pool.robot_ids
                 if self._streams.get(rid)]
        order.sort(key=lambda rid: -self._priority.get(rid, 0))
        return order

    def _dispatch_front(self) -> Optional[InFlightChunk]:
        """Gather staged frames straight into the pool's next ping-pong
        staging set (no per-robot ``np.stack``, no fresh chunk buffers)
        and dispatch — nothing here blocks on device execution. Returns
        None when no bound robot has frames."""
        order = self._gather_order()
        if not order:
            return None
        seq = self.dispatches
        self.dispatches += 1
        span = functools.partial(self._spans, chunk=seq)
        with span("engine.stage"):
            staging, counts, manifest, stamps = self._gather(order)
        with span("engine.dispatch"):
            fl = self.pool.dispatch_staged(staging, counts, manifest,
                                           self.dt_imu, span=span)
        now = self._clock()
        fl.meta.update(seq=seq, stamps=stamps, dispatched=now)
        for rid, ts in stamps.items():
            waits = [now - t for t in ts]
            self.queue_waits.setdefault(rid, []).extend(waits)
            for w in waits:
                self.decomp["queue_wait"].add(w)
        if fl.needs_flush:
            self.flushes += 1
        return fl

    def _gather(self, order: List[Any]):
        """Write the queued frames of ``order``'s robots into the next
        staging set: (staging set, per-slot frame counts, manifest,
        robot id -> arrival times of its gathered frames)."""
        ipf = int(np.asarray(
            self._streams[order[0]][0][1][2]).shape[0])
        staging = self.pool.acquire_staging(self.chunk, ipf)
        counts = np.zeros(self.pool.capacity, np.int64)
        manifest: List[Tuple[Any, int, int]] = []
        stamps: Dict[Any, List[float]] = {}
        remaining = self.gather_budget
        for rid in order:
            q = self._streams[rid]
            take = min(self.chunk, len(q))
            if remaining is not None:
                take = min(take, remaining)
                if take == 0:
                    break        # budget spent; lower priorities wait
            s = self.pool.slot_of(rid)
            ts = []
            for j in range(take):
                t, (il, ir, ac, gy, gp) = q.popleft()
                staging.il[j, s] = il
                staging.ir[j, s] = ir
                staging.ac[j, s] = ac
                staging.gy[j, s] = gy
                staging.gps[j, s] = np.nan if gp is None else gp
                ts.append(t)
            counts[s] = take
            manifest.append((rid, s, take))
            stamps[rid] = ts
            if remaining is not None:
                remaining -= take
        return staging, counts, manifest, stamps

    def _drain_oldest(self, poses: Dict[Any, List[np.ndarray]]) -> None:
        """Drain the oldest in-flight chunk: the one blocking pose sync
        (plus its deferred host stage), latency stamped at THIS point —
        the time the pose actually became available to the caller."""
        fl = self._inflight.popleft()
        out = self.pool.drain_chunk(
            fl, span=functools.partial(self._spans, chunk=fl.meta["seq"]))
        now = self._clock()
        stamps = fl.meta["stamps"]
        in_flight = now - fl.meta["dispatched"]
        for rid, p in out.items():
            poses.setdefault(rid, []).append(p)
            ts = stamps.get(rid, ())
            self.latencies.setdefault(rid, []).extend(
                now - t for t in ts)
            for _ in ts:
                self.decomp["in_flight"].add(in_flight)
            self.frames_served += len(ts)
        for rid, c in fl.counts.items():
            self.counts += c
            self.robot_counts[rid] = self.robot_counts.get(rid, 0) + c

    def _maybe_shrink(self, poses: Dict[Any, List[np.ndarray]]) -> None:
        """Shrink-on-idle: after ``shrink_after`` consecutive boundaries
        at or below the low-water occupancy, halve the pool — bounded
        below by the highest bound slot (slots never relocate; admission
        fills lowest-first, so long-idle pools compact naturally) and
        ``shrink_min_capacity``. Flushes the pipeline first, exactly
        like the overflow grow: resize refuses under in-flight chunks."""
        if self.shrink_after is None:
            return
        cap = self.pool.capacity
        if (cap <= self.shrink_min_capacity
                or self.pool.occupancy
                > self.shrink_low_water * cap):
            self._low_chunks = 0
            return
        self._low_chunks += 1
        if self._low_chunks < self.shrink_after:
            return
        bound = self.pool._slot_of.values()
        floor = max(self.shrink_min_capacity,
                    max(bound) + 1 if bound else 1)
        target = max(floor, cap // 2)
        if target >= cap:
            return      # a high bound slot pins the capacity for now
        while self._inflight:
            self._drain_oldest(poses)
        self.pool.resize(target)
        self.shrinks += 1
        self._low_chunks = 0

    @staticmethod
    def _merge(poses: Dict[Any, List[np.ndarray]]
               ) -> Dict[Any, np.ndarray]:
        return {rid: ps[0] if len(ps) == 1 else np.concatenate(ps)
                for rid, ps in poses.items()}

    def run_chunk(self) -> Dict[Any, np.ndarray]:
        """One serving iteration: drain control requests, gather staged
        frames into the next ping-pong buffer, dispatch, and sync poses
        one chunk behind (``inflight`` deep). Returns robot id ->
        (n_b, 3) poses DRAINED this call — at depth 2 these are the
        previous call's dispatch, while this call's chunk executes
        under the gather/stage of the next one."""
        t0 = self._clock()
        poses: Dict[Any, List[np.ndarray]] = {}
        had_requests = bool(self._requests)
        if self._inflight and self._mutates_inflight_slot():
            # a leave or swap whose slot is still referenced by an
            # in-flight chunk races the per-slot host map a deferred
            # SLAM replay owes (retire pops it; a swap to Registration
            # reads it at the next dispatch) — flush so the host-stage
            # order matches the synchronous reference bitwise. Joins
            # and already-served leaves/swaps keep the pipeline:
            # admits land in slots a flushing retire emptied, and
            # retire itself is pure slot-table bookkeeping.
            while self._inflight:
                self._drain_oldest(poses)
        self._drain_requests(poses)
        self._maybe_shrink(poses)
        # keep room for this boundary's dispatch (the knob may be
        # lowered mid-run; steady state never enters this loop)
        while len(self._inflight) >= self.inflight:
            self._drain_oldest(poses)
        fl = self._dispatch_front()
        if fl is not None:
            self._inflight.append(fl)
            self.peak_inflight = max(self.peak_inflight,
                                     len(self._inflight))
            # feedback chunks (Registration flush, host-Kalman fix)
            # already paid their sync — return their poses now; other
            # chunks drain one behind the dispatch front
            target = 0 if fl.needs_flush else self.inflight - 1
            while len(self._inflight) > target:
                self._drain_oldest(poses)
        elif self._inflight:
            # nothing to dispatch: the tail still makes progress
            self._drain_oldest(poses)
        if had_requests or fl is not None or poses:
            # idle boundaries (nothing queued, staged, or in flight)
            # would record near-zero walls and poison the rsd
            self.tracker.add(self._clock() - t0)
            self.chunks += 1
        return self._merge(poses)

    def flush(self) -> Dict[Any, np.ndarray]:
        """Drain every in-flight chunk (the pipeline tail) and return
        the poses, concatenated per robot."""
        poses: Dict[Any, List[np.ndarray]] = {}
        while self._inflight:
            self._drain_oldest(poses)
        return self._merge(poses)

    def _drainable_frames(self) -> bool:
        """True when some queued frame can still reach a dispatch: its
        robot is bound, or a join for it is queued. Frames for unknown
        robots never drain and must not spin ``run_until_drained``."""
        if not self._streams:
            return False
        bound = set(self.pool.robot_ids)
        bound.update(rid for kind, rid, _ in self._requests
                     if kind == "join")
        return any(q for rid, q in self._streams.items() if rid in bound)

    def run_until_drained(self, max_chunks: int = 10_000
                          ) -> Dict[Any, np.ndarray]:
        """Drive ``run_chunk`` until no requests, drainable frames or
        IN-FLIGHT chunks remain (the pipelined tail is flushed, never
        dropped), concatenating per-robot poses across chunks."""
        out: Dict[Any, List[np.ndarray]] = {}
        for _ in range(max_chunks):
            if not (self._requests or self._inflight
                    or self._drainable_frames()):
                break
            for rid, p in self.run_chunk().items():
                out.setdefault(rid, []).append(p)
        for rid, p in self.flush().items():
            out.setdefault(rid, []).append(p)
        return {rid: np.concatenate(ps) for rid, ps in out.items()}

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------
    @staticmethod
    def _pcts(a) -> Dict[str, float]:
        a = np.asarray(a, np.float64)
        return {"p50_s": float(np.percentile(a, 50)) if a.size else 0.0,
                "p99_s": float(np.percentile(a, 99)) if a.size else 0.0}

    def counters(self) -> Dict[str, Any]:
        """The chunk program's work counters over every real frame
        drained (``step.COUNTS``: live track slots summed over frames,
        tracks consumed, MSCKF updates applied in the program, updates
        shipped to the host), in total and per robot, with the frames
        counted and the chunks that forced a feedback flush."""
        def named(c, frames):
            return {"frames": int(frames),
                    **{k: int(v) for k, v in zip(COUNTS, c)}}
        per_robot = {str(rid): named(c, len(self.latencies.get(rid, ())))
                     for rid, c in self.robot_counts.items()}
        return {**named(self.counts, self.frames_served),
                "flushes": self.flushes, "per_robot": per_robot}

    def latency_report(self) -> Dict[str, Any]:
        """Gateway-facing summary: per-chunk drain stats (via the
        tracker's non-resetting ``snapshot``), the chunk-boundary
        decomposition by host span and per-frame wait, per-robot p50/p99
        arrival-to-pose latency split into queue wait (arrival-to-
        dispatch) and pipeline residence (dispatch-to-sync), the work
        counters, and the churn/retrace counters."""
        per_robot = {}
        for rid, lat in self.latencies.items():
            st = {"frames": int(len(lat)), **self._pcts(lat)}
            qw = self.queue_waits.get(rid, [])
            st["queue_wait"] = self._pcts(qw)
            # the non-queue remainder: device execution + pipeline
            # residence of each frame (total minus its queue wait)
            n = min(len(lat), len(qw))
            st["in_pipeline"] = self._pcts(
                np.asarray(lat[:n]) - np.asarray(qw[:n]))
            per_robot[str(rid)] = st
        return {
            "chunks": self.chunks,
            "frames_served": self.frames_served,
            "rejected_joins": self.rejected,
            "inflight": self.inflight,
            "peak_inflight": self.peak_inflight,
            "chunk_wall": self.tracker.snapshot(),
            "decomposition": {name: tr.snapshot()
                              for name, tr in self.decomp.items()},
            "per_robot": per_robot,
            "counters": self.counters(),
            "pool": {
                "capacity": self.pool.capacity,
                "occupancy": self.pool.occupancy,
                "admissions": self.pool.admissions,
                "departures": self.pool.departures,
                "scenario_swaps": self.pool.scenario_swaps,
                "resizes": self.pool.resizes,
                "shrinks": self.shrinks,
                "chunk_traces": self.pool.chunk_trace_count(),
                "retired_chunk_traces": self.pool.retired_chunk_traces,
            },
        }
