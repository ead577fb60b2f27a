"""Paged robot-state pool: continuous admission without retraces.

The batch-static ``FleetLocalizer`` compiles ONE chunk program for a
fixed batch B — PR 4's inactive-row machinery already proves a dispatch
mixing active and inactive rows costs nothing. This module turns that
invariant into a serving primitive: a pool of capacity ``C`` whose
per-robot ``LocalizerState`` rows live in the fleet's padded (C, ...)
state buffers, managed like an LLM server's paged KV cache:

  slot table    robot id -> slot index (one state row per robot)
  free list     recycled slots, reused LIFO-by-lowest-index
  generations   per-slot admission counters — a ``SlotTicket`` captures
                the generation at admission, and reads through a ticket
                whose slot has since been recycled raise
                ``StaleGeneration`` instead of silently returning the
                NEXT occupant's state

Admission binds a robot to a free slot and initializes its state row
with ONE jitted donated scatter (a dynamic-index write — the slot id is
traced, so every admission reuses a single compiled program); departure
recycles the slot and bumps its generation. The chunk program never
sees any of it: free slots ride as inactive rows, so ``chunk_traces``
stays 1 across arbitrary churn.

Ragged arrival is the fleet's 2-D active mask: each chunk dispatch
advances robot b by exactly the ``counts[b] <= K`` frames it staged,
as a per-column prefix of the fixed-K chunk.

The explicitly-slow path is ``resize``: when admissions exceed C, a new
fleet is compiled at C' > C and the occupied rows are carried across —
host gather, re-pad to the new capacity, re-place across the robots
mesh (``fleet_mesh.shard_states``). That costs one retrace of the chunk
program, counted separately (``retired_chunk_traces``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, ContextManager, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.environment import MODE_VIO
from repro.core.fleet import FleetLocalizer, no_span
from repro.core.localizer import _ChunkStager, init_localizer_state
from repro.distributed.fleet_mesh import shard_states


class PoolFull(RuntimeError):
    """Admission requested with no free slot (capacity exhausted).
    Callers choose the slow path explicitly: ``resize`` or reject."""


class StagingOverrun(RuntimeError):
    """A host staging set was acquired (or written) while its previous
    chunk was still in flight — the pipelined drain fell more than
    ``staging_depth`` chunks behind the dispatch front."""


class _StagingSet:
    """One ping-pong host staging buffer set: the fixed (K, C, ...)
    arrays a chunk's gathered frames are written straight into (no
    per-robot ``np.stack``, no fresh ``np.zeros`` per chunk). Paired
    1:1 with an input-ring slot: ``device_put`` ALIASES these arrays on
    CPU, so a set is write-protected from dispatch until its chunk is
    drained — writes to an in-flight set raise instead of corrupting
    the executing chunk. Stale data from the set's previous chunk is
    left in place: inactive (frame, slot) lanes are ``lax.cond``-gated
    in the scan (select discards their values bitwise), and active
    lanes are always fully rewritten (GPS gets an explicit NaN when a
    frame carries no fix)."""

    __slots__ = ("il", "ir", "ac", "gy", "gps", "in_flight")

    def __init__(self, K: int, C: int, H: int, W: int, ipf: int):
        self.il = np.zeros((K, C, H, W), np.float32)
        self.ir = np.zeros((K, C, H, W), np.float32)
        self.ac = np.zeros((K, C, ipf, 3), np.float32)
        self.gy = np.zeros((K, C, ipf, 3), np.float32)
        self.gps = np.full((K, C, 3), np.nan, np.float32)
        self.in_flight = False

    def _arrays(self):
        return (self.il, self.ir, self.ac, self.gy, self.gps)

    def protect(self) -> None:
        """Dispatch: freeze the set until its chunk drains."""
        self.in_flight = True
        for a in self._arrays():
            a.setflags(write=False)

    def release(self) -> None:
        """Drain: the chunk's execution is complete (its outputs were
        synced), so the aliased host memory is reusable."""
        self.in_flight = False
        for a in self._arrays():
            a.setflags(write=True)


class InFlightChunk:
    """One dispatched-but-undrained chunk: device-resident outputs plus
    the slot->robot manifest that maps them back to robots at drain
    time, the staging set to release, and the deferred host work.

    ``outs`` are un-synced JAX arrays — nothing blocks until ``drain``
    reads ``outs.p``. ``manifest`` is a tuple of ``(robot_id, slot,
    n_frames)`` captured at dispatch, so poses route to the robot that
    OWNED the slot when the chunk was dispatched even if it departed
    (or the slot was recycled) while the chunk was in flight.
    ``needs_flush`` marks chunks whose scenario contract (Registration
    chunk-flush feedback, the host-Kalman operating point) forced the
    feedback fix at dispatch — pipelined callers drain them
    immediately instead of holding them back.

    ``retired`` pins the chunk's DONATED input state (the pre-chunk
    pool states) until drain: dropping the last reference to a donated
    jax.Array whose consuming execution is still in flight blocks the
    caller in the buffer destructor (~the chunk's full device time on
    the CPU runtime) — the one hidden sync that would serialize the
    whole pipeline. Held here, the destructor runs at drain time, when
    the execution has provably completed and deletion is free.

    ``counts`` is filled by the drain: robot id -> its frames' summed
    ``FrameOutputs.counts`` (real frames of its manifest slot only)."""

    __slots__ = ("outs", "manifest", "staging", "pending_slam",
                 "needs_flush", "retired", "meta", "counts")

    def __init__(self, outs, manifest, staging, pending_slam,
                 needs_flush, retired=None):
        self.outs = outs
        self.manifest = manifest
        self.staging = staging
        self.pending_slam = pending_slam
        self.needs_flush = needs_flush
        self.retired = retired
        self.meta = {}               # caller scratch (engine timestamps)
        self.counts: Dict[Any, np.ndarray] = {}


class StaleGeneration(RuntimeError):
    """A SlotTicket outlived its slot binding: the robot departed and
    the slot was (or may have been) recycled to a new occupant."""


class UnknownRobot(KeyError):
    """Operation on a robot id the slot table does not hold."""


@dataclass(frozen=True)
class SlotTicket:
    """Admission receipt: the binding of a robot to a slot at a
    generation. Every read API validates the generation, so a ticket
    held across the robot's departure can never observe the slot's next
    occupant."""
    robot_id: Any
    slot: int
    generation: int


def _write_row(states, row, slot):
    """One admission: scatter a fresh single-robot state row into the
    pooled (C, ...) buffers at a TRACED slot index (dynamic-update-slice
    — one compiled program serves every slot), donating the pool
    buffers so the write is in place."""
    return jax.tree_util.tree_map(
        lambda b, r: b.at[slot].set(r), states, row)


class RobotStatePool:
    """Fixed-capacity paged pool of per-robot localizer state.

    Wraps a ``FleetLocalizer`` of batch ``capacity`` (pool slots ==
    fleet batch rows; the fleet may pad further for a robots mesh) and
    owns its batched state. ``admit``/``retire``/``assign_scenario``
    are slot-table writes; ``step_chunk`` advances every occupied slot
    by its own staged frame count in one fleet dispatch.
    """

    def __init__(self, cfg, cam, capacity: int, *,
                 window: Optional[int] = None, scheduler=None,
                 mesh=None, devices=None,
                 host_kalman_fallback: bool = True,
                 adaptive: bool = False, staging_depth: int = 2):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if staging_depth < 1:
            raise ValueError("staging_depth must be >= 1")
        self.cfg = cfg
        self.cam = cam
        self.capacity = capacity
        self._fleet_kw = dict(window=window, scheduler=scheduler,
                              mesh=mesh, devices=devices,
                              host_kalman_fallback=host_kalman_fallback,
                              adaptive=adaptive)
        self.fleet = FleetLocalizer(cfg, cam, batch=capacity,
                                    **self._fleet_kw)
        self.states = self.fleet.init_state()
        # --- the page table ---
        self._slot_of: Dict[Any, int] = {}       # robot id -> slot
        self._ticket_of: Dict[Any, SlotTicket] = {}
        self._free: List[int] = list(range(capacity - 1, -1, -1))
        self.generation = np.zeros(capacity, np.int64)
        self._mode = np.full(capacity, MODE_VIO, np.int32)
        # persistent input ring: chunk staging rides the async pipeline
        # machinery (pre-sharded device_put; committed async H2D on
        # accelerator backends) — one ring slot AND one host ping-pong
        # staging set per in-flight chunk the caller may keep
        self.staging_depth = int(staging_depth)
        self._stager = _ChunkStager(slots=max(2, self.staging_depth))
        self._staging: List[_StagingSet] = []
        self._staging_key: Optional[Tuple[int, int]] = None   # (K, ipf)
        self._staging_next = 0
        # host-tracked per-slot absolute frame bases: lets the dispatch
        # front hand the SLAM replay its frame indices without syncing
        # ``states.frame_idx`` (which would block on the previous chunk)
        self._base_idx = np.zeros(capacity, np.int64)
        self._writer = jax.jit(_write_row, donate_argnums=(0,))
        self._ipf: Optional[int] = None          # IMU samples per frame
        # --- churn counters ---
        self.admissions = 0
        self.departures = 0
        self.scenario_swaps = 0
        self.resizes = 0
        # chunk traces retired by resizes (each resize compiles a new
        # fleet program — the explicitly-slow path, counted apart from
        # the steady-state ``chunk_traces == 1`` invariant)
        self.retired_chunk_traces = 0

    # ------------------------------------------------------------------
    # page-table views
    # ------------------------------------------------------------------
    @property
    def robot_ids(self) -> Tuple[Any, ...]:
        """Bound robots in admission order (dict insertion order)."""
        return tuple(self._slot_of)

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> int:
        return self.capacity - len(self._free)

    def slot_of(self, robot_id) -> int:
        try:
            return self._slot_of[robot_id]
        except KeyError:
            raise UnknownRobot(robot_id) from None

    def ticket_of(self, robot_id) -> SlotTicket:
        try:
            return self._ticket_of[robot_id]
        except KeyError:
            raise UnknownRobot(robot_id) from None

    def chunk_trace_count(self) -> int:
        """Traces of the LIVE chunk program — 1 across arbitrary churn;
        only a resize (new program) resets it. ``retired_chunk_traces``
        accumulates the pre-resize programs' counts."""
        return self.fleet.chunk_trace_count()

    def mode_of(self, robot_id) -> int:
        return int(self._mode[self.slot_of(robot_id)])

    # ------------------------------------------------------------------
    # admission / departure / assignment: slot-table writes
    # ------------------------------------------------------------------
    def _resolve_mode(self, scenario) -> int:
        """Scenario name or mode id -> validated registry id."""
        tab = self.fleet.scenarios
        if isinstance(scenario, str):
            if scenario not in tab.names:
                raise ValueError(
                    f"unknown scenario {scenario!r}; this pool's fleet "
                    f"compiled against {list(tab.names)}")
            return tab.id_of(scenario)
        mid = int(scenario)
        tab.validate_ids([mid])
        return mid

    def admit(self, robot_id, scenario=MODE_VIO, p0=None, v0=None,
              q0=None, slot: Optional[int] = None) -> SlotTicket:
        """Bind ``robot_id`` to a free slot and initialize its state row
        host-side — one slot-table write plus one jitted scatter; the
        chunk program is untouched (zero retrace). Raises ``PoolFull``
        when no slot is free (callers pick the slow path: ``resize``).

        ``slot`` pins an explicit free slot (deterministic layouts for
        equivalence tests); default is the lowest free index."""
        if robot_id in self._slot_of:
            raise ValueError(f"robot {robot_id!r} already admitted")
        mid = self._resolve_mode(scenario)
        if not self._free:
            raise PoolFull(
                f"pool at capacity {self.capacity} "
                f"({self.occupancy} occupied) — resize() is the "
                "explicitly-slow overflow path")
        if slot is None:
            s = self._free.pop()
        else:
            if slot not in self._free:
                raise ValueError(f"slot {slot} is not free")
            self._free.remove(slot)
            s = slot
        row = init_localizer_state(self.cfg, self.fleet.window,
                                   p0=p0, v0=v0, q0=q0)
        self.states = self._writer(self.states, row, jnp.int32(s))
        if self.fleet.mesh is not None:
            # keep the pooled state placed across the robots mesh (the
            # scatter's output sharding follows XLA defaults otherwise)
            self.states = shard_states(self.states, self.fleet.mesh)
        # a recycled slot must not inherit the previous occupant's host
        # stage (SLAM keyframes/map are per-robot, keyed by slot)
        self.fleet._robots.pop(s, None)
        self._mode[s] = mid
        self._base_idx[s] = 0        # fresh row -> frame_idx restarts
        self._slot_of[robot_id] = s
        tk = SlotTicket(robot_id, s, int(self.generation[s]))
        self._ticket_of[robot_id] = tk
        self.admissions += 1
        return tk

    def retire(self, robot_id) -> None:
        """Departure: recycle the robot's slot (free-list push + a
        generation bump that invalidates outstanding tickets). The
        row's buffers stay in place as an inactive pad row until the
        slot is re-admitted."""
        s = self.slot_of(robot_id)
        del self._slot_of[robot_id]
        del self._ticket_of[robot_id]
        self.generation[s] += 1
        self._mode[s] = MODE_VIO
        self.fleet._robots.pop(s, None)
        self._free.append(s)
        # lowest free index first keeps slot assignment deterministic
        self._free.sort(reverse=True)
        self.departures += 1

    def assign_scenario(self, robot_id, scenario) -> None:
        """Re-assign a bound robot's scenario: a slot-table write, live
        at the next chunk dispatch via the traced mode id (the PR 5/7
        migration path — zero retraces). The robot's host-stage state
        (its map) is kept: it is the same machine in a new environment."""
        self._mode[self.slot_of(robot_id)] = self._resolve_mode(scenario)
        self.scenario_swaps += 1

    # ------------------------------------------------------------------
    # reads (generation-checked)
    # ------------------------------------------------------------------
    def _check(self, ticket: SlotTicket) -> int:
        cur = self._slot_of.get(ticket.robot_id)
        if (cur != ticket.slot
                or self.generation[ticket.slot] != ticket.generation):
            raise StaleGeneration(
                f"ticket {ticket} is stale: slot {ticket.slot} is at "
                f"generation {int(self.generation[ticket.slot])}")
        return ticket.slot

    def position(self, ticket: SlotTicket) -> np.ndarray:
        """(3,) current position for a live ticket (host copy)."""
        s = self._check(ticket)
        return np.asarray(self.states.filt.p)[s]

    def state_row(self, ticket: SlotTicket):
        """Host copy of the robot's full state row (live tickets only)."""
        s = self._check(ticket)
        return jax.tree_util.tree_map(lambda x: np.asarray(x)[s],
                                      self.states)

    def positions(self) -> Dict[Any, np.ndarray]:
        """robot id -> (3,) position for every bound robot (one host
        transfer for the pooled buffer)."""
        p = np.asarray(self.states.filt.p)
        return {rid: p[s].copy() for rid, s in self._slot_of.items()}

    # ------------------------------------------------------------------
    # the hot path: one fleet dispatch advances every occupied slot.
    # Split into a dispatch FRONT (acquire_staging -> write frames ->
    # dispatch_staged, nothing blocks) and a drain BACK (drain_chunk,
    # the one pose sync) so the serving engine can keep depth-D chunks
    # in flight; step_chunk composes the two as the synchronous
    # reference path.
    # ------------------------------------------------------------------
    def acquire_staging(self, chunk: int, ipf: int) -> _StagingSet:
        """Next ping-pong host staging set (round-robin, aligned with
        the input-ring slots), writable. Raises ``StagingOverrun`` when
        every set is still in flight — the caller must drain a chunk
        before staging another. Reallocates lazily when the chunk shape
        changes (first call, new K/ipf, post-resize)."""
        key = (int(chunk), int(ipf))
        if self._staging_key != key:
            if any(st.in_flight for st in self._staging):
                raise StagingOverrun(
                    "chunk shape changed while chunks are in flight")
            fe = self.cfg.frontend
            self._staging = [
                _StagingSet(key[0], self.capacity, fe.height, fe.width,
                            key[1])
                for _ in range(self.staging_depth)]
            self._staging_key = key
            self._staging_next = 0
        st = self._staging[self._staging_next]
        if st.in_flight:
            raise StagingOverrun(
                f"all {self.staging_depth} staging sets in flight — "
                "drain before staging another chunk")
        self._staging_next = (self._staging_next + 1) % self.staging_depth
        return st

    def staging_in_flight(self) -> int:
        return sum(1 for st in self._staging if st.in_flight)

    def dispatch_staged(self, staging: _StagingSet, counts: np.ndarray,
                        manifest: Tuple[Tuple[Any, int, int], ...],
                        dt_imu: float,
                        span: Callable[[str], ContextManager] = no_span
                        ) -> InFlightChunk:
        """Dispatch one gathered chunk WITHOUT syncing its outputs.

        ``staging`` holds the written frames, ``counts`` the per-slot
        staged frame counts, ``manifest`` the ``(robot_id, slot, n)``
        routing captured by the gatherer. Scenario feedback that cannot
        be deferred is applied here (mirroring ``FleetLocalizer.run``'s
        per-robot flush policy): the Registration chunk-flush fix and
        the host-Kalman fallback sync only the slices they need and
        mark the chunk ``needs_flush``; SLAM replay — append-only
        bookkeeping — is deferred to ``drain_chunk``. The staging set
        is write-protected until the chunk drains. ``span`` names the
        fleet's dispatch steps and ``pool.feedback`` (see
        ``repro.serve.spans``)."""
        K, C = staging.il.shape[:2]
        active = np.arange(K)[:, None] < np.asarray(counts)[None, :]
        base_idx = self._base_idx.copy()
        retired = self.states     # donated below; pinned until drain
        states, outs, work = self.fleet.dispatch_chunk(
            self.states, staging.il, staging.ir, staging.ac, staging.gy,
            staging.gps, self._mode.copy(), dt_imu, active=active,
            stager=self._stager, base_idx=base_idx, span=span)
        self.states = states
        self._base_idx += np.asarray(counts, self._base_idx.dtype)
        staging.protect()
        needs_flush = work.kalman_off or work.has_reg
        if needs_flush:
            with span("pool.feedback"):
                if work.kalman_off:
                    # feedback: the boundary update must reach the next
                    # dispatch
                    self.states = self.fleet._host_kalman_fix(
                        self.states, outs, work.act)
                if work.has_reg:
                    # the chunk-flush contract: Registration pose fixes
                    # sync their robots' slices and land before the
                    # next dispatch
                    self.states = self.fleet._registration_fix(
                        self.states, outs, work.mode_np, work.act)
        pending_slam = ((work.mode_np, work.act, work.base_idx)
                        if work.has_slam else None)
        return InFlightChunk(outs, tuple(manifest), staging,
                             pending_slam, needs_flush, retired=retired)

    def drain_chunk(self, fl: InFlightChunk,
                    span: Callable[[str], ContextManager] = no_span
                    ) -> Dict[Any, np.ndarray]:
        """The one pose sync: block until ``fl``'s chunk has executed,
        run its deferred SLAM replay, release its staging set, and
        route poses back through the manifest. Chunks must drain in
        dispatch order (the engine's FIFO deque guarantees it). The
        sync also fetches the frames' work counters into ``fl.counts``;
        ``span`` names ``pool.sync`` and ``pool.host_stage``."""
        with span("pool.sync"):
            # blocks until the chunk completes
            p, counts = jax.device_get((fl.outs.p, fl.outs.counts))
            fl.retired = None        # donated input state: now free
        with span("pool.host_stage"):
            if fl.pending_slam is not None:
                self.fleet._slam_replay(fl.outs, *fl.pending_slam)
                fl.pending_slam = None
            fl.staging.release()
        fl.counts = {rid: counts[:n, s].sum(axis=0)
                     for rid, s, n in fl.manifest}
        return {rid: p[:n, s].copy() for rid, s, n in fl.manifest}

    def write_frames(self, staging: _StagingSet, slot: int,
                     frames: Tuple) -> int:
        """Write one robot's ``(imgs_l, imgs_r, accel, gyro, gps)``
        stack into its staging column (rows ``[0:n]``); GPS ``None``
        becomes NaN (the scan's no-fix sentinel — stale finite values
        from the set's previous chunk must never read as a fix)."""
        n = int(np.asarray(frames[0]).shape[0])
        if n == 0:
            return 0
        if n > staging.il.shape[0]:
            raise ValueError(
                f"staged {n} frames > chunk {staging.il.shape[0]}")
        staging.il[:n, slot] = frames[0]
        staging.ir[:n, slot] = frames[1]
        staging.ac[:n, slot] = frames[2]
        staging.gy[:n, slot] = frames[3]
        staging.gps[:n, slot] = (np.nan if frames[4] is None
                                 else frames[4])
        return n

    def dispatch_chunk(self, frames: Dict[Any, Tuple], dt_imu: float,
                       chunk: int) -> Optional[InFlightChunk]:
        """Dispatch front over a ``frames`` dict (robot id -> per-robot
        stacks): gather into the next staging set and dispatch. Returns
        None (no dispatch) when nothing is staged."""
        staged = [(rid, self.slot_of(rid), fr)
                  for rid, fr in frames.items()
                  if int(np.asarray(fr[0]).shape[0]) > 0]
        if not staged:
            return None
        if self._ipf is None:
            self._ipf = int(np.asarray(staged[0][2][2]).shape[1])
        staging = self.acquire_staging(chunk, self._ipf)
        counts = np.zeros(self.capacity, np.int64)
        manifest = []
        for rid, s, fr in staged:
            counts[s] = self.write_frames(staging, s, fr)
            manifest.append((rid, s, int(counts[s])))
        return self.dispatch_staged(staging, counts, manifest, dt_imu)

    def step_chunk(self, frames: Dict[Any, Tuple], dt_imu: float,
                   chunk: int) -> Dict[Any, np.ndarray]:
        """Advance staged per-robot frame streams one fixed-K chunk,
        SYNCHRONOUSLY (dispatch + immediate drain — the pipelined
        path's bitwise reference).

        ``frames``: robot id -> ``(imgs_l, imgs_r, imu_accel, imu_gyro,
        gps)`` with leading per-robot frame count ``n_b <= chunk``
        (``gps`` may be None). Ragged arrival is the per-column prefix
        of the fleet's 2-D active mask; free slots and robots with no
        staged frames ride as inactive rows. K is pinned to ``chunk``
        so every serving dispatch — full, ragged or nearly empty —
        reuses the one compiled trace.

        Returns robot id -> (n_b, 3) poses for the frames drained this
        chunk (empty dict, no dispatch, when nothing is staged)."""
        fl = self.dispatch_chunk(frames, dt_imu, chunk)
        return {} if fl is None else self.drain_chunk(fl)

    # ------------------------------------------------------------------
    # the explicitly-slow path: elastic capacity overflow
    # ------------------------------------------------------------------
    def resize(self, new_capacity: int) -> None:
        """Re-compile the pool at ``new_capacity`` slots, carrying every
        occupied row across pools bitwise: host-gather the old padded
        state, re-pad (grow) or truncate the pad rows (shrink) to the
        new fleet's batch, re-place across the robots mesh. Slot
        indices, tickets and generations are preserved. Costs one
        retrace of the chunk program (the old program's traces
        accumulate in ``retired_chunk_traces``).

        Shrinking requires every BOUND slot to sit below the new
        capacity — admission fills lowest-index-first, so after the
        high-water robots depart the top rows are pure pad and the pool
        can drop them without relocating anyone (relocation would
        invalidate tickets). Both directions refuse while chunks are in
        flight: the ring/staging capacity axis dies with the pool."""
        if new_capacity < 1:
            raise ValueError("capacity must be >= 1")
        if new_capacity == self.capacity:
            raise ValueError(
                f"resize must change capacity: {new_capacity} == "
                f"{self.capacity}")
        if self.staging_in_flight():
            raise StagingOverrun(
                "resize with chunks in flight — drain (flush) the "
                "pipeline before resizing the pool")
        if new_capacity < self.capacity:
            high = sorted(s for s in self._slot_of.values()
                          if s >= new_capacity)
            if high:
                raise ValueError(
                    f"cannot shrink to {new_capacity}: bound slots "
                    f"{high} would be dropped (slots never relocate — "
                    "tickets pin them)")
        old_cap = self.capacity
        keep = min(old_cap, new_capacity)
        old_states = jax.device_get(self.states)
        old_robots = self.fleet._robots
        self.retired_chunk_traces += self.fleet.chunk_trace_count()

        self.fleet = FleetLocalizer(self.cfg, self.cam,
                                    batch=new_capacity, **self._fleet_kw)
        self.fleet._robots.update(
            {s: r for s, r in old_robots.items() if s < new_capacity})
        fresh = jax.device_get(self.fleet.init_state())

        def carry(old, new):
            out = np.asarray(new).copy()
            out[:keep] = np.asarray(old)[:keep]
            return out
        carried = jax.tree_util.tree_map(carry, old_states, fresh)
        self.states = shard_states(
            jax.tree_util.tree_map(jnp.asarray, carried), self.fleet.mesh)

        self.capacity = new_capacity
        self.generation = np.concatenate(
            [self.generation[:keep],
             np.zeros(new_capacity - keep, np.int64)])
        self._mode = np.concatenate(
            [self._mode[:keep],
             np.full(new_capacity - keep, MODE_VIO, np.int32)])
        self._free = sorted(
            [s for s in self._free if s < new_capacity]
            + list(range(old_cap, new_capacity)), reverse=True)
        self._base_idx = np.concatenate(
            [self._base_idx[:keep],
             np.zeros(new_capacity - keep, self._base_idx.dtype)])
        # old ring slots and staging sets die with the pool (their
        # capacity axis no longer matches)
        self._stager = _ChunkStager(slots=max(2, self.staging_depth))
        self._staging = []
        self._staging_key = None
        self._staging_next = 0
        self.resizes += 1

    # ------------------------------------------------------------------
    def check_invariants(self) -> None:
        """Slot-table consistency (the churn-fuzz assertion surface):
        bound slots and the free list partition [0, C); tickets match
        their slots' live generations; every bound mode id is
        registered."""
        bound = sorted(self._slot_of.values())
        assert len(bound) == len(set(bound)), "duplicate slot binding"
        assert sorted(bound + list(self._free)) == list(
            range(self.capacity)), "slot table + free list != [0, C)"
        for rid, s in self._slot_of.items():
            tk = self._ticket_of[rid]
            assert tk.slot == s and tk.generation == int(
                self.generation[s]), f"stale live ticket for {rid!r}"
        self.fleet.scenarios.validate_ids(
            self._mode[list(self._slot_of.values())]
            if self._slot_of else [])
