"""Sharded fleet batching: B robots across a ``robots`` device mesh.

The ROADMAP's scaling axis — serving heavy traffic from many machines —
falls out of the fused per-frame step: because ``localize_step`` is a
pure function of fixed-shape arrays, ``jax.vmap`` turns it into a batched
program that advances B robots per device dispatch. Each robot keeps its
own filter, track ring buffer and operating mode; mode dispatch happens
INSIDE the batch (``lax.switch`` on a per-robot int32 mode id), so one
compiled program serves a fleet whose members are simultaneously in VIO,
SLAM and Registration environments. SLAM robots get their windowed
BA/marginalization inside the dispatch too (``core.backend.ba``); the
per-robot host stage that remains is append-only map bookkeeping for
SLAM and the dynamically-sized Registration fix.

Since PR 4 the fleet axis is *placed* explicitly instead of living on
device 0: pass a ``robots`` mesh (``repro.distributed.fleet_mesh``) and
the batched step/chunk programs are wrapped in ``jax.shard_map`` over
the B axis — each device scans its local fleet slice (K x B/D
robot-frames per dispatch), the scheduler's OffloadPlan enters as
replicated scalars (one plan is valid on every shard: its inputs are
per-robot static shapes), and the async input ring ``device_put``s each
staged chunk pre-sharded so host->device copies overlap per device.
When B does not divide the device count the fleet is padded with
inactive robots (``active=False``, the partial-chunk trick); a 1-device
mesh is bitwise-equal to the unsharded path. ``mesh=None`` (default)
keeps the single-device execution exactly as before.

State buffers are donated, so fleet covariances and track SRAM-analogue
buffers update in place across frames. ``run`` drives whole sequences
through the chunked scan with the same async double-buffered input ring
as the single-robot ``Localizer.run`` — chunk N+1 is staged while
chunk N executes, and the host stage drains one chunk behind the
dispatch front with a PER-ROBOT flush policy: only Registration robots'
chunk-end slices sync before the next dispatch (their pose fix is
feedback); SLAM robots' append-only replay always defers one chunk.
"""
from __future__ import annotations

import contextlib
from typing import Callable, ContextManager, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.eudoxus import EudoxusConfig
from repro.core import scenarios as scen
from repro.core import scheduler as sched, tracks
from repro.core.backend import tracking
from repro.core.environment import MODE_VIO, select_mode_id
from repro.core.localizer import (Localizer, LocalizerState, TracedStep,
                                  _ChunkStager, host_kalman_update,
                                  init_localizer_state, resolve_kernel_plan,
                                  resolve_marg_kernel)
from repro.core.step import (FrameInputs, FrameOutputs, TracedChunk,
                             flags_from_plan)
# NB: import names directly — the package re-exports the ``fleet_mesh``
# factory under the module's own name, shadowing the submodule attribute
from repro.distributed.fleet_mesh import (chunk_sharding, fleet_mesh,
                                          mesh_shards, padded_batch,
                                          robot_sharding, shard_fleet_chunk,
                                          shard_fleet_step, shard_states)


def no_span(name: str) -> ContextManager:
    """The default ``span`` of a dispatch: names and times nothing
    (the serving engine passes its own, see ``repro.serve.spans``)."""
    return contextlib.nullcontext()


class ChunkHostWork(NamedTuple):
    """Host-side follow-up owed by one chunk dispatch — everything the
    drain back of a pipelined caller needs to finish the chunk later
    (or to decide it cannot be deferred at all).

    ``kalman_off`` and ``has_reg`` are FEEDBACK: their host fixes must
    reach the batched state before the next dispatch, so a pipelined
    caller applies them at the dispatch front (a bubble, only at those
    operating points). ``has_slam`` is append-only bookkeeping with no
    state dependency — the one piece that can ride a chunk behind."""
    mode_np: np.ndarray      # validated (B,) mode ids
    act: np.ndarray          # (K, B_padded) activity mask
    base_idx: np.ndarray     # per-robot absolute frame base (pre-chunk)
    kalman_off: bool         # in-scan MSCKF update gated off -> host fix
    has_slam: bool           # SLAM robots advanced -> deferred replay
    has_reg: bool            # chunk-flush robots advanced -> immediate fix


class FleetLocalizer:
    """Batched localizer: B robots, one fused dispatch per frame/chunk,
    optionally sharded over a ``robots`` device mesh.

    VIO robots are fully served by the batched dispatch. SLAM /
    Registration robots additionally get a per-robot host map stage after
    the dispatch (maps are dynamically sized and persist across frames),
    backed by a lazily-created ``Localizer`` per robot — see ``maps`` /
    ``robot_host(b)``.

    ``mesh`` (or ``devices``, a device list shorthand) turns on sharded
    execution: the B axis is split across the mesh with ``shard_map``;
    ``batch`` is padded up to a multiple of the shard count with
    inactive robots that are dispatched but never read back. INPUTS
    always use the real batch size B (padding happens here); the state
    pytree and raw FrameOutputs returned by step/step_chunk/run carry
    the padded batch (rows ``batch:`` are inert pad robots — slice with
    ``[:fleet.batch]``, or use ``positions()``/``maps`` which strip
    them).
    """

    def __init__(self, cfg: EudoxusConfig, cam, batch: int,
                 window: Optional[int] = None,
                 scheduler: Optional[sched.LatencyModels] = None,
                 mesh=None, devices=None,
                 host_kalman_fallback: bool = True,
                 adaptive: bool = False):
        if mesh is not None and devices is not None:
            raise ValueError("pass mesh or devices, not both")
        self.cfg = cfg
        self.cam = cam
        self.batch = batch
        # adaptive: per-scenario offload plans (each at its spec's dma_bw
        # budget) lowered into per-mode gate tables — a mixed fleet runs
        # drone-tuned and car-tuned gates in the SAME compiled program,
        # and a mid-run mode_ids change re-resolves gates by table
        # lookup, never by retracing. Default off (static fleet plan).
        self.adaptive = adaptive
        self._gate_structure = None  # pinned gate-key set (retrace guard)
        self.mesh = fleet_mesh(devices) if devices is not None else mesh
        self.n_shards = mesh_shards(self.mesh)
        # pad the fleet so B divides the shard count; pad robots are
        # inactive (chunk path) or compute-and-discard (per-frame path)
        self.padded = padded_batch(batch, self.mesh)
        self._pad = self.padded - batch
        self.window = window or cfg.backend.msckf_window
        self.scheduler = scheduler or sched.LatencyModels()
        # frozen scenario-registry snapshot this fleet compiles against
        # (mode ids are indices into it; scenarios registered AFTER
        # construction need a new FleetLocalizer)
        self.scenarios = scen.table()
        self.host_kalman_fallback = host_kalman_fallback
        self.host_kalman_fixes = 0   # chunk-boundary host updates applied
        # (K, n_real) -> frozen (K, B_padded) prefix mask: steady-state
        # chunk dispatches reuse one immutable mask instead of
        # re-allocating it per dispatch (see _active_mask)
        self._mask_cache = {}
        self.dispatch_count = 0
        self.ba_runs = 0             # in-scan BA passes across the fleet
        self.deferred_drains = 0     # SLAM replays drained a chunk late
        self.last_stager: Optional[_ChunkStager] = None
        # one BoW vocabulary device array shared by the batched program
        # and every robot's host stage
        self.vocab = jnp.asarray(
            tracking.make_vocab(cfg.backend.bow_vocab_size))
        self._offload_plan = resolve_marg_kernel(
            self.scheduler.plan_frame(
                self.window, tracks.MAX_UPDATES,
                map_points=cfg.backend.max_map_points,
                ba_landmarks=cfg.backend.ba_landmarks), cfg)
        # host-stage state (SLAM keyframes/map, Registration map) is
        # created lazily per robot on first non-VIO frame — an all-VIO
        # fleet allocates nothing
        self._robots = {}
        # batch over state + per-frame inputs; the offload flags and IMU
        # dt are fleet-wide scalars
        self._traced = TracedStep(cfg, cam, self.vocab,
                                  scenarios=self.scenarios)
        vstep = jax.vmap(self._traced,
                         in_axes=(0, 0, 0, 0, 0, 0, 0, None, None))
        # chunk x fleet: lax.scan over K frames of the vmapped transition
        # — one dispatch advances B robots K frames (steady state: one
        # trace per chunk size); staged chunk inputs are donated back
        self._traced_chunk = TracedChunk(cfg, cam, self.vocab, fleet=True,
                                         scenarios=self.scenarios)
        if self.mesh is None:
            self._fused_fleet = jax.jit(vstep, donate_argnums=(0,))
            self._fused_fleet_chunk = jax.jit(self._traced_chunk,
                                              donate_argnums=(0, 1))
            self._state_sharding = None
            self._frame_in_sharding = None
            self._chunk_in_sharding = None
        else:
            # shard_map over the robots axis: each device runs the SAME
            # per-shard program on its local B/D slice — no cross-robot
            # collectives exist, so a 1-device mesh is bitwise-equal to
            # the unsharded path above
            self._fused_fleet = jax.jit(
                shard_fleet_step(vstep, self.mesh), donate_argnums=(0,))
            self._fused_fleet_chunk = jax.jit(
                shard_fleet_chunk(self._traced_chunk, self.mesh),
                donate_argnums=(0, 1))
            self._state_sharding = robot_sharding(self.mesh)
            self._frame_in_sharding = robot_sharding(self.mesh)
            self._chunk_in_sharding = chunk_sharding(self.mesh)

    # ------------------------------------------------------------------
    def init_state(self, p0=None, v0=None, q0=None) -> LocalizerState:
        """Stacked (B_padded, ...) state placed across the robots mesh.
        p0/v0/q0: optional (B,3)/(B,3)/(B,4) per-robot initial conditions
        for the REAL batch; pad robots start from defaults."""
        def one(b):
            real = b < self.batch
            return init_localizer_state(
                self.cfg, self.window,
                p0=None if (p0 is None or not real) else p0[b],
                v0=None if (v0 is None or not real) else v0[b],
                q0=None if (q0 is None or not real) else q0[b])

        states = [one(b) for b in range(self.padded)]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)
        return shard_states(stacked, self.mesh)

    def fused_trace_count(self) -> int:
        return self._traced.traces

    def robot_host(self, b: int) -> Localizer:
        """Host-stage handler for robot b (maps, keyframes), created on
        first use."""
        if b not in self._robots:
            self._robots[b] = Localizer(self.cfg, self.cam,
                                        window=self.window,
                                        scheduler=self.scheduler,
                                        vocab=self.vocab)
        return self._robots[b]

    @property
    def maps(self):
        """Per-robot maps; None for robots whose host stage never ran."""
        return [self._robots[b].map if b in self._robots else None
                for b in range(self.batch)]

    # ------------------------------------------------------------------
    # batch-axis padding helpers (inactive robots make B divide shards)
    # ------------------------------------------------------------------
    def _pad0(self, a, dtype, fill=0.0) -> np.ndarray:
        """Pad a per-frame (B, ...) array to (B_padded, ...)."""
        a = np.asarray(a, dtype)
        if self._pad == 0:
            return a
        return np.concatenate(
            [a, np.full((self._pad,) + a.shape[1:], fill, dtype)])

    def _pad1(self, a, dtype, fill=0.0) -> np.ndarray:
        """Pad a chunk (K, B, ...) array to (K, B_padded, ...)."""
        a = np.asarray(a, dtype)
        if self._pad == 0:
            return a
        pad_shape = (a.shape[0], self._pad) + a.shape[2:]
        return np.concatenate([a, np.full(pad_shape, fill, dtype)], axis=1)

    def _padded_modes(self, mode_np: np.ndarray) -> np.ndarray:
        """(B_padded,) mode ids — pad robots ride as VIO (no host
        stage, no SLAM block)."""
        return np.concatenate(
            [np.asarray(mode_np, np.int32),
             np.full(self._pad, MODE_VIO, np.int32)])

    def _put(self, tree, sharding):
        """Ship a host pytree to the device(s): pre-sharded across the
        robots mesh when one is configured, default placement when
        ``sharding`` is None (the single placement point for all
        non-ring dispatch inputs; the async ring's equivalent lives in
        ``_ChunkStager.stage``)."""
        return jax.device_put(tree, sharding)

    # ------------------------------------------------------------------
    def step(self, states: LocalizerState, imgs_l, imgs_r, imu_accel,
             imu_gyro, gps, mode_ids, dt_imu: float
             ) -> Tuple[LocalizerState, FrameOutputs]:
        """Advance every robot one frame in a single batched dispatch
        (sharded over the robots mesh when one is configured).

        imgs_l/imgs_r: (B,H,W); imu_accel/gyro: (B,K,3); gps: (B,3) with
        NaN rows where unavailable; mode_ids: (B,) int32 (see
        ``environment.select_mode_id``). B is the REAL batch; padding to
        the mesh width happens here (pad robots see NaN GPS and zero
        frames, and are never read back).
        """
        mode_np = self.scenarios.validate_ids(mode_ids)
        args = (self._pad0(imgs_l, np.float32),
                self._pad0(imgs_r, np.float32),
                self._pad0(imu_accel, np.float32),
                self._pad0(imu_gyro, np.float32),
                self._pad0(gps, np.float32, fill=np.nan),
                self._padded_modes(mode_np))
        if self._frame_in_sharding is not None:
            args = self._put(args, self._frame_in_sharding)
        states, outs = self._fused_fleet(
            states, *args,
            flags_from_plan(self._offload_plan, modes=mode_np,
                            table=self.scenarios),
            jnp.float32(dt_imu))
        self.dispatch_count += 1
        states = self._host_map_stage(states, outs, mode_np)
        return states, outs

    def _host_map_stage(self, states: LocalizerState, outs: FrameOutputs,
                        mode_ids: np.ndarray) -> LocalizerState:
        """Per-robot host map work after the batched dispatch, driven by
        each robot's scenario spec (``host_stage``): no-op for a fleet
        of host-stage-free scenarios (VIO and its variants; pad robots
        are VIO by construction and never enter)."""
        tab = self.scenarios
        slam = tab.mask(mode_ids, tab.host_stage_ids("slam"))
        hist_np = np.asarray(outs.hist) if slam.any() else None
        if slam.any():
            self.ba_runs += int(np.asarray(outs.ba_ran)
                                [:len(mode_ids)][slam].sum())
        for b in np.nonzero(tab.mask(mode_ids, tab.host_stage_ids()))[0]:
            st_b = jax.tree_util.tree_map(lambda x: x[b], states)
            fr_b = jax.tree_util.tree_map(lambda x: x[b], outs.fr)
            if tab.specs[int(mode_ids[b])].host_stage == "slam":
                self.robot_host(b)._slam_step(st_b, fr_b,
                                              hist=hist_np[b])
            else:                       # "registration"
                new_b = self.robot_host(b)._registration_step(st_b, fr_b)
                if new_b is not st_b:   # registration fused a pose fix
                    states = states._replace(filt=jax.tree_util.tree_map(
                        lambda batch, one: batch.at[b].set(one),
                        states.filt, new_b.filt))
        return states

    # ------------------------------------------------------------------
    # chunked fleet pipeline: K frames x B robots in one dispatch
    # ------------------------------------------------------------------
    def step_chunk(self, states: LocalizerState, imgs_l, imgs_r, imu_accel,
                   imu_gyro, gps, mode_ids, dt_imu: float,
                   active=None, stager: Optional[_ChunkStager] = None
                   ) -> Tuple[LocalizerState, FrameOutputs]:
        """Advance every robot K frames in ONE batched scan dispatch
        (``core.step.fleet_chunk``, shard_mapped over the robots mesh
        when one is configured): chunk x fleet amortization of launch
        overhead on both axes, split across devices.

        imgs_l/imgs_r: (K,B,H,W); imu_accel/gyro: (K,B,ipf,3); gps:
        (K,B,3) with NaN rows where unavailable; mode_ids: (B,) per-robot
        modes held for the chunk; active: optional (K,) bool padding mask
        for trailing partial chunks, or a (K,B) per-robot prefix matrix
        (the serving pool's ragged-arrival path — robot b advances only
        its own ``active[:, b].sum()`` frames). Either way K stays
        static -> one trace.

        VIO and SLAM robots are exact (SLAM BA/marginalization run inside
        the scan; map growth is replayed in frame order after the chunk).
        Registration robots' host-stage pose fix is applied once at the
        END of the chunk — chunk-granularity feedback; use K=1 (``step``)
        when per-frame registration feedback matters.

        This is the SYNCHRONOUS reference: dispatch + host drain in one
        call. Pipelined callers split it — ``dispatch_chunk`` is the
        front, ``finish_chunk`` (or the per-half methods) the back.
        """
        states, outs, work = self.dispatch_chunk(
            states, imgs_l, imgs_r, imu_accel, imu_gyro, gps, mode_ids,
            dt_imu, active=active, stager=stager)
        states = self.finish_chunk(states, outs, work)
        return states, outs

    def dispatch_chunk(self, states: LocalizerState, imgs_l, imgs_r,
                       imu_accel, imu_gyro, gps, mode_ids, dt_imu: float,
                       active=None, stager: Optional[_ChunkStager] = None,
                       base_idx: Optional[np.ndarray] = None,
                       span: Callable[[str], ContextManager] = no_span
                       ) -> Tuple[LocalizerState, FrameOutputs,
                                  ChunkHostWork]:
        """The dispatch FRONT of ``step_chunk``: stage + dispatch one
        chunk and return un-synced device-resident outputs plus the
        ``ChunkHostWork`` owed on the host. Nothing here blocks on the
        dispatched chunk, with one caveat: ``base_idx=None`` reads
        ``states.frame_idx`` to the host, which waits for the PREVIOUS
        chunk. Pipelined callers (the serving pool) pass their own
        host-tracked frame bases so the dispatch front never syncs.

        ``span(name)`` wraps the host's three steps: ``fleet.build``
        (the chunk's host arrays and plan), ``fleet.h2d`` (their
        placement on the device) and ``fleet.enqueue`` (the jitted
        call)."""
        K = np.asarray(imgs_l).shape[0]
        mode_np = self.scenarios.validate_ids(mode_ids)
        act, n_real = self._active_mask(K, active)
        if base_idx is None:
            base_idx = np.asarray(states.frame_idx)  # pre-chunk, per robot

        with span("fleet.build"):
            inputs_np = self._build_chunk(imgs_l, imgs_r, imu_accel,
                                          imu_gyro, gps, mode_np, act)
            plan = self._chunk_plan(n_real)
            flags = self._fleet_flags(plan, mode_np)
        # external callers (the serving pool) may own a persistent
        # _ChunkStager: staging then rides the input ring (pre-sharded
        # device_put, committed async H2D on accelerators) instead of
        # the default one-shot placement
        with span("fleet.h2d"):
            if stager is None:
                inputs = self._put(inputs_np, self._chunk_in_sharding)
                staged = None
            else:
                staged = stager.stage(inputs_np, self._chunk_in_sharding)
                inputs = staged.inputs
        with span("fleet.enqueue"):
            states, outs = self._fused_fleet_chunk(
                states, inputs, flags, jnp.float32(dt_imu))
        if staged is not None:
            staged.consumed = True       # buffers donated to the dispatch
        self.dispatch_count += 1

        tab = self.scenarios
        col_active = act[:, :len(mode_np)].any(axis=0)
        work = ChunkHostWork(
            mode_np=mode_np, act=act, base_idx=base_idx,
            kalman_off=bool(self.host_kalman_fallback
                            and self._kalman_off(plan, mode_np)),
            has_slam=bool((tab.mask(mode_np, tab.host_stage_ids("slam"))
                           & col_active).any()),
            has_reg=bool((tab.mask(mode_np, tab.chunk_flush_ids())
                          & col_active).any()))
        return states, outs, work

    def finish_chunk(self, states: LocalizerState, outs: FrameOutputs,
                     work: ChunkHostWork) -> LocalizerState:
        """The drain BACK of ``step_chunk``: apply the chunk's owed host
        work synchronously, in the reference order (host-Kalman fix,
        SLAM replay, registration fix). Pipelined callers instead apply
        the feedback halves at dispatch and defer ``_slam_replay``."""
        if work.kalman_off:
            states = self._host_kalman_fix(states, outs, work.act)
        if work.has_slam or work.has_reg:
            states = self._host_chunk_stage(states, outs, work.mode_np,
                                            work.act, work.base_idx)
        return states

    def _chunk_plan(self, n_real: int) -> sched.OffloadPlan:
        """Per-chunk offload plan at the chunk's REAL frame count (the
        launch-overhead amortization a trailing partial chunk actually
        gets) — the single resolution point for step_chunk and both
        run() modes, so their flags can never diverge. On a mesh it is
        resolved ONCE for all shards (``plan_fleet_chunk``): every model
        input is a per-robot static shape and the amortization uses the
        per-shard local batch, so the plan is identical on every shard
        and enters the sharded dispatch as replicated scalars. With
        ``mesh=None`` the amortization stays the pre-mesh ``plan_chunk``
        behavior (over K only) so the unsharded path's decisions are
        untouched by this refactor.

        With ``adaptive=True`` this returns a dict of ONE plan per
        registered scenario instead — shared sizes (one program, shared
        shapes), per-spec ``dma_bw`` in the break-even — which
        ``_fleet_flags`` lowers into per-mode gate tables."""
        kw = dict(batch=self.padded if self.mesh is not None else 1,
                  shards=self.n_shards,
                  map_points=self.cfg.backend.max_map_points,
                  ba_landmarks=self.cfg.backend.ba_landmarks)
        if self.adaptive:
            plans = self.scheduler.plan_scenarios(
                self.scenarios.specs, self.window, tracks.MAX_UPDATES,
                max(n_real, 1), **kw)
            return {spec.name: resolve_kernel_plan(
                        plans[spec.name], self.cfg, self.window,
                        transfer_bw=spec.dma_bw)
                    for spec in self.scenarios.specs}
        return resolve_marg_kernel(self.scheduler.plan_fleet_chunk(
            self.window, tracks.MAX_UPDATES, max(n_real, 1), **kw),
            self.cfg)

    def _fleet_flags(self, plan, mode_np):
        """Lower a chunk plan into dispatch flags: scalar gates for the
        static fleet plan; per-mode gate tables for the adaptive
        per-scenario dict, with the gate-key STRUCTURE pinned on first
        build so later re-plans (new scheduler fits, migrated modes)
        only ever change table values — never the traced pytree."""
        if isinstance(plan, dict):
            flags = flags_from_plan(plan, modes=mode_np,
                                    table=self.scenarios,
                                    gate_structure=self._gate_structure)
            if self._gate_structure is None:
                self._gate_structure = tuple(flags.gates)
            return flags
        return flags_from_plan(plan, modes=mode_np, table=self.scenarios)

    def _kalman_off(self, plan, mode_np) -> bool:
        """True when the chunk's in-scan MSCKF update is gated off for
        any robot present — the host-fallback trigger (per-robot
        applicability is resolved from the scan's ``upd_skipped``)."""
        if isinstance(plan, dict):
            return any(not plan[self.scenarios.names[m]].kalman_gain
                       for m in {int(m) for m in mode_np})
        return not plan.kalman_gain

    def _active_mask(self, K: int, active) -> Tuple[np.ndarray, int]:
        """(K, B_padded) activity mask from an optional (K,) prefix mask
        or a (K, B) PER-ROBOT prefix matrix; pad-robot columns are
        always inactive.

        The 2-D form is the serving pool's ragged-arrival path: each
        column b is robot b's own contiguous prefix (robots may have
        staged fewer than K frames this chunk, and free pool slots stage
        none), so one fixed-K dispatch serves arbitrary per-robot frame
        counts without retracing. ``n_real`` is then the LONGEST prefix
        — the launch-amortization the chunk actually gets.

        Prefix masks are cached keyed on ``(K, n_real)``: steady-state
        serving dispatches (full chunks, and the recurring partial
        shapes) do no host-side mask allocation. Cached masks are shared
        with staged FrameInputs and must never be mutated (the staging
        buffers are written once — see ``_ChunkStager``)."""
        if active is not None and np.asarray(active).ndim == 2:
            a = np.asarray(active, bool)
            if a.shape != (K, self.batch):
                raise ValueError("per-robot active mask must be "
                                 f"(K={K}, B={self.batch}), got {a.shape}")
            counts = a.sum(axis=0)
            # every column must be a contiguous prefix (same host-stage
            # frame-indexing argument as the 1-D form, per robot)
            prefix = np.arange(K)[:, None] < counts[None, :]
            if not (a == prefix).all():
                raise ValueError("per-robot active mask columns must be "
                                 "contiguous prefixes")
            act = np.zeros((K, self.padded), bool)
            act[:, :self.batch] = a
            return act, int(counts.max(initial=0))
        if active is None:
            n_real = K
        else:
            act1d = np.asarray(active, bool)
            n_real = int(act1d.sum())
            # the host stage maps scan slot j to filter frame base+j,
            # which is only correct when the real frames form a prefix
            # (trailing padding) — reject gap masks instead of silently
            # skewing SLAM keyframe indices / dropping registration fixes
            if not act1d[:n_real].all():
                raise ValueError("active mask must be a contiguous prefix "
                                 f"(got {act1d.tolist()})")
        key = (K, n_real)
        act = self._mask_cache.get(key)
        if act is None:
            act = np.broadcast_to((np.arange(K) < n_real)[:, None],
                                  (K, self.padded)).copy()
            act[:, self.batch:] = False
            act.setflags(write=False)    # shared across dispatches
            self._mask_cache[key] = act
        return act, n_real

    def _build_chunk(self, imgs_l, imgs_r, imu_accel, imu_gyro, gps,
                     mode_np: np.ndarray, act: np.ndarray) -> FrameInputs:
        """Pre-stack one (K, B_padded) chunk as fresh host arrays
        (written once, never mutated after device_put — see
        ``_ChunkStager``)."""
        K = act.shape[0]
        return FrameInputs(
            img_l=self._pad1(imgs_l, np.float32),
            img_r=self._pad1(imgs_r, np.float32),
            accel=self._pad1(imu_accel, np.float32),
            gyro=self._pad1(imu_gyro, np.float32),
            gps=self._pad1(gps, np.float32, fill=np.nan),
            mode=np.ascontiguousarray(np.broadcast_to(
                self._padded_modes(mode_np), (K, self.padded))),
            active=act)

    def run(self, states: LocalizerState, imgs_l, imgs_r, imu_accel,
            imu_gyro, gps, mode_ids, dt_imu: float, chunk: int = 8,
            overlap: bool = True) -> LocalizerState:
        """Drive a T-frame fleet sequence in K-frame chunks through the
        async double-buffered pipeline: stage chunk N+1 (pre-stack +
        per-shard device_put) while chunk N executes, drain host map
        stages one chunk behind the dispatch front. imgs_l/imgs_r:
        (T,B,H,W); imu_accel/gyro: (T,B,ipf,3); gps: (T,B,3);
        mode_ids: (B,).

        PER-ROBOT flush policy: Registration robots' chunk-end pose
        fixes are applied before the next dispatch (feedback — but only
        THEIR output slices sync, a per-robot ragged drain at each
        robot's last active frame); SLAM robots' append-only replay
        always defers one chunk, so a mixed fleet keeps the pipeline
        full instead of draining fleet-wide whenever any robot is in
        Registration. ``overlap=False`` degenerates to sequential
        ``step_chunk`` calls.
        """
        T = np.asarray(imgs_l).shape[0]
        chunk = max(int(chunk), 1)
        mode_np = self.scenarios.validate_ids(mode_ids)
        segments = [list(range(s, min(s + chunk, T)))
                    for s in range(0, T, chunk)]
        if not segments:                 # T == 0: nothing to localize
            return states
        tab = self.scenarios
        slam_active = bool(tab.mask(mode_np,
                                    tab.host_stage_ids("slam")).any())
        has_flush = bool(tab.mask(mode_np, tab.chunk_flush_ids()).any())
        dt = jnp.float32(dt_imu)
        base_idx = np.asarray(states.frame_idx)

        def build(seg):
            """One padded segment's host-side FrameInputs + activity
            mask (the single staging builder for both run() modes)."""
            sl = slice(seg[0], seg[-1] + 1)
            n = len(seg)
            act, _ = self._active_mask(
                chunk, None if n == chunk else np.arange(chunk) < n)

            def take(a, fill=0.0):
                a = np.asarray(a, np.float32)[sl]
                if n < chunk:
                    a = np.concatenate(
                        [a, np.zeros((chunk - n,) + a.shape[1:], a.dtype)])
                return self._pad1(a, np.float32, fill=fill)

            return FrameInputs(
                img_l=take(imgs_l), img_r=take(imgs_r),
                accel=take(imu_accel), gyro=take(imu_gyro),
                gps=take(gps, fill=np.nan),
                mode=np.ascontiguousarray(np.broadcast_to(
                    self._padded_modes(mode_np), (chunk, self.padded))),
                active=act), act

        def seg_plan(seg):
            # resolved at the chunk's REAL frame count — identical to
            # step_chunk's resolution, so run()/step_chunk/overlap modes
            # can never disagree on a partial chunk's decisions
            return self._chunk_plan(len(seg))

        if not overlap:
            for seg in segments:
                inputs_np, act = build(seg)
                inputs = self._put(inputs_np, self._chunk_in_sharding)
                plan = seg_plan(seg)
                states, outs = self._fused_fleet_chunk(
                    states, inputs, self._fleet_flags(plan, mode_np), dt)
                self.dispatch_count += 1
                if self.host_kalman_fallback and self._kalman_off(plan,
                                                                  mode_np):
                    states = self._host_kalman_fix(states, outs, act)
                if tab.mask(mode_np, tab.host_stage_ids()).any():
                    states = self._host_chunk_stage(
                        states, outs, mode_np, act,
                        base_idx + np.int32(seg[0]))
            return states

        stager = _ChunkStager()
        self.last_stager = stager
        inputs_np, act0 = build(segments[0])
        staged = stager.stage(inputs_np, self._chunk_in_sharding)
        pending = None               # one deferred SLAM replay
        for si, seg in enumerate(segments):
            act = act0
            plan = seg_plan(seg)
            states, outs = self._fused_fleet_chunk(
                states, staged.inputs, self._fleet_flags(plan, mode_np), dt)
            staged.consumed = True
            self.dispatch_count += 1
            if si + 1 < len(segments):
                inputs_np, act0 = build(segments[si + 1])
                staged = stager.stage(inputs_np, self._chunk_in_sharding)
            if self.host_kalman_fallback and self._kalman_off(plan, mode_np):
                # feedback: the boundary update must reach the next
                # dispatch (a bubble, only at the host-Kalman operating
                # point)
                states = self._host_kalman_fix(states, outs, act)
            if pending is not None:
                self._slam_replay(*pending)
                pending = None
            if has_flush:
                # per-robot ragged flush: sync ONLY the chunk-flush
                # (Registration) robots' last-active-frame slices before
                # the next dispatch; everything else stays pipelined
                states = self._registration_fix(states, outs, mode_np, act)
            if slam_active:
                pending = (outs, mode_np, act, base_idx + np.int32(seg[0]))
                self.deferred_drains += 1
        if pending is not None:
            self._slam_replay(*pending)
        return states

    # ------------------------------------------------------------------
    # host stages (per-robot, after a chunk dispatch)
    # ------------------------------------------------------------------
    def _host_chunk_stage(self, states, outs, mode_np, act, base_idx):
        """Synchronous drain of one completed chunk: ordered SLAM replay
        then Registration chunk-end fixes (the overlap pipeline calls the
        two halves separately — SLAM deferred, Registration immediate)."""
        self._slam_replay(outs, mode_np, act, base_idx)
        return self._registration_fix(states, outs, mode_np, act)

    def _slam_replay(self, outs, mode_np, act, base_idx) -> None:
        """Ordered per-frame host replay for SLAM-host-stage robots:
        append-only bookkeeping from scan outputs — no device work, no
        ``states`` dependency, so the overlap pipeline can run it a
        chunk late."""
        slam = self.scenarios.mask(mode_np,
                                   self.scenarios.host_stage_ids("slam"))
        if not slam.any():
            return
        K = act.shape[0]
        B = len(mode_np)
        p_np = np.asarray(outs.p)        # (K, B_padded, 3)
        q_np = np.asarray(outs.q)
        # one device->host transfer for the chunk's frontend outputs
        # (per-robot per-leaf slicing would sync K x B x leaves times)
        fr_np = jax.device_get(outs.fr)
        hist_np = np.asarray(outs.hist)
        self.ba_runs += int((np.asarray(outs.ba_ran)
                             & act)[:, :B][:, slam].sum())
        for j in range(K):
            for b in np.nonzero(slam)[0]:
                if not act[j, b]:
                    continue
                fr_b = jax.tree_util.tree_map(lambda x: x[j][b], fr_np)
                self.robot_host(b)._slam_frame(
                    q_np[j, b], p_np[j, b], int(base_idx[b]) + j, fr_b,
                    hist=hist_np[j, b])

    def _registration_fix(self, states, outs, mode_np, act):
        """Chunk-end registration pose fixes, per robot: each
        Registration robot syncs only ITS last active frame's frontend
        slice (ragged across robots), runs place recognition + PnP on
        the host, and fuses the fix back into the batched filter state.
        ``states`` must be the live post-chunk state."""
        reg = np.nonzero(self.scenarios.mask(
            mode_np, self.scenarios.chunk_flush_ids()))[0]
        if reg.size == 0:
            return states
        assert states is not None, "registration drain deferred"
        last = np.maximum(act.sum(axis=0) - 1, 0)    # last active frame
        for b in reg:
            j = int(last[b])
            if not act[j, b]:
                continue
            st_b = jax.tree_util.tree_map(lambda x: x[b], states)
            fr_b = jax.tree_util.tree_map(
                lambda x: np.asarray(x[j, b]), outs.fr)
            new_b = self.robot_host(b)._registration_step(st_b, fr_b)
            if new_b is not st_b:       # registration fused a pose fix
                states = states._replace(filt=jax.tree_util.tree_map(
                    lambda batch, one: batch.at[b].set(one),
                    states.filt, new_b.filt))
        return states

    def _host_kalman_fix(self, states, outs, act):
        """Chunk-boundary host Kalman fallback, per robot: when the scan
        skipped the in-program MSCKF update (``offload_kalman=False``),
        apply the registry's host-path update for each robot whose LAST
        active frame consumed tracks (only that frame's clone window
        matches the boundary state — see ``Localizer._host_kalman_fix``).
        """
        skipped = np.asarray(outs.upd_skipped)       # (K, B_padded)
        last = np.maximum(act.sum(axis=0) - 1, 0)
        fixed_b, fixed_filt = [], []
        for b in range(self.batch):
            j = int(last[b])
            if not act[j, b] or not skipped[j, b]:
                continue
            filt_b = jax.tree_util.tree_map(lambda x: x[b], states.filt)
            fixed_b.append(b)
            fixed_filt.append(host_kalman_update(
                filt_b, np.asarray(outs.upd_uv[j, b]),
                np.asarray(outs.upd_valid[j, b]), self.cam))
            self.host_kalman_fixes += 1
        if fixed_b:
            # one batched scatter for all fixed robots (a per-robot
            # .at[b].set would copy every (B, d, d) covariance leaf B
            # times over)
            idx = jnp.asarray(fixed_b)
            upd = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                         *fixed_filt)
            states = states._replace(filt=jax.tree_util.tree_map(
                lambda batch, u: batch.at[idx].set(u), states.filt, upd))
        return states

    def chunk_trace_count(self) -> int:
        return self._traced_chunk.traces

    def step_envs(self, states, imgs_l, imgs_r, imu_accel, imu_gyro, gps,
                  gps_available, map_available, dt_imu: float,
                  gps_degraded=False, airborne=False):
        """Convenience wrapper taking the (extended) Fig. 2 environment
        booleans ((B,) arrays) instead of pre-resolved mode ids."""
        mode_ids = select_mode_id(gps_available, map_available,
                                  gps_degraded=gps_degraded,
                                  airborne=airborne)
        gps = np.asarray(gps, np.float32).copy()
        gps[~np.asarray(gps_available, bool)] = np.nan
        return self.step(states, imgs_l, imgs_r, imu_accel, imu_gyro, gps,
                         mode_ids, dt_imu)

    # ------------------------------------------------------------------
    def positions(self, states: LocalizerState) -> np.ndarray:
        """(B,3) current position estimates for the REAL batch (host
        copy; pad robots stripped)."""
        return np.asarray(states.filt.p)[:self.batch]
