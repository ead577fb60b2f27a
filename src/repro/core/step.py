"""Scenario compiler + pure state threading for the localization hot path.

This module is the functional half of the localizer split: everything
here is a pure function of fixed-shape arrays — no host state, no maps,
no timing. ``core.localizer.Localizer`` owns orchestration (host map
stages, scheduling, stats) and drives these functions.

Since the scenario-primitive registry this module is a COMPILER: the
per-frame transition is no longer a hand-written monolith with
hard-coded backends — ``localize_step`` lowers a frozen
``core.scenarios.ScenarioTable`` (every registered ``ScenarioSpec``,
each an ordered composition of ``core.primitives``) into one scan body:

  * the shared spine (frontend, track ring, IMU propagate/augment,
    MSCKF consume/update) runs unconditionally, in declared order;
  * each scenario's switch primitives become one branch of the in-scan
    ``lax.switch`` on the mode id (out-of-range ids take a trailing
    pass-through branch instead of clamping onto a wrong backend);
  * gated primitives (BoW histogram, windowed BA + Schur
    marginalization) compile behind a SCALAR activity cond — built from
    the per-scenario activity flags, so an all-VIO dispatch skips them
    at runtime even under vmap — with an inner per-frame/per-robot cond
    on a baked uses-table, and per-scenario knobs (BA cadence) resolved
    through baked lookup tables indexed by the mode id.

One compiled chunk program therefore serves EVERY registered scenario,
and a vmapped fleet mixes scenarios per robot, exactly as the paper's
runtime-reconfigurable accelerator serves its modes from one fabric.

Three granularities, all one compiled program each:

  ``localize_step``      one frame -> one dispatch (PR 1's fused step;
                         the K=1 special case)
  ``localize_chunk``     K frames -> one dispatch: ``lax.scan`` of the
                         frame transition over a chunk, amortizing the
                         Python->device round trip (the paper's frame
                         pipelining, Sec. VI-B)
  ``fleet_chunk``        K frames x B robots -> one dispatch (scan of
                         the vmapped transition)

The scheduler's offload decisions are resolved host-side per chunk
(``scheduler.OffloadPlan``, keyed by primitive name) and enter as the
traced per-primitive gates / per-scenario activity scalars of
``PlanFlags``. Chunks are padded to a fixed K with ``active=False``
frames (the transition passes state through unchanged), so every chunk —
including the trailing partial one — reuses the same trace.
"""
from __future__ import annotations

import dataclasses
import functools
from collections.abc import Mapping
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.eudoxus import EudoxusConfig
from repro.core import primitives as prim
from repro.core import scenarios as scen
from repro.core import scheduler as sched
from repro.core import tracks
from repro.core.backend import ba as ba_mod
from repro.core.backend import msckf
from repro.core.frontend import orb, pipeline
from repro.core.frontend.pipeline import FrontendResult


class LocalizerState(NamedTuple):
    """Device-resident per-robot state — a pure pytree threaded through
    the donated fused step / chunk scan (covariance and track buffers
    update in place). Composes the frontend, track and windowed-BA scan
    carries."""
    filt: msckf.MsckfState
    tracks_uv: jax.Array     # (N, W, 2) uv observations across the window
    tracks_valid: jax.Array  # (N, W) bool
    prev_img: jax.Array      # (H, W) previous left image (LK source)
    prev_yx: jax.Array       # (N, 2) int32 previous frame's features
    prev_valid: jax.Array    # (N,) bool
    frame_idx: jax.Array     # () int32
    ba: ba_mod.BAState       # SLAM keyframe window + marginalization prior


class KernelConfigs:
    """The plan's autotuned per-kernel launch configs as a STATIC
    trace-time constant.

    Registered as a leafless pytree whose aux_data is the object itself:
    the configs never become traced values, they select which Pallas
    launch geometry gets traced — so a different tuned profile is a
    different treedef and jit recompiles at the next dispatch (config
    changes recompile at load time, never mid-run), while an identical
    profile hashes equal and reuses the compiled program. The empty
    instance (untuned) leaves every kernel on its built-in literal
    blocks, bitwise."""

    __slots__ = ("_items",)

    def __init__(self, configs: Mapping = None):
        items = []
        if configs:
            for k in sorted(configs):
                v = configs[k]
                if not v:
                    continue
                items.append((str(k), tuple(sorted(dict(v).items()))))
        object.__setattr__(self, "_items", tuple(items))

    def get(self, key: str) -> Dict:
        """Launch kwargs for kernel ``key`` ({} when untuned)."""
        for k, v in self._items:
            if k == key:
                return dict(v)
        return {}

    def as_dict(self) -> Dict[str, Dict]:
        return {k: dict(v) for k, v in self._items}

    def __bool__(self) -> bool:
        return bool(self._items)

    def __eq__(self, other) -> bool:
        return (isinstance(other, KernelConfigs)
                and self._items == other._items)

    def __hash__(self) -> int:
        return hash(self._items)

    def __repr__(self) -> str:
        return f"KernelConfigs({dict(self._items)!r})"


jax.tree_util.register_pytree_node(
    KernelConfigs, lambda c: ((), c), lambda aux, children: aux)

EMPTY_CONFIGS = KernelConfigs()


class PlanFlags(NamedTuple):
    """The scheduler's pre-resolved decisions as they enter the fused
    dispatch, generalized to the primitive registry:

    ``gates``   primitive offload key -> traced gate: a () bool (one
                fleet-wide decision, the default path) or an
                (n_scen+1,) bool PER-MODE GATE TABLE lowered from
                per-scenario OffloadPlans (``scheduler.plan_scenarios``)
                — ``localize_step`` indexes tables by the traced mode id
                down to per-frame scalars, so a mixed fleet runs
                drone-tuned and car-tuned gates in ONE compiled program
                and a scenario migration (new mode id at a chunk
                boundary) re-resolves gates with zero retraces. Keys
                come from the bound ``ScenarioTable.gate_keys``, which
                also carry the megakernel selectors (``frontend_fused``/
                ``cov_update``/``marg_schur``): those pick the fused
                Pallas spine inside a primitive via ``lax.cond`` rather
                than gating the work itself. When the plan decides one
                of them off host-side the key is absent here and the
                primitive traces only the reference path
                (bitwise-identical program).
    ``active``  scenario name -> () bool — any frame of this dispatch
                runs the scenario. Always SCALARS (never batched), so
                the conds they gate survive vmap as real branches: an
                all-VIO fleet/chunk skips the whole gated heavy block at
                runtime instead of executing both sides of a batched
                select.

    ``configs``  the plan's autotuned per-kernel launch configs as a
                STATIC ``KernelConfigs`` (never traced: block sizes are
                launch geometry, not data). ``EMPTY_CONFIGS`` — the
                untuned default — keeps every kernel on its built-in
                literals bitwise.

    The legacy field views (``kalman``/``marg``/``marg_pallas``/
    ``slam``) read the corresponding entries."""
    gates: Dict[str, jax.Array]
    active: Dict[str, jax.Array]
    configs: KernelConfigs = EMPTY_CONFIGS

    @property
    def kalman(self):
        return self.gates["msckf_update"]

    @property
    def marg(self):
        return self.gates["ba_marginalize"]

    @property
    def marg_pallas(self):
        return self.gates["marg_schur"]

    @property
    def slam(self):
        return self.active["slam"]


# Gate keys whose lax.cond is elided entirely (not traced) when the
# host-side plan decision is False — see flags_from_plan.
_STATIC_DROP_GATES = frozenset({"frontend_fused", "cov_update"})


def flags_from_plan(plan, slam_active=None, modes=None,
                    table: scen.ScenarioTable = None,
                    gate_structure=None) -> PlanFlags:
    """OffloadPlan (or per-scenario plan mapping) -> the traced
    in-dispatch flag bundle.

    ``plan`` is either ONE ``scheduler.OffloadPlan`` (the default
    fleet-wide path: scalar gates, bitwise-identical to the
    pre-adaptive program) or a ``{scenario name: OffloadPlan}`` mapping
    (``scheduler.plan_scenarios``): then every kept gate key lowers to
    an (n_scen+1,) bool GATE TABLE — row i is scenario i's decision,
    the pad row is the key's default for invalid ids — indexed by the
    traced mode id inside the scan (exactly like the ``ba_every`` knob
    lookup). Tables are emitted even when momentarily uniform, so a
    later re-plan (online refit, scenario migration) changes VALUES,
    never the pytree structure: zero retraces.

    ``modes``: the mode ids present in the dispatch (drives the
    per-scenario activity scalars; scenarios not present skip their
    gated blocks at runtime). ``slam_active`` is the legacy single-flag
    form (only the SLAM block was gated pre-registry); with neither,
    every scenario is conservatively active. ``table`` defaults to the
    current global registry snapshot — pass the localizer's bound table
    so the flag pytree structure matches its compiled program.

    Megakernel selector keys (``frontend_fused``/``cov_update``) are
    DROPPED from the gate dict when the plan decides them off
    host-side: both sides of their ``lax.cond`` are numerically
    equivalent, but merely tracing the fused branch perturbs XLA fusion
    under vmap enough to break bitwise parity with the pre-megakernel
    program — omitting the key keeps the reference spine statically
    untouched. A plan that turns one on (or carries a traced value)
    keeps the key, so forced-Pallas runs trace the fused branch. With
    per-scenario plans the drop rule is the UNION over scenarios: the
    key is traced in if ANY scenario's plan enables it (the disabled
    scenarios' rows stay False). ``gate_structure`` (an iterable of
    gate keys) overrides the drop rule entirely — pass a previous
    bundle's ``flags.gates.keys()`` to pin the compiled program's flag
    structure across online re-plans."""
    table = table if table is not None else scen.table()
    multi = (isinstance(plan, Mapping)
             and not isinstance(plan, sched.OffloadPlan)
             and bool(plan)
             and all(isinstance(v, Mapping) for v in plan.values()))
    gates = {}
    for k in table.gate_keys:
        if multi:
            default = sched.PLAN_KEY_DEFAULTS.get(k, True)
            vals = [bool(plan[nm].get(k, default)) if nm in plan
                    else default for nm in table.names]
            if gate_structure is not None:
                if k not in gate_structure:
                    continue
            elif k in _STATIC_DROP_GATES and not any(vals):
                continue
            gates[k] = jnp.asarray(vals + [default], bool)
        else:
            v = plan.get(k, True)
            if gate_structure is not None:
                if k not in gate_structure:
                    continue
            elif (k in _STATIC_DROP_GATES and not isinstance(v, jax.Array)
                    and not bool(v)):
                continue
            gates[k] = jnp.asarray(v)
    if modes is not None:
        act = table.activity(modes)
    else:
        act = {nm: True for nm in table.names}
        if slam_active is not None and "slam" in act:
            act["slam"] = bool(slam_active)
    active = {nm: jnp.asarray(bool(v)) for nm, v in act.items()}
    if multi:
        # one compiled program has ONE launch geometry per kernel:
        # merge per-scenario configs first-wins over the table order
        # (plans resolved from the same installed profile agree anyway)
        merged = {}
        for nm in table.names:
            if nm in plan:
                for k, v in (getattr(plan[nm], "configs", None)
                             or {}).items():
                    merged.setdefault(k, v)
        configs = KernelConfigs(merged)
    else:
        configs = KernelConfigs(getattr(plan, "configs", None))
    return PlanFlags(gates=gates, active=active, configs=configs)


class FrameInputs(NamedTuple):
    """One frame's inputs. For a K-frame chunk every leaf gains a
    leading (K,) axis and becomes the ``xs`` of the scan; ``active``
    marks padding frames (state passes through untouched) so partial
    chunks reuse the fixed-K trace."""
    img_l: jax.Array   # (H, W) float32
    img_r: jax.Array   # (H, W) float32
    accel: jax.Array   # (ipf, 3) float32 IMU accel ending at this frame
    gyro: jax.Array    # (ipf, 3) float32
    gps: jax.Array     # (3,) float32, NaN when unavailable
    mode: jax.Array    # () int32 scenario mode id (registry index)
    active: jax.Array  # () bool; False = padding frame


class FrameOutputs(NamedTuple):
    """Per-frame scan outputs: what the host stage needs after the chunk
    returns. SLAM map bookkeeping replays from ``fr``/``hist``/``p``/``q``
    without touching the device (append-only); ``ba_cost``/``ba_ran``
    surface the in-scan BA passes for observability. ``upd_*`` carry the
    consumed-track update buffers OUT of the scan when the scheduler
    skipped the in-program MSCKF update (``msckf_update`` gate False) so
    the host can apply a chunk-boundary Kalman fallback instead of
    dropping the observations entirely (zeros whenever the update ran
    in-scan)."""
    fr: FrontendResult
    p: jax.Array        # (3,) post-frame position
    q: jax.Array        # (4,) post-frame orientation quaternion
    hist: jax.Array     # (V,) BoW histogram — scenarios with the
    #                     bow_histogram primitive only (zeros otherwise;
    #                     Registration queries compute theirs in the
    #                     host stage against the live map)
    ba_cost: jax.Array  # () float32 latest windowed-BA cost
    ba_ran: jax.Array   # () bool — BA+marginalization executed this frame
    upd_uv: jax.Array      # (max_updates, W, 2) consumed tracks, or zeros
    upd_valid: jax.Array   # (max_updates, W) bool
    upd_skipped: jax.Array  # () bool — tracks were consumed but the
    #                         in-scan update was gated off this frame
    counts: jax.Array   # (4,) int32 work done by the frame (``COUNTS``)


# what ``FrameOutputs.counts`` counts, in order: live track slots after
# the frame, tracks consumed, the in-program MSCKF update applied (0/1),
# the update shipped to the host instead (0/1, ``upd_skipped``)
COUNTS = ("tracks", "consumed", "updates_applied", "updates_shipped")


def _frame_counts(c: prim.FrameCarry) -> jax.Array:
    return jnp.stack([jnp.sum(c.tracks_valid[:, -1], dtype=jnp.int32),
                      c.upd_count.astype(jnp.int32),
                      c.upd_applied.astype(jnp.int32),
                      c.upd_skipped.astype(jnp.int32)])


# --------------------------------------------------------------------------
# the step compiler: ScenarioTable -> one scan body
# --------------------------------------------------------------------------

_MISSING = object()


def _gated_params(g: scen.GatedUse, table: scen.ScenarioTable, be_cfg,
                  safe_mode: jax.Array) -> Dict:
    """Per-scenario knobs for a shared gated block, resolved through
    baked lookup tables indexed by the (already-bounded) mode id, so one
    compiled block serves scenarios with different knobs.

    ``ba_every`` (the BA cadence) resolves use-level param > spec knob >
    config default, per scenario. Any other ``use(...)`` param must be
    declared by EVERY scenario using the primitive (there is no generic
    stage default to fall back on); uniform scalar values bake directly
    (bitwise-identical to a pre-registry constant), differing numeric
    values become a per-mode lookup table (non-user/invalid rows carry a
    masked placeholder — the uses-table cond keeps them unreached)."""
    params: Dict = {}
    n = len(table)
    use_params = [None if p is None else dict(p) for p in g.params_by_id]
    if g.name == "ba_marginalize":
        vals = []
        for i in range(n):
            u = use_params[i] or {}
            vals.append(int(u.get("ba_every") or table.specs[i].ba_every
                            or be_cfg.ba_every))
        if len(set(vals)) == 1:
            params["ba_every"] = vals[0]
        else:
            arr = jnp.asarray(vals + [vals[0]], jnp.int32)  # pad: invalid id
            params["ba_every"] = arr[safe_mode]
    users = [i for i in range(n) if use_params[i] is not None]
    keys = sorted(set().union(
        *(use_params[i].keys() for i in users), set()) - set(params))
    for k in keys:
        vals = [use_params[i].get(k, _MISSING) for i in users]
        declared = [v for v in vals if v is not _MISSING]
        if not declared:
            continue
        if len(declared) < len(vals):
            missing = [table.specs[i].name for i, v in zip(users, vals)
                       if v is _MISSING]
            raise ValueError(
                f"gated primitive {g.name!r}: param {k!r} must be "
                f"declared by every scenario using the primitive "
                f"(missing in {missing}) — or promote it to a "
                "spec-level knob resolved in _gated_params")
        if all(v == declared[0] for v in declared[1:]):
            params[k] = declared[0]
        elif all(isinstance(v, (int, float)) for v in declared):
            by_id = dict(zip(users, declared))
            row = [by_id.get(i, declared[0]) for i in range(n)]
            row.append(declared[0])                     # invalid-id pad
            dtype = (jnp.int32 if all(isinstance(v, int) for v in declared)
                     else jnp.float32)
            params[k] = jnp.asarray(row, dtype)[safe_mode]
        else:
            raise ValueError(
                f"gated primitive {g.name!r}: per-scenario values for "
                f"{k!r} must be scalars to lower into a lookup table "
                f"(got {declared!r})")
    return params


def localize_step(state: LocalizerState, img_l: jax.Array, img_r: jax.Array,
                  accel: jax.Array, gyro: jax.Array, gps: jax.Array,
                  mode: jax.Array, flags: PlanFlags,
                  dt_imu: jax.Array, *, cfg, be_cfg,
                  fx: float, fy: float, cx: float, cy: float,
                  baseline: float, vocab: jax.Array,
                  allow_pallas_marg: bool = True,
                  scenarios: scen.ScenarioTable = None
                  ) -> Tuple[LocalizerState, FrameOutputs]:
    """One fused frame, compiled from the scenario registry: shared
    spine -> per-scenario ``lax.switch`` -> gated heavy blocks -> new
    state. Pure function of fixed-shape arrays; jitted with
    ``donate_argnums=(0,)`` by the Localizer (and the body of the chunk
    scan below — the K=1 special case IS this function).

    gps: (3,) world position, NaN when unavailable. mode: () int32
    scenario id (out-of-range ids pass through the mode dispatch).
    flags: the scheduler's pre-resolved decisions as traced bools.
    ``scenarios``: the frozen ScenarioTable to compile (default: the
    global registry at trace time).
    """
    table = scenarios if scenarios is not None else scen.table()
    n_scen = len(table)
    w = state.tracks_uv.shape[1]
    n_hist = 2 ** vocab.shape[0]

    # out-of-range ids lower to the trailing pass-through branch and the
    # all-False row of every gated uses-table (the satellite fix: an
    # unknown scenario must not silently run a wrong backend)
    mode = jnp.asarray(mode, jnp.int32)
    safe_mode = jnp.where((mode >= 0) & (mode < n_scen), mode,
                          jnp.int32(n_scen))

    # per-mode gate TABLES (scenario-adaptive plans lower each kept key
    # to an (n_scen+1,) bool row set — see flags_from_plan) index down
    # to this frame's scalars here, before any primitive runs, so every
    # primitive keeps consuming () gates regardless of whether the
    # dispatch carries one fleet-wide plan or one plan per scenario
    frame_gates = {k: (v[safe_mode] if getattr(v, "ndim", 0) == 1 else v)
                   for k, v in flags.gates.items()}
    frame_flags = PlanFlags(gates=frame_gates, active=flags.active,
                            configs=flags.configs)

    ctx = prim.FrameCtx(cfg=cfg, be_cfg=be_cfg, fx=fx, fy=fy, cx=cx, cy=cy,
                        baseline=baseline, vocab=vocab, flags=frame_flags,
                        dt_imu=dt_imu,
                        allow_pallas_marg=allow_pallas_marg)
    c = prim.FrameCarry(
        img_l=img_l, img_r=img_r, accel=accel, gyro=gyro, gps=gps,
        mode=mode, filt=state.filt, tracks_uv=state.tracks_uv,
        tracks_valid=state.tracks_valid, prev_img=state.prev_img,
        prev_yx=state.prev_yx, prev_valid=state.prev_valid,
        frame_idx=state.frame_idx, ba=state.ba,
        hist=jnp.zeros((n_hist,), jnp.float32),
        ba_ran=jnp.bool_(False),
        upd_uv=jnp.zeros((tracks.MAX_UPDATES, w, 2), jnp.float32),
        upd_valid=jnp.zeros((tracks.MAX_UPDATES, w), bool),
        upd_skipped=jnp.bool_(False), upd_count=jnp.int32(0),
        upd_applied=jnp.bool_(False))

    # --- shared spine: mode-independent, unconditional, declared order.
    # Every primitive's stage, here and below, runs under a named scope
    # of its registry name: the compiled program's op metadata, and so a
    # device trace, tells the primitives' ops apart
    for use_ in table.spine:
        p = prim.get_primitive(use_.name)
        with jax.named_scope(p.name):
            c = p.stage(ctx, c, use_.param_dict())

    # --- per-scenario switch: each scenario's light filter work becomes
    # one branch (params baked per branch); branch n_scen = pass-through
    def _branch(uses):
        def br(filt):
            c2 = dataclasses.replace(c, filt=filt)
            for u in uses:
                p = prim.get_primitive(u.name)
                with jax.named_scope(p.name):
                    f_new = p.stage(ctx, c2, u.param_dict())
                c2 = dataclasses.replace(c2, filt=f_new)
            return c2.filt
        return br

    branches = [_branch(uses) for uses in table.switch_uses]
    branches.append(lambda f: f)            # unknown id: pass-through
    c = dataclasses.replace(c, filt=jax.lax.switch(safe_mode, branches,
                                                   c.filt))

    # --- gated heavy blocks (paper Sec. VI-A's variation-dominating
    # kernels): outer cond on the SCALAR any-user-scenario-active flag
    # (a real runtime skip even under vmap), inner cond on the baked
    # per-mode uses-table (batched select in a fleet, like the
    # pre-registry ``mode == MODE_SLAM``)
    for g in table.gated:
        p = prim.get_primitive(g.name)
        active_any = jnp.any(jnp.stack(
            [jnp.asarray(flags.active.get(nm, True))
             for nm in g.scenario_names]))
        uses_row = [i in g.scenario_ids for i in range(n_scen)] + [False]
        uses_arr = jnp.asarray(uses_row, bool)
        params = _gated_params(g, table, be_cfg, safe_mode)
        operand = tuple(getattr(c, f) for f in g.writes)
        carry_now = c

        def _live(op, _g=g, _p=p, _params=params, _c=carry_now,
                  _uses=uses_arr):
            def run(op2):
                c2 = dataclasses.replace(_c, **dict(zip(_g.writes, op2)))
                with jax.named_scope(_p.name):
                    return _p.stage(ctx, c2, _params)
            return jax.lax.cond(_uses[safe_mode], run, lambda op2: op2, op)

        vals = jax.lax.cond(active_any, _live, lambda op: op, operand)
        c = dataclasses.replace(c, **dict(zip(g.writes, vals)))

    # --- assemble the post-frame state and scan outputs
    new_state = LocalizerState(
        filt=c.filt, tracks_uv=c.tracks_uv, tracks_valid=c.tracks_valid,
        prev_img=c.prev_img, prev_yx=c.prev_yx, prev_valid=c.prev_valid,
        frame_idx=c.frame_idx + 1, ba=c.ba)
    outs = FrameOutputs(fr=c.fr, p=c.filt.p, q=c.filt.q, hist=c.hist,
                        ba_cost=c.ba.last_cost, ba_ran=c.ba_ran,
                        upd_uv=c.upd_uv, upd_valid=c.upd_valid,
                        upd_skipped=c.upd_skipped, counts=_frame_counts(c))
    return new_state, outs


def _zero_frontend_result(state: LocalizerState) -> FrontendResult:
    """Shape/dtype-matched placeholder for padding frames (the inactive
    branch of the chunk transition must return the same pytree)."""
    n = state.prev_valid.shape[0]
    return FrontendResult(
        yx=jnp.zeros((n, 2), jnp.int32),
        score=jnp.zeros((n,), jnp.float32),
        valid=jnp.zeros((n,), bool),
        desc=jnp.zeros((n, orb.N_BITS), bool),
        disparity=jnp.zeros((n,), jnp.float32),
        stereo_valid=jnp.zeros((n,), bool),
        prev_yx=jnp.zeros((n, 2), jnp.float32),
        track_valid=jnp.zeros((n,), bool))


def _zero_outputs(state: LocalizerState, vocab: jax.Array,
                  fr: FrontendResult) -> FrameOutputs:
    """Shape-matched FrameOutputs for padding frames."""
    w = state.tracks_uv.shape[1]
    return FrameOutputs(fr=fr, p=state.filt.p, q=state.filt.q,
                        hist=jnp.zeros((2 ** vocab.shape[0],), jnp.float32),
                        ba_cost=state.ba.last_cost,
                        ba_ran=jnp.bool_(False),
                        upd_uv=jnp.zeros((tracks.MAX_UPDATES, w, 2),
                                         jnp.float32),
                        upd_valid=jnp.zeros((tracks.MAX_UPDATES, w), bool),
                        upd_skipped=jnp.bool_(False),
                        counts=jnp.zeros((len(COUNTS),), jnp.int32))


def frame_transition(state: LocalizerState, inp: FrameInputs,
                     flags: PlanFlags, dt_imu: jax.Array, *,
                     cfg, be_cfg, fx: float, fy: float, cx: float,
                     cy: float, baseline: float, vocab: jax.Array,
                     allow_pallas_marg: bool = True,
                     scenarios: scen.ScenarioTable = None
                     ) -> Tuple[LocalizerState, FrameOutputs]:
    """The scan-able FrameState -> FrameState transition: one frame of
    the compiled ``localize_step`` gated by ``inp.active`` (padding
    frames pass state through so a fixed-K chunk serves any sequence
    length)."""
    def live(st):
        return localize_step(st, inp.img_l, inp.img_r, inp.accel,
                             inp.gyro, inp.gps, inp.mode, flags,
                             dt_imu, cfg=cfg, be_cfg=be_cfg, fx=fx, fy=fy,
                             cx=cx, cy=cy, baseline=baseline, vocab=vocab,
                             allow_pallas_marg=allow_pallas_marg,
                             scenarios=scenarios)

    def skip(st):
        return st, _zero_outputs(st, vocab, _zero_frontend_result(st))

    return jax.lax.cond(inp.active, live, skip, state)


def localize_chunk(state: LocalizerState, inputs: FrameInputs,
                   flags: PlanFlags, dt_imu: jax.Array, *,
                   cfg, be_cfg, fx: float, fy: float, cx: float, cy: float,
                   baseline: float, vocab: jax.Array,
                   allow_pallas_marg: bool = True,
                   scenarios: scen.ScenarioTable = None
                   ) -> Tuple[LocalizerState, FrameOutputs]:
    """K frames in ONE dispatch: ``lax.scan`` of the frame transition.

    inputs: FrameInputs with (K, ...) leaves. Returns the post-chunk
    state and per-frame FrameOutputs stacked along K. The offload plan
    and IMU dt are chunk-wide scalars (resolved by the scheduler per
    chunk, not per frame)."""
    def body(st, x):
        return frame_transition(st, x, flags, dt_imu, cfg=cfg,
                                be_cfg=be_cfg, fx=fx, fy=fy, cx=cx, cy=cy,
                                baseline=baseline, vocab=vocab,
                                allow_pallas_marg=allow_pallas_marg,
                                scenarios=scenarios)

    return jax.lax.scan(body, state, inputs)


def fleet_chunk(states: LocalizerState, inputs: FrameInputs,
                flags: PlanFlags, dt_imu: jax.Array, *,
                cfg, be_cfg, fx: float, fy: float, cx: float, cy: float,
                baseline: float, vocab: jax.Array,
                allow_pallas_marg: bool = True,
                scenarios: scen.ScenarioTable = None
                ) -> Tuple[LocalizerState, FrameOutputs]:
    """K frames x B robots in ONE dispatch: scan over the chunk axis of
    the vmapped transition. states: (B, ...) pytree; inputs: FrameInputs
    with (K, B, ...) leaves (per-robot modes/activity inside the batch).
    """
    def vbody(sts, x):
        return jax.vmap(
            lambda st, xi: frame_transition(
                st, xi, flags, dt_imu, cfg=cfg, be_cfg=be_cfg, fx=fx,
                fy=fy, cx=cx, cy=cy, baseline=baseline, vocab=vocab,
                allow_pallas_marg=allow_pallas_marg,
                scenarios=scenarios))(sts, x)

    return jax.lax.scan(vbody, states, inputs)


def init_localizer_state(cfg: EudoxusConfig, window: int, p0=None, v0=None,
                         q0=None) -> LocalizerState:
    """Fresh device-resident state for one robot, composed from the
    frontend, track and windowed-BA scan carries."""
    n = cfg.frontend.max_features
    fe = pipeline.init_carry(cfg.frontend)
    tr = tracks.init_carry(n, window)
    return LocalizerState(
        filt=msckf.init_state(
            window,
            p0=None if p0 is None else jnp.asarray(p0, jnp.float32),
            v0=None if v0 is None else jnp.asarray(v0, jnp.float32),
            q0=None if q0 is None else jnp.asarray(q0, jnp.float32)),
        tracks_uv=tr.uv,
        tracks_valid=tr.valid,
        prev_img=fe.prev_img,
        prev_yx=fe.prev_yx,
        prev_valid=fe.prev_valid,
        frame_idx=jnp.int32(0),
        ba=ba_mod.init_ba_state(cfg.backend.ba_window))


def _bind(fn, cfg: EudoxusConfig, cam, vocab: jax.Array,
          scenarios: scen.ScenarioTable = None):
    """Close a step/chunk function over its static configuration (the
    frozen configs, camera intrinsics and scenario-table snapshot) and
    the shared BoW vocabulary (a device constant baked into the
    trace)."""
    return functools.partial(fn, cfg=cfg.frontend, be_cfg=cfg.backend,
                             fx=cam.fx, fy=cam.fy, cx=cam.cx, cy=cam.cy,
                             baseline=cam.baseline, vocab=vocab,
                             scenarios=scenarios)


class TracedStep:
    """``localize_step`` bound to a config/camera/vocab/scenario-table,
    counting traces.

    The wrapper body runs once per jit trace, so ``traces`` counts
    compilations without relying on private JAX cache APIs. Shared by
    ``Localizer`` (jitted directly) and ``FleetLocalizer`` (vmapped)."""

    def __init__(self, cfg: EudoxusConfig, cam, vocab: jax.Array,
                 scenarios: scen.ScenarioTable = None):
        self._step = _bind(localize_step, cfg, cam, vocab,
                           scenarios=scenarios)
        self.traces = 0

    def __call__(self, *args):
        self.traces += 1
        return self._step(*args)


class TracedChunk:
    """``localize_chunk`` (or ``fleet_chunk`` when ``fleet=True``) bound
    to a config/camera/vocab/scenario-table, counting traces. Steady
    state: exactly one trace — chunk padding keeps K static and
    ``active`` masking keeps shapes data-independent."""

    def __init__(self, cfg: EudoxusConfig, cam, vocab: jax.Array,
                 fleet: bool = False,
                 scenarios: scen.ScenarioTable = None):
        fn = fleet_chunk if fleet else localize_chunk
        self._chunk = _bind(fn, cfg, cam, vocab, scenarios=scenarios)
        self.traces = 0

    def __call__(self, state, inputs, flags, dt_imu):
        self.traces += 1
        return self._chunk(state, inputs, flags, dt_imu)
