"""Fundamental localization primitives — the units scenarios compose.

The paper's central claim is that one framework "adapts to different
operating scenarios by fusing fundamental algorithmic primitives"
(Sec. III-IV; the drone prototype is the same primitives re-instantiated,
and the CICC'22 runtime-reconfigurable accelerator makes the same point
in hardware). This module is that claim as code: each primitive is a
named, pure stage over a per-frame carry, with a declared scheduler
offload key and kernel-registry binding. ``core.scenarios`` composes
them into ``ScenarioSpec`` pipelines and ``core.step`` lowers the
registered spec set into the single compiled scan body.

Placement contract (how ``core.step`` lowers a primitive):

``spine``
    Mode-independent work shared by every scenario. Runs unconditionally
    for every frame, in pipeline order. Signature:
    ``stage(ctx, carry, params) -> FrameCarry``. Every registered
    scenario must declare the identical spine prefix (same primitives,
    same params, same order) — the spine defines the state shapes one
    compiled program threads for the whole fleet.

``switch``
    Light per-scenario filter work. Lowered into the branch list of the
    in-scan ``lax.switch`` on the mode id (one branch per registered
    scenario, plus a trailing pass-through branch for out-of-range ids).
    May read the whole carry (via trace-time closure) but writes ONLY
    the filter state: ``stage(ctx, carry, params) -> MsckfState``.
    Params are baked per scenario at trace time (each branch is its own
    traced function).

``gated``
    Heavy blocks (the paper's variation-dominating kernels). Lowered
    behind a SCALAR ``lax.cond`` on the scenario-activity flags — a
    dispatch containing no scenario that uses the primitive skips the
    block at runtime even under vmap — with an inner per-frame/per-robot
    cond on the mode id. Declares ``writes`` (the carry fields it may
    update); signature: ``stage(ctx, carry, params) -> tuple`` matching
    ``writes``. Per-scenario int params are resolved through baked
    lookup tables indexed by the mode id, so one shared block serves
    scenarios with different knobs (e.g. BA cadence).

``offload_key`` is the primitive's name in the scheduler's
``OffloadPlan`` (the per-chunk offload decision that enters the dispatch
as a traced gate — ``ctx.gate(name)``); ``kernel`` names the
``kernels.registry`` entry backing the primitive's hot loop (the
Pallas-vs-XLA resolution point) and ``latency_kernel`` the
``scheduler.KERNEL_MODELS`` latency-model family its offload decision is
fitted against.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import tracks
from repro.core.backend import ba as ba_mod
from repro.core.backend import fusion, msckf, tracking
from repro.core.frontend import pipeline


@dataclass(frozen=True)
class FrameCtx:
    """Per-trace bindings shared by every primitive: the frozen configs,
    camera intrinsics, BoW vocabulary, the scheduler's traced flags and
    the IMU integration step."""
    cfg: Any                 # frontend config
    be_cfg: Any              # backend config
    fx: float
    fy: float
    cx: float
    cy: float
    baseline: float
    vocab: jax.Array
    flags: Any               # step.PlanFlags (gates/active dicts)
    dt_imu: jax.Array
    allow_pallas_marg: bool = True

    def gate(self, key: str) -> jax.Array:
        """The scheduler's traced offload gate for ``key`` (True when
        the plan has no opinion — offload by default)."""
        gates = getattr(self.flags, "gates", None)
        if gates is None or key not in gates:
            return jnp.bool_(True)
        return gates[key]

    def kernel_config(self, key: str) -> Dict[str, Any]:
        """The plan's autotuned launch config for kernel ``key`` — a
        STATIC kwargs dict ({} = the kernel's built-in defaults). Unlike
        gates these never trace: they pick the Pallas launch geometry at
        trace time (step.KernelConfigs)."""
        configs = getattr(self.flags, "configs", None)
        if configs is None:
            return {}
        return configs.get(key)


@dataclass(frozen=True)
class FrameCarry:
    """The per-frame carry a primitive pipeline threads: the frame's
    inputs (read-only), the ``LocalizerState`` fields, and the products
    later primitives / the output assembly read. Composed in Python at
    trace time — stages return an updated copy via
    ``dataclasses.replace``."""
    # frame inputs
    img_l: jax.Array
    img_r: jax.Array
    accel: jax.Array
    gyro: jax.Array
    gps: jax.Array
    mode: jax.Array
    # LocalizerState threading
    filt: Any
    tracks_uv: jax.Array
    tracks_valid: jax.Array
    prev_img: jax.Array
    prev_yx: jax.Array
    prev_valid: jax.Array
    frame_idx: jax.Array     # PRE-frame index (incremented at assembly)
    ba: Any
    # per-frame products (defaults are the padding/non-participating
    # values, so scenarios omitting a producer still assemble outputs)
    fr: Any = None
    hist: Any = None
    ba_ran: Any = None
    upd_uv: Any = None
    upd_valid: Any = None
    upd_skipped: Any = None
    upd_count: Any = None      # tracks consumed this frame
    upd_applied: Any = None    # the in-program MSCKF update ran


def _replace(c: FrameCarry, **kw) -> FrameCarry:
    return dataclasses.replace(c, **kw)


@dataclass(frozen=True)
class Primitive:
    """One registered fundamental primitive (see module docstring for
    the placement contract)."""
    name: str
    stage: Callable
    placement: str = "spine"            # spine | switch | gated
    writes: Tuple[str, ...] = ()        # gated only: carry fields written
    offload_key: Optional[str] = None   # scheduler.OffloadPlan key
    kernel: Optional[str] = None        # kernels.registry binding
    latency_kernel: Optional[str] = None  # scheduler.KERNEL_MODELS family
    description: str = ""


PRIMITIVES: Dict[str, Primitive] = {}


def register_primitive(p: Primitive) -> Primitive:
    if p.placement not in ("spine", "switch", "gated"):
        raise ValueError(f"unknown placement {p.placement!r} for {p.name}")
    if p.placement == "gated" and not p.writes:
        raise ValueError(f"gated primitive {p.name} must declare writes")
    PRIMITIVES[p.name] = p
    return p


def get_primitive(name: str) -> Primitive:
    try:
        return PRIMITIVES[name]
    except KeyError:
        raise KeyError(f"unknown primitive {name!r}; registered: "
                       f"{sorted(PRIMITIVES)}") from None


# --------------------------------------------------------------------------
# spine stages (mode-independent; run for every frame of every scenario)
# --------------------------------------------------------------------------

def _frontend(ctx: FrameCtx, c: FrameCarry, params: Mapping) -> FrameCarry:
    """FAST+ORB features, stereo correspondences, LK tracks (paper
    Sec. IV frontend). When the plan carries a ``frontend_fused`` gate
    (and the Pallas kill switch is on), the FE+MO slice is selected
    between the fused megakernel and the unfused composition by the
    traced gate; plans without the key keep the unfused program — and
    its numerics — statically unchanged."""
    fe_carry = pipeline.FrontendCarry(prev_img=c.prev_img,
                                      prev_yx=c.prev_yx,
                                      prev_valid=c.prev_valid)
    gates = getattr(ctx.flags, "gates", None)
    fused_gate = None
    if (ctx.allow_pallas_marg and gates is not None
            and "frontend_fused" in gates):
        fused_gate = gates["frontend_fused"]
    fe_carry, fr = pipeline.step_carry(
        fe_carry, c.img_l, c.img_r, ctx.cfg, fused_gate=fused_gate,
        fused_config=ctx.kernel_config("frontend_fused"))
    return _replace(c, fr=fr, prev_img=fe_carry.prev_img,
                    prev_yx=fe_carry.prev_yx,
                    prev_valid=fe_carry.prev_valid)


def _track_ring(ctx: FrameCtx, c: FrameCarry, params: Mapping) -> FrameCarry:
    """Fixed-shape track ring buffer over the clone window; frame 0
    falls out naturally (all-False prev_valid reseeds every slot)."""
    tracks_uv, tracks_valid = tracks.roll_and_update(
        c.tracks_uv, c.tracks_valid, c.fr.yx, c.fr.valid,
        c.fr.prev_yx, c.fr.track_valid)
    return _replace(c, tracks_uv=tracks_uv, tracks_valid=tracks_valid)


def _imu_propagate(ctx: FrameCtx, c: FrameCarry,
                   params: Mapping) -> FrameCarry:
    """MSCKF propagate + clone augmentation (frame 0 defines the start
    pose, so propagation is skipped there). A plan-supplied
    ``cov_update`` gate selects the fused covariance megakernel — one
    VMEM-resident P sweep over all IMU samples plus the clone insertion
    — against the scan-based reference; plans without the key keep the
    reference program statically."""

    def ref_path(f):
        f2 = jax.lax.cond(
            c.frame_idx > 0,
            lambda s: msckf.propagate(s, c.accel, c.gyro, dt=ctx.dt_imu),
            lambda s: s, f)
        return msckf.augment(f2)

    gates = getattr(ctx.flags, "gates", None)
    if (not ctx.allow_pallas_marg or gates is None
            or "cov_update" not in gates):
        return _replace(c, filt=ref_path(c.filt))

    def fused_path(f):
        from repro.kernels import cov_update
        q, p, v, F_seq, Q = msckf.propagate_terms(f, c.accel, c.gyro,
                                                  dt=ctx.dt_imu)
        do = c.frame_idx > 0
        q = jnp.where(do, q, f.q)
        p = jnp.where(do, p, f.p)
        v = jnp.where(do, v, f.v)
        P = cov_update.fused_update(f.P, F_seq, Q, do,
                                    **ctx.kernel_config("cov_update"))
        W = f.clones_q.shape[0]
        return f._replace(
            q=q, p=p, v=v,
            clones_q=jnp.concatenate([f.clones_q[1:], q[None]], axis=0),
            clones_p=jnp.concatenate([f.clones_p[1:], p[None]], axis=0),
            n_clones=jnp.minimum(f.n_clones + 1, W), P=P)

    filt = jax.lax.cond(gates["cov_update"], fused_path, ref_path, c.filt)
    return _replace(c, filt=filt)


def _msckf_update(ctx: FrameCtx, c: FrameCarry,
                  params: Mapping) -> FrameCarry:
    """MSCKF update on CONSUMED tracks only (ended this frame, or at
    full window length) — each observation used exactly once, the MSCKF
    consistency requirement. The scheduler's gate skips the in-dispatch
    update (accuracy-for-latency, paper Fig. 17's host-bound operating
    point); consumed observations then ship out through ``upd_*`` so the
    chunk-boundary host fallback can still feed them to the filter."""
    uv, vd, count, consumed = tracks.select_consumed(c.tracks_uv,
                                                     c.tracks_valid)
    do_consume = (count >= tracks.MIN_UPDATE_TRACKS) & (c.frame_idx >= 3)
    gate = ctx.gate("msckf_update")
    filt = jax.lax.cond(
        do_consume & gate,
        lambda f: msckf.update(f, uv, vd, fx=ctx.fx, fy=ctx.fy,
                               cx=ctx.cx, cy=ctx.cy)[0],
        lambda f: f, c.filt)
    tracks_valid = jnp.where(do_consume,
                             tracks.consume(c.tracks_valid, consumed),
                             c.tracks_valid)
    upd_skipped = do_consume & ~gate
    return _replace(c, filt=filt, tracks_valid=tracks_valid,
                    upd_uv=jnp.where(upd_skipped, uv, 0.0),
                    upd_valid=jnp.where(upd_skipped, vd, False),
                    upd_skipped=upd_skipped,
                    upd_count=jnp.where(do_consume, count, 0),
                    upd_applied=do_consume & gate)


# --------------------------------------------------------------------------
# switch stages (per-scenario branch of the in-scan mode dispatch)
# --------------------------------------------------------------------------

def _gps_fusion(ctx: FrameCtx, c: FrameCarry, params: Mapping):
    """Loosely-coupled GPS position fusion (NaN-safe: invalid fixes get
    zero weight). ``sigma_gps`` down-weights degraded receivers (the
    VIO_DEGRADED knob); default keeps ``fusion.gps_update``'s own."""
    sigma = params.get("sigma_gps")
    if sigma is None:
        return fusion.gps_update(c.filt, c.gps)[0]
    return fusion.gps_update(c.filt, c.gps, sigma_gps=float(sigma))[0]


def _map_query(ctx: FrameCtx, c: FrameCarry, params: Mapping):
    """Registration's in-scan stub: the dynamically-sized map
    projection + PnP runs in the host stage (the map cannot live in a
    fixed-shape scan carry); this primitive declares the offload key /
    projection-kernel binding the host stage resolves against and keeps
    the filter untouched in-scan."""
    return c.filt


# --------------------------------------------------------------------------
# gated stages (heavy blocks behind the scalar activity cond)
# --------------------------------------------------------------------------

def _bow_histogram(ctx: FrameCtx, c: FrameCarry, params: Mapping):
    """BoW histogram of this frame's descriptors — the host map stage
    replays keyframe appends from it without touching the device."""
    return (tracking.bow_histogram(c.fr.desc, c.fr.valid, ctx.vocab),)


def _ba_marginalize(ctx: FrameCtx, c: FrameCarry, params: Mapping):
    """SLAM windowed BA + Schur marginalization, in-scan (paper
    Sec. VI-A's variation-dominating kernel): push the post-frame pose
    as a keyframe and, on the exact host-path trigger, run the
    fixed-shape BA round with the blocked ``marg_schur`` Pallas/XLA
    kernel selected by the traced ``marg_schur`` gate. ``ba_every`` is
    the per-scenario cadence knob (a baked lookup when scenarios
    disagree). Feedback-free by construction: results live in BAState /
    the scan outputs."""
    R = msckf.quat_to_rot(c.filt.q)
    ba2 = ba_mod.push_keyframe(c.ba, R, c.filt.p)
    ba_every = params.get("ba_every", ctx.be_cfg.ba_every)
    trigger = ((ba2.n_kf >= ctx.be_cfg.ba_min_keyframes)
               & (c.frame_idx % ba_every == 0)
               & ctx.gate("ba_marginalize"))

    def run_ba(b):
        pts, pv = ba_mod.backproject_stereo(
            c.fr.yx, c.fr.disparity, c.fr.stereo_valid, R, c.filt.p,
            fx=ctx.fx, fy=ctx.fy, cx=ctx.cx, cy=ctx.cy,
            baseline=ctx.baseline)
        lms, lmv = ba_mod.select_landmarks(pts, pv,
                                           ctx.be_cfg.ba_landmarks)
        intr = jnp.asarray([ctx.fx, ctx.fy, ctx.cx, ctx.cy], jnp.float32)
        return ba_mod.ba_round(
            b, lms, lmv, intr, lm_iters=ctx.be_cfg.lm_iters,
            lm_lambda0=ctx.be_cfg.lm_lambda0,
            marg_pallas=ctx.gate("marg_schur"),
            allow_pallas=ctx.allow_pallas_marg,
            marg_config=ctx.kernel_config("marg_schur"))

    ba3 = jax.lax.cond(trigger, run_ba, lambda b: b, ba2)
    return ba3, trigger


# --------------------------------------------------------------------------
# the registry
# --------------------------------------------------------------------------

register_primitive(Primitive(
    name="frontend", stage=_frontend, placement="spine",
    offload_key="frontend", kernel="frontend_fused",
    latency_kernel="frontend_fused",
    description="FAST+ORB features, stereo match, LK tracking "
                "(fused FE+MO megakernel behind the frontend_fused gate)"))

register_primitive(Primitive(
    name="track_ring", stage=_track_ring, placement="spine",
    description="fixed-shape track ring buffer over the clone window"))

register_primitive(Primitive(
    name="imu_propagate", stage=_imu_propagate, placement="spine",
    kernel="cov_update", latency_kernel="cov_update",
    description="MSCKF IMU propagation + clone augmentation "
                "(fused covariance megakernel behind the cov_update gate)"))

register_primitive(Primitive(
    name="msckf_update", stage=_msckf_update, placement="spine",
    offload_key="msckf_update", kernel="kalman_gain",
    latency_kernel="kalman_gain",
    description="MSCKF update on consumed tracks (Kalman gain kernel)"))

register_primitive(Primitive(
    name="gps_fusion", stage=_gps_fusion, placement="switch",
    kernel="kalman_gain", latency_kernel="kalman_gain",
    description="loosely-coupled GPS position fusion (NaN-safe)"))

register_primitive(Primitive(
    name="map_query", stage=_map_query, placement="switch",
    offload_key="map_query", kernel="projection",
    latency_kernel="projection",
    description="registration map projection/PnP (host-stage backed)"))

register_primitive(Primitive(
    name="bow_histogram", stage=_bow_histogram, placement="gated",
    writes=("hist",), kernel="hamming",
    description="BoW histogram for keyframe place recognition"))

register_primitive(Primitive(
    name="ba_marginalize", stage=_ba_marginalize, placement="gated",
    writes=("ba", "ba_ran"), offload_key="ba_marginalize",
    kernel="marg_schur", latency_kernel="marginalization",
    description="windowed BA + Schur marginalization (in-scan)"))
