"""The PRE-REFACTOR fused step, frozen verbatim as a test oracle.

This is the hand-written ~120-line monolith that ``core.step`` replaced
with the scenario-primitive compiler: the three backends hard-coded as
``lax.switch(jnp.clip(mode, 0, 2), ...)`` lambdas and the SLAM BA block
special-cased inline. ``tests/test_scenarios.py`` drives it against the
registry-compiled step on identical inputs and asserts BITWISE equality
for the legacy VIO/SLAM/Registration modes across the per-frame,
chunked and fleet paths.

Copied from ``src/repro/core/step.py`` @ pre-registry HEAD — do not
"fix" or modernize it; its value is being exactly the old behavior.
The ``flags`` argument is the new ``PlanFlags`` (its legacy
``kalman``/``marg``/``marg_pallas``/``slam`` views read the same
decisions the old NamedTuple fields carried). The one later addition
is ``FrameOutputs.counts``, the per-frame work counters, computed here
from the monolith's own intermediates so the compiled step's counters
are checked against an independent count.
"""
import jax
import jax.numpy as jnp

from repro.core import tracks
from repro.core.backend import ba as ba_mod
from repro.core.backend import fusion, msckf, tracking
from repro.core.environment import MODE_SLAM
from repro.core.frontend import pipeline
from repro.core.step import (FrameOutputs, LocalizerState,
                             _zero_frontend_result, _zero_outputs)


def localize_step_monolith(state, img_l, img_r, accel, gyro, gps, mode,
                           flags, dt_imu, *, cfg, be_cfg, fx, fy, cx, cy,
                           baseline, vocab, allow_pallas_marg=True):
    """Verbatim pre-registry ``localize_step``."""
    fe_carry = pipeline.FrontendCarry(prev_img=state.prev_img,
                                      prev_yx=state.prev_yx,
                                      prev_valid=state.prev_valid)
    fe_carry, fr = pipeline.step_carry(fe_carry, img_l, img_r, cfg)

    tracks_uv, tracks_valid = tracks.roll_and_update(
        state.tracks_uv, state.tracks_valid, fr.yx, fr.valid,
        fr.prev_yx, fr.track_valid)

    filt = jax.lax.cond(
        state.frame_idx > 0,
        lambda f: msckf.propagate(f, accel, gyro, dt=dt_imu),
        lambda f: f, state.filt)
    filt = msckf.augment(filt)

    uv, vd, count, consumed = tracks.select_consumed(tracks_uv, tracks_valid)
    do_consume = (count >= tracks.MIN_UPDATE_TRACKS) & (state.frame_idx >= 3)
    filt = jax.lax.cond(
        do_consume & flags.kalman,
        lambda f: msckf.update(f, uv, vd, fx=fx, fy=fy, cx=cx, cy=cy)[0],
        lambda f: f, filt)
    tracks_valid = jnp.where(do_consume,
                             tracks.consume(tracks_valid, consumed),
                             tracks_valid)
    upd_skipped = do_consume & ~flags.kalman
    upd_uv = jnp.where(upd_skipped, uv, 0.0)
    upd_valid = jnp.where(upd_skipped, vd, False)

    filt = jax.lax.switch(jnp.clip(mode, 0, 2),
                          [lambda f: fusion.gps_update(f, gps)[0],
                           lambda f: f, lambda f: f], filt)

    n_hist = 2 ** vocab.shape[0]

    def slam_branch(ba_in):
        hist = tracking.bow_histogram(fr.desc, fr.valid, vocab)
        R = msckf.quat_to_rot(filt.q)
        ba2 = ba_mod.push_keyframe(ba_in, R, filt.p)
        trigger = ((ba2.n_kf >= be_cfg.ba_min_keyframes)
                   & (state.frame_idx % be_cfg.ba_every == 0)
                   & flags.marg)

        def run_ba(b):
            pts, pv = ba_mod.backproject_stereo(
                fr.yx, fr.disparity, fr.stereo_valid, R, filt.p,
                fx=fx, fy=fy, cx=cx, cy=cy, baseline=baseline)
            lms, lmv = ba_mod.select_landmarks(pts, pv,
                                               be_cfg.ba_landmarks)
            intr = jnp.asarray([fx, fy, cx, cy], jnp.float32)
            return ba_mod.ba_round(
                b, lms, lmv, intr, lm_iters=be_cfg.lm_iters,
                lm_lambda0=be_cfg.lm_lambda0,
                marg_pallas=flags.marg_pallas,
                allow_pallas=allow_pallas_marg)

        ba3 = jax.lax.cond(trigger, run_ba, lambda b: b, ba2)
        return ba3, trigger, hist

    def not_slam(ba_in):
        return (ba_in, jnp.bool_(False),
                jnp.zeros((n_hist,), jnp.float32))

    ba_state, ba_ran, hist = jax.lax.cond(
        flags.slam,
        lambda b: jax.lax.cond(mode == MODE_SLAM, slam_branch,
                               not_slam, b),
        not_slam, state.ba)

    new_state = LocalizerState(
        filt=filt, tracks_uv=tracks_uv, tracks_valid=tracks_valid,
        prev_img=fe_carry.prev_img, prev_yx=fe_carry.prev_yx,
        prev_valid=fe_carry.prev_valid,
        frame_idx=state.frame_idx + 1, ba=ba_state)
    counts = jnp.stack([
        jnp.sum(tracks_valid[:, -1]).astype(jnp.int32),
        jnp.where(do_consume, count, 0).astype(jnp.int32),
        (do_consume & flags.kalman).astype(jnp.int32),
        upd_skipped.astype(jnp.int32)])
    outs = FrameOutputs(fr=fr, p=filt.p, q=filt.q, hist=hist,
                        ba_cost=ba_state.last_cost, ba_ran=ba_ran,
                        upd_uv=upd_uv, upd_valid=upd_valid,
                        upd_skipped=upd_skipped, counts=counts)
    return new_state, outs


def frame_transition_monolith(state, inp, flags, dt_imu, **kw):
    """Pre-registry active-gated transition over the monolith step."""
    def live(st):
        return localize_step_monolith(st, inp.img_l, inp.img_r, inp.accel,
                                      inp.gyro, inp.gps, inp.mode, flags,
                                      dt_imu, **kw)

    def skip(st):
        return st, _zero_outputs(st, kw["vocab"], _zero_frontend_result(st))

    return jax.lax.cond(inp.active, live, skip, state)


def localize_chunk_monolith(state, inputs, flags, dt_imu, **kw):
    """Pre-registry K-frame chunk scan over the monolith transition."""
    def body(st, x):
        return frame_transition_monolith(st, x, flags, dt_imu, **kw)

    return jax.lax.scan(body, state, inputs)


def fleet_chunk_monolith(states, inputs, flags, dt_imu, **kw):
    """Pre-registry K x B fleet chunk over the monolith transition."""
    def vbody(sts, x):
        return jax.vmap(
            lambda st, xi: frame_transition_monolith(st, xi, flags,
                                                     dt_imu, **kw))(sts, x)

    return jax.lax.scan(vbody, states, inputs)
