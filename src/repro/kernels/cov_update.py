"""Fused IMU covariance megakernel — propagate + state augment on P tiles.

``msckf.propagate`` sweeps K IMU samples with a lax.scan whose body does
F·P·Fᵀ+Q on the 15×15 IMU block and F·P_ic on the clone coupling — each
sample re-reads and re-writes the full (d, d) covariance through HBM.
``msckf.augment`` then permutes the clone blocks and inserts the new
clone rows, another full-P round trip.

This kernel fuses both: the covariance is the kernel's OUTPUT block and
stays VMEM-resident across the whole grid — grid step i applies sample
i's transition in place; the last step applies the augment permutation
and clone-row insertion on the already-hot tile. DRAM sees exactly one
P read and one P write for the whole propagate+augment sequence.

The nominal integration (quaternion state, tiny) stays in XLA —
``msckf.propagate_terms`` produces the per-sample F blocks the kernel
consumes. ``do_prop`` is a traced (1,1) gate: frame 0 skips propagation
but still augments, matching the spine's ``frame_idx > 0`` cond without
changing kernel shapes. ``update_ref`` is the registry's XLA reference
composition (same math as propagate-then-augment on the P slice).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import functools

from repro.kernels.common import default_interpret, pick_block


def _propagate_P(P, F, Q):
    """One sample's covariance transition on the IMU block (same update
    as ``msckf.propagate``'s scan body, on an already-loaded P). The new
    blocks are placed by concatenation, not indexed writes: a Pallas TPU
    kernel cannot lower a scatter."""
    Pii = P[:15, :15]
    Pic = P[:15, 15:]
    Pii_new = F @ Pii @ F.T + Q
    Pic_new = F @ Pic
    top = jnp.concatenate([0.5 * (Pii_new + Pii_new.T), Pic_new], axis=1)
    bottom = jnp.concatenate([Pic_new.T, P[15:, 15:]], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


def _augment_P(P):
    """Clone-window permutation + new-clone row/col insertion (same
    sequence as ``msckf.augment``: J selects the first 6 error dims, so
    P·Jᵀ is P's first 6 columns)."""
    d = P.shape[0]
    rows = jnp.concatenate([P[:15], P[21:], P[15:21]], axis=0)
    P_shift = jnp.concatenate([rows[:, :15], rows[:, 21:], rows[:, 15:21]],
                              axis=1)
    PJ = P_shift[:d - 6, :6]                          # (d-6, 6)
    JPJ = P_shift[:6, :6]                             # (6, 6)
    top = jnp.concatenate([P_shift[:d - 6, :d - 6], PJ], axis=1)
    bottom = jnp.concatenate([PJ.T, JPJ], axis=1)
    return jnp.concatenate([top, bottom], axis=0)


def _cov_kernel(F_ref, Q_ref, gate_ref, P_ref, out_ref, *, bk):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _load():
        out_ref[...] = P_ref[...]                     # one DRAM read of P

    gate = gate_ref[...][0, 0] > 0
    Q = Q_ref[...]
    # bk samples per grid step, applied in the SAME sequential order as
    # the bk=1 grid — bitwise-identical result at any tiling, fewer grid
    # steps (the autotuner's block_k knob trades grid overhead against
    # per-step F-block residency)
    for j in range(bk):
        P = out_ref[...]
        P_upd = _propagate_P(P, F_ref[...][j], Q)
        out_ref[...] = jnp.where(gate, P_upd, P)

    @pl.when(i == pl.num_programs(0) - 1)
    def _augment():
        out_ref[...] = _augment_P(out_ref[...])


def fused_update(P: jax.Array, F_seq: jax.Array, Q: jax.Array,
                 do_prop: jax.Array, *, block_k: int = 1,
                 interpret: Optional[bool] = None) -> jax.Array:
    """P (d,d), F_seq (K,15,15), Q (15,15), do_prop () int32/bool ->
    augmented post-propagation covariance (d,d). ``block_k`` — IMU
    samples consumed per grid step (numerics-exact at any value: the
    sweep stays strictly sequential)."""
    if interpret is None:
        interpret = default_interpret()
    d = P.shape[0]
    K = F_seq.shape[0]
    bk = pick_block(K, block_k)
    gate = jnp.asarray(do_prop, jnp.int32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_cov_kernel, bk=bk),
        grid=(K // bk,),
        in_specs=[pl.BlockSpec((bk, 15, 15), lambda i: (i, 0, 0)),
                  pl.BlockSpec((15, 15), lambda i: (0, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  pl.BlockSpec((d, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((d, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((d, d), P.dtype),
        interpret=interpret,
        name="cov_update",
    )(F_seq, Q, gate, P)


def update_ref(P: jax.Array, F_seq: jax.Array, Q: jax.Array,
               do_prop: jax.Array) -> jax.Array:
    """Unfused XLA reference of the same covariance sweep (the registry's
    host path and the parity oracle)."""
    def step(P, F):
        return _propagate_P(P, F, Q), None

    P_prop, _ = jax.lax.scan(step, P, F_seq)
    return _augment_P(jnp.where(jnp.asarray(do_prop) > 0, P_prop, P))
