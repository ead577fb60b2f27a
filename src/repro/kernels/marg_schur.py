"""Blocked Schur-accumulation Pallas kernel — the marginalization unit.

SLAM marginalization (paper Sec. VI-A) eliminates the landmark block
A_mm = [[A, B], [B^T, D]] whose A is block-diagonal (M small 3x3 blocks).
Every Schur term the elimination needs is one landmark-indexed reduction

    Y = sum_m G_m A_m^{-1} G_m^T          (6K, 6K)
    y = sum_m G_m A_m^{-1} b_m            (6K,)

where G_m (6K, 3) stacks the pose<->landmark coupling blocks of landmark
m over all K window poses. ``core.backend.ba.marginalize_schur`` slices
S_D, the kept-pose prior and the couplings straight out of (Y, y), so
this reduction IS the marginalization kernel's inner loop.

The Pallas kernel blocks the reduction over landmark tiles: each grid
step inverts its tile's 3x3 blocks in registers (closed-form adjugate —
the paper's specialized small-inverse/reciprocal unit) and accumulates
the tile's outer products into the (6K, 6K) output, the same
revisit-and-accumulate pattern as the blocked matmul. ``accumulate_ref``
is the XLA path of the registry's ``marg_schur`` entry.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import default_interpret


def _inv3x3(a: jax.Array) -> jax.Array:
    """Closed-form batched 3x3 inverse via the adjugate (the reciprocal/
    small-inverse unit): a (m, 3, 3) -> (m, 3, 3)."""
    c00 = a[:, 1, 1] * a[:, 2, 2] - a[:, 1, 2] * a[:, 2, 1]
    c01 = a[:, 0, 2] * a[:, 2, 1] - a[:, 0, 1] * a[:, 2, 2]
    c02 = a[:, 0, 1] * a[:, 1, 2] - a[:, 0, 2] * a[:, 1, 1]
    c10 = a[:, 1, 2] * a[:, 2, 0] - a[:, 1, 0] * a[:, 2, 2]
    c11 = a[:, 0, 0] * a[:, 2, 2] - a[:, 0, 2] * a[:, 2, 0]
    c12 = a[:, 0, 2] * a[:, 1, 0] - a[:, 0, 0] * a[:, 1, 2]
    c20 = a[:, 1, 0] * a[:, 2, 1] - a[:, 1, 1] * a[:, 2, 0]
    c21 = a[:, 0, 1] * a[:, 2, 0] - a[:, 0, 0] * a[:, 2, 1]
    c22 = a[:, 0, 0] * a[:, 1, 1] - a[:, 0, 1] * a[:, 1, 0]
    det = a[:, 0, 0] * c00 + a[:, 0, 1] * c10 + a[:, 0, 2] * c20
    adj = jnp.stack([jnp.stack([c00, c01, c02], -1),
                     jnp.stack([c10, c11, c12], -1),
                     jnp.stack([c20, c21, c22], -1)], -2)
    return adj / det[:, None, None]


def _tile_terms(g: jax.Array, a: jax.Array, b: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """One landmark tile's contribution: g (mb, D, 3), a (mb, 3, 3),
    b (mb, 3) -> (D, D), (D,)."""
    ga = jnp.einsum("mdi,mij->mdj", g, _inv3x3(a))
    return (jnp.einsum("mdi,mei->de", ga, g),
            jnp.einsum("mdi,mi->d", ga, b))


# --------------------------------------------------------------------------
# the kernels' lane layout
# --------------------------------------------------------------------------
#
# Inside the kernels the landmark axis is the LANE axis: every per-landmark
# quantity (a Jacobian entry, an A_m entry, a coupling-block column) is one
# row of an (rows, mb) tile, so the 3x3 algebra is elementwise VPU work over
# lane vectors and the reduction over landmarks is one lane-contracting
# matmul per coupling column. The wrappers transpose into that layout in
# XLA; rows are grouped in blocks padded to a multiple of 8 sublanes, so
# every in-kernel slice is tile-aligned. Padding rows are zero and add
# exact zeros to every sum.

def _round8(n: int) -> int:
    return -(-n // 8) * 8


# Landmark tile on the lane axis. Every shipped configuration keeps at
# most 64 landmarks, which is one whole-axis tile; budgets that are a
# multiple of 128 are tiled 128 lanes at a time.
LANE_TILE = 128


def _lane_tile(m: int) -> int:
    """``LANE_TILE`` where it divides ``m``, else the whole axis."""
    return LANE_TILE if m % LANE_TILE == 0 else m


def _lane_inv3x3(a):
    """``_inv3x3`` on a 3x3 nested list of (1, mb) lane vectors."""
    c00 = a[1][1] * a[2][2] - a[1][2] * a[2][1]
    c01 = a[0][2] * a[2][1] - a[0][1] * a[2][2]
    c02 = a[0][1] * a[1][2] - a[0][2] * a[1][1]
    c10 = a[1][2] * a[2][0] - a[1][0] * a[2][2]
    c11 = a[0][0] * a[2][2] - a[0][2] * a[2][0]
    c12 = a[0][2] * a[1][0] - a[0][0] * a[1][2]
    c20 = a[1][0] * a[2][1] - a[1][1] * a[2][0]
    c21 = a[0][1] * a[2][0] - a[0][0] * a[2][1]
    c22 = a[0][0] * a[1][1] - a[0][1] * a[1][0]
    det = a[0][0] * c00 + a[0][1] * c10 + a[0][2] * c20
    return [[c00 / det, c01 / det, c02 / det],
            [c10 / det, c11 / det, c12 / det],
            [c20 / det, c21 / det, c22 / det]]


def _lane_schur(G, a, b):
    """One landmark tile's Schur terms in the lane layout. G: 3 arrays
    (D, mb), column j of every landmark's (D, 3) coupling block; a: 3x3
    of (1, mb); b: 3 of (1, mb) -> Y (D, D), y (D, 1)."""
    ainv = _lane_inv3x3(a)
    yy = yv = None
    for j in range(3):
        ga = G[0] * ainv[0][j] + G[1] * ainv[1][j] + G[2] * ainv[2][j]
        t = jax.lax.dot_general(ga, G[j], (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        v = jnp.sum(ga * b[j], axis=1, keepdims=True)
        yy = t if yy is None else yy + t
        yv = v if yv is None else yv + v
    return yy, yv


def _accumulate_into(yy_ref, yv_ref, first, terms):
    @pl.when(first)
    def _init():
        yy_ref[...] = jnp.zeros_like(yy_ref)
        yv_ref[...] = jnp.zeros_like(yv_ref)

    yy, yv = terms
    yy_ref[...] += yy
    yv_ref[...] += yv


def _schur_kernel(g_ref, a_ref, b_ref, yy_ref, yv_ref, *, dp):
    G = [g_ref[j * dp:(j + 1) * dp, :] for j in range(3)]
    a = [[a_ref[3 * i + j:3 * i + j + 1, :] for j in range(3)]
         for i in range(3)]
    b = [b_ref[j:j + 1, :] for j in range(3)]
    _accumulate_into(yy_ref, yv_ref, pl.program_id(0) == 0,
                     _lane_schur(G, a, b))


def accumulate(g: jax.Array, a: jax.Array, b: jax.Array, *,
               interpret: Optional[bool] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Y = sum_m g_m a_m^{-1} g_m^T, y = sum_m g_m a_m^{-1} b_m, blocked
    over landmark tiles. g (M, D, 3), a (M, 3, 3), b (M, 3)."""
    if interpret is None:
        interpret = default_interpret()
    m, d, _ = g.shape
    dp = _round8(d)
    mb = _lane_tile(m)
    gt = jnp.pad(g.transpose(2, 1, 0), ((0, 0), (0, dp - d), (0, 0)))
    yy, yv = pl.pallas_call(
        functools.partial(_schur_kernel, dp=dp),
        grid=(m // mb,),
        in_specs=[pl.BlockSpec((3 * dp, mb), lambda i: (0, i)),
                  pl.BlockSpec((9, mb), lambda i: (0, i)),
                  pl.BlockSpec((3, mb), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((dp, dp), lambda i: (0, 0)),
                   pl.BlockSpec((dp, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((dp, dp), g.dtype),
                   jax.ShapeDtypeStruct((dp, 1), g.dtype)],
        interpret=interpret,
        name="marg_schur_accumulate",
    )(gt.reshape(3 * dp, m), a.reshape(m, 9).T, b.T)
    return yy[:d, :d], yv[:d, 0]


def accumulate_ref(g: jax.Array, a: jax.Array, b: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """Unblocked XLA reference of the same reduction (the registry's
    host/xla path; also the vmap-friendly in-scan fallback)."""
    return _tile_terms(g, a, b)


# --------------------------------------------------------------------------
# widened entry: consume the BA normal-equation assembly directly
# --------------------------------------------------------------------------
#
# ``ba.ba_round`` used to materialize the full Gauss-Newton blocks
# (Hpl (K,M,6,3), Hll (M,3,3), bl (M,3) — mapping.build_normal_eqs)
# before handing them to ``accumulate``. But every one of those blocks
# is a landmark-local contraction of the residual Jacobians, so the JᵀJ
# assembly tiles over landmarks exactly like the Schur reduction does.
# ``accumulate_normal`` fuses both: each grid step contracts its
# landmark tile's (K, mb, 2, ·) Jacobian slabs into the tile's G/A/b
# blocks in VMEM and feeds them straight to the Schur accumulation —
# Hpl/Hll/bl never exist at full M in HBM.

def _normal_tile(r: jax.Array, jx: jax.Array, jl: jax.Array,
                 jitter: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Tile-local normal-equation assembly: r (K,mb,2), jx (K,mb,2,6),
    jl (K,mb,2,3) -> g (mb, 6K, 3), a (mb, 3, 3), b (mb, 3). Same
    contractions as ``mapping.build_normal_eqs`` restricted to the tile
    (all three are landmark-local, so tiling over m is exact)."""
    k, mb_ = jx.shape[0], jx.shape[1]
    hll = jnp.einsum("kmri,kmrj->mij", jl, jl)
    hpl = jnp.einsum("kmri,kmrj->kmij", jx, jl)
    bl = jnp.einsum("kmri,kmr->mi", jl, r)
    g = hpl.transpose(1, 0, 2, 3).reshape(mb_, 6 * k, 3)
    a = hll + jitter * jnp.eye(3, dtype=hll.dtype)[None]
    return g, a, bl


def _lane_normal(r, jx, jl, jitter, kp):
    """``_normal_tile`` in the lane layout: r (2kp, mb) rows (rr, k);
    jx (12kp, mb) rows (i, rr, k); jl (6kp, mb) rows (j, rr, k) ->
    the ``_lane_schur`` operands, G rows ordered (i, k)."""
    def blk(x, c, rr):
        return x[(2 * c + rr) * kp:(2 * c + rr + 1) * kp]

    res = [r[:kp], r[kp:2 * kp]]
    L = [[blk(jl, j, rr) for rr in (0, 1)] for j in range(3)]
    X = [[blk(jx, i, rr) for rr in (0, 1)] for i in range(6)]
    a = [[jnp.sum(L[i][0] * L[j][0] + L[i][1] * L[j][1], axis=0,
                  keepdims=True) + (jitter if i == j else 0.0)
          for j in range(3)] for i in range(3)]
    b = [jnp.sum(L[j][0] * res[0] + L[j][1] * res[1], axis=0,
                 keepdims=True) for j in range(3)]
    G = [jnp.concatenate([X[i][0] * L[j][0] + X[i][1] * L[j][1]
                          for i in range(6)], axis=0) for j in range(3)]
    return G, a, b


def _normal_kernel(r_ref, jx_ref, jl_ref, yy_ref, yv_ref, *, jitter, kp):
    G, a, b = _lane_normal(r_ref[...], jx_ref[...], jl_ref[...], jitter,
                           kp)
    _accumulate_into(yy_ref, yv_ref, pl.program_id(0) == 0,
                     _lane_schur(G, a, b))


def _normal_db_kernel(r_hbm, jx_hbm, jl_hbm, yy_ref, yv_ref,
                      r_s, jx_s, jl_s, r_sem, jx_sem, jl_sem,
                      *, jitter, kp, mb, nt):
    """Explicitly double-buffered variant of ``_normal_kernel``: the
    Jacobian slabs stay HBM-resident (memory_space=ANY) and each
    landmark tile is DMA'd into one slot of a two-deep VMEM ping-pong —
    the async copy of tile t+1 is issued BEFORE tile t's compute, so
    the HBM->VMEM transfer overlaps the contraction instead of
    serializing ahead of it (the automatic-pipelining grid can't overlap
    here because the accumulator output blocks every grid step on the
    same tile). Tiles are consumed in the identical ascending order as
    the grid version, so the float accumulation is bitwise-identical."""

    def copies(t, slot):
        sl = pl.ds(t * mb, mb)
        return (pltpu.make_async_copy(r_hbm.at[:, sl], r_s.at[slot],
                                      r_sem.at[slot]),
                pltpu.make_async_copy(jx_hbm.at[:, sl], jx_s.at[slot],
                                      jx_sem.at[slot]),
                pltpu.make_async_copy(jl_hbm.at[:, sl], jl_s.at[slot],
                                      jl_sem.at[slot]))

    yy_ref[...] = jnp.zeros_like(yy_ref)
    yv_ref[...] = jnp.zeros_like(yv_ref)
    for c in copies(0, 0):                     # warm-up: tile 0 -> slot 0
        c.start()

    def step(t, carry):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < nt)
        def _prefetch():                       # overlaps this tile's math
            for c in copies(t + 1, jax.lax.rem(t + 1, 2)):
                c.start()

        for c in copies(t, slot):
            c.wait()
        G, a, b = _lane_normal(r_s[slot], jx_s[slot], jl_s[slot], jitter,
                               kp)
        yy, yv = _lane_schur(G, a, b)
        yy_ref[...] += yy
        yv_ref[...] += yv
        return carry

    jax.lax.fori_loop(0, nt, step, 0)


def accumulate_normal(r: jax.Array, jx: jax.Array, jl: jax.Array, *,
                      jitter: float = 1e-4, double_buffer: bool = False,
                      interpret: Optional[bool] = None
                      ) -> Tuple[jax.Array, jax.Array]:
    """Fused JᵀJ assembly + Schur accumulation from BA residual
    Jacobians: r (K,M,2), jx (K,M,2,6), jl (K,M,2,3) -> (6K,6K), (6K,).

    The landmark (lane) axis is tiled by ``_lane_tile``.
    ``double_buffer`` swaps the automatically-pipelined grid for the
    explicit two-deep VMEM ping-pong (``_normal_db_kernel``) —
    bitwise-identical results; it needs >= 2 tiles to have anything to
    overlap, so single-tile shapes fall back to the grid form."""
    if interpret is None:
        interpret = default_interpret()
    k, m = jx.shape[0], jx.shape[1]
    kp = _round8(k)
    mb = _lane_tile(m)
    nt = m // mb

    def lanes(x):        # (K, M, 2, c) -> (c*2*kp, M), rows (c, rr, k)
        x = jnp.pad(x, ((0, kp - k),) + ((0, 0),) * (x.ndim - 1))
        return x.transpose(3, 2, 0, 1).reshape(-1, m)

    ops = (lanes(r[..., None]), lanes(jx), lanes(jl))
    dp = 6 * kp
    out_shape = [jax.ShapeDtypeStruct((dp, dp), jx.dtype),
                 jax.ShapeDtypeStruct((dp, 1), jx.dtype)]
    if double_buffer and nt >= 2:
        yy, yv = pl.pallas_call(
            functools.partial(_normal_db_kernel, jitter=jitter, kp=kp,
                              mb=mb, nt=nt),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((2, o.shape[0], mb), o.dtype)
                            for o in ops]
            + [pltpu.SemaphoreType.DMA((2,))] * 3,
            interpret=interpret,
            name="marg_schur_normal",
        )(*ops)
    else:
        yy, yv = pl.pallas_call(
            functools.partial(_normal_kernel, jitter=jitter, kp=kp),
            grid=(nt,),
            in_specs=[pl.BlockSpec((o.shape[0], mb), lambda i: (0, i))
                      for o in ops],
            out_specs=[pl.BlockSpec((dp, dp), lambda i: (0, 0)),
                       pl.BlockSpec((dp, 1), lambda i: (0, 0))],
            out_shape=out_shape,
            interpret=interpret,
            name="marg_schur_normal",
        )(*ops)
    # kernel rows are (i, k); the reference layout is d = 6k + i
    yy = yy.reshape(6, kp, 6, kp)[:, :k, :, :k].transpose(1, 0, 3, 2)
    yv = yv.reshape(6, kp)[:, :k].T
    return yy.reshape(6 * k, 6 * k), yv.reshape(6 * k)


def accumulate_normal_ref(r: jax.Array, jx: jax.Array, jl: jax.Array, *,
                          jitter: float = 1e-4
                          ) -> Tuple[jax.Array, jax.Array]:
    """Unblocked XLA reference: full normal-equation assembly (identical
    contractions to ``mapping.build_normal_eqs``) then the unblocked
    Schur reduction — the exact op sequence ``ba_round`` ran before the
    fusion, relocated behind the registry's xla path."""
    g, a, b = _normal_tile(r, jx, jl, jitter)
    return _tile_terms(g, a, b)
