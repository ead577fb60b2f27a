"""Fused frontend megakernel — detect + describe + match in one pipeline.

The paper's frontend accelerator wins by fusing the FE tasks into one
pipelined block: the frame streams through IF (blur), FD (FAST-9 +
NMS) and FC (rBRIEF) without ever round-tripping intermediates through
DRAM, and only the fixed feature-budget SRAM crosses to the matching
unit. The unfused XLA spine (``frontend/filters.py`` + ``fast.py`` +
``orb.py`` + ``stereo.py``) materializes the blurred frame, the full
score map and the descriptor matrix in HBM between ops.

This module is the Pallas twin: three ``pallas_call`` stages.

  kernel A (_fe_kernel):   (8, 128)-aligned slabs of the padded frame ->
                           separable Gaussian blur + FAST-9 + cell NMS
                           in one pass over row-blocks; only the smoothed
                           frame and the cell maxima leave VMEM, and the
                           top-N budget runs on the maxima in XLA
                           (``fast.topk_cells``).
  kernel B (_fc_kernel):   smoothed frame + top-N corner budget ->
                           orientation + rotated-BRIEF + bit packing.
  kernel C (_mo_kernel):   packed descriptors -> SWAR-popcount epipolar
                           match (the stereo_hamming unit, fused with
                           the constraint masking + argmin).

The composition (``fe_match``) is numerically exact vs the unfused
reference (``core.frontend.pipeline._fe_match_ref``): same tap order,
same op sequence, integer hamming distances that are exact in fp32.
Shapes stay static under the scan via the fixed ``max_features`` corner
budget. ``supported()`` gates dispatch: the NMS reshape trick needs the
frame to be a whole number of NMS cells (odd sizes fall back to XLA).

On a TPU, kernels A and C compile; kernel B does not (its per-corner
pixel sampling is a 2-D gather that Mosaic cannot lower), so the
registry keeps the whole composition on XLA there
(``KernelSpec.tpu_blocker``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.frontend import fast, filters, orb, stereo
from repro.kernels.common import default_interpret, pick_block


def supported(h: int, w: int, cell: int) -> bool:
    """Fused path needs whole NMS cells (the reshape-NMS crop must be a
    no-op so corner coordinates match the reference bitwise)."""
    return h % cell == 0 and w % cell == 0 and h >= cell and w >= cell


# --------------------------------------------------------------------------
# kernel A: blur + FAST-9 scoring over row-blocks of the padded frame
# --------------------------------------------------------------------------

def _fe_block_compute(S, row0, *, taps, H, W, bh, pad, threshold, arc_len):
    """The blur + FAST-9 math for one row-block. ``S`` is the block's
    slab of the padded frame, at least (bh + 2p, W + 2p): its first row
    is padded row ``row0``, so its first output row is image row
    ``row0``, and every tap and ring offset is a static slice of it.
    Shared verbatim by the auto-pipelined and double-buffered kernels,
    so both are bitwise equal by construction."""
    r = len(taps) // 2

    # IF: separable Gaussian on this row-block (vertical then horizontal,
    # same tap order / accumulation as filters._conv1d -> bitwise equal;
    # the pad-p border doubles as both passes' edge padding)
    vP = jnp.zeros((bh, S.shape[1]), jnp.float32)
    for ti, t in enumerate(taps):
        o = (pad - r) + ti
        vP = vP + S[o:o + bh, :] * t
    smooth = jnp.zeros((bh, W), jnp.float32)
    for tj, t in enumerate(taps):
        o = (pad - r) + tj
        smooth = smooth + vP[:, o:o + W] * t

    # FD: FAST-9 on the RAW block (ring offsets read from the same pad)
    center = S[pad:pad + bh, pad:pad + W]
    ring = jnp.stack([S[pad + dy:pad + dy + bh, pad + dx:pad + dx + W]
                      for dy, dx in fast.CIRCLE])     # (16, bh, W)
    diff = ring - center[None]
    brighter = diff > threshold
    darker = diff < -threshold

    def has_arc(flags):
        out = jnp.zeros(flags.shape[1:], bool)
        for start in range(16):
            run = flags[start % 16]
            for j in range(1, arc_len):
                run = run & flags[(start + j) % 16]
            out = out | run
        return out

    corner_b = has_arc(brighter)
    corner_d = has_arc(darker)
    sb = jnp.sum(jnp.where(brighter, jnp.abs(diff) - threshold, 0.0), axis=0)
    sd = jnp.sum(jnp.where(darker, jnp.abs(diff) - threshold, 0.0), axis=0)
    score = jnp.where(corner_b, sb, 0.0) + jnp.where(corner_d, sd, 0.0)
    margin = 16
    yy = row0 + jax.lax.broadcasted_iota(jnp.int32, (bh, W), 0)
    xx = jax.lax.broadcasted_iota(jnp.int32, (bh, W), 1)
    inside = ((yy >= margin) & (yy < H - margin) &
              (xx >= margin) & (xx < W - margin))
    return smooth, jnp.where(inside, score, 0.0)


def _cell_max(score, cell):
    """Cell NMS of one (bh, W) score block without a reshape: the same
    (max score, first row-major index in the cell) as
    ``fast.cell_argmax``, for cell (c, j) at row ``c * cell`` of the
    returned (bh, W // cell) pair (float; indices < cell**2 are exact).

    Each element first takes the best of its cell by rolls along both
    axes: at each shift s = 1, 2, 4, ... it looks s sublanes (then
    lanes) away both ways and keeps the neighbour's (score, index) when
    that neighbour lies in the same cell and wins — higher score, or the
    same score at a lower index, so the first maximum wins as argmax's
    does. Then one 0/1 selection matmul at HIGHEST precision (exact)
    picks lane ``j * cell`` of every cell."""
    bh, W = score.shape
    y = jax.lax.broadcasted_iota(jnp.int32, (bh, W), 0).astype(jnp.float32)
    x = jax.lax.broadcasted_iota(jnp.int32, (bh, W), 1).astype(jnp.float32)
    gy, gx = jnp.floor(y / cell), jnp.floor(x / cell)
    m, k = score, (y - gy * cell) * cell + (x - gx * cell)
    for axis, g in ((0, gy), (1, gx)):
        n, s = score.shape[axis], 1
        while s < cell:
            for shift in (s, n - s):
                m2 = pltpu.roll(m, shift, axis)
                k2 = pltpu.roll(k, shift, axis)
                take = ((pltpu.roll(g, shift, axis) == g)
                        & ((m2 > m) | ((m2 == m) & (k2 < k))))
                m, k = jnp.where(take, m2, m), jnp.where(take, k2, k)
            s *= 2
    wc = W // cell
    sel = (jax.lax.broadcasted_iota(jnp.int32, (W, wc), 0)
           == cell * jax.lax.broadcasted_iota(jnp.int32, (W, wc), 1))
    sel = sel.astype(jnp.float32)

    def pick(v):
        return jax.lax.dot_general(v, sel, (((1,), (0,)), ((), ())),
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    return pick(m), pick(k)


def _store_cells(score, cell, best_ref, idx_ref):
    best, idx = _cell_max(score, cell)
    for c in range(best_ref.shape[0]):
        best_ref[c:c + 1, :] = best[c * cell:c * cell + 1, :]
        idx_ref[c:c + 1, :] = idx[c * cell:c * cell + 1, :].astype(
            jnp.int32)


def _fe_kernel(slab_ref, smooth_ref, best_ref, idx_ref, *, bh, cell, **kw):
    smooth, score = _fe_block_compute(slab_ref[...], pl.program_id(0) * bh,
                                      bh=bh, **kw)
    smooth_ref[...] = smooth
    _store_cells(score, cell, best_ref, idx_ref)


def _fe_db_kernel(pad_hbm, smooth_ref, best_ref, idx_ref, slab, sem, *,
                  bh, pad, nt, cell, **kw):
    """Double-buffered kernel A: the padded frame stays HBM-resident
    (memory_space=ANY) and each grid step's (bh+2p)-row slab lands in
    one slot of a two-deep VMEM ping-pong. The copy of slab i+1 is
    issued before slab i's blur/score compute, so the HBM->VMEM
    transfer rides under the arithmetic; TPU grids run sequentially, so
    the scratch started at step i is exactly what step i+1 waits on.
    Math is ``_fe_block_compute`` on the slab — bitwise equal to the
    auto-pipelined kernel."""
    i = pl.program_id(0)
    rows = slab.shape[1]

    def copy(t, slot):
        return pltpu.make_async_copy(pad_hbm.at[pl.ds(t * bh, rows), :],
                                     slab.at[slot], sem.at[slot])

    @pl.when(i == 0)
    def _warm():
        copy(0, 0).start()

    slot = jax.lax.rem(i, 2)

    @pl.when(i + 1 < nt)
    def _prefetch():
        copy(i + 1, jax.lax.rem(i + 1, 2)).start()

    copy(i, slot).wait()
    smooth, score = _fe_block_compute(slab[slot], i * bh, bh=bh, pad=pad,
                                      **kw)
    smooth_ref[...] = smooth
    _store_cells(score, cell, best_ref, idx_ref)


def fe_cells(img: jax.Array, cfg, *, block_cells: int = 8,
             double_buffer: bool = False,
             interpret: Optional[bool] = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel A on one (H, W) image: the Gaussian-smoothed frame (H, W)
    and, per NMS cell of the FAST-9 score map (zero outside the
    16-pixel margin), its max score and first-maximum index
    (H // cell, W // cell) — ``fast.cell_argmax`` of the score map.
    ``block_cells`` sizes the row-block in NMS cells (autotuned);
    ``double_buffer`` swaps in the explicit ping-pong variant
    (single-block frames fall back — nothing to overlap)."""
    if interpret is None:
        interpret = default_interpret()
    H, W = img.shape
    cell = cfg.nms_window
    taps = filters.gaussian_taps(cfg.gaussian_sigma)
    pad = max(len(taps) // 2, 3)                      # blur radius vs ring
    bc = pick_block(H // cell, block_cells)
    bh, wc = bc * cell, W // cell
    nt = H // bh
    # slabs are (bh + 2p) rows by (W + 2p) columns, rounded up to whole
    # (8, 128) tiles; the rounding beyond the edge pad is never read
    rows = -(-(bh + 2 * pad) // 8) * 8
    cols = -(-(W + 2 * pad) // 128) * 128
    P = jnp.pad(img.astype(jnp.float32), pad, mode="edge")
    P = jnp.pad(P, ((0, rows - bh - 2 * pad), (0, cols - W - 2 * pad)))

    kern_kw = dict(taps=taps, H=H, W=W, bh=bh, pad=pad, cell=cell,
                   threshold=cfg.fast_threshold, arc_len=cfg.fast_arc_len)
    # cell maxima leave as (nt, bc, wc): the block's last two dims are
    # the array's, whatever bc is, so no (8, 128) tiling applies
    out_specs = [pl.BlockSpec((bh, W), lambda i: (i, 0))] + [
        pl.BlockSpec((None, bc, wc), lambda i: (i, 0, 0))] * 2
    out_shape = [jax.ShapeDtypeStruct((H, W), jnp.float32),
                 jax.ShapeDtypeStruct((nt, bc, wc), jnp.float32),
                 jax.ShapeDtypeStruct((nt, bc, wc), jnp.int32)]
    if double_buffer and nt >= 2:
        smooth, best, idx = pl.pallas_call(
            functools.partial(_fe_db_kernel, nt=nt, **kern_kw),
            grid=(nt,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((2, rows, cols),
                                       jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
            interpret=interpret,
            name="frontend_fused_cells",
        )(P)
    else:
        # overlapping slabs: element-offset row blocks
        smooth, best, idx = pl.pallas_call(
            functools.partial(_fe_kernel, **kern_kw),
            grid=(nt,),
            in_specs=[pl.BlockSpec((pl.Element(rows), pl.Element(cols)),
                                   lambda i: (i * bh, 0))],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            name="frontend_fused_cells",
        )(P)
    return (smooth, best.reshape(nt * bc, wc), idx.reshape(nt * bc, wc))


def _detect_describe(img: jax.Array, cfg, interpret: bool,
                     block_cells: int = 8, block_n: int = 128,
                     double_buffer: bool = False
                     ) -> Tuple[fast.Features, jax.Array, jax.Array]:
    """One image through kernels A + B: Features, desc (N,256) bool,
    packed (N,8) uint32. ``block_n`` sizes kernel B's corner tile."""
    smooth, best, idx = fe_cells(img, cfg, block_cells=block_cells,
                                 double_buffer=double_buffer,
                                 interpret=interpret)
    feats = fast.topk_cells(best, idx, cfg.max_features, cfg.nms_window)
    desc_u8, packed = _describe(smooth, feats.yx, interpret, block_n=block_n)
    return feats, desc_u8 != 0, packed


# --------------------------------------------------------------------------
# kernel B: orientation + rBRIEF + bit packing on the corner budget
# --------------------------------------------------------------------------

def _fc_kernel(img_ref, yx_ref, cdy_ref, cdx_ref, pairs_ref,
               desc_ref, packed_ref):
    img = img_ref[...]
    yx = yx_ref[...]
    # the FPGA's pattern ROMs arrive as operands (kernels can't capture
    # array constants); arithmetic is orb's, bit for bit
    ang = orb.orientation_t(img, yx, cdy_ref[...], cdx_ref[...])
    desc = orb.describe_t(img, yx, ang, pairs_ref[...])
    desc_ref[...] = desc.astype(jnp.uint8)
    packed_ref[...] = orb.pack_bits(desc)


def _describe(smooth: jax.Array, yx: jax.Array, interpret: bool,
              block_n: int = 128) -> Tuple[jax.Array, jax.Array]:
    H, W = smooth.shape
    n = yx.shape[0]
    bn = pick_block(n, block_n)
    cdy, cdx = orb.circle_offsets()
    nc = cdy.shape[0]
    return pl.pallas_call(
        _fc_kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((H, W), lambda i: (0, 0)),
                  pl.BlockSpec((bn, 2), lambda i: (i, 0)),
                  pl.BlockSpec((nc,), lambda i: (0,)),
                  pl.BlockSpec((nc,), lambda i: (0,)),
                  pl.BlockSpec((orb.N_BITS, 4), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bn, orb.N_BITS), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 8), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n, orb.N_BITS), jnp.uint8),
                   jax.ShapeDtypeStruct((n, 8), jnp.uint32)],
        interpret=interpret,
        name="frontend_fused_describe",
    )(smooth, yx, jnp.asarray(cdy), jnp.asarray(cdx),
      jnp.asarray(orb.PAIRS))


# --------------------------------------------------------------------------
# kernel C: SWAR-popcount epipolar match on packed descriptors
# --------------------------------------------------------------------------

_BIG_INT = 1 << 30      # python int: folds into the kernel (no capture)


def _popcount32(x):
    x = x - ((x >> 1) & 0x55555555)
    x = (x & 0x33333333) + ((x >> 2) & 0x33333333)
    x = (x + (x >> 4)) & 0x0F0F0F0F
    return (x * 0x01010101) >> 24


def _mo_kernel(pl_ref, yxl_ref, vl_ref, pr_ref, yxr_ref, vr_ref,
               idx_ref, best_ref, disp_ref, *, max_disparity, row_tol):
    a = pl_ref[...]                                   # (bn, 8) uint32
    b = pr_ref[...]                                   # (NR, 8)
    x = jnp.bitwise_xor(a[:, None, :], b[None, :, :])
    # per-word counts are <= 32: the int32 sum is exact (TPU kernels
    # cannot reduce unsigned integers)
    dist = jnp.sum(_popcount32(x.astype(jnp.uint32)).astype(jnp.int32),
                   axis=-1)
    yxl = yxl_ref[...]                                # (bn, 2)
    yxr = yxr_ref[...]                                # (2, NR): transposed
    rowdiff = jnp.abs(yxl[:, 0:1] - yxr[0:1, :])
    disp = yxl[:, 1:2] - yxr[1:2, :]
    ok = ((rowdiff <= row_tol) & (disp >= 0) & (disp <= max_disparity)
          & (vl_ref[...] > 0) & (vr_ref[...] > 0))
    dist = jnp.where(ok, dist, _BIG_INT)
    # first-occurrence argmin as two min-reductions (a TPU kernel's
    # argmin does not promise the first of tied lanes, and jnp.argmin
    # does); lane indices < 2^24 are exact in float32
    best = jnp.min(dist, axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    idx = jnp.min(jnp.where(dist == best, lane.astype(jnp.float32),
                            float(dist.shape[1])), axis=1,
                  keepdims=True).astype(jnp.int32)
    # the winners' values by masked reduction (a TPU kernel cannot
    # gather along lanes); exactly one lane per row is selected
    pick = lane == idx
    dval = jnp.sum(jnp.where(pick, disp.astype(jnp.float32), 0.0), axis=1,
                   keepdims=True)
    idx_ref[...] = idx
    best_ref[...] = best
    disp_ref[...] = dval


def match_packed(pk_l, yxl, vl, pk_r, yxr, vr, *, max_disparity: int,
                 hamming_budget: int, row_tol: int = 2,
                 block_n: int = 128,
                 interpret: Optional[bool] = None) -> stereo.StereoMatches:
    """Epipolar-constrained hamming match on packed (N,8) descriptors.
    Integer distances order identically to the float reference (hamming
    <= 256 is exact in fp32), so right_idx/valid match bitwise."""
    if interpret is None:
        interpret = default_interpret()
    NL, NR = pk_l.shape[0], pk_r.shape[0]
    bn = pick_block(NL, block_n)
    idx, best, dval = pl.pallas_call(
        functools.partial(_mo_kernel, max_disparity=max_disparity,
                          row_tol=row_tol),
        grid=(NL // bn,),
        in_specs=[pl.BlockSpec((bn, 8), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 2), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                  pl.BlockSpec((NR, 8), lambda i: (0, 0)),
                  pl.BlockSpec((2, NR), lambda i: (0, 0)),
                  pl.BlockSpec((1, NR), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((NL, 1), jnp.int32),
                   jax.ShapeDtypeStruct((NL, 1), jnp.int32),
                   jax.ShapeDtypeStruct((NL, 1), jnp.float32)],
        interpret=interpret,
        name="frontend_fused_match",
    )(pk_l, yxl, vl.astype(jnp.int32)[:, None],
      pk_r, yxr.T, vr.astype(jnp.int32)[None, :])
    return stereo.StereoMatches(
        right_idx=idx[:, 0],
        disparity=jnp.maximum(dval[:, 0], 0.0),
        valid=best[:, 0] <= hamming_budget)


# --------------------------------------------------------------------------
# composition: the registry's pallas path
# --------------------------------------------------------------------------

def fe_match(img_l: jax.Array, img_r: jax.Array, cfg, *,
             block_cells: int = 8, block_n: int = 128,
             double_buffer: bool = False,
             interpret: Optional[bool] = None):
    """Fused FE + MO for one stereo frame: returns (fl, fr, dl, matches),
    the same tuple as ``pipeline._fe_match_ref`` (DR refinement and LK
    tracking stay shared, outside the fusion boundary).

    ``block_cells``/``block_n``/``double_buffer`` are the autotuner's
    launch knobs (kernel A row-block in NMS cells, kernel B/C corner
    tile, explicit ping-pong staging of the padded frame) — every
    setting is numerics-exact, the defaults reproduce the untuned
    kernel bitwise."""
    if interpret is None:
        interpret = default_interpret()
    fl, dl, pk_l = _detect_describe(img_l.astype(jnp.float32), cfg,
                                    interpret, block_cells=block_cells,
                                    block_n=block_n,
                                    double_buffer=double_buffer)
    fr, _, pk_r = _detect_describe(img_r.astype(jnp.float32), cfg,
                                   interpret, block_cells=block_cells,
                                   block_n=block_n,
                                   double_buffer=double_buffer)
    m = match_packed(pk_l, fl.yx, fl.valid, pk_r, fr.yx, fr.valid,
                     max_disparity=cfg.stereo_max_disparity,
                     hamming_budget=cfg.stereo_hamming_budget,
                     block_n=block_n, interpret=interpret)
    return fl, fr, dl, m
