"""Pallas TPU kernel: halo-resident near-field direct interactions (P2P).

The P2P stage dominates FMM runtime (paper Eq 10, the ``d N B / P`` term),
so it gets a hand-written kernel.  The old wrapper gathered each leaf box's
3x3 neighborhood into a dense ``(boxes, 9s)`` source slab — 9x the particle
data staged through HBM before the kernel even started.  This version stages
nothing:

  * the leaf grid is padded by ±1 box (zeros at the domain edge; under
    ``shard_map`` the ghost rows have already been exchanged by the caller)
    and the Pallas grid tiles it into ``(BY, BX)`` blocks whose BlockSpecs
    read **overlapping halo windows** directly from the padded grid
    (``pl.Element`` offsets in the TPU-legal form of ``kernels/halo.py``);
  * the kernel slices the 9 neighbor offsets out of its VMEM tile and
    evaluates the pair interaction on the VPU, keeping the accumulators in
    VMEM across the whole 9-offset reduction — one HBM write per output
    tile, ``(BB, st, s)`` pair temporaries instead of the old
    ``(BB, s, 9s)``;
  * the pair interaction itself comes from the equation spec
    (``core/equations.py: p2p_terms`` — explicit real/imag arithmetic, the
    MXU/VPU have no complex type).  The kernel body is equation-independent
    and emits ``eq.nout`` complex channels; passive source != target
    evaluation (probe grids, tracers) runs through the SAME kernel with the
    targets as a separate ``(BY, BX, st)`` block, while the default
    source == target mode slices its targets out of the already-loaded
    halo tile (no extra input streams — the pre-registry data path).

Block sizing: the wrapper shrinks ``(BY, BX)`` until one offset's
(BY*BX, st, s) f32 pair tensor fits ``PAIR_BYTES``, and the lane dimension
(s) should be a multiple of 128 on real hardware (pad ``s`` accordingly;
correctness does not depend on it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import equations as _eqs
from .halo import LANES, SUBLANES, halo_tiling, round_up

P2P_HALO = 1    # ghost rows/cols of particle data needed by a slab
PAIR_BYTES = 1 << 19   # bound on one offset's (BY*BX, st, s) f32 pair tensor


def pair_block(block: tuple[int, int], st: int, s: int) -> tuple[int, int]:
    """Shrink ``block`` so one offset's pair tensor fits ``PAIR_BYTES``:
    ``BX`` down to one sublane tile first, then ``BY``."""
    boxes = max(PAIR_BYTES // (4 * st * s), 1)
    bx = min(block[1], max(boxes, SUBLANES))
    by = max(min(block[0], boxes // round_up(bx, SUBLANES)), 1)
    return by, bx


def _p2p_kernel(*refs, eq, sigma: float | None, BY: int, BX: int,
                st: int, s: int, tgt_from_src: bool):
    if tgt_from_src:
        zr_ref, zi_ref, qr_ref, qi_ref, m_ref, *out_refs = refs
    else:
        txr_ref, txi_ref, zr_ref, zi_ref, qr_ref, qi_ref, m_ref, \
            *out_refs = refs
    BB = BY * BX
    if tgt_from_src:
        # source == target mode: the targets ARE the halo window's interior
        # — read them out of the source windows instead of paying two extra
        # HBM->VMEM input streams (st == s here)
        tx = zr_ref[1:1 + BY, 1:1 + BX, :].reshape(BB, st)
        ty = zi_ref[1:1 + BY, 1:1 + BX, :].reshape(BB, st)
    else:
        tx = txr_ref[...].reshape(BB, st)        # (BY, BX, st) target block
        ty = txi_ref[...].reshape(BB, st)
    nout = len(out_refs) // 2

    def offset_row(r, accs):
        # the three P2P_OFFSETS of source row dy = r - 1, in their order;
        # a loop over rows keeps the kernel body (and its compile) 3x
        # smaller than unrolling all nine offsets
        accs = list(accs)
        rows = pl.ds(r, BY)
        for dx in range(3):
            def src(ref):
                return ref[rows, dx:dx + BX, :].reshape(BB, s)
            sx, sy, sqr, sqi, sm = map(src, (zr_ref, zi_ref, qr_ref, qi_ref,
                                             m_ref))
            ddx = tx[:, :, None] - sx[:, None, :]        # (BB, st, s)
            ddy = ty[:, :, None] - sy[:, None, :]
            r2 = ddx * ddx + ddy * ddy
            valid = (sm[:, None, :] > 0) & (r2 > 0.0)
            moll = None
            if sigma is not None:
                moll = 1.0 - jnp.exp(-r2 / (2.0 * sigma * sigma))
            terms = eq.p2p_terms(ddx, ddy, r2, valid, sqr[:, None, :],
                                 sqi[:, None, :], moll)
            for c, (tre, tim) in enumerate(terms):
                accs[2 * c] = accs[2 * c] + tre.sum(axis=-1)
                accs[2 * c + 1] = accs[2 * c + 1] + tim.sum(axis=-1)
        return tuple(accs)

    accs = jax.lax.fori_loop(
        0, 3, offset_row,
        tuple(jnp.zeros((BB, st), jnp.float32) for _ in range(2 * nout)))
    for i, ref in enumerate(out_refs):
        ref[...] = accs[i].reshape(BY, BX, st)


@functools.partial(jax.jit, static_argnames=("sigma", "block", "interpret",
                                             "lane_pad", "eq"))
def p2p_pallas_slab(z_halo, q_halo, mask_halo, sigma=None,
                    block: tuple[int, int] = (8, 8),
                    interpret: bool | None = None,
                    lane_pad: bool = False, z_tgt=None, eq=None):
    """P2P over a slab with ±1 ghost rows/cols already attached.

    z_halo/q_halo: complex (rows+2, cols+2, s); mask_halo: bool.  Ghosts are
    zeros at domain edges or exchanged halos under ``shard_map``.  Returns
    the interior (rows, cols, st) complex output per slot — with a trailing
    ``eq.nout`` channel axis for multi-output equations.  ``z_tgt``
    (rows, cols, st) switches to passive-target evaluation (targets carry
    no halo; masked-off target slots yield don't-care values the caller
    masks); None evaluates at the sources themselves.

    ``lane_pad=True`` pads the slot axes up to lane multiples of 128
    (real-TPU layout; DESIGN.md §5) — padded source slots carry ``mask=0``
    so they are structurally excluded and the numerics are unchanged; the
    output is sliced back to ``st``.  ``interpret=None`` runs the Pallas
    interpreter only off-TPU (``kernels.ops._interpret``).
    """
    if interpret is None:
        from .ops import _interpret
        interpret = _interpret()
    eq = _eqs.get_equation(eq)
    rows, cols, s = (z_halo.shape[0] - 2, z_halo.shape[1] - 2,
                     z_halo.shape[2])
    tgt_from_src = z_tgt is None
    st = s if tgt_from_src else z_tgt.shape[2]
    sl = round_up(s, LANES) if lane_pad else s
    stl = sl if tgt_from_src else (round_up(st, LANES) if lane_pad else st)
    t = halo_tiling(rows, cols, pair_block(block, stl, sl))

    def prep(x, lanes, cols_to):
        return jnp.pad(x.astype(jnp.float32),
                       ((0, t.rows_p - rows), (0, cols_to - x.shape[1]),
                        (0, lanes - x.shape[2])))

    # halo'd sources (rows+2 -> rows_p+2 rows, halo_cols columns)
    zr, zi, qr, qi, m = (prep(x, sl, t.halo_cols) for x in (
        z_halo.real, z_halo.imag, q_halo.real, q_halo.imag, mask_halo))

    halo_spec = t.window_spec(sl)
    tgt_spec = t.block_spec(stl)
    out_shape = [jax.ShapeDtypeStruct((t.rows_p, t.cols_p, stl),
                                      jnp.float32)] * (2 * eq.nout)

    if tgt_from_src:
        inputs = (zr, zi, qr, qi, m)
        in_specs = [halo_spec] * 5
    else:
        # bare targets (rows -> rows_p, cols -> cols_p)
        txr = prep(z_tgt.real, stl, t.cols_p)
        txi = prep(z_tgt.imag, stl, t.cols_p)
        inputs = (txr, txi, zr, zi, qr, qi, m)
        in_specs = [tgt_spec, tgt_spec] + [halo_spec] * 5

    outs = pl.pallas_call(
        functools.partial(_p2p_kernel, eq=eq, sigma=sigma, BY=t.BY, BX=t.BX,
                          st=stl, s=sl, tgt_from_src=tgt_from_src),
        grid=t.grid,
        in_specs=in_specs,
        out_specs=[tgt_spec] * (2 * eq.nout),
        out_shape=out_shape,
        interpret=interpret,
        name="p2p_kernel",
    )(*inputs)

    chans = [(outs[2 * c][:rows, :cols, :st] +
              1j * outs[2 * c + 1][:rows, :cols, :st]).astype(z_halo.dtype)
             for c in range(eq.nout)]
    return chans[0] if eq.nout == 1 else jnp.stack(chans, axis=-1)


def p2p_pallas(z, q, mask, sigma=None, block: tuple[int, int] = (8, 8),
               interpret: bool | None = None, lane_pad: bool = False,
               eq=None):
    """P2P over a (ny, nx, s) dense leaf grid.  Returns complex output per
    slot (trailing channel axis for multi-output equations).

    z, q: complex64; mask: bool.  ``interpret=None`` runs the kernel body
    in the Pallas interpreter off-TPU and compiles it on TPU.
    """
    pad = ((P2P_HALO, P2P_HALO), (P2P_HALO, P2P_HALO), (0, 0))
    return p2p_pallas_slab(jnp.pad(z, pad), jnp.pad(q, pad),
                           jnp.pad(mask, pad), sigma=sigma, block=block,
                           interpret=interpret, lane_pad=lane_pad, eq=eq)
