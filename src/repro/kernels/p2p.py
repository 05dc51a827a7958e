"""Pallas TPU kernel: halo-resident near-field direct interactions (P2P).

The P2P stage dominates FMM runtime (paper Eq 10, the ``d N B / P`` term),
so it gets a hand-written kernel.  The old wrapper gathered each leaf box's
3x3 neighborhood into a dense ``(boxes, 9s)`` source slab — 9x the particle
data staged through HBM before the kernel even started.  This version
stages a relayout and three column shifts:

  * **boxes on the lanes.**  Each particle field is an f32 plane array
    ``(s, rows, cols)``: the leaf slots are a leading axis, the box rows lie
    on the 8 sublanes and the box columns on the 128 lanes (the longer of
    the launch's two box axes, so a thin rim strip runs along the lanes).
    A pair tensor is ``(tc, BY, BX)`` — ``tc`` target slots of every box in
    the block against ONE source slot of the neighbour box — so each box
    and neighbour offset costs exactly ``st * s`` pair evaluations, and no
    slot axis is ever padded to 128.
  * the leaf grid is padded by ±1 box (zeros at the domain edge; under
    ``shard_map`` the ghost rows have already been exchanged by the caller)
    and each source field is staged as three copies shifted by 0, 1 and 2
    columns, so every neighbour offset's columns are one lane-aligned
    block.  The Pallas grid tiles the grid into ``(BY, BX)`` blocks whose
    BlockSpecs read **overlapping row windows** of ``BY + 8`` rows (or the
    whole extent) directly from those planes (``pl.Element`` offsets in the
    TPU-legal form of ``kernels/halo.py``);
  * the kernel takes the 9 neighbour offsets as static ``[dx, j, dy:dy+BY]``
    slices of its VMEM windows and loops over the source slots ``j``,
    keeping the ``(tc, BY, BX)`` accumulators in registers across the whole
    9-offset reduction — one HBM write per output block;
  * the pair interaction itself comes from the equation spec
    (``core/equations.py: p2p_terms`` — explicit real/imag arithmetic, the
    MXU/VPU have no complex type).  The kernel body is equation-independent
    and emits ``eq.nout`` complex channels.  Targets always arrive as their
    own ``(tc, BY, BX)`` blocks: the sources' own interior in the default
    source == target mode, a separate point set in passive-target mode
    (probe grids, tracers).

Block sizing: ``BY`` is a multiple of 8 and ``BX`` of 128 (``lane_pad``; 8
in the interpreter), or the whole extent.  ``pair_block`` shrinks the
block until one ``(1, BY, BX)`` pair tensor fits ``PAIR_BYTES``, and the
target slots are split into grid chunks of ``tc`` so that one
``(tc, BY, BX)`` tensor does.  The source slots are split into grid chunks
too, so that the fifteen source windows fit ``WINDOW_BYTES`` of VMEM
whatever the slot count; their partial sums accumulate in the resident
output block.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import equations as _eqs
from .halo import LANES, SUBLANES, halo_tiling, round_up

P2P_HALO = 1    # ghost rows/cols of particle data needed by a slab
P2P_BLOCK = (16, 128)    # default (BY, BX) box block of one launch
PAIR_BYTES = 1 << 15     # bound on one (tc, BY, BX) f32 pair tensor
WINDOW_BYTES = 1 << 22   # bound on one step's (3, sk, BY + 8, BX) windows


def _chunk(n: int, cap: int) -> int:
    """Size of the fewest equal chunks of at most ``cap`` that cover ``n``."""
    return -(-n // -(-n // max(cap, 1)))


def pair_block(block: tuple[int, int],
               lanes: int = LANES) -> tuple[int, int]:
    """Shrink ``block`` (rows first, down to one sublane tile, then columns,
    down to ``lanes``) until one ``(1, BY, BX)`` f32 pair tensor fits
    ``PAIR_BYTES``; both come out whole tiles."""
    cap = PAIR_BYTES // 4
    bx = max(lanes, min(round_up(block[1], lanes),
                        cap // SUBLANES // lanes * lanes))
    by = max(SUBLANES, min(round_up(block[0], SUBLANES),
                           cap // bx // SUBLANES * SUBLANES))
    return by, bx


def _p2p_kernel(txr_ref, txi_ref, *refs, eq, sigma: float | None, BY: int,
                sk: int):
    src_refs, out_refs = refs[:5], refs[5:]
    tx = txr_ref[...]              # (tc, BY, BX) target block
    ty = txi_ref[...]

    def pairs(dy, dx):
        # source box (y + dy - 1, x + dx - 1), one of the 9 P2P_OFFSETS: the
        # dx-shifted copy of each (3, sk, BY + 8, BX) window, rows dy..
        def slot(j, accs):
            sx, sy, sqr, sqi, sm = (ref[dx, j, dy:dy + BY, :][None]
                                    for ref in src_refs)
            ddx = tx - sx                                # (tc, BY, BX)
            ddy = ty - sy
            r2 = ddx * ddx + ddy * ddy
            valid = (sm > 0) & (r2 > 0.0)
            moll = None
            if sigma is not None:
                moll = 1.0 - jnp.exp(-r2 / (2.0 * sigma * sigma))
            terms = eq.p2p_terms(ddx, ddy, r2, valid, sqr, sqi, moll)
            return tuple(a + t for a, t in
                         zip(accs, (x for pair in terms for x in pair)))
        return slot

    accs = tuple(jnp.zeros(tx.shape, jnp.float32) for _ in out_refs)
    for dy in range(3):
        for dx in range(3):
            accs = jax.lax.fori_loop(0, sk, pairs(dy, dx), accs)

    @pl.when(pl.program_id(3) == 0)
    def _():
        for ref in out_refs:
            ref[...] = jnp.zeros(ref.shape, jnp.float32)

    for ref, acc in zip(out_refs, accs):
        ref[...] += acc


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

    ``lane_pad=True`` pads the lane axis — the box columns — to a multiple
    of 128 and takes ``BX`` in whole lane tiles (real-TPU layout; DESIGN.md
    §5); ``False`` rounds ``BX`` to 8 columns only, which the interpreter
    accepts.  Padded boxes carry ``mask=0`` so they are structurally
    excluded and the numerics are unchanged; the output is sliced back.
    ``interpret=None`` runs the Pallas interpreter only off-TPU
    (``kernels.ops._interpret``).
    """
    if interpret is None:
        from .ops import _interpret
        interpret = _interpret()
    eq = _eqs.get_equation(eq)
    s = z_halo.shape[2]
    if z_tgt is None:
        z_tgt = z_halo[1:-1, 1:-1]
    st = z_tgt.shape[2]
    # slots lead; the longer box axis goes on the lanes
    swap = z_tgt.shape[0] > z_tgt.shape[1]
    perm = (2, 1, 0) if swap else (2, 0, 1)
    rows, cols = (z_tgt.shape[1], z_tgt.shape[0]) if swap else z_tgt.shape[:2]
    lanes = LANES if lane_pad else SUBLANES
    t = halo_tiling(rows, cols, pair_block(block, lanes),
                    row_align=SUBLANES, col_align=lanes)
    tc = _chunk(st, PAIR_BYTES // (4 * t.BY * t.BX))
    sk = _chunk(s, WINDOW_BYTES // (5 * 3 * 4 * t.win_rows * t.BX))
    st_p, s_p = round_up(st, tc), round_up(s, sk)

    def planes(x, depth, rows_to, cols_to):
        x = jnp.transpose(x.astype(jnp.float32), perm)
        return jnp.pad(x, ((0, depth - x.shape[0]), (0, rows_to - x.shape[1]),
                           (0, cols_to - x.shape[2])))

    tgt = [planes(x, st_p, t.rows_p, t.cols_p) for x in (z_tgt.real,
                                                         z_tgt.imag)]
    # each source field as three copies shifted by dx = 0, 1, 2 columns, so
    # that every offset's columns are a lane-aligned block
    src = [planes(x, s_p, t.halo_rows, t.cols_p + 2) for x in (
        z_halo.real, z_halo.imag, q_halo.real, q_halo.imag, mask_halo)]
    src = [jnp.stack([x[:, :, dx:dx + t.cols_p] for dx in range(3)])
           for x in src]

    BY, BX = t.BY, t.BX
    grid = (*t.grid, st_p // tc, s_p // sk)
    tgt_spec = pl.BlockSpec((tc, BY, BX), lambda i, j, c, k: (c, i, j))
    win_spec = pl.BlockSpec(
        (pl.Element(3), pl.Element(sk), pl.Element(t.win_rows),
         pl.Element(BX)),
        lambda i, j, c, k: (0, k * sk, i * BY, j * BX))
    out_shape = [jax.ShapeDtypeStruct((st_p, t.rows_p, t.cols_p),
                                      jnp.float32)] * (2 * eq.nout)

    outs = pl.pallas_call(
        functools.partial(_p2p_kernel, eq=eq, sigma=sigma, BY=BY, sk=sk),
        grid=grid,
        in_specs=[tgt_spec] * 2 + [win_spec] * 5,
        out_specs=[tgt_spec] * (2 * eq.nout),
        out_shape=out_shape,
        interpret=interpret,
        name="p2p_kernel",
    )(*tgt, *src)

    back = (2, 1, 0) if swap else (1, 2, 0)
    chans = [jnp.transpose(outs[2 * c][:st, :rows, :cols] +
                           1j * outs[2 * c + 1][:st, :rows, :cols], back)
             .astype(z_halo.dtype) for c in range(eq.nout)]
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


# ---------------------------------------------------------------------------
# Chunk-pair mode: the spill layout's near field (core/quadtree.Spill).
#
# Each run is a target chunk of ``s`` slots against up to ``R`` source
# chunks of ``s`` slots.  The caller gathers each run as one row of f32:
# targets ``(U, 2s)`` (x, then y) and sources ``(U, R*5s)`` (for each
# source chunk x, y, Re q, Im q, mask), with ``(U,)`` the number of live
# source chunks of each run.  The kernel takes ``lanes`` rows a block and
# transposes them in VMEM, so that the runs lie on the 128 lanes as boxes
# do above (an XLA transpose would cost a pass over the gathered rows).
# It then runs over the R source chunks and, within one, over its s
# slots, so that a run costs exactly ``R * s * s`` pair evaluations,
# accumulated in f32 registers as the dense kernel's are.
# ---------------------------------------------------------------------------

CHUNK_LANES = 512        # runs per block of the chunk-pair kernel


def _p2p_chunk_kernel(tgt_ref, src_ref, len_ref, out_ref, *, eq,
                      sigma: float | None, R: int, s: int):
    t = tgt_ref[...].T                 # (2s, BL) target slots of each run
    tx, ty = t[:s], t[s:]
    planes = src_ref[...].T            # (R*5s, BL)
    live = len_ref[...]                # (1, BL) live source chunks

    def chunk(r, accs):
        on = live > r
        for j in range(s):
            sx, sy, sqr, sqi, sm = (
                planes[(5 * r + f) * s + j:(5 * r + f) * s + j + 1]
                for f in range(5))
            ddx = tx - sx
            ddy = ty - sy
            r2 = ddx * ddx + ddy * ddy
            valid = (sm > 0) & on & (r2 > 0.0)
            moll = None
            if sigma is not None:
                moll = 1.0 - jnp.exp(-r2 / (2.0 * sigma * sigma))
            terms = eq.p2p_terms(ddx, ddy, r2, valid, sqr, sqi, moll)
            accs = tuple(a + t for a, t in
                         zip(accs, (x for pair in terms for x in pair)))
        return accs

    accs = tuple(jnp.zeros(tx.shape, jnp.float32)
                 for _ in range(out_ref.shape[0]))
    for r in range(R):
        accs = chunk(r, accs)
    for c, acc in enumerate(accs):
        out_ref[c] = acc


@functools.partial(jax.jit, static_argnames=("sigma", "lanes", "interpret",
                                             "eq"))
def p2p_chunk_pallas(tgt, src, run_len, sigma=None, lanes: int = CHUNK_LANES,
                     interpret: bool | None = None, eq=None):
    """P2P of chunk runs given as rows: ``tgt`` (U, 2s) target positions,
    ``src`` (U, R*5s) source positions, charges and occupancy, ``run_len``
    (U,) the live source chunks of each run; run ``u``'s targets meet the
    ``run_len[u] * s`` sources of its live chunks.  Returns
    (2 * eq.nout, s, U) f32: each channel's real and imaginary parts.

    Runs lie on the lanes, ``lanes`` of them a block (a multiple of 128; a
    launch with fewer runs takes them all in one block; the last block
    may be partial, its missing runs neither read nor written).  A
    particle meets itself at ``r2 = 0`` and is left out, as in the dense
    kernel.
    """
    if interpret is None:
        from .ops import _interpret
        interpret = _interpret()
    eq = _eqs.get_equation(eq)
    U, two_s = tgt.shape
    s = two_s // 2
    R = src.shape[1] // (5 * s)
    BL = lanes if U > lanes else U
    return pl.pallas_call(
        functools.partial(_p2p_chunk_kernel, eq=eq, sigma=sigma, R=R, s=s),
        grid=(pl.cdiv(U, BL),),
        in_specs=[pl.BlockSpec((BL, 2 * s), lambda i: (i, 0)),
                  pl.BlockSpec((BL, 5 * R * s), lambda i: (i, 0)),
                  pl.BlockSpec((1, BL), lambda i: (0, i))],
        out_specs=pl.BlockSpec((2 * eq.nout, s, BL), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((2 * eq.nout, s, U), jnp.float32),
        interpret=interpret,
        name="p2p_chunk_kernel",
    )(tgt, src, run_len.reshape(1, U).astype(jnp.int32))
