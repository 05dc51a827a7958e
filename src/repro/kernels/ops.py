"""jit'd public wrappers for the Pallas kernels.

Every kernel entry point takes ``interpret=None``, which ``_interpret``
resolves: the Pallas interpreter executes the kernel body off-TPU (CPU
validation), and on a TPU the compiled kernels always run.

The M2L/P2P wrappers come in two forms with one kernel behind both: the
grid form (serial driver — zero ghosts attached here) and the slab form
(sharded driver — ghosts already exchanged by the caller).  See DESIGN.md
§4/§5.

Plan-aware block autotuning (DESIGN.md §5/§9; Holm et al., arXiv:1311.1006):
for M2L, ``block=None`` resolves the ``(BY, BX)`` launch tiling from a
small static table keyed by the launch-shape class the execution plan
implies — the monolithic/interior tile, or one of the thin rim strips of
the overlapped driver.  P2P takes its own block (``p2p.P2P_BLOCK``; the
kernel lays the longer box axis of any launch on the lanes).  Block shape
is a pure perf knob (equivalent outputs, pinned by tests), so either can be
retuned per backend without touching numerics.  Lane padding
(``lane_pad=None`` -> pad on real TPU only) pads the kernels' lane axes —
the box columns for P2P, ``4p`` for M2L — to multiples of 128 inside the
wrappers; padded lanes are structural zeros, so this too is numerics-free.

Under the substep pipeline (DESIGN.md §12) the rim-strip launches of the
overlapped driver may execute while a second exchange buffer is in
flight (next substep's packed P2P halo, or the cut-level gather).  The
kernels are oblivious to this: launch shapes, block tables, and operand
buffers are unchanged — the in-flight buffer is a *different* array the
consumer reads later, never an alias of a kernel operand, so no kernel
ever races a collective.
"""
from __future__ import annotations

import jax

from . import flash_attn as _fa
from . import m2l as _m2l
from . import p2p as _p2p
from ..core import expansions as _ex


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Plan-aware block autotuning
# ---------------------------------------------------------------------------

# Static M2L (BY, BX) per launch-shape class.  Classes map onto what an
# execution plan actually launches: full interior/monolithic tiles, wide
# row-slab tiles, and the thin rim strips of the overlapped driver (a few
# rows/cols spanning the whole tile edge).  Values are clipped to the
# launch extents; the kernel rounds BX up to whole sublanes (8) and pads
# non-dividing extents up to a block multiple (kernels/halo.py), so any
# entry is legal for any shape.  Every BX here is a multiple of 8: a
# multi-block halo window then reads BX + 8 columns for the BX + 2 it uses.
BLOCK_TABLE: dict[str, tuple[int, int]] = {
    "rim_row": (2, 32),     # thin row strips: keep the whole strip in one
    "rim_col": (32, 8),     # pass along its long axis
    "small": (4, 8),        # tiles smaller than one default block
    "wide": (4, 64),        # row-slab tiles much wider than tall
    "tile": (4, 32),        # default interior launch: 128-row M2L GEMMs
}


def _shape_class(rows: int, cols: int) -> str:
    if rows <= 4 and cols > 4 * rows:
        return "rim_row"
    if cols <= 4 and rows > 4 * cols:
        return "rim_col"
    if rows <= 4 and cols <= 4:
        return "small"
    if cols >= 4 * rows:
        return "wide"
    return "tile"


def autotune_block(rows: int, cols: int) -> tuple[int, int]:
    """Pick ``(BY, BX)`` for a static (rows, cols) launch from BLOCK_TABLE.

    Clipped to the launch extents so a block never exceeds the grid it
    tiles.  Pure perf knob — every choice is bit-equivalent (DESIGN.md §5).
    """
    by, bx = BLOCK_TABLE[_shape_class(rows, cols)]
    return max(min(by, rows), 1), max(min(bx, cols), 1)


def _resolve(block, rows: int, cols: int, lane_pad):
    if block is None:
        block = autotune_block(rows, cols)
    if lane_pad is None:
        lane_pad = not _interpret()
    return block, lane_pad


def p2p_apply_slab(z_halo, q_halo, mask_halo, sigma,
                   block: tuple[int, int] | None = None,
                   lane_pad: bool | None = None, z_tgt=None, eq=None):
    """P2P over a slab with ±1 ghost rows/cols attached (sharded driver).

    ``block=None`` takes ``p2p.P2P_BLOCK``; ``lane_pad=None`` pads the box
    columns (the kernel's lane axis) to a multiple of 128 on real TPU.
    ``z_tgt`` selects passive-target evaluation and ``eq`` the equation
    spec supplying the pair interaction (vortex default).
    """
    if lane_pad is None:
        lane_pad = not _interpret()
    if block is None:
        block = _p2p.P2P_BLOCK
    return _p2p.p2p_pallas_slab(z_halo, q_halo, mask_halo, sigma=sigma,
                                block=block, interpret=_interpret(),
                                lane_pad=lane_pad, z_tgt=z_tgt, eq=eq)


def p2p_apply_chunks(tgt, src, run_len, sigma, eq=None):
    """P2P of the spill layout's chunk runs (``p2p.p2p_chunk_pallas``)."""
    return _p2p.p2p_chunk_pallas(tgt, src, run_len, sigma=sigma,
                                 interpret=_interpret(), eq=eq)


def m2l_apply(me, level: int, p: int, block: tuple[int, int] | None = None,
              lane_pad: bool | None = None, eq=None):
    """Parity-folded M2L for one level's full (ny, nx, p) ME grid."""
    block, lane_pad = _resolve(block, me.shape[0] // 2, me.shape[1] // 2,
                               lane_pad)
    return _m2l.m2l_pallas(me, level, p, block=block, interpret=_interpret(),
                           lane_pad=lane_pad, eq=eq)


def m2l_apply_slab(me_halo, level: int, p: int, row0: int = 0,
                   halo: int = _ex.M2L_HALO, col0: int = 0, col_halo: int = 0,
                   block: tuple[int, int] | None = None,
                   lane_pad: bool | None = None, eq=None):
    """Parity-folded M2L over a halo'd row slab or 2-D tile (sharded
    driver); ``col_halo>0`` means column ghosts are attached too.

    ``block=None`` autotunes ``(BY, BX)`` from the parent-plane launch
    shape (the tile/rim geometry the plan implies); ``lane_pad=None`` pads
    ``4p`` to a lane multiple of 128 on real TPU.  ``eq`` selects the
    equation spec supplying the folded operator (vortex default).
    """
    if block is None or lane_pad is None:
        rows = me_halo.shape[0] - 2 * halo
        _, PR, _ = _ex.m2l_slab_geometry(rows, row0, halo)
        if col_halo == 0:
            PC = me_halo.shape[1] // 2
        else:
            _, PC, _ = _ex.m2l_slab_geometry(me_halo.shape[1] - 2 * col_halo,
                                             col0, col_halo)
        block, lane_pad = _resolve(block, PR, PC, lane_pad)
    return _m2l.m2l_pallas_slab(me_halo, level, p, row0=row0, halo=halo,
                                col0=col0, col_halo=col_halo,
                                block=block, interpret=_interpret(),
                                lane_pad=lane_pad, eq=eq)


def flash_attention(q, k, v, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """Blockwise attention; q (B,H,T,d), k/v (B,Hkv,S,d)."""
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_interpret())
