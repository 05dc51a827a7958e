"""Pallas TPU kernel: halo-resident, parity-folded multipole-to-local (M2L).

M2L is the second FMM hot spot (paper Eq 10, term ``c``): every box at every
level receives exactly 27 (p x p) transform-accumulates.  The old kernel
wrapper materialized a ``(nb, 40p)`` gathered ME tensor in HBM (40x the grid)
and computed all 40 candidate offsets with parity masks folded in at gather
time — ~1.5x excess flops plus 40x staging traffic.  This kernel does
neither:

  * the grid is relayouted once into **parent planes** — the 2x2 child
    parities stacked along the coefficient axis, ``(PR+2, PC+2, 4p)`` with a
    ±1 parent halo (= 2 child rows; see DESIGN.md §4).  Same bytes as the
    grid itself, no 40x staging tensor;
  * the Pallas grid tiles the parent grid into ``(BY, BX)`` blocks whose
    BlockSpecs read **overlapping halo windows** directly from the padded
    parent-plane grid (``pl.Element`` offsets in the TPU-legal form of
    ``kernels/halo.py``), so the halo never exists as a separate HBM
    buffer;
  * the parity-folded ``(8, 4p, 4p)`` block operator (scale-normalized,
    hence level-independent — DESIGN.md §3) is VMEM-resident across the
    whole launch; its structural zero blocks *are* the parity masks, so
    every box receives exactly its 27 valid interactions;
  * the LE accumulator lives in VMEM registers across the full 8-neighbor
    reduction: per tile, 8 complex matmuls ``(BY*BX, 4p) @ (4p, 4p)``
    (expanded to 4 real GEMMs each for the MXU), one HBM write at the end.

On real hardware pad 4p (68 for p=17) up to lane multiples; correctness is
independent of padding.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import equations as _eqs
from ..core import expansions as ex
from ..core.quadtree import PARENT_NEIGH8
from .halo import LANES, halo_tiling, round_up


def _dot(a, b):
    # full f32 on the MXU (a default-precision f32 dot is one bf16 pass)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _m2l_kernel(sr_ref, si_ref, wr_ref, wi_ref, or_ref, oi_ref,
                *, BY: int, BX: int, p4: int):
    tr = sr_ref[...]            # (BY+2, >= BX+2, 4p) halo window, real
    ti = si_ref[...]
    wr = wr_ref[...]            # (8, 4p, 4p) folded operator, VMEM-resident
    wi = wi_ref[...]
    accr = jnp.zeros((BY * BX, p4), jnp.float32)
    acci = jnp.zeros((BY * BX, p4), jnp.float32)
    for d, (Dx, Dy) in enumerate(PARENT_NEIGH8):
        ar = tr[1 + Dy:1 + Dy + BY, 1 + Dx:1 + Dx + BX, :].reshape(BY * BX, p4)
        ai = ti[1 + Dy:1 + Dy + BY, 1 + Dx:1 + Dx + BX, :].reshape(BY * BX, p4)
        # complex GEMM via 4 real GEMMs (MXU); accumulator stays in VMEM
        accr = accr + _dot(ar, wr[d]) - _dot(ai, wi[d])
        acci = acci + _dot(ar, wi[d]) + _dot(ai, wr[d])
    or_ref[...] = accr.reshape(BY, BX, p4)
    oi_ref[...] = acci.reshape(BY, BX, p4)


@functools.partial(jax.jit, static_argnames=("level", "p", "row0", "halo",
                                             "col0", "col_halo", "block",
                                             "interpret", "lane_pad", "eq"))
def m2l_pallas_slab(me_halo: jnp.ndarray, level: int, p: int, row0: int = 0,
                    halo: int = ex.M2L_HALO, col0: int = 0, col_halo: int = 0,
                    block: tuple[int, int] = (8, 8),
                    interpret: bool | None = None,
                    lane_pad: bool = False, eq=None) -> jnp.ndarray:
    """Parity-folded M2L over a halo'd slab/tile — same contract as
    ``expansions.m2l_folded``: ``me_halo`` is (rows + 2*halo,
    cols + 2*col_halo, p) with ghost data attached, ``row0``/``col0``
    anchor the global parity (``col_halo=0`` means full-width columns,
    zero-padded internally).  Returns the (rows, cols, p) LE slab.

    ``lane_pad=True`` pads the stacked coefficient axis ``4p`` up to a lane
    multiple of 128 (real-TPU layout; DESIGN.md §5) — the folded operator is
    zero-padded to match, so the extra lanes contribute exact zeros and the
    numerics are unchanged; the accumulator is sliced back to ``4p``.

    ``eq`` selects the equation spec supplying the folded block operator
    and dimension scalar (core/equations.py; vortex default) — the kernel
    body is equation-independent: one contraction, any registered operator.
    ``interpret=None`` runs the Pallas interpreter only off-TPU
    (``kernels.ops._interpret``).
    """
    if interpret is None:
        from .ops import _interpret
        interpret = _interpret()
    eq = _eqs.get_equation(eq)
    rows = me_halo.shape[0] - 2 * halo
    cols = me_halo.shape[1] - 2 * col_halo
    p4 = 4 * p
    p4l = round_up(p4, LANES) if lane_pad else p4
    stack, (PR, shift), (PC, cshift) = ex.m2l_slab_stack(me_halo, p, row0,
                                                         halo, col0, col_halo)

    t = halo_tiling(PR, PC, block)
    pad = ((0, t.rows_p - PR), (0, t.halo_cols - (PC + 2)), (0, p4l - p4))
    sr = jnp.pad(stack.real.astype(jnp.float32), pad)
    si = jnp.pad(stack.imag.astype(jnp.float32), pad)

    W = eq.m2l_folded(p, level)
    wpad = ((0, 0), (0, p4l - p4), (0, p4l - p4))
    wr = jnp.asarray(np.pad(W.real, wpad), dtype=jnp.float32)
    wi = jnp.asarray(np.pad(W.imag, wpad), dtype=jnp.float32)

    halo_spec = t.window_spec(p4l)
    op_spec = pl.BlockSpec((8, p4l, p4l), lambda i, j: (0, 0, 0))
    out_spec = t.block_spec(p4l)
    out_shape = [jax.ShapeDtypeStruct((t.rows_p, t.cols_p, p4l),
                                      jnp.float32)] * 2

    br, bi = pl.pallas_call(
        functools.partial(_m2l_kernel, BY=t.BY, BX=t.BX, p4=p4l),
        grid=t.grid,
        in_specs=[halo_spec, halo_spec, op_spec, op_spec],
        out_specs=[out_spec, out_spec],
        out_shape=out_shape,
        interpret=interpret,
        name="m2l_kernel",
    )(sr, si, wr, wi)

    acc = (br[:PR, :PC, :p4] + 1j * bi[:PR, :PC, :p4]).astype(me_halo.dtype)
    le = ex.from_parent_planes(acc, p)                   # (2PR, 2PC, p)
    le = jax.lax.slice_in_dim(le, shift, shift + rows, axis=0)
    le = jax.lax.slice_in_dim(le, cshift, cshift + cols, axis=1)
    return le * eq.m2l_scale(level)


def m2l_pallas(me: jnp.ndarray, level: int, p: int,
               block: tuple[int, int] = (8, 8),
               interpret: bool | None = None, lane_pad: bool = False,
               eq=None) -> jnp.ndarray:
    """Fused M2L over a full (ny, nx, p) complex ME grid -> (ny, nx, p) LE."""
    me_halo = jnp.pad(me, ((ex.M2L_HALO, ex.M2L_HALO), (0, 0), (0, 0)))
    return m2l_pallas_slab(me_halo, level, p, row0=0, halo=ex.M2L_HALO,
                           block=block, interpret=interpret,
                           lane_pad=lane_pad, eq=eq)
