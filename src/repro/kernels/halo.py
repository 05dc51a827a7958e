"""Overlapping halo windows in the form the TPU compiler accepts.

Both FMM kernels tile a ``(rows, cols)`` box grid into ``(BY, BX)`` blocks
and read each block's ±1-box neighbourhood as ONE overlapping window of the
halo'd array, at element offsets, so the halo never exists as a separate
HBM buffer.  Mosaic constrains such windows:

  * every block axis is ``pl.Element`` or none is;
  * the last two axes are tiled (8 sublanes, 128 lanes): a window's extent
    on the sublane axis must be a multiple of 8 or the array's whole
    extent, and on the lane axis a multiple of 128 or whole.

So a tiled box axis takes blocks that are whole tiles of it, and a grid
with several blocks along it reads ``round_up(B + 2, align)`` boxes per
window (``B + 8`` on sublanes), of which the kernel uses ``B + 2``; the
halo'd array is zero-padded at the far end so the last window stays in
bounds.  A single block reads the whole extent.  The M2L
kernel keeps its box rows on a leading, untiled axis (``row_align=1``: any
``BY``) and its box columns on sublanes.  The P2P kernel puts box rows on
sublanes and box columns on lanes; it reads row windows, and takes its
column halo from three column-shifted copies instead of a lane window.
"""
from __future__ import annotations

import dataclasses

from jax.experimental import pallas as pl

SUBLANES = 8
LANES = 128


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class HaloTiling:
    """Launch geometry of one halo-window kernel over a (rows, cols) grid."""

    BY: int
    BX: int
    rows_p: int        # rows padded to a multiple of BY
    cols_p: int        # cols padded to a multiple of BX
    win_rows: int      # row extent of one halo window
    win_cols: int      # column extent of one halo window

    @property
    def grid(self) -> tuple[int, int]:
        return self.rows_p // self.BY, self.cols_p // self.BX

    @property
    def halo_rows(self) -> int:
        """Row extent the halo'd input array is padded to."""
        return self.rows_p - self.BY + self.win_rows

    @property
    def halo_cols(self) -> int:
        """Column extent the halo'd input array is padded to."""
        return self.cols_p - self.BX + self.win_cols

    def window_spec(self, lanes: int) -> pl.BlockSpec:
        """(win_rows, win_cols, lanes) window over the halo'd array."""
        BY, BX = self.BY, self.BX
        return pl.BlockSpec(
            (pl.Element(self.win_rows), pl.Element(self.win_cols),
             pl.Element(lanes)),
            lambda i, j: (i * BY, j * BX, 0))

    def block_spec(self, lanes: int) -> pl.BlockSpec:
        """(BY, BX, lanes) block of an un-halo'd (rows_p, cols_p) array."""
        return pl.BlockSpec((self.BY, self.BX, lanes),
                            lambda i, j: (i, j, 0))


def _halo_axis(n: int, b: int, align: int) -> tuple[int, int, int]:
    """Block, padded extent and window extent of one halo'd box axis."""
    B = round_up(max(1, min(b, n)), align)
    n_p = round_up(n, B)
    return B, n_p, B + 2 if n_p == B else round_up(B + 2, align)


def halo_tiling(rows: int, cols: int, block: tuple[int, int],
                row_align: int = 1, col_align: int = SUBLANES) -> HaloTiling:
    """Clip ``block`` to the grid and round each axis up to whole tiles of
    it: ``row_align``/``col_align`` are 1 (untiled), 8 (sublanes) or 128
    (lanes)."""
    BY, rows_p, win_rows = _halo_axis(rows, block[0], row_align)
    BX, cols_p, win_cols = _halo_axis(cols, block[1], col_align)
    return HaloTiling(BY=BY, BX=BX, rows_p=rows_p, cols_p=cols_p,
                      win_rows=win_rows, win_cols=win_cols)
