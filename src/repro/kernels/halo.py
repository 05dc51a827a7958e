"""Overlapping halo windows in the form the TPU compiler accepts.

Both FMM kernels tile a ``(rows, cols)`` box grid into ``(BY, BX)`` blocks
and read each block's ±1-box neighbourhood as ONE overlapping window of the
halo'd array, at element offsets, so the halo never exists as a separate
HBM buffer.  Mosaic constrains such windows:

  * every block axis is ``pl.Element`` or none is;
  * the last two axes are tiled (8 sublanes, 128 lanes): a window's column
    extent must be a multiple of 8 or the array's whole extent, and its
    lane extent a multiple of 128 or whole.

So ``BX`` is a multiple of 8, and a grid with several column blocks reads
``round_up(BX + 2, 8)`` columns per window (``BX + 8``), of which the
kernel uses ``BX + 2``; the halo'd array is zero-padded on the right so the
last window stays in bounds.  A single column block reads the whole width.
Rows are a leading, untiled axis: any ``BY`` works there.
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
    win_cols: int      # column extent of one halo window

    @property
    def grid(self) -> tuple[int, int]:
        return self.rows_p // self.BY, self.cols_p // self.BX

    @property
    def halo_cols(self) -> int:
        """Column extent the halo'd input array is padded to."""
        return self.cols_p - self.BX + self.win_cols

    def window_spec(self, lanes: int) -> pl.BlockSpec:
        """(BY+2, win_cols, lanes) window over the halo'd array."""
        BY, BX = self.BY, self.BX
        return pl.BlockSpec(
            (pl.Element(BY + 2), pl.Element(self.win_cols), pl.Element(lanes)),
            lambda i, j: (i * BY, j * BX, 0))

    def block_spec(self, lanes: int) -> pl.BlockSpec:
        """(BY, BX, lanes) block of an un-halo'd (rows_p, cols_p) array."""
        return pl.BlockSpec((self.BY, self.BX, lanes),
                            lambda i, j: (i, j, 0))


def halo_tiling(rows: int, cols: int, block: tuple[int, int]) -> HaloTiling:
    """Clip ``block`` to the grid and round ``BX`` up to whole sublanes."""
    BY = max(1, min(block[0], rows))
    BX = round_up(max(1, min(block[1], cols)), SUBLANES)
    rows_p, cols_p = round_up(rows, BY), round_up(cols, BX)
    win = BX + 2 if cols_p == BX else round_up(BX + 2, SUBLANES)
    return HaloTiling(BY=BY, BX=BX, rows_p=rows_p, cols_p=cols_p,
                      win_cols=win)
