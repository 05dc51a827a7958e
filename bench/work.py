"""The work an FMM evaluation needs, as the rooflines count it.

Every count here reads only the tree level, the expansion order ``p`` and
the particles per leaf box, which ``box_counts`` bins from the positions
themselves.  Nothing reads slots, lane padding, kernel block shapes or the
zero blocks of a folded operator: an implementation that pads less does the
same work in less time, and its roofline share rises.

Floating-point operations are counted as real operations; a complex
multiply-add is 8 (four products, four sums).  Bytes are the least traffic
the stage needs at float32: each input read once and each output written
once.
"""
from __future__ import annotations

import numpy as np

# one near-field pair of the regularized vortex kernel, as the reference
# writes it: dx, dy (2), r2 (3), the mollifier 1 - exp(-r2/2s^2) (3, the
# exponential counted once), a = g*moll/r2 (2), a*dx and a*dy (2), and the
# two accumulations (2)
FLOPS_PER_PAIR = 14
CMAC = 8                        # real operations in a complex multiply-add
BYTES_PER_PARTICLE = 20         # x, y and strength read (12), u, v written (8)
BYTES_PER_COEFF = 8             # one complex64 expansion coefficient
M2L_LIST = 27                   # interaction-list size of an interior box


def box_counts(positions, level: int) -> np.ndarray:
    """(n, n) particles per leaf box of the unit square, rows = y."""
    n = 1 << level
    ij = np.clip((np.asarray(positions, np.float64) * n).astype(np.int64),
                 0, n - 1)
    return np.bincount(ij[:, 1] * n + ij[:, 0],
                       minlength=n * n).reshape(n, n)


def p2p_pairs(counts) -> int:
    """Ordered particle pairs in the 3x3 near field of every box, less each
    particle's pair with itself: sum_b n_b (sum over b's 3x3 boxes of n)
    - N."""
    c = np.asarray(counts, np.int64)
    padded = np.pad(c, 1)
    n0, n1 = c.shape
    neigh = sum(padded[1 + dy:1 + dy + n0, 1 + dx:1 + dx + n1]
                for dy in (-1, 0, 1) for dx in (-1, 0, 1))
    return int((c * neigh).sum() - c.sum())


def p2p_cost(counts) -> tuple[float, float]:
    """(flops, bytes) of one near-field evaluation."""
    n = float(np.asarray(counts).sum())
    return FLOPS_PER_PAIR * float(p2p_pairs(counts)), BYTES_PER_PARTICLE * n


def m2l_cost(level: int, p: int) -> tuple[float, float]:
    """(flops, bytes) of the M2L translations of levels 2..level: 27 p x p
    complex multiply-adds per box, each box's multipole expansion read once
    and its local expansion written once."""
    boxes = sum(4 ** lv for lv in range(2, level + 1))
    return (float(boxes * M2L_LIST * p * p * CMAC),
            float(boxes * p * BYTES_PER_COEFF * 2))


def evaluation_flops(counts, level: int, p: int) -> float:
    """All the operations of one evaluation: P2M and L2P (p complex
    multiply-adds per particle each), M2M (levels 1..L) and L2L (levels
    3..L) as triangular p x p translations per box, M2L and P2P."""
    n = float(np.asarray(counts).sum())
    tri = p * (p + 1) // 2
    m2m = sum(4 ** lv for lv in range(1, level + 1)) * tri * CMAC
    l2l = sum(4 ** lv for lv in range(3, level + 1)) * tri * CMAC
    return (2 * n * p * CMAC + m2m + l2l + m2l_cost(level, p)[0]
            + p2p_cost(counts)[0])


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time: the larger of the compute and memory
    bounds on one chip."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
