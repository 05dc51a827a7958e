"""Quadtree geometry, Morton indexing, and dense tree construction.

The paper (PetFMM, §2.1) uses a pointer quadtree.  On TPU we use *dense level
grids*: level ``l`` of the tree is a ``(2^l, 2^l, ...)`` array in row-major
grid order ``(iy, ix)``.  Morton (z-order) indices are used by the
partitioner (paper §4/§5.1) to enumerate subtrees and their neighbor sets.

Domain is the unit square ``[0, 1]^2``.  Box side at level ``l`` is
``2**-l``; particle positions are complex ``z = x + 1j*y``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Morton (z-order) indexing — used by the partitioner, not the dense kernels.
# ---------------------------------------------------------------------------


def _part1by1(x: np.ndarray) -> np.ndarray:
    """Interleave zeros: abcd -> 0a0b0c0d (supports up to 16-bit inputs)."""
    # NB: copy before the in-place ops — ``asarray`` aliases uint32 inputs
    # and the bit-twiddling must never mutate the caller's array.
    x = np.array(x, dtype=np.uint32, copy=True)
    x &= np.uint32(0x0000FFFF)
    x = (x | (x << 8)) & np.uint32(0x00FF00FF)
    x = (x | (x << 4)) & np.uint32(0x0F0F0F0F)
    x = (x | (x << 2)) & np.uint32(0x33333333)
    x = (x | (x << 1)) & np.uint32(0x55555555)
    return x


def _compact1by1(x: np.ndarray) -> np.ndarray:
    x = np.array(x, dtype=np.uint32, copy=True)   # never mutate the caller
    x &= np.uint32(0x55555555)
    x = (x | (x >> 1)) & np.uint32(0x33333333)
    x = (x | (x >> 2)) & np.uint32(0x0F0F0F0F)
    x = (x | (x >> 4)) & np.uint32(0x00FF00FF)
    x = (x | (x >> 8)) & np.uint32(0x0000FFFF)
    return x


def morton_encode(ix: np.ndarray, iy: np.ndarray) -> np.ndarray:
    """(ix, iy) grid coords -> z-order index (paper's quadtree numbering)."""
    return (_part1by1(iy) << 1) | _part1by1(ix)


def morton_decode(code: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    code = np.asarray(code, dtype=np.uint32)
    return _compact1by1(code), _compact1by1(code >> 1)


# ---------------------------------------------------------------------------
# Interaction-list algebra for the dense uniform tree.
#
# A source box at relative offset (dx, dy), |dx|,|dy| <= 3, is in the
# interaction list of a target box iff (a) it is not a near neighbor
# (max(|dx|,|dy|) >= 2) and (b) its parent is a neighbor of the target's
# parent.  Condition (b) depends only on the *parity* of the target's grid
# coordinate:   |floor((parity + d) / 2)| <= 1.
# There are 40 candidate offsets; each parity class admits exactly 27.
# ---------------------------------------------------------------------------

M2L_OFFSETS: list[tuple[int, int]] = [
    (dx, dy)
    for dy in range(-3, 4)
    for dx in range(-3, 4)
    if max(abs(dx), abs(dy)) >= 2
]
assert len(M2L_OFFSETS) == 40


def parity_valid(parity: int, d: int) -> bool:
    """True iff parent(target+d) is a neighbor of parent(target)."""
    import math

    return abs(math.floor((parity + d) / 2)) <= 1


# VALIDITY[o, py, px]: offset o is in the interaction list of boxes with
# grid-coordinate parities (iy % 2 == py, ix % 2 == px).
M2L_VALIDITY = np.zeros((len(M2L_OFFSETS), 2, 2), dtype=bool)
for _o, (_dx, _dy) in enumerate(M2L_OFFSETS):
    for _py in range(2):
        for _px in range(2):
            M2L_VALIDITY[_o, _py, _px] = parity_valid(_px, _dx) and parity_valid(_py, _dy)
# Each parity class has exactly 27 interaction-list members (paper §5.2).
assert (M2L_VALIDITY.sum(axis=0) == 27).all()

# Near-field stencil (self + 8 neighbors).
P2P_OFFSETS: list[tuple[int, int]] = [(dx, dy) for dy in (-1, 0, 1) for dx in (-1, 0, 1)]

# ---------------------------------------------------------------------------
# Parent-granularity (parity-folded) interaction algebra.
#
# Key identity (DESIGN.md §4): the parity validity above only ever excludes
# the extreme offsets d = ±3 (d = -3 needs odd parity, d = +3 even parity),
# so the 27 valid offsets of a box with parity (py, px) form the contiguous
# 6x6 window  dy in [-2-py, 3-py], dx in [-2-px, 3-px]  minus the 3x3 near
# field — i.e. exactly the children of the target's parent's 3x3 parent
# neighborhood, minus near neighbors.  Working on 2x2 child blocks therefore
# folds every parity mask into the *structure* of the operator: each
# (target-child, source-child, parent-offset) triple is either a valid
# interaction or a structural zero; nothing is masked at run time.
# ---------------------------------------------------------------------------

# The 8 contributing parent offsets (the (0,0) parent holds only near
# neighbors of every child, so its block is identically zero and dropped).
PARENT_NEIGH8: list[tuple[int, int]] = [
    (dx, dy) for dy in (-1, 0, 1) for dx in (-1, 0, 1) if (dx, dy) != (0, 0)
]

# M2L_PARITY_OFFSETS[py][px]: the 27 child-granularity offsets valid for
# parity class (py, px), in (parent-offset, source-child) raster order —
# the order the folded operator contracts them in.
M2L_PARITY_OFFSETS: list[list[list[tuple[int, int]]]] = [[[] for _ in range(2)]
                                                         for _ in range(2)]
for _py in range(2):
    for _px in range(2):
        for (_Dx, _Dy) in PARENT_NEIGH8:
            for _sy in range(2):
                for _sx in range(2):
                    _d = (2 * _Dx + _sx - _px, 2 * _Dy + _sy - _py)
                    if max(abs(_d[0]), abs(_d[1])) >= 2:
                        M2L_PARITY_OFFSETS[_py][_px].append(_d)

# Cross-check the folded enumeration against the mask table: same 27 sets.
for _py in range(2):
    for _px in range(2):
        _folded = set(M2L_PARITY_OFFSETS[_py][_px])
        _masked = {off for _o, off in enumerate(M2L_OFFSETS)
                   if M2L_VALIDITY[_o, _py, _px]}
        assert _folded == _masked and len(_folded) == 27


# ---------------------------------------------------------------------------
# Geometry helpers
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Domain:
    """Physical root box mapped onto the solver's unit square.

    The FMM machinery everywhere assumes the unit domain ``[0, 1]^2``; a
    :class:`Domain` records the affine map from PHYSICAL coordinates to
    that unit square so the root box can GROW when particles escape (the
    stepper's domain-expansion recovery rung) without touching any of the
    tree/kernel geometry.  ``to_unit``/``from_unit`` act on ``(N, 2)``
    position arrays; the identity domain is bit-transparent.

    Scaling contract for the stepper (unit quantities fed to the solver):
    ``sigma_unit = sigma / size`` and — for the Biot-Savart/vortex kernel,
    where velocity ~ Gamma / r — ``gamma_unit = gamma / size**2``, so unit
    trajectories advanced with the physical ``dt`` map back to physical
    trajectories exactly.
    """

    origin: tuple[float, float] = (0.0, 0.0)
    size: float = 1.0

    def to_unit(self, positions: np.ndarray) -> np.ndarray:
        return (np.asarray(positions, np.float64)
                - np.asarray(self.origin)) / self.size

    def from_unit(self, positions: np.ndarray) -> np.ndarray:
        return np.asarray(positions, np.float64) * self.size \
            + np.asarray(self.origin)

    @property
    def is_identity(self) -> bool:
        return self.origin == (0.0, 0.0) and self.size == 1.0

    @staticmethod
    def covering(positions: np.ndarray, margin: float = 0.25,
                 at_least: Optional["Domain"] = None) -> "Domain":
        """Smallest square (plus relative ``margin`` per side) containing
        every position — and, when ``at_least`` is given, that whole domain
        too, so expansion never orphans the current root box."""
        pos = np.asarray(positions, np.float64)
        lo, hi = pos.min(axis=0), pos.max(axis=0)
        if at_least is not None:
            o = np.asarray(at_least.origin)
            lo = np.minimum(lo, o)
            hi = np.maximum(hi, o + at_least.size)
        side = max(float((hi - lo).max()), 1e-9)
        size = side * (1.0 + 2.0 * margin)
        center = (lo + hi) / 2.0
        origin = center - size / 2.0
        return Domain(origin=(float(origin[0]), float(origin[1])), size=size)


def box_size(level: int) -> float:
    return 2.0 ** (-level)


def box_centers(level: int) -> np.ndarray:
    """Complex centers of all boxes at ``level``, shape (2^l, 2^l) [iy, ix]."""
    n = 1 << level
    r = box_size(level)
    xs = (np.arange(n) + 0.5) * r
    cx, cy = np.meshgrid(xs, xs, indexing="xy")  # [iy, ix]
    return (cx + 1j * cy).astype(np.complex128)


# ---------------------------------------------------------------------------
# Dense tree container
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Spill:
    """Overflow ("spill") chunks of a tree's crowded boxes.

    A box holding more particles than the dense capacity ``s`` keeps its
    first ``s`` in the dense grid; the rest fill chunks of ``s`` slots each,
    ``K`` in all, ordered by box.  ``box`` is each chunk's flat row-major
    leaf box.  Everything the chunks touch lies in ``window``, ``(y0, x0,
    wy, wx)``: the boxes with chunks and their neighbours.

    **Near field.**  One chunk index space: ``i < W = wy * wx`` is the
    dense part of window box ``i`` (row-major), ``W + k`` spill chunk
    ``k``.  A box's 3x3 neighbourhood is three rows of three boxes, and
    the chunks of one such row are consecutive in both parts, so each
    target chunk's sources are a few *runs* of at most ``run`` consecutive
    chunks: ``run_start``/``run_len``/``run_tgt`` (U,).  The runs cover
    every pair of neighbouring chunks of which at least one is a spill
    chunk; the dense grid's own kernel does the rest.  Targets are ordered
    by their number of runs, and ``groups`` lists ``(runs, targets)`` for
    each count, so that summing a target's runs is a reshape.
    ``spill_pos`` (K,) and ``window_pos`` (W,) give each spill chunk's and
    each window box's place in that target order (``T``, past the end,
    where a window box is no target).

    **Far field.**  ``box_chunks`` (B, Cmax) lists the chunks of each of
    the ``B`` boxes with chunks (``K`` pads), ``window_box`` (W,) each
    window box's place among them (``B``: none), and ``chunk_wbox`` (K,)
    each chunk's window box.

    ``particles`` and ``pairs`` count the spilled particles and the chunk
    pairs the runs cover (empty dense boxes included).
    """

    z: jax.Array           # complex64 (K, s) particle positions
    q: jax.Array           # complex64 (K, s) pseudo-charges
    mask: jax.Array        # bool      (K, s) slot occupancy
    box: jax.Array         # int32 (K,)  flat leaf box of each chunk
    chunk_wbox: jax.Array  # int32 (K,)  window box of each chunk
    run_tgt: jax.Array     # int32 (U,)  target chunk of each run
    run_start: jax.Array   # int32 (U,)  first source chunk of each run
    run_len: jax.Array     # int32 (U,)  source chunks in each run
    spill_pos: jax.Array   # int32 (K,)  target place of each spill chunk
    window_pos: jax.Array  # int32 (W,)  target place of each window box
    box_chunks: jax.Array  # int32 (B, Cmax) chunks of each box with some
    window_box: jax.Array  # int32 (W,)  place of each window box among B
    window: tuple = dataclasses.field(metadata=dict(static=True))
    run: int = dataclasses.field(metadata=dict(static=True))
    groups: tuple = dataclasses.field(metadata=dict(static=True))
    particles: int = dataclasses.field(metadata=dict(static=True))
    pairs: int = dataclasses.field(metadata=dict(static=True))

    @property
    def chunks(self) -> int:
        return self.z.shape[0]


# Chunks per run of the spill near field: longer runs mean fewer, larger
# gathers, and more padded pairs.
SPILL_RUN = 8


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Tree:
    """Dense uniform quadtree of particles, plus spill chunks.

    ``z``/``q``/``mask`` have shape ``(n, n, s)`` with ``n = 2**level`` leaf
    boxes per side and ``s`` padded slots per box.  ``q`` already includes
    the ``gamma / (2*pi*i)`` pseudo-charge factor for the Biot-Savart kernel.
    ``spill`` holds the particles of boxes fuller than ``s`` (None when no
    box is: the tree is then the dense grid alone).
    """

    z: jax.Array       # complex64 (n, n, s) particle positions
    q: jax.Array       # complex64 (n, n, s) pseudo-charges
    mask: jax.Array    # bool      (n, n, s) slot occupancy
    level: int = dataclasses.field(metadata=dict(static=True))
    sigma: float = dataclasses.field(metadata=dict(static=True))
    spill: Optional[Spill] = None

    @property
    def nside(self) -> int:
        return 1 << self.level

    @property
    def slots(self) -> int:
        return self.z.shape[-1]

    @property
    def num_particles(self) -> jax.Array:
        n = self.mask.sum()
        return n if self.spill is None else n + self.spill.mask.sum()

    # the spill layout's counters (all 0 for a dense tree)

    @property
    def spill_particles(self) -> int:
        return 0 if self.spill is None else self.spill.particles

    @property
    def spill_chunks(self) -> int:
        return 0 if self.spill is None else self.spill.chunks

    @property
    def spill_pairs(self) -> int:
        return 0 if self.spill is None else self.spill.pairs

    @property
    def spill_pair_evals(self) -> int:
        """Pair evaluations of the spill near field: every run's ``run``
        source chunks, padding included, ``s * s`` each."""
        if self.spill is None:
            return 0
        sp = self.spill
        return sp.run_tgt.shape[0] * sp.run * self.slots * self.slots


def require_dense(tree: Tree, who: str) -> None:
    """Refuse a tree with spill chunks on a path that reads the dense grid
    alone."""
    if tree.spill is not None:
        raise ValueError(
            f"{who} takes a dense tree; this one has {tree.spill_chunks} "
            f"spill chunks ({tree.spill_particles} particles over the "
            f"capacity of {tree.slots} slots): build it with slots at least "
            f"the fullest box's occupancy")


@dataclasses.dataclass(frozen=True)
class TreeIndex:
    """Host-side bookkeeping to map tree slots back to input order."""

    box_of_particle: np.ndarray   # (N,) flat row-major box id per input particle
    slot_of_particle: np.ndarray  # (N,) slot within the box, or its chunk
    counts: np.ndarray            # (n, n) particles per box
    chunk_of_particle: np.ndarray  # (N,) spill chunk, -1 in the dense grid


def choose_level(num_particles: int, target_per_box: float = 4.0, max_level: int = 12) -> int:
    """Pick the tree depth so the mean leaf occupancy ~ ``target_per_box``."""
    level = 0
    while level < max_level and num_particles / float(4 ** (level + 1)) >= target_per_box:
        level += 1
    return level


def box_counts(positions: np.ndarray, level: int) -> np.ndarray:
    """Particles per leaf box, ``(n, n)`` row-major (``[iy, ix]``), binned
    as :func:`build_tree` bins them: positions outside the unit square
    count in the edge boxes."""
    n = 1 << level
    ij = np.clip((np.asarray(positions) * n).astype(np.int64), 0, n - 1)
    return np.bincount(ij[:, 1] * n + ij[:, 0],
                       minlength=n * n).reshape(n, n)


# Expected number of boxes, over a whole histogram, that a uniform random
# field fills past the flat bound of ``choose_slots``.
FLAT_TAIL = 1e-6


def poisson_bound(mean: float, boxes: int, tail: float = FLAT_TAIL) -> int:
    """The smallest ``c`` such that ``boxes`` independent Poisson counts of
    mean ``mean`` exceed ``c`` in fewer than ``tail`` boxes on average:
    ``boxes * P(X > c) <= tail``."""
    k = np.arange(int(mean + 12.0 * np.sqrt(mean) + 64.0) + 1)
    logk = np.concatenate([[0.0], np.cumsum(np.log(k[1:]))])   # log k!
    pmf = np.exp(k * np.log(max(mean, 1e-300)) - mean - logk)
    above = np.cumsum(pmf[::-1])[::-1] - pmf                    # P(X > k)
    return int(np.flatnonzero(boxes * above <= tail)[0])


def choose_slots(counts: np.ndarray) -> int:
    """The dense capacity the occupancy histogram ``counts`` calls for.

    Let ``m`` be the mean occupancy of the occupied boxes.  If the fullest
    box holds at most :func:`poisson_bound` ``(m, occupied boxes)``, the
    histogram is flat -- a lattice, or a uniform random field, whose
    occupied boxes are Poisson counts of a mean below ``m``, so that one
    exceeds that bound in about one histogram of a million -- and the
    capacity is the fullest box: nothing spills.  Otherwise the tail is a
    cluster, whose fullest boxes would pad every box to their size; the
    capacity is the smallest power of two at or above ``m``, and the
    tail's particles go to spill chunks of that many slots.

    ``build_tree`` does not call this: its default capacity is the fullest
    box, which every path takes.  A caller that evaluates a clustered
    field serially passes this pick as ``slots``.
    """
    counts = np.asarray(counts)
    most = int(counts.max()) if counts.size else 0
    occupied = int((counts > 0).sum())
    if occupied == 0:
        return 1
    mean = counts.sum() / occupied
    if most <= poisson_bound(mean, occupied):
        return most
    return 1 << int(np.ceil(np.log2(max(mean, 1.0))))


def _spill_layout(counts: np.ndarray, chunk_box: np.ndarray, n: int
                  ) -> dict:
    """The index arrays and static facts of :class:`Spill` beside the
    particles: the window, the near field's runs and their target groups,
    and the far field's per-box chunk lists."""
    nn, K, R = n * n, len(chunk_box), SPILL_RUN
    nspill = np.bincount(chunk_box, minlength=nn)
    first = np.zeros(nn + 1, np.int64)
    np.cumsum(nspill, out=first[1:])
    occupied = counts.reshape(nn) > 0
    has = np.flatnonzero(nspill)
    hy, hx = np.divmod(has, n)
    y0, x0 = max(hy.min() - 1, 0), max(hx.min() - 1, 0)
    wy, wx = min(hy.max() + 2, n) - y0, min(hx.max() + 2, n) - x0
    W = wy * wx
    wyy, wxx = np.divmod(np.arange(W), wx)
    wbox = (y0 + wyy) * n + x0 + wxx                  # flat box of each
    near = np.zeros(W, bool)
    for dy in (-1, 0, 1):
        for dx in (-1, 0, 1):
            yy, xx = y0 + wyy + dy, x0 + wxx + dx
            ok = (yy >= 0) & (yy < n) & (xx >= 0) & (xx < n)
            near[ok] |= nspill[yy[ok] * n + xx[ok]] > 0
    # targets: every spill chunk, and every occupied window box with a
    # spill chunk among its neighbours
    dense_t = np.flatnonzero(occupied[wbox] & near)
    t_id = np.concatenate([W + np.arange(K), dense_t])
    t_box = np.concatenate([chunk_box, wbox[dense_t]])
    t_spill = t_id >= W
    ty, tx = np.divmod(t_box, n)
    tgt, start, length = [], [], []

    def runs_of(t, c0, c1):         # chunks [c0, c1) of target t, in runs
        runs = -(-(c1 - c0) // R)
        k = np.arange(runs.sum()) - np.repeat(np.cumsum(runs) - runs, runs)
        st = np.repeat(c0, runs) + k * R
        tgt.append(np.repeat(t, runs))
        start.append(st)
        length.append(np.minimum(R, np.repeat(c1, runs) - st))

    for dy in (-1, 0, 1):
        t = np.flatnonzero((ty + dy >= 0) & (ty + dy < n))
        lo = np.maximum(tx[t] - 1, 0)
        hi = np.minimum(tx[t] + 1, n - 1)
        row = ty[t] + dy
        # the row's three dense boxes, for a spill target
        d = t_spill[t]
        w0 = ((row - y0) * wx + lo - x0)[d]
        runs_of(t[d], w0, w0 + (hi - lo + 1)[d])
        # the row's spill chunks
        runs_of(t, W + first[row * n + lo], W + first[row * n + hi + 1])
    tgt, start, length = (np.concatenate(a) for a in (tgt, start, length))
    per = np.bincount(tgt, minlength=len(t_id))
    order = np.lexsort((np.arange(len(t_id)), per))   # targets, by runs
    place = np.empty(len(t_id), np.int64)
    place[order] = np.arange(len(t_id))
    runs_order = np.argsort(place[tgt], kind="stable")
    counts_, sizes = np.unique(per[order], return_counts=True)
    window_pos = np.full(W, len(t_id), np.int64)
    window_pos[dense_t] = place[K:]
    cmax = int(nspill.max())
    box_chunks = np.where(np.arange(cmax)[None] < nspill[has][:, None],
                          first[has][:, None] + np.arange(cmax)[None], K)
    window_box = np.full(nn, len(has), np.int64)
    window_box[has] = np.arange(len(has))
    i32 = lambda a: jnp.asarray(np.asarray(a, np.int32))
    return dict(
        chunk_wbox=i32((chunk_box // n - y0) * wx + chunk_box % n - x0),
        run_tgt=i32(t_id[tgt][runs_order]),
        run_start=i32(start[runs_order]), run_len=i32(length[runs_order]),
        spill_pos=i32(place[:K]), window_pos=i32(window_pos),
        box_chunks=i32(box_chunks), window_box=i32(window_box[wbox]),
        window=(int(y0), int(x0), int(wy), int(wx)), run=R,
        groups=tuple((int(c), int(m)) for c, m in zip(counts_, sizes)),
        pairs=int(length.sum()))


def build_tree(
    positions: np.ndarray,
    gamma: np.ndarray,
    level: int,
    sigma: float,
    slots: Optional[int] = None,
    dtype=np.complex64,
    charge_scale: Optional[complex] = None,
) -> tuple[Tree, TreeIndex]:
    """Bin particles into the dense leaf grid and its spill chunks
    (host-side, NumPy).

    positions: (N, 2) float in [0, 1)^2;  gamma: (N,) real strengths.
    ``slots`` is the dense capacity of every box (None: the fullest box's
    occupancy, so that nothing spills).  A box with more particles keeps
    its first ``slots`` in the dense grid and the rest in spill chunks of
    ``slots`` (:class:`Spill`); with none, the tree is the dense grid
    alone.  ``choose_slots`` picks a capacity for a clustered field.
    ``charge_scale`` maps the input strength to the stored pseudo-charge
    ``q`` -- the equation spec's ``charge_scale`` (core/equations.py); None
    keeps the vortex default ``1/(2*pi*i)`` (circulation -> Biot-Savart
    pseudo-charge).  The dense grid plus spill chunks is the TPU-native
    form of the paper's ragged per-box particle lists (see DESIGN.md §3).
    """
    positions = np.asarray(positions, dtype=np.float64)
    gamma = np.asarray(gamma, dtype=np.float64)
    n = 1 << level
    ij = np.clip((positions * n).astype(np.int64), 0, n - 1)
    ix, iy = ij[:, 0], ij[:, 1]
    box = iy * n + ix  # flat row-major box id

    order = np.argsort(box, kind="stable")
    sorted_box = box[order]
    counts = np.bincount(sorted_box, minlength=n * n)
    if slots is None:
        slots = max(int(counts.max()) if counts.size else 0, 1)

    # slot index = rank of the particle within its (sorted) box run
    starts = np.zeros(n * n + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    rank = np.arange(len(box)) - starts[sorted_box]
    dense = rank < slots
    slot_sorted = np.where(dense, rank, (rank - slots) % slots)

    if charge_scale is None:
        charge_scale = 1.0 / (2j * np.pi)
    zsrc = positions[order, 0] + 1j * positions[order, 1]
    qsrc = gamma[order] * charge_scale

    def fill(rows, at, nrows):
        z = np.zeros((nrows, slots), dtype=np.complex128)
        q = np.zeros((nrows, slots), dtype=np.complex128)
        m = np.zeros((nrows, slots), dtype=bool)
        z[rows, slot_sorted[at]] = zsrc[at]
        q[rows, slot_sorted[at]] = qsrc[at]
        m[rows, slot_sorted[at]] = True
        return z, q, m

    zflat, qflat, mflat = fill(sorted_box[dense], dense, n * n)

    chunk_sorted = np.full(len(box), -1, dtype=np.int64)
    spill = None
    over = ~dense
    if over.any():
        per_box = -(-np.maximum(counts - slots, 0) // slots)
        chunk_first = np.cumsum(per_box) - per_box
        chunk_sorted[over] = (chunk_first[sorted_box[over]]
                              + (rank[over] - slots) // slots)
        K = int(per_box.sum())
        chunk_box = np.repeat(np.arange(n * n), per_box)
        zs, qs, ms = fill(chunk_sorted[over], over, K)
        spill = Spill(z=jnp.asarray(zs, dtype=dtype),
                      q=jnp.asarray(qs, dtype=dtype), mask=jnp.asarray(ms),
                      box=jnp.asarray(chunk_box, dtype=jnp.int32),
                      particles=int(over.sum()),
                      **_spill_layout(counts, chunk_box, n))

    slot_of_particle = np.empty(len(box), dtype=np.int64)
    slot_of_particle[order] = slot_sorted
    chunk_of_particle = np.empty(len(box), dtype=np.int64)
    chunk_of_particle[order] = chunk_sorted

    tree = Tree(
        z=jnp.asarray(zflat.reshape(n, n, slots), dtype=dtype),
        q=jnp.asarray(qflat.reshape(n, n, slots), dtype=dtype),
        mask=jnp.asarray(mflat.reshape(n, n, slots)),
        level=level,
        sigma=float(sigma),
        spill=spill,
    )
    index = TreeIndex(box_of_particle=box, slot_of_particle=slot_of_particle,
                      counts=counts.reshape(n, n),
                      chunk_of_particle=chunk_of_particle)
    return tree, index


def rebuild_tree(tree: Tree, new_z: jnp.ndarray, aux=None):
    """Device-side rebinning: place particles into a fresh dense tree.

    The jit-able counterpart of :func:`build_tree` — a whole advection step
    can run on device with no host round-trip (core/stepper.py).  ``new_z``
    holds updated complex positions in ``tree``'s slot layout; charges and
    occupancy come from ``tree``.  ``aux`` is an optional pytree of
    per-slot ``(n, n, s)`` arrays rebinned alongside the particles (e.g.
    the pre-step positions an RK2 midpoint stage needs).

    Returns ``(new_tree, new_aux, ok)``.  Slot capacity stays fixed at
    ``tree.slots``; when a box overflows, the surplus particles are dropped
    from the new tree and ``ok`` is False — callers must check it and
    rebuild at a deeper level / larger capacity on the host (the stepper's
    occupancy guard does this before overflow is ever reached).

    Positions outside the unit square are clamped into the edge boxes,
    matching ``build_tree``'s host binning.

    Placement is a read, never a scatter: after a stable sort by box, box
    ``b``'s particles are the sorted run ``[start[b], start[b] + count[b])``,
    so slot ``k`` of box ``b`` reads sorted element ``start[b] + k`` when
    ``k < count[b]`` and is empty otherwise.  Each output plane is then one
    gather (a TPU runs a scatter one update at a time).  Box ``b`` keeps
    its first ``s`` particles in input slot order; the surplus is dropped.
    """
    require_dense(tree, "rebuild_tree")
    n, s = tree.nside, tree.slots
    nb, N = n * n, n * n * s
    z = new_z.reshape(N)

    ix = jnp.clip((z.real * n).astype(jnp.int32), 0, n - 1)
    iy = jnp.clip((z.imag * n).astype(jnp.int32), 0, n - 1)
    box = jnp.where(tree.mask.reshape(N), iy * n + ix, nb)  # empty sort last
    sb, order = jax.lax.sort((box, jnp.arange(N, dtype=jnp.int32)),
                             num_keys=1, is_stable=True)

    # box b's sorted run is [edges[b], edges[b + 1])
    edges = jnp.searchsorted(sb, jnp.arange(nb + 1, dtype=jnp.int32))
    count = jnp.diff(edges)
    ok = jnp.all(count <= s)
    k = jnp.arange(s, dtype=jnp.int32)
    live = k < count[:, None]                         # (nb, s): the new mask
    idx = order[jnp.where(live, edges[:-1, None] + k, 0)]

    def place(vals):
        got = vals.reshape(N)[idx]
        return jnp.where(live, got, jnp.zeros((), got.dtype)).reshape(n, n, s)

    new_tree = Tree(z=place(z), q=place(tree.q), mask=live.reshape(n, n, s),
                    level=tree.level, sigma=tree.sigma)
    new_aux = jax.tree_util.tree_map(place, aux) if aux is not None else None
    return new_tree, new_aux, ok


def gather_particle_values(values, index: TreeIndex) -> np.ndarray:
    """Read per-slot results back into the original particle order.

    ``values`` is the dense grid's ``(n, n, s)`` array, or for a tree with
    spill chunks the pair ``(dense, spill)`` that ``fmm_evaluate`` returns.
    """
    dense, spill = values if isinstance(values, tuple) else (values, None)
    n2 = index.counts.size
    out = np.asarray(dense).reshape(n2, -1)[index.box_of_particle,
                                            index.slot_of_particle]
    spilled = index.chunk_of_particle >= 0
    if spilled.any():
        flat = np.asarray(spill)
        flat = flat.reshape(flat.shape[0], -1)
        out[spilled] = flat[index.chunk_of_particle[spilled],
                            index.slot_of_particle[spilled]]
    return out
