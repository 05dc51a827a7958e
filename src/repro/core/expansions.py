"""2D complex multipole/local expansions and translation operators.

The far-field kernel is the singular complex velocity kernel

    W(z) = sum_j q_j / (z - z_j),        q_j = gamma_j / (2*pi*i),

which is the paper's ``1/|x|^2``-type substitution kernel (PetFMM §3): the
Gaussian-regularized Biot-Savart kernel equals this singular kernel times a
mollifier that is ~1 at interaction-list distances.

Multipole expansion (ME) about a box center c with radius (side) r:

    W(z) = sum_{k=0}^{p-1} a_k / (z - c)^{k+1}

Local expansion (LE):

    W(z) = sum_{l=0}^{p-1} b_l (z - c)^l

**Scale normalization (beyond-paper, see DESIGN.md §3):** we store
``ahat_k = a_k r^-k`` and ``bhat_l = b_l r^l``.  All translation operators
then become *level independent*; M2L carries a single ``1/r`` scalar (the
kernel has dimension 1/length).  One (4,p,p) M2M tensor, one parity-folded
(8,4p,4p) M2L block operator and one (4,p,p) L2L tensor serve the whole
tree and stay resident in VMEM inside the Pallas kernels.

**Parity folding (DESIGN.md §4):** M2L works at parent granularity.  The
leaf/level grid is relayouted into four child-parity planes stacked along
the coefficient axis — a ``(ny/2, nx/2, 4p)`` "parent-plane" grid — and the
whole 40-offset masked reduction collapses to 8 shifted matmuls against the
parent-neighbor block operator, whose zero blocks *are* the parity masks.
Every box receives exactly its 27 valid interactions; nothing is computed
and thrown away, and the halo needed from neighbors shrinks from ±3 child
rows to ±1 parent row (= 2 child rows).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .quadtree import (M2L_OFFSETS, M2L_VALIDITY, PARENT_NEIGH8, box_size)

# Child offsets within a parent, (cy, cx) in {0,1}^2; delta_hat = (c_child -
# c_parent) / r_parent = ((cx - .5)/2, (cy - .5)/2).
CHILD_OFFSETS = [(cy, cx) for cy in range(2) for cx in range(2)]

# The translation contractions run in full f32: at default precision a
# TPU does f32 dots in one bf16 pass, which the p = 17 error bound can't
# absorb.
_HIGHEST = jax.lax.Precision.HIGHEST


def _binom_table(n: int) -> np.ndarray:
    c = np.zeros((n, n), dtype=np.float64)
    c[:, 0] = 1.0
    for i in range(1, n):
        for j in range(1, i + 1):
            c[i, j] = c[i - 1, j - 1] + c[i - 1, j]
    return c


@functools.lru_cache(maxsize=None)
def m2m_operator(p: int) -> np.ndarray:
    """(4, p, p) tensor: ahat_parent[m] = sum_k Op[c, m, k] ahat_child[k].

    Op[c, m, k] = C(m, k) * dhat_c^(m-k) * 2^-k   (k <= m), with
    dhat_c = (child center - parent center) / r_parent.
    """
    C = _binom_table(p)
    op = np.zeros((4, p, p), dtype=np.complex128)
    for ci, (cy, cx) in enumerate(CHILD_OFFSETS):
        dhat = ((cx - 0.5) / 2.0) + 1j * ((cy - 0.5) / 2.0)
        for m in range(p):
            for k in range(m + 1):
                op[ci, m, k] = C[m, k] * dhat ** (m - k) * 2.0 ** (-k)
    return op


@functools.lru_cache(maxsize=None)
def l2l_operator(p: int) -> np.ndarray:
    """(4, p, p) tensor: bhat_child[m] = sum_l Op[c, m, l] bhat_parent[l].

    Op[c, m, l] = 2^-m * C(l, m) * dhat_c^(l-m)   (l >= m).
    """
    C = _binom_table(p)
    op = np.zeros((4, p, p), dtype=np.complex128)
    for ci, (cy, cx) in enumerate(CHILD_OFFSETS):
        dhat = ((cx - 0.5) / 2.0) + 1j * ((cy - 0.5) / 2.0)
        for m in range(p):
            for l in range(m, p):
                op[ci, m, l] = 2.0 ** (-m) * C[l, m] * dhat ** (l - m)
    return op


@functools.lru_cache(maxsize=None)
def m2l_operator(p: int) -> np.ndarray:
    """(40, p, p) tensor: bhat_tgt[l] = (1/r) sum_k Op[o, l, k] ahat_src[k].

    For source at integer offset d = (dx, dy) from the target (in units of
    the level box size), dhat = c_src - c_tgt (normalized) = dx + 1j*dy and

        Op[o, l, k] = (-1)^(k+1) * C(k+l, l) * dhat^-(k+l+1).
    """
    C = _binom_table(2 * p)
    op = np.zeros((len(M2L_OFFSETS), p, p), dtype=np.complex128)
    for oi, (dx, dy) in enumerate(M2L_OFFSETS):
        dhat = float(dx) + 1j * float(dy)
        for l in range(p):
            for k in range(p):
                op[oi, l, k] = (-1.0) ** (k + 1) * C[k + l, l] * dhat ** (-(k + l + 1))
    return op


# ---------------------------------------------------------------------------
# Stage implementations (pure jnp; dense level grids).
# Grids: me / le at level l have shape (n, n, p), n = 2**l, row-major (iy,ix).
# ---------------------------------------------------------------------------


def _zhat(z: jnp.ndarray, mask: jnp.ndarray, centers: jnp.ndarray,
          r: float) -> jnp.ndarray:
    """Slot offsets from the box center in box units, 0 in empty slots.

    An empty slot holds ``z = 0``, up to ``2**level`` box widths from its
    center; its ``p - 1``-th power overflows f32 from level 8 at p = 17,
    and a masked zero times ``inf`` is NaN.  Live slots sit inside their
    box, so ``|zhat| <= 1/sqrt(2)`` there.
    """
    return jnp.where(mask, (z - centers[..., None]) / r, 0.0)


def p2m(z: jnp.ndarray, q: jnp.ndarray, mask: jnp.ndarray, centers: jnp.ndarray,
        r: float, p: int, coeff: np.ndarray | None = None) -> jnp.ndarray:
    """Particles -> normalized MEs at the leaf level.  -> (n, n, p).

    ``coeff`` is an optional (p,) per-order charge map ``c_k`` (the
    equation spec's ``p2m_coeff``): ``ahat_k = c_k sum q zhat^k``.  None
    is the identity map of the velocity kernel.

    The moments accumulate one power at a time in f32 elementwise
    arithmetic: no ``(n, n, s, p)`` power stack (on a TPU each
    ``(n, n, s)`` plane is lane-padded to 128 slots) and no MXU
    contraction (bf16 passes at default precision).
    """
    zhat = _zhat(z, mask, centers, r)              # (n, n, s)
    term = jnp.where(mask, q, 0.0)                 # q zhat^k, k = 0
    moments = []
    for _ in range(p):
        moments.append(term.sum(axis=-1))
        term = term * zhat
    me = jnp.stack(moments, axis=-1)
    if coeff is not None:
        me = me * jnp.asarray(coeff, dtype=me.dtype)
    return me


def m2m(me_child: jnp.ndarray, p: int, op: np.ndarray | None = None
        ) -> jnp.ndarray:
    """Child level grid (2ny, 2nx, p) -> parent grid (ny, nx, p).

    Rectangular grids supported (row slabs under the parallel
    decomposition).  ``op`` overrides the (4, p, p) translation tensor
    (equation specs supply theirs; None is the velocity kernel's).
    """
    op = jnp.asarray(m2m_operator(p) if op is None else op,
                     dtype=me_child.dtype)
    ny, nx = me_child.shape[0] // 2, me_child.shape[1] // 2
    c = me_child.reshape(ny, 2, nx, 2, p)          # [py, cy, px, cx, k]
    # CHILD_OFFSETS order is (cy, cx) row-major -> index c = cy*2+cx
    c = c.transpose(0, 2, 1, 3, 4).reshape(ny, nx, 4, p)
    return jnp.einsum("yxck,cmk->yxm", c, op, precision=_HIGHEST)


def parity_mask(n: int, validity_o: np.ndarray) -> np.ndarray:
    """(n, n) bool mask from a (2, 2) [py, px] parity-validity table."""
    return parity_mask_rect(n, n, validity_o)


def parity_mask_rect(rows: int, cols: int, validity_o: np.ndarray,
                     row0: int = 0) -> np.ndarray:
    """(rows, cols) parity mask; ``row0`` is the global index of row 0."""
    iy = (np.arange(rows) + row0) % 2
    ix = np.arange(cols) % 2
    return validity_o[np.ix_(iy, ix)]


def m2l_masked40(me: jnp.ndarray, level: int, p: int) -> jnp.ndarray:
    """Dense M2L via 40 masked shifted matmuls (the pre-folding formulation).

    Kept as the independent oracle for the parity-folded path: every box
    computes all 40 candidate offsets and the parity masks discard ~1/3 of
    the work afterwards.  Do not use on the hot path.
    """
    n = me.shape[0]
    r = 2.0 ** (-level)
    ops = m2l_operator(p)
    pad = jnp.pad(me, ((3, 3), (3, 3), (0, 0)))
    le = jnp.zeros_like(me)
    for oi, (dx, dy) in enumerate(M2L_OFFSETS):
        src = pad[3 + dy:3 + dy + n, 3 + dx:3 + dx + n, :]
        op = jnp.asarray(ops[oi], dtype=me.dtype)
        contrib = jnp.einsum("yxk,lk->yxl", src, op)
        m = jnp.asarray(parity_mask(n, M2L_VALIDITY[oi]), dtype=me.dtype)
        le = le + contrib * m[..., None]
    return le / r


# ---------------------------------------------------------------------------
# Parity-folded M2L (parent granularity) — the hot path.
# ---------------------------------------------------------------------------

M2L_HALO = 2   # child rows/cols of ghost data needed by an even-aligned slab


def fold_operator(base: np.ndarray, p: int) -> np.ndarray:
    """Fold a (40, p, p) child-offset M2L operator ``[o, l, k]`` into the
    (8, 4p, 4p) parent-neighbor block operator.

    ``W[d, s*p + k, c*p + l]`` maps coefficient ``k`` of source child ``s``
    of parent-neighbor ``PARENT_NEIGH8[d]`` to coefficient ``l`` of target
    child ``c`` (children in CHILD_OFFSETS order).  Blocks for near-neighbor
    (child-distance < 2) pairs are structurally zero — these zeros are the
    parity masks, folded in.  Exactly 27 blocks per target child are
    nonzero, so the contraction performs exactly the valid interactions.
    The folding is purely geometric, so any equation's base operator
    (core/equations.py) folds the same way.
    """
    idx = {off: i for i, off in enumerate(M2L_OFFSETS)}
    W = np.zeros((8, 4 * p, 4 * p), dtype=np.complex128)
    for di, (Dx, Dy) in enumerate(PARENT_NEIGH8):
        for si, (sy, sx) in enumerate(CHILD_OFFSETS):
            for ci, (py, px) in enumerate(CHILD_OFFSETS):
                d = (2 * Dx + sx - px, 2 * Dy + sy - py)
                if max(abs(d[0]), abs(d[1])) >= 2:
                    # bhat_tgt[l] = sum_k Op[o, l, k] ahat_src[k]
                    W[di, si * p:(si + 1) * p, ci * p:(ci + 1) * p] = base[idx[d]].T
    return W


@functools.lru_cache(maxsize=None)
def m2l_folded_operator(p: int) -> np.ndarray:
    """The velocity kernel's folded block operator (see ``fold_operator``)."""
    return fold_operator(m2l_operator(p), p)


def to_parent_planes(grid: jnp.ndarray, p: int) -> jnp.ndarray:
    """(2R, 2C, p) even-aligned child grid -> (R, C, 4p) parent planes.

    Plane ``c = cy*2 + cx`` (CHILD_OFFSETS order) holds the child with local
    parity (cy, cx); row 0 of ``grid`` must have even global parity.
    """
    R, C = grid.shape[0] // 2, grid.shape[1] // 2
    g = grid.reshape(R, 2, C, 2, p).transpose(0, 2, 1, 3, 4)
    return g.reshape(R, C, 4 * p)


def from_parent_planes(stack: jnp.ndarray, p: int) -> jnp.ndarray:
    """(R, C, 4p) parent planes -> (2R, 2C, p) child grid (inverse layout)."""
    R, C = stack.shape[0], stack.shape[1]
    g = stack.reshape(R, C, 2, 2, p).transpose(0, 2, 1, 3, 4)
    return g.reshape(2 * R, 2 * C, p)


def m2l_slab_geometry(rows: int, row0: int, halo: int) -> tuple[int, int, int]:
    """Index algebra shared by the jnp and Pallas folded M2L paths.

    Returns ``(lo, PR, shift)``: ``lo`` is the local index (into the halo'd
    slab) of the first source child row, ``PR`` the number of parent rows
    covering the interior, ``shift`` the interior's offset within its first
    parent cell.  Raises if ``halo`` ghost rows cannot cover the ±1 parent
    source neighborhood (even-aligned even-length slabs need 2; odd
    alignment or odd length needs 3).
    """
    g0, g1 = row0, row0 + rows - 1
    Ps, Pe = g0 // 2, g1 // 2
    PR = Pe - Ps + 1
    shift = g0 - 2 * Ps
    lo = (2 * Ps - 2) - g0 + halo            # first needed source child row
    hi = (2 * Pe + 3) - g0 + halo            # last needed source child row
    if lo < 0 or hi > rows + 2 * halo - 1:
        raise ValueError(
            f"halo={halo} too small for rows={rows}, row0={row0}: the ±1 "
            f"parent source window needs rows [{lo}, {hi}] of the slab")
    return lo, PR, shift


def m2l_slab_stack(me_halo: jnp.ndarray, p: int, row0: int, halo: int,
                   col0: int = 0, col_halo: int = 0
                   ) -> tuple[jnp.ndarray, tuple[int, int], tuple[int, int]]:
    """Stage a halo'd slab (or 2-D tile) into the parent-plane layout.

    Shared, parity-critical front end of both the jnp and Pallas folded
    M2L paths: slices the ±1-parent source window out of the slab and
    relayouts to parent planes.  With ``col_halo=0`` the columns span the
    full (even) grid width and the ±1-parent column window is zero-padded
    here (row-slab and serial callers); with ``col_halo>0`` the slab
    carries exchanged column ghosts too (2-D tiles under ``shard_map``)
    and the same geometry algebra runs on the column axis, anchored at
    ``col0``.  Returns ``(stack, (PR, rshift), (PC, cshift))`` with
    ``stack`` of shape (PR+2, PC+2, 4p).
    """
    rows = me_halo.shape[0] - 2 * halo
    lo, PR, rshift = m2l_slab_geometry(rows, row0, halo)
    sub = jax.lax.slice_in_dim(me_halo, lo, lo + 2 * (PR + 2), axis=0)
    if col_halo == 0:
        cols = me_halo.shape[1]
        if cols % 2:
            raise ValueError("M2L slab columns must span the full (even) width")
        sub = jnp.pad(sub, ((0, 0), (2, 2), (0, 0)))
        PC, cshift = cols // 2, 0
    else:
        cols = me_halo.shape[1] - 2 * col_halo
        clo, PC, cshift = m2l_slab_geometry(cols, col0, col_halo)
        sub = jax.lax.slice_in_dim(sub, clo, clo + 2 * (PC + 2), axis=1)
    return to_parent_planes(sub, p), (PR, rshift), (PC, cshift)


def m2l_folded(me_halo: jnp.ndarray, level: int, p: int, row0: int = 0,
               halo: int = M2L_HALO, col0: int = 0,
               col_halo: int = 0, op: np.ndarray | None = None,
               scale: float | None = None) -> jnp.ndarray:
    """Parity-folded M2L over a slab/tile with ghost data attached.

    ``me_halo``: (rows + 2*halo, cols + 2*col_halo, p) — the interior plus
    ``halo`` ghost rows above and below and ``col_halo`` ghost columns left
    and right (zeros at domain edges, exchanged halos under ``shard_map``).
    With ``col_halo=0`` columns span the full grid width (even) and the
    column window is zero-padded internally.  ``row0``/``col0`` are the
    global indices of the first interior row/column and anchor the parity
    pattern; any alignment is supported given enough halo.  Returns the
    (rows, cols, p) LE slab.

    This is the single M2L implementation behind the serial driver, the
    sharded driver (1-D bands and 2-D tiles), and the jnp reference; the
    Pallas kernel (kernels/m2l.py) computes the same contraction tile by
    tile.  ``op``/``scale`` override the folded block operator and the
    dimension scalar (equation specs supply theirs — core/equations.py);
    the defaults are the velocity kernel's.
    """
    rows = me_halo.shape[0] - 2 * halo
    cols = me_halo.shape[1] - 2 * col_halo
    stack, (PR, rshift), (PC, cshift) = m2l_slab_stack(me_halo, p, row0, halo,
                                                       col0, col_halo)
    W = m2l_folded_operator(p) if op is None else op
    if scale is None:
        scale = float(2.0 ** level)          # 1 / box_size(level), exact
    acc = jnp.zeros((PR, PC, 4 * p), dtype=me_halo.dtype)
    for d, (Dx, Dy) in enumerate(PARENT_NEIGH8):
        src = stack[1 + Dy:1 + Dy + PR, 1 + Dx:1 + Dx + PC, :]
        acc = acc + jnp.einsum("yxa,ab->yxb", src,
                               jnp.asarray(W[d], dtype=me_halo.dtype),
                               precision=_HIGHEST)
    le = from_parent_planes(acc, p)                        # (2PR, 2PC, p)
    le = jax.lax.slice_in_dim(le, rshift, rshift + rows, axis=0)
    le = jax.lax.slice_in_dim(le, cshift, cshift + cols, axis=1)
    return le * scale


def m2l_reference(me: jnp.ndarray, level: int, p: int) -> jnp.ndarray:
    """Dense M2L over a full (n, n, p) grid — parity-folded jnp path."""
    me_halo = jnp.pad(me, ((M2L_HALO, M2L_HALO), (0, 0), (0, 0)))
    return m2l_folded(me_halo, level, p, row0=0, halo=M2L_HALO)


def l2l(le_parent: jnp.ndarray, p: int) -> jnp.ndarray:
    """Parent grid (ny, nx, p) -> child grid (2ny, 2nx, p)."""
    op = jnp.asarray(l2l_operator(p), dtype=le_parent.dtype)
    ny, nx = le_parent.shape[0], le_parent.shape[1]
    c = jnp.einsum("yxl,cml->yxcm", le_parent, op,
                   precision=_HIGHEST)                 # (ny, nx, 4, m)
    c = c.reshape(ny, nx, 2, 2, p).transpose(0, 2, 1, 3, 4)
    return c.reshape(2 * ny, 2 * nx, p)


def l2p_eval(le: jnp.ndarray, z: jnp.ndarray, mask: jnp.ndarray,
             centers: jnp.ndarray, r: float, p: int,
             modes: tuple[str, ...] = ("value",)) -> jnp.ndarray:
    """Evaluate leaf LEs at (source or target) positions, per channel.

    ``mask`` marks the occupied slots; empty slots are evaluated at their
    box center, so their output is finite (and masked by the caller).

    ``modes`` is the equation spec's ``l2p_modes``; each entry emits one
    complex channel: ``"value"`` is the LE polynomial itself (the velocity
    for the vortex kernel, the complex potential for Laplace) and
    ``"ngrad"`` its negated z-derivative ``-(1/r) sum_l l bhat_l
    zhat^(l-1)`` (the Laplace field).  Returns (n, n, s) for one mode,
    (n, n, s, len(modes)) otherwise.

    Each polynomial is evaluated by Horner's rule in f32 elementwise
    arithmetic over ``(n, n, s)`` planes (see :func:`p2m` for why).
    """
    zhat = _zhat(z, mask, centers, r)              # (n, n, s)

    def horner(coef):                              # sum_l coef_l zhat^l
        acc = jnp.broadcast_to(coef[..., -1:], zhat.shape)
        for l in range(coef.shape[-1] - 2, -1, -1):
            acc = acc * zhat + coef[..., l:l + 1]
        return acc

    outs = []
    for mode in modes:
        if mode == "value":
            outs.append(horner(le))
        elif mode == "ngrad":
            lw = jnp.arange(1, p, dtype=le.real.dtype)
            outs.append(-horner(le[..., 1:] * lw) / r)
        else:
            raise ValueError(f"unknown l2p mode {mode!r}")
    return outs[0] if len(outs) == 1 else jnp.stack(outs, axis=-1)


# -- Expansion evaluation helpers (unit tests / debugging) ------------------


def eval_me(ahat: np.ndarray, center: complex, r: float, z: np.ndarray) -> np.ndarray:
    """Evaluate a normalized ME at points z (far from the box)."""
    zh = (np.asarray(z) - center) / r
    out = np.zeros_like(zh, dtype=np.complex128)
    for k in range(len(ahat) - 1, -1, -1):
        out = (out + ahat[k]) / zh
    return out / r


def eval_le(bhat: np.ndarray, center: complex, r: float, z: np.ndarray) -> np.ndarray:
    """Evaluate a normalized LE at points z (inside the box)."""
    zh = (np.asarray(z) - center) / r
    out = np.zeros_like(zh, dtype=np.complex128)
    for l in range(len(bhat) - 1, -1, -1):
        out = out * zh + bhat[l]
    return out
