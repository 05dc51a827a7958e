"""Serial dense FMM driver (pure JAX, jit-able end to end).

Mirrors the paper's bird's-eye view (Fig 2): upward sweep (P2M, M2M),
downward sweep (M2L, L2L), evaluation (L2P + near-field P2P).  All stages
operate on dense level grids; see DESIGN.md §3 for the TPU-native layout.

The M2L and P2P hot paths go through ONE slab-oriented implementation each
(``m2l_slab_fn`` / ``p2p_slab_fn``): the serial driver attaches zero ghost
rows, the ``shard_map`` driver (core/parallel_fmm.py) attaches exchanged
halos — same math, same kernels, same parity-folded operators either way
(DESIGN.md §4-§5).

Every kernel-specific piece — P2M charge map, translation operators, M2L
dimension scalar, L2P evaluation modes, the P2P pair interaction, output
arity — comes from an :class:`~repro.core.equations.EquationSpec`
(DESIGN.md §10).  The drivers consume only the spec: there are no
equation-name branches here (grep-guarded in tests/test_equations.py).
``fmm_velocity`` is the vortex-kernel wrapper over the generic
``fmm_evaluate``; passing ``targets`` evaluates the sources' field at a
separate batch of passive target points (the ``tracer`` mode).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import equations as eqs
from . import expansions as ex
from . import health as hw
from .. import obs
from .quadtree import P2P_OFFSETS, Tree, box_centers, box_size


# ---------------------------------------------------------------------------
# Unified slab dispatchers — the one M2L / P2P path for both drivers.
# ---------------------------------------------------------------------------


def m2l_slab_fn(p: int, use_kernels: bool = False, eq=None):
    """Returns ``fn(me_halo, level, row0=0, halo=M2L_HALO, col0=0,
    col_halo=0) -> le_slab``.

    ``me_halo`` carries ``halo`` ghost rows top and bottom — and, when
    ``col_halo > 0``, ghost columns left and right (2-D tiles) — zeros at
    domain edges, exchanged halos under ``shard_map``; ``row0``/``col0``
    anchor the global parity.  Both the jnp path and the Pallas kernel path
    implement the same parity-folded contraction (exactly 27 interactions
    per box), with the block operator and dimension scalar supplied by the
    equation spec (vortex by default).
    """
    eq = eqs.get_equation(eq)
    if use_kernels:
        from ..kernels import ops as kops

        def fn(me_halo, level, row0=0, halo=ex.M2L_HALO, col0=0, col_halo=0):
            return kops.m2l_apply_slab(me_halo, level, p, row0=row0,
                                       halo=halo, col0=col0,
                                       col_halo=col_halo, eq=eq)
        return fn

    def fn(me_halo, level, row0=0, halo=ex.M2L_HALO, col0=0, col_halo=0):
        return ex.m2l_folded(me_halo, level, p, row0=row0, halo=halo,
                             col0=col0, col_halo=col_halo,
                             op=eq.m2l_folded(p, level),
                             scale=eq.m2l_scale(level))
    return fn


def m2l_grid_fn(p: int, use_kernels: bool = False, eq=None):
    """Grid form of ``m2l_slab_fn``: ``fn(grid, level)`` over a full
    (ny, nx, p) level grid, zero ghost rows attached here.  Used by the
    serial driver and for the replicated root-tree levels of the sharded
    driver."""
    slab = m2l_slab_fn(p, use_kernels, eq)
    hpad = ((ex.M2L_HALO, ex.M2L_HALO), (0, 0), (0, 0))

    def fn(grid, level):
        return slab(jnp.pad(grid, hpad), level)
    return fn


def p2p_slab_reference(z_halo, q_halo, mask_halo, sigma, z_tgt=None, eq=None):
    """Pure-jnp P2P over a slab with ±1 ghost rows/cols attached.

    ``z_tgt`` (rows, cols, st) evaluates the sources' field at separate
    target points instead of at the sources themselves (passive-target
    mode); None keeps source == target.  The pair interaction is the
    equation spec's ``p2p_terms`` — one formula shared with the Pallas
    kernel and the direct oracle.
    """
    eq = eqs.get_equation(eq)
    rows, cols = z_halo.shape[0] - 2, z_halo.shape[1] - 2
    zt = z_halo[1:1 + rows, 1:1 + cols] if z_tgt is None else z_tgt
    out = None
    for (dx, dy) in P2P_OFFSETS:
        zs = z_halo[1 + dy:1 + dy + rows, 1 + dx:1 + dx + cols]
        qs = q_halo[1 + dy:1 + dy + rows, 1 + dx:1 + dx + cols]
        ms = mask_halo[1 + dy:1 + dy + rows, 1 + dx:1 + dx + cols]
        w = eq.pairwise(zt, zs, qs, ms, sigma)
        out = w if out is None else out + w
    return out


def p2p_slab_fn(use_kernels: bool = False, eq=None):
    """Returns ``fn(z_halo, q_halo, mask_halo, sigma, z_tgt=None) -> w``
    over a slab with ±1 ghost rows/cols already attached; ``z_tgt`` selects
    passive-target evaluation (see ``p2p_slab_reference``)."""
    eq = eqs.get_equation(eq)
    if use_kernels:
        from ..kernels import ops as kops

        def fn(z_halo, q_halo, mask_halo, sigma, z_tgt=None):
            return kops.p2p_apply_slab(z_halo, q_halo, mask_halo, sigma,
                                       z_tgt=z_tgt, eq=eq)
        return fn

    def fn(z_halo, q_halo, mask_halo, sigma, z_tgt=None):
        return p2p_slab_reference(z_halo, q_halo, mask_halo, sigma,
                                  z_tgt=z_tgt, eq=eq)
    return fn


def p2p_chunk_reference(tgt, src, run_len, sigma, eq=None):
    """Pure-jnp P2P of chunk runs given as rows, as the kernel takes them:
    ``tgt`` (U, 2s) target positions (x, then y), ``src`` (U, R*5s) for
    each source chunk its positions, charges and occupancy, ``run_len``
    (U,) the live source chunks of each run.  Returns (2 * eq.nout, s, U):
    each channel's real and imaginary parts, from the equation's
    ``p2p_terms`` as in the kernel."""
    eq = eqs.get_equation(eq)
    U, two_s = tgt.shape
    s = two_s // 2
    R = src.shape[1] // (5 * s)
    t = tgt.T.reshape(2, s, 1, U)
    sx, sy, sqr, sqi, sm = jnp.transpose(src.reshape(U, R, 5, s),
                                         (2, 1, 3, 0)).reshape(5, 1, R * s, U)
    on = jnp.repeat(jnp.arange(R)[:, None] < run_len, s, axis=0)  # (R*s, U)
    ddx, ddy = t[0] - sx, t[1] - sy
    r2 = ddx * ddx + ddy * ddy
    moll = None if sigma is None else 1.0 - jnp.exp(-r2 / (2.0 * sigma
                                                            * sigma))
    terms = eq.p2p_terms(ddx, ddy, r2, (sm > 0) & on & (r2 > 0.0), sqr, sqi,
                         moll)
    return jnp.stack([x.sum(axis=1) for pair in terms for x in pair])


def p2p_chunk_fn(use_kernels: bool = False, eq=None):
    """Returns ``fn(tgt, src, run_len, sigma) -> planes`` over chunk runs:
    the Pallas chunk-pair kernel or its jnp reference."""
    eq = eqs.get_equation(eq)
    if use_kernels:
        from ..kernels import ops as kops
        return functools.partial(kops.p2p_apply_chunks, eq=eq)
    return functools.partial(p2p_chunk_reference, eq=eq)


# ---------------------------------------------------------------------------
# Interior/rim overlapped tile execution (DESIGN.md §9).
#
# A padded device tile is split into an INTERIOR — every box at least one
# halo width from each tile edge, whose stencil reads only local data — and
# four RIM strips along the edges, whose stencils read the exchanged ghost
# buffer.  The interior compute has no data dependency on the halo
# collectives, so the scheduler can hide the exchange behind it; the rim
# strips are computed from the buffer afterwards and stitched over the
# edges.  The serial driver is the degenerate zero-rim case of the same
# slab contract (no ghosts, interior == everything: ``m2l_grid_fn`` /
# ``near_field`` attach zero halos and run one monolithic slab), so there
# is still exactly one M2L / P2P formulation.
# ---------------------------------------------------------------------------


def m2l_tile_overlapped(m2l_slab, me_local: jnp.ndarray, me_buf: jnp.ndarray,
                        level: int, rows_valid, cols_valid) -> jnp.ndarray:
    """Interior/rim M2L over one padded tile.

    ``me_local`` is the (rmax, cmax, p) padded tile (padding rows/cols are
    zero); ``me_buf`` is the (rmax+2w, cmax+2w, p) two-axis halo buffer
    from ``_tile_halo`` (w = ``expansions.M2L_HALO``), with neighbors' data
    adjacent to the *valid* extents ``rows_valid``/``cols_valid`` (traced
    per-device scalars; tile origins and valid extents are parity-even at
    every sharded level, so ``row0=col0=0`` anchors every slice).  Returns
    the (rmax, cmax, p) LE tile; boxes outside the valid extents carry
    don't-care values exactly as in the monolithic path (masked out
    downstream).
    """
    w = ex.M2L_HALO
    rmax, cmax, p = me_local.shape
    le = jnp.zeros_like(me_local)
    if rmax > 2 * w and cmax > 2 * w:
        # interior: depends only on me_local -> overlappable with the
        # collectives filling me_buf
        interior = m2l_slab(me_local, level, halo=w, col_halo=w)
        le = jax.lax.dynamic_update_slice(le, interior, (w, w, 0))
    # rim strips: each strip's own w-halo is cut out of the exchanged
    # buffer (strip anchors stay parity-even, so row0=col0=0 holds)
    top = m2l_slab(jax.lax.slice_in_dim(me_buf, 0, 3 * w, axis=0),
                   level, halo=w, col_halo=w)                    # (w, cmax)
    bot = m2l_slab(jax.lax.dynamic_slice(
        me_buf, (rows_valid - w, 0, 0), (3 * w, cmax + 2 * w, p)),
        level, halo=w, col_halo=w)                               # (w, cmax)
    left = m2l_slab(jax.lax.slice_in_dim(me_buf, 0, 3 * w, axis=1),
                    level, halo=w, col_halo=w)                   # (rmax, w)
    right = m2l_slab(jax.lax.dynamic_slice(
        me_buf, (0, cols_valid - w, 0), (rmax + 2 * w, 3 * w, p)),
        level, halo=w, col_halo=w)                               # (rmax, w)
    le = jax.lax.dynamic_update_slice(le, left, (0, 0, 0))
    le = jax.lax.dynamic_update_slice(le, right, (0, cols_valid - w, 0))
    le = jax.lax.dynamic_update_slice(le, top, (0, 0, 0))
    le = jax.lax.dynamic_update_slice(le, bot, (rows_valid - w, 0, 0))
    return le


def p2p_tile_overlapped(p2p_slab, z, q, mask, z_buf, q_buf, m_buf,
                        rows_valid, cols_valid, sigma,
                        z_tgt=None) -> jnp.ndarray:
    """Interior/rim P2P over one padded tile (halo width 1).

    ``z/q/mask`` are the (rmax, cmax, s) local tile; ``*_buf`` the
    (rmax+2, cmax+2, s) exchanged particle buffers (one packed collective —
    see ``parallel_fmm``).  The interior pass reads the local tile as its
    own ±1 halo (the overlap-independent bulk: P2P dominates FMM runtime),
    the four rim strips read the buffer, and the strips are stitched over
    the edges.  ``z_tgt`` (rmax, cmax, st) switches to passive-target
    evaluation: targets are tile-local (no halo of their own), so the
    interior/rim split partitions the TARGET boxes and the same stitching
    applies.  Returns the (rmax, cmax, s|st[, C]) output tile.
    """
    rmax, cmax, s = z.shape
    zt = z if z_tgt is None else z_tgt
    st = zt.shape[2]

    def tgt_block(r0, c0, nr, nc):
        if z_tgt is None:
            return None
        return jax.lax.dynamic_slice(z_tgt, (r0, c0, 0), (nr, nc, st))

    # probe one strip call to learn the static output channel shape
    def run(zh, qh, mh, tgt):
        return p2p_slab(zh, qh, mh, sigma, z_tgt=tgt)

    trail = (rmax, cmax, st)
    out_sample_shape = None
    wout = None
    if rmax > 2 and cmax > 2:
        interior = run(z, q, mask, tgt_block(1, 1, rmax - 2, cmax - 2))
        out_sample_shape = interior.shape[3:]
        wout = jnp.zeros(trail + out_sample_shape, interior.dtype)
        zi = (0,) * len(out_sample_shape)
        wout = jax.lax.dynamic_update_slice(wout, interior, (1, 1, 0) + zi)

    def row_strip(r0, tr0):
        sl = lambda a: jax.lax.dynamic_slice(a, (r0, 0, 0), (3, cmax + 2, s))
        return run(sl(z_buf), sl(q_buf), sl(m_buf),
                   tgt_block(tr0, 0, 1, cmax))                   # (1, cmax)

    def col_strip(c0, tc0):
        sl = lambda a: jax.lax.dynamic_slice(a, (0, c0, 0), (rmax + 2, 3, s))
        return run(sl(z_buf), sl(q_buf), sl(m_buf),
                   tgt_block(0, tc0, rmax, 1))                   # (rmax, 1)

    west = col_strip(0, 0)
    if wout is None:
        out_sample_shape = west.shape[3:]
        wout = jnp.zeros(trail + out_sample_shape, west.dtype)
    zi = (0,) * len(out_sample_shape)
    wout = jax.lax.dynamic_update_slice(wout, west, (0, 0, 0) + zi)
    wout = jax.lax.dynamic_update_slice(wout, col_strip(cols_valid - 1,
                                                        cols_valid - 1),
                                        (0, cols_valid - 1, 0) + zi)
    wout = jax.lax.dynamic_update_slice(wout, row_strip(0, 0), (0, 0, 0) + zi)
    wout = jax.lax.dynamic_update_slice(wout, row_strip(rows_valid - 1,
                                                        rows_valid - 1),
                                        (rows_valid - 1, 0, 0) + zi)
    return wout


def upward_sweep(tree: Tree, p: int, eq=None) -> list[jnp.ndarray]:
    """Build normalized MEs for every level; returns me[l] for l=0..L.
    Spill chunks add their moments to their boxes' leaf expansions."""
    eq = eqs.get_equation(eq)
    L = tree.level
    me = [None] * (L + 1)
    with obs.scope("fmm.p2m"):
        centers = jnp.asarray(box_centers(L), dtype=tree.z.dtype)
        me[L] = ex.p2m(tree.z, tree.q, tree.mask, centers, box_size(L), p,
                       coeff=eq.p2m_coeff(p))
        sp = tree.spill
        if sp is not None:
            with obs.scope("fmm.spill"):
                me[L] = me[L] + _spill_moments(tree, centers, p, eq)
    mop = eq.m2m_operator(p)
    with obs.scope("fmm.m2m"):
        for l in range(L, 0, -1):
            me[l - 1] = ex.m2m(me[l], p, op=mop)
    return me


def downward_sweep(me: list[jnp.ndarray], p: int,
                   m2l_fn=None) -> list[jnp.ndarray]:
    """Build LEs for levels 2..L (levels 0-1 have empty interaction lists).

    L2L is the plain polynomial recentering of the local expansion, shared
    by every registered equation; the equation specifics live in ``m2l_fn``
    (built by ``m2l_grid_fn`` from the spec).
    """
    L = len(me) - 1
    m2l = m2l_fn or m2l_grid_fn(p)
    le = [None] * (L + 1)
    for l in range(2, L + 1):
        with obs.scope("fmm.m2l"):
            le[l] = m2l(me[l], l)
        if l > 2:
            with obs.scope("fmm.l2l"):
                le[l] = le[l] + ex.l2l(le[l - 1], p)
    return le


def near_field(tree: Tree, p2p_fn=None, z_tgt=None) -> jnp.ndarray:
    """P2P over the 3x3 stencil with the regularized kernel.

    ``z_tgt`` (n, n, st) evaluates at passive targets instead of the
    sources.  Returns (n, n, s|st[, C]).
    """
    slab = p2p_fn or p2p_slab_fn(use_kernels=False)
    pad = ((1, 1), (1, 1), (0, 0))
    with obs.scope("fmm.p2p"):
        return slab(jnp.pad(tree.z, pad), jnp.pad(tree.q, pad),
                    jnp.pad(tree.mask, pad), tree.sigma, z_tgt)


def _spill_moments(tree: Tree, centers, p: int, eq) -> jnp.ndarray:
    """The spill chunks' multipole moments about their boxes' centres,
    summed per box and laid on the (n, n, p) leaf grid.

    Both sums are gathers over static index lists (``Spill.box_chunks``,
    then ``window_box``) and the window is padded into place: a scatter
    into the grid runs update by update on a TPU (7.9 ms for 59,151 of
    them), and one into an (n*n, p) array fixes the layout of every
    level-L grid the sweeps derive from it, padded 64-fold."""
    sp = tree.spill
    n, L = tree.nside, tree.level
    y0, x0, wy, wx = sp.window
    cw = centers[y0:y0 + wy, x0:x0 + wx].reshape(wy * wx)
    mk = ex.p2m(sp.z, sp.q, sp.mask, cw[sp.chunk_wbox], box_size(L), p,
                coeff=eq.p2m_coeff(p))                        # (K, p)
    zero = jnp.zeros((1, p), mk.dtype)
    per_box = jnp.concatenate([mk, zero])[sp.box_chunks].sum(axis=1)
    win = jnp.concatenate([per_box, zero])[sp.window_box]     # (W, p)
    return jnp.pad(win.reshape(wy, wx, p),
                   ((y0, n - y0 - wy), (x0, n - x0 - wx), (0, 0)))


def spill_near_field(tree: Tree, chunk_fn) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The near field of every pair of neighbouring chunks with a spill
    chunk in it (``Spill``'s runs), by ``chunk_fn`` (``p2p_chunk_fn``).

    The window's dense boxes, the spill chunks and ``run`` empty chunks
    form one table, a row of ``5 s`` floats a chunk (x, y, Re q, Im q,
    mask), and ``run`` shifted copies of it side by side hold in row ``c``
    the chunks ``c .. c + run - 1``: each run is then one whole row, and
    its sources come in one row gather.  (A gather of partial rows, or of
    ``vmap``-ed dynamic slices, runs index by index on a TPU: 0.36 s for
    703,001 runs.)  The chunk kernel takes these rows as they come and
    puts the runs on the lanes itself, in VMEM: the output is ``(s, U)``
    planes, since a ``(U, s)`` array would pad its ``s`` slots to 128
    lanes on a TPU.  A target's runs are adjacent and its group gives
    their number, so their sum is a reshape, with no scatter.  Returns
    ``(dense, spill)``: the part to add to the dense grid's near field,
    (n, n, s[, C]), and the spill chunks' whole near field, (K, s[, C]).
    """
    sp = tree.spill
    n, s, R = tree.nside, tree.slots, sp.run
    y0, x0, wy, wx = sp.window
    with obs.scope("fmm.p2p"), obs.scope("fmm.spill"):
        def chunks(z, q, m):        # (chunks, 5 s) f32 rows
            return jnp.concatenate([z.real, z.imag, q.real, q.imag,
                                    m.astype(z.real.dtype)], axis=-1)
        win = lambda a: a[y0:y0 + wy, x0:x0 + wx].reshape(wy * wx, s)
        table = jnp.concatenate([
            chunks(win(tree.z), win(tree.q), win(tree.mask)),
            chunks(sp.z, sp.q, sp.mask),
            jnp.zeros((R, 5 * s), jnp.float32)])
        rows = table.shape[0] - R
        shifted = jnp.concatenate([table[r:r + rows] for r in range(R)],
                                  axis=1)
        src = shifted[sp.run_start]                           # (U, R*5s)
        tgt = table[:rows, :2 * s][sp.run_tgt]                # (U, 2s)
        w = chunk_fn(tgt, src, sp.run_len, tree.sigma)        # (2C, s, U)
        parts, at = [], 0
        for runs, targets in sp.groups:
            parts.append(w[..., at:at + runs * targets]
                         .reshape(w.shape[:2] + (targets, runs)).sum(-1))
            at += runs * targets
        acc = jnp.concatenate(parts + [jnp.zeros(w.shape[:2] + (1,),
                                                 w.dtype)], axis=-1)

        def cplx(planes):           # (2C, s, ...) -> (..., s[, C])
            c = planes[0::2] + 1j * planes[1::2]
            c = jnp.moveaxis(c, (0, 1), (-1, -2))
            return (c[..., 0] if c.shape[-1] == 1 else c).astype(tree.z.dtype)
        spill = cplx(jnp.take(acc, sp.spill_pos, axis=-1))
        dense = cplx(jnp.take(acc, sp.window_pos, axis=-1)
                     .reshape(acc.shape[:2] + (wy, wx)))
        pad = ((y0, n - y0 - wy), (x0, n - x0 - wx)) + \
            ((0, 0),) * (dense.ndim - 2)
        return jnp.pad(dense, pad), spill


def _mask_channels(mask, out):
    """Zero masked slots, broadcasting over trailing output channels."""
    m = mask if out.ndim == mask.ndim else mask[..., None]
    return jnp.where(m, out, 0.0)


@functools.partial(jax.jit, static_argnames=("p", "eq", "use_kernels",
                                             "with_health"))
def fmm_evaluate(tree: Tree, p: int, eq=None, use_kernels: bool = False,
                 targets: Tree | None = None, with_health: bool = False):
    """Complete FMM evaluation of any registered equation.

    Returns (n, n, s) complex for single-channel equations, or
    (n, n, s, eq.nout) with the spec's channel order (e.g. Laplace:
    potential value, field).  ``targets`` — a second :class:`Tree` at the
    same level holding passive target points (charges ignored) — switches
    to source != target evaluation: the output is per TARGET slot,
    (n, n, st[, C]).  ``use_kernels=True`` routes M2L and P2P through the
    Pallas kernels (interpret mode off-TPU); both routes share the
    parity-folded slab implementations above.

    A tree with spill chunks (``quadtree.Spill``) returns the pair
    ``(dense, spill)``: the dense grid's output as above and the chunks'
    (K, s[, C]); ``quadtree.gather_particle_values`` reads either form.
    The chunks' work -- their P2M and L2P, and the near field of every
    chunk pair with a spill chunk in it -- sits under the scope
    ``fmm.spill``; a dense tree traces none of it.  Passive targets take
    dense trees.

    ``with_health=True`` additionally returns a ``health.N_FIELDS`` int32
    health word computed inside the same program (non-finite sentinels on
    the leaf expansion coefficients and the masked output — the serial
    driver has no halo exchange, so that field stays 0); the result is then
    ``(out, health)`` with no extra host sync.
    """
    eq = eqs.get_equation(eq)
    if targets is None and eq.needs_targets:
        raise ValueError(f"equation {eq.name!r} requires a targets tree")
    if targets is not None and targets.level != tree.level:
        raise ValueError("targets tree level != source tree level")
    if targets is not None and (tree.spill is not None
                                or targets.spill is not None):
        raise ValueError("passive targets take dense trees: spill chunks "
                         "are evaluated at the sources only")
    sp = tree.spill
    if eq.q_is_real:
        # real-charge equations read only Re q, in BOTH drivers: the
        # sharded halo exchange drops the Im q plane, so projecting here
        # keeps serial == sharded even on a tree whose charges were built
        # with a mismatched (complex) charge_scale
        real = lambda q: (q.real + 0j).astype(q.dtype)
        if sp is not None:
            sp = dataclasses.replace(sp, q=real(sp.q))
        tree = dataclasses.replace(tree, q=real(tree.q), spill=sp)
    L = tree.level
    p2p = p2p_slab_fn(use_kernels, eq)
    zt = None if targets is None else targets.z
    out_mask = tree.mask if targets is None else targets.mask
    me = le = far = spill_far = None
    if L >= 2:
        # (tiny trees are all near field)
        m2l_fn = m2l_grid_fn(p, use_kernels, eq)
        me = upward_sweep(tree, p, eq)
        le = downward_sweep(me, p, m2l_fn=m2l_fn)
        z_eval = tree.z if targets is None else targets.z
        with obs.scope("fmm.l2p"):
            centers = jnp.asarray(box_centers(L), dtype=tree.z.dtype)
            far = ex.l2p_eval(le[L], z_eval, out_mask, centers, box_size(L),
                              p, eq.l2p_modes)
            if sp is not None:
                with obs.scope("fmm.spill"):
                    y0, x0, wy, wx = sp.window
                    at = lambda g: g[y0:y0 + wy, x0:x0 + wx].reshape(
                        wy * wx, -1)[sp.chunk_wbox]
                    spill_far = ex.l2p_eval(
                        at(le[L]), sp.z, sp.mask, at(centers)[:, 0],
                        box_size(L), p, eq.l2p_modes)
    near = near_field(tree, p2p_fn=p2p, z_tgt=zt)
    if sp is not None:
        dense_add, spill_near = spill_near_field(
            tree, p2p_chunk_fn(use_kernels, eq))
        near = near + dense_add
    with obs.scope("fmm.assemble"):
        out = _mask_channels(out_mask, near if far is None else far + near)
        if sp is not None:
            with obs.scope("fmm.spill"):
                spill_out = _mask_channels(
                    sp.mask, spill_near if far is None
                    else spill_far + spill_near)
    if not with_health:
        return out if sp is None else (out, spill_out)
    with obs.scope("fmm.health"):
        health = hw.empty()
        if me is not None:
            health = hw.with_flag(health, hw.F_COEFF,
                                  jnp.maximum(hw.nonfinite(me[L]),
                                              hw.nonfinite(le[L])))
        bad = hw.nonfinite(out, out_mask)
        if sp is not None:
            bad = jnp.maximum(bad, hw.nonfinite(spill_out, sp.mask))
        health = hw.with_flag(health, hw.F_VEL, bad)
    return (out if sp is None else (out, spill_out)), health


def fmm_velocity(tree: Tree, p: int, use_kernels: bool = False,
                 with_health: bool = False):
    """Complex velocity W = u - iv per slot — the vortex-kernel form of
    :func:`fmm_evaluate` (the registry's bit-compatible default)."""
    return fmm_evaluate(tree, p, eq=eqs.VORTEX, use_kernels=use_kernels,
                        with_health=with_health)


def fmm_velocity_singular(tree: Tree, p: int) -> jnp.ndarray:
    """FMM with the singular kernel also in the near field.

    Isolates pure series-truncation error: comparing against a singular
    direct sum measures the p-convergence of the expansions alone
    (no Type-I kernel-substitution error; cf. paper §7.1 and ref [8]).
    """
    return fmm_velocity(dataclasses.replace(tree, sigma=None), p)


def flops_estimate(tree_level: int, slots: int, p: int, eq=None,
                   grid: tuple[int, int] | None = None,
                   cut: int | None = None) -> dict:
    """Rough FLOP census per stage (used by benchmarks & cost-model checks).

    The M2L term counts 27 (p x p) apply-accumulates per box — and since
    the parity-folded implementation (expansions.m2l_folded) performs
    exactly the 27 valid interactions (structural zero blocks, no runtime
    masks), this is the work the hot path actually does, not just the
    useful fraction of a 40-offset masked sweep.  Consistency with
    cost_model.N_IL and the folded operator's block sparsity is asserted in
    tests/test_cost_model.py.

    The census reads the equation spec: P2P and L2P scale with the output
    arity ``eq.nout`` (the downward coefficient sweep is shared across
    channels, so M2M/M2L/L2L do not).  Alongside the flop stages (summed
    into ``total``) it reports the sharded driver's P2P exchange as the
    driver actually executes it since PR 4: ONE packed collective round of
    ``p2p_exchange_planes`` f32 planes (4 for real-charge equations, 5
    otherwise) costing ``p2p_exchange_collectives`` ppermutes on a
    ``grid=(Pr, Pc)`` device grid — not the three unfused (z, q, mask)
    rounds the pre-PR-4 census priced.  ``grid=None`` means serial (zero
    collectives).

    Since the substep pipeline (DESIGN.md §12) the census also reports
    the overlap windows the pipelined issue order opens: ``cut`` is the
    gather cut level (``plan.level - plan.sharded_depth()``; default 2),
    ``gather_overlap_flops`` is the sharded M2L work issued between the
    cut-level all_gather and its first consumption (the root-tree
    sweep), and ``p2p_prefetch_rounds`` counts packed exchange rounds
    issued a substep ahead of their consumer (1 per RK2 step when
    sharded, 0 serial).  These are windows, not extra work — they are
    NOT summed into ``total``.
    """
    eq = eqs.get_equation(eq)
    L, s, C = tree_level, slots, eq.nout
    nleaf = 4 ** L
    cmul = 6.0  # complex multiply-add ~ 6 real flops
    stages = {
        "p2m": nleaf * s * p * 2 * cmul,
        "m2m": sum(4 ** l for l in range(1, L + 1)) * p * p * cmul,
        "m2l": sum(4 ** l for l in range(2, L + 1)) * 27 * p * p * cmul,
        "l2l": sum(4 ** l for l in range(3, L + 1)) * p * p * cmul,
        "l2p": nleaf * s * p * 2 * cmul * C,
        "p2p": nleaf * 9 * s * s * 12.0 * C,
    }
    stages["total"] = sum(stages.values())
    planes = 4 if eq.q_is_real else 5
    if grid is None:
        collectives = 0
    else:
        collectives = 2 * int(grid[0] > 1) + 2 * int(grid[1] > 1)
    stages["p2p_exchange_planes"] = float(planes)
    stages["p2p_exchange_collectives"] = float(collectives)
    n = 1 << L
    stages["p2p_exchange_bytes"] = float(collectives * n * planes * s * 4)
    if cut is None:
        cut = min(2, L)
    stages["gather_overlap_flops"] = (
        0.0 if grid is None else
        sum(4 ** l for l in range(cut + 1, L + 1)) * 27 * p * p * cmul)
    stages["p2p_prefetch_rounds"] = 0.0 if grid is None else 1.0
    return stages
