"""Distributed FMM under ``shard_map`` (paper §4, TPU-native form).

Execution layout ("mode A", DESIGN.md §3/§7/§8): the leaf grid is sharded
into device tiles described by a static execution plan — either a 1-D
:class:`~repro.core.plan.SlabPlan` (contiguous, parity-even row bands) or a
2-D :class:`~repro.core.plan.BlockPlan` (a ``Pr x Pc`` tensor grid of
parity-even row-x-column tiles).  Both kinds execute through ONE body: a
slab is simply the ``Pr x 1`` special case of a block (``SlabPlan.as_block``),
so there are no duplicated drivers.  The plan is produced by the cost-model
partitioner (core/plan.py over core/partition.py), which makes the paper's
load balancer actually schedule the sharded execution instead of assuming
``n // P`` rows per device.  Levels deep enough that tile boundaries stay
aligned are sharded the same way; levels below the cut form the paper's
*root tree* and are replicated via one ``all_gather`` (the SPMD equivalent
of the paper's root-tree rank + broadcast, with no serial bottleneck).

Communication structure (maps 1:1 onto the paper's Fig 3):
  * M2M / L2L  — subtree <-> root tree only: the single all_gather at the
    cut level, reassembled across unequal tiles by static 2-D owner maps
    (paper: "no communication between subtrees" for these ops);
  * M2L        — lateral/diagonal neighbor tiles: ±2-row/column halo
    exchange per sharded level via ``lax.ppermute``, sliced at each tile's
    *valid* edges (parity folding shrinks the paper's ±3 child-box halo to
    ±1 parent line — DESIGN.md §4);
  * P2P        — neighbor particles: ±1-row/column halo of (z, q, mask),
    packed into ONE buffer so the exchange is a single ``_tile_halo`` round
    (4 ppermutes) instead of three (12) — ``_pack_particles``.

The two-axis exchange runs columns first, then rows *of the column-extended
strips*: because the tile grid is a tensor product, east/west neighbors own
my exact row range, so the row strips carry the freshly attached column
halos and the diagonal (corner) ghosts arrive with them — M2L's and P2P's
corner interactions are complete with two ppermute hops per axis and no
separate corner transfer.

Interior/rim overlap (DESIGN.md §9): with ``overlap=True`` the driver
issues every halo collective *first* (the packed P2P exchange before the
upward sweep, the per-level M2L exchanges before the root-tree work) and
computes each tile's interior — every box at least one halo width from the
tile edges, the overwhelming bulk of the work — from local data alone while
the collectives are in flight; only the thin rim strips along the tile
edges consume the exchanged buffers (``fmm.m2l_tile_overlapped`` /
``fmm.p2p_tile_overlapped``), and they are stitched over the interior.
``overlap=False`` keeps the paper's serial exchange-then-compute ordering;
the two orderings share the same slab implementations and agree to f32
roundoff.  ``plan.halo_volume`` prices the rim recompute and
``plan.plan_comm_cost`` the overlap-aware serial comm residue.

Substep pipelining (DESIGN.md §12) extends the frontier further:
``pipeline=True`` defers the cut-level all_gather's first consumption past
all sharded-level M2L compute (the gather hides behind the downward sweep
instead of serializing in front of the root tree), and
``parallel_fmm_p2p_prefetch`` lets the RK2 stepper issue the NEXT
substep's packed P2P exchange while the current substep's trailing work
finishes — the cross-substep double buffer, consumed via the
``p2p_halo`` argument.

M2L and P2P themselves are the SAME slab implementations the serial driver
uses (core/fmm.py: ``m2l_slab_fn`` / ``p2p_slab_fn``, column halos handled
by the shared ``expansions.m2l_slab_stack`` geometry); this module only
adds the halo exchanges, the tile padding, and the root-tree replication
around them.  Padded rows/columns carry ``mask=False`` and zero expansions
and are masked out of the result.

The cost model (core/cost_model.py) predicts these volumes and
``plan.halo_volume`` prices them per plan; the partitioner chooses the tile
decomposition and ``core/stepper.py`` closes the dynamic feedback loop.
"""
from __future__ import annotations

import functools
from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import equations as _eqs
from . import expansions as ex
from . import faults as _faults
from . import fmm
from . import health as hw
from .. import obs
from .plan import BlockPlan, SlabPlan, uniform_plan
from .quadtree import Tree, box_centers, box_size, require_dense

def _tile_halo(x: jnp.ndarray, width: int, rows_valid, cols_valid,
               axis_name: str, grid: tuple[int, int]) -> jnp.ndarray:
    """Attach ±``width`` ghost rows AND columns at the *valid* tile edges.

    ``x`` is a (rows_max, cols_max, ...) padded tile whose rows
    ``[0, rows_valid)`` and columns ``[0, cols_valid)`` are valid (padding
    is zero).  Returns (rows_max + 2w, cols_max + 2w, ...): my tile at
    offset ``(w, w)``, neighbors' edge data placed immediately adjacent to
    my valid extents (the upper/left neighbor's strips at offset 0, the
    lower/right neighbor's at ``w + rows_valid`` / ``w + cols_valid``).

    Columns are exchanged first; the row strips are then cut from the
    column-extended buffer, so they carry the column halos and the corner
    (diagonal-neighbor) ghosts ride along — no separate corner transfer.
    Domain-edge tiles receive zeros (consistent with the serial zero
    padding).  Devices are laid out ``d = i * Pc + j`` on the 1-D mesh
    axis; all four exchanges are single-hop ``ppermute``.

    A single-rank axis is degenerate: its ghost strips are structurally
    zero, so no collective is issued for it — and when the COLUMN axis is
    degenerate the row strips are shipped at raw width ``cmax`` instead of
    the column-extended ``cmax + 2w``, since the 2w extra columns would
    carry known zeros.  A ``Pr x 1`` slab therefore pays exactly one
    axis's ppermute round at minimal width (pinned by HLO-shape tests);
    the exchanged values are identical either way.
    """
    Pr, Pc = grid
    w = width
    rmax, cmax = x.shape[0], x.shape[1]
    trail = x.shape[2:]
    zi = (0,) * len(trail)
    # -- phase 1: columns (east/west neighbors own my exact row range) -----
    if Pc > 1:
        right_edge = jax.lax.dynamic_slice_in_dim(x, cols_valid - w, w, 1)
        left_edge = x[:, :w]
        # my right edge -> east neighbor's left halo, and vice versa
        recv_l = jax.lax.ppermute(right_edge, axis_name,
                                  [(i * Pc + j, i * Pc + j + 1)
                                   for i in range(Pr) for j in range(Pc - 1)])
        recv_r = jax.lax.ppermute(left_edge, axis_name,
                                  [(i * Pc + j, i * Pc + j - 1)
                                   for i in range(Pr) for j in range(1, Pc)])
        xc = jnp.zeros((rmax, cmax + 2 * w) + trail, x.dtype)
        xc = jax.lax.dynamic_update_slice_in_dim(xc, x, w, 1)
        xc = jax.lax.dynamic_update_slice_in_dim(xc, recv_l, 0, 1)
        xc = jax.lax.dynamic_update_slice_in_dim(xc, recv_r, w + cols_valid, 1)
        c0 = 0
    else:
        xc, c0 = x, w          # raw-width strips, placed at column offset w
    # -- phase 2: rows of the column-extended strips (corners ride along) --
    buf = jnp.zeros((rmax + 2 * w, cmax + 2 * w) + trail, x.dtype)
    buf = jax.lax.dynamic_update_slice(buf, xc, (w, c0) + zi)
    if Pr > 1:
        bot_edge = jax.lax.dynamic_slice_in_dim(xc, rows_valid - w, w, 0)
        top_edge = xc[:w]
        recv_t = jax.lax.ppermute(bot_edge, axis_name,
                                  [(d, d + Pc) for d in range((Pr - 1) * Pc)])
        recv_b = jax.lax.ppermute(top_edge, axis_name,
                                  [(d, d - Pc) for d in range(Pc, Pr * Pc)])
        buf = jax.lax.dynamic_update_slice(buf, recv_t, (0, c0) + zi)
        buf = jax.lax.dynamic_update_slice(buf, recv_b,
                                           (w + rows_valid, c0) + zi)
    return buf


def _pack_particles(z, q, mask, q_real: bool = False) -> jnp.ndarray:
    """Stack (z, q, mask) into ONE real (rows, cols, planes, s) buffer — so
    the P2P halo exchange is a single packed ``_tile_halo`` round (4
    ppermutes) instead of three (12).  The payload width is spec-dependent:
    planes are [Re z, Im z, Re q, Im q, mask] (5) for complex-charge
    equations, [Re z, Im z, Re q, mask] (4) when the equation spec declares
    ``q_is_real`` (e.g. Laplace charges).  f32 carries the complex64
    components and the bool mask exactly, so the round-trip is lossless."""
    planes = [z.real, z.imag, q.real]
    if not q_real:
        planes.append(q.imag)
    planes.append(mask.astype(jnp.float32))
    return jnp.stack(planes, axis=2)


def _unpack_particles(buf: jnp.ndarray, dtype, q_real: bool = False):
    """Inverse of :func:`_pack_particles` (on an exchanged, halo'd buffer)."""
    z = (buf[:, :, 0] + 1j * buf[:, :, 1]).astype(dtype)
    if q_real:
        q = (buf[:, :, 2] + 0j).astype(dtype)
        m = buf[:, :, 3] > 0.5
    else:
        q = (buf[:, :, 2] + 1j * buf[:, :, 3]).astype(dtype)
        m = buf[:, :, 4] > 0.5
    return z, q, m


def _parallel_fmm_body(z, q, mask, *extra, plan: BlockPlan, l_cut: int,
                       p: int, sigma, axis_name: str, use_kernels: bool,
                       overlap: bool, eq, pipeline: bool = False,
                       prefetched: bool = False, with_health: bool = False,
                       faults: tuple = ()):
    """Runs on each device over its padded (rows_max, cols_max, s) tile.

    ``overlap=True`` runs the interior/rim pipeline (DESIGN.md §9): every
    halo collective is issued before the compute that can hide it — the
    packed P2P exchange before the upward sweep, the per-level M2L
    exchanges before the root-tree work — and each exchanged buffer is
    consumed only by the thin rim strips, while the tile interiors (the
    bulk of the work) depend on local data alone.  ``overlap=False`` keeps
    the monolithic ordering: each exchange completes into a buffer the
    whole tile's compute then reads (the paper's serial comm-plus-compute
    model, Eqs 16-20).  Both orderings share the identical slab
    implementations and agree to f32 roundoff.

    ``pipeline=True`` additionally defers the first CONSUMPTION of the
    cut-level ``all_gather`` (DESIGN.md §12): every sharded level's M2L
    output is computed right after the gather is issued — it depends only
    on local MEs and the per-level exchanges — so the gather's flight time
    hides behind the bulk of the downward sweep instead of serializing in
    front of the replicated root tree; the root-tree sweep then runs at
    the gathered buffer's first use and the precomputed M2L outputs fold
    into the L2L chain unchanged (same adds, same order: the two orderings
    trace the same ops).  ``pipeline=False`` traces exactly the pre-§12
    program.

    ``prefetched=True`` means the LAST positional argument is the packed
    P2P halo buffer already exchanged by
    :func:`parallel_fmm_p2p_prefetch` (the cross-substep double buffer);
    the body then skips its own exchange round but still applies fault
    injection and the health sentinel to the buffer, so the guarded paths
    see identical data either way.

    Everything kernel-specific — charge map, translation operators, packed
    P2P payload width, L2P modes, output arity — comes from the equation
    spec ``eq``; ``targets``, when present, is the ``(z_t, mask_t)`` pair
    of a passive target tile evaluated against the sources' expansions and
    near field (same plan, same halos).
    """
    extra = list(extra)
    p2p_pre = extra.pop() if prefetched else None
    zt, mt = extra if extra else (None, None)
    L = plan.level
    Pr, Pc = plan.grid
    rows_max, cols_max = plan.rows_max, plan.cols_max
    dtype = z.dtype
    if eq.q_is_real:
        # the packed halo drops the Im q plane; project the LOCAL charges
        # too so interior and rim interactions read identical data even
        # when the tree was built with a mismatched complex charge_scale
        # (serial fmm_evaluate applies the same projection)
        q = (q.real + 0j).astype(dtype)

    m2l_slab = fmm.m2l_slab_fn(p, use_kernels, eq)
    m2l_grid = fmm.m2l_grid_fn(p, use_kernels, eq)
    p2p_slab = fmm.p2p_slab_fn(use_kernels, eq)

    # static per-device tile records, looked up by device index
    with obs.scope("fmm.exchange"):
        di = jax.lax.axis_index(axis_name)
        dev = np.arange(Pr * Pc)
        my_row0 = jnp.asarray(np.asarray(plan.row0, np.int32)[dev // Pc])[di]
        my_rows = jnp.asarray(np.asarray(plan.rows, np.int32)[dev // Pc])[di]
        my_col0 = jnp.asarray(np.asarray(plan.col0, np.int32)[dev % Pc])[di]
        my_cols = jnp.asarray(np.asarray(plan.cols, np.int32)[dev % Pc])[di]

    def halo(x, width, rows_valid, cols_valid):
        with obs.scope("fmm.exchange"):
            return _tile_halo(x, width, rows_valid, cols_valid, axis_name,
                              (Pr, Pc))

    # ---- P2P halo: ONE packed exchange round (z, q, mask ride together) ---
    # Issued first under ``overlap`` so the collective is in flight through
    # the entire upward sweep; only the rim strips of the near field read
    # it.  The payload width is spec-dependent (real-charge equations drop
    # the Im q plane); targets are tile-local and exchange nothing.  A
    # prefetched buffer (the cross-substep double buffer, DESIGN.md §12)
    # replaces the exchange but not the fault/health plumbing downstream.
    with obs.scope("fmm.exchange"):
        if p2p_pre is not None:
            p2p_buf = p2p_pre
        else:
            p2p_buf = halo(_pack_particles(z, q, mask, eq.q_is_real), 1,
                           my_rows, my_cols)
        p2p_buf = _faults.corrupt_halo(p2p_buf, faults, di, (Pr, Pc))
    with obs.scope("fmm.health"):
        halo_bad = hw.nonfinite(p2p_buf) if with_health else None
    with obs.scope("fmm.exchange"):
        z_buf, q_buf, m_buf = _unpack_particles(p2p_buf, dtype,
                                                eq.q_is_real)

    # ---- upward sweep -----------------------------------------------------
    # Padding rows/cols have mask=False everywhere, so their MEs are exactly
    # zero and M2M keeps them zero at every coarser tile level.
    with obs.scope("fmm.p2m"):
        # centers padded below/right so the dynamic slice never clamps
        centers = jnp.asarray(box_centers(L), dtype=dtype)
        centers = jnp.pad(centers, ((0, rows_max), (0, cols_max)))
        my_centers = jax.lax.dynamic_slice(centers, (my_row0, my_col0),
                                           (rows_max, cols_max))
        me = {L: ex.p2m(z, q, mask, my_centers, box_size(L), p,
                        coeff=eq.p2m_coeff(p))}
    mop = eq.m2m_operator(p)
    with obs.scope("fmm.m2m"):
        for lv in range(L, l_cut, -1):
            me[lv - 1] = ex.m2m(me[lv], p, op=mop)

    # overlap: issue every sharded level's M2L exchange now, before the
    # root-tree gather/compute and the tile interiors that can hide them
    me_bufs = {}
    if overlap:
        for lv in range(l_cut + 1, L + 1):
            shift = L - lv
            me_bufs[lv] = halo(me[lv], ex.M2L_HALO, my_rows >> shift,
                               my_cols >> shift)
            if with_health:
                with obs.scope("fmm.health"):
                    halo_bad = jnp.maximum(halo_bad,
                                           hw.nonfinite(me_bufs[lv]))

    # gather the cut level -> replicated root tree (paper's M2M to root);
    # unequal tiles are reassembled by the plan's static 2-D owner maps.
    cut_shift = L - l_cut
    with obs.scope("fmm.exchange"):
        gathered = jax.lax.all_gather(me[l_cut], axis_name, axis=0,
                                      tiled=False)

    def sharded_m2l(lv, bad):
        """One sharded level's M2L: interior+rim under ``overlap``, else the
        monolithic exchange-then-slab (local MEs only — no gather input)."""
        shift = L - lv
        with obs.scope("fmm.exchange"):
            rv, cv = my_rows >> shift, my_cols >> shift
        if overlap:
            with obs.scope("fmm.m2l"):
                return fmm.m2l_tile_overlapped(m2l_slab, me[lv], me_bufs[lv],
                                               lv, rv, cv), bad
        me_buf = halo(me[lv], ex.M2L_HALO, rv, cv)
        if with_health:
            with obs.scope("fmm.health"):
                bad = jnp.maximum(bad, hw.nonfinite(me_buf))
        with obs.scope("fmm.m2l"):
            return m2l_slab(me_buf, lv, col_halo=ex.M2L_HALO), bad

    # pipeline (DESIGN.md §12): consume NOTHING from the gather yet — every
    # sharded level's M2L reads only local MEs and the per-level exchanges,
    # so this bulk compute hides the all_gather's flight time.  The outputs
    # fold into the L2L chain below with the same adds in the same order.
    le_m2l: dict[int, jnp.ndarray] = {}
    if pipeline:
        for lv in range(l_cut + 1, L + 1):
            le_m2l[lv], halo_bad = sharded_m2l(lv, halo_bad)

    # first consumption of the gathered buffer: the replicated root tree
    owner, loc_r, loc_c = plan.tile_maps(cut_shift)
    with obs.scope("fmm.exchange"):
        me_cut_full = gathered[jnp.asarray(owner), jnp.asarray(loc_r),
                               jnp.asarray(loc_c)]
    me_rep = {l_cut: me_cut_full}
    with obs.scope("fmm.m2m"):
        for lv in range(l_cut, 0, -1):
            me_rep[lv - 1] = ex.m2m(me_rep[lv], p, op=mop)

    # ---- downward sweep ---------------------------------------------------
    # replicated root-tree levels 2 .. l_cut (same folded path, zero ghosts)
    le_rep: dict[int, jnp.ndarray] = {}
    for lv in range(2, l_cut + 1):
        with obs.scope("fmm.m2l"):
            le_rep[lv] = m2l_grid(me_rep[lv], lv)
        if lv > 2:
            with obs.scope("fmm.l2l"):
                le_rep[lv] = le_rep[lv] + ex.l2l(le_rep[lv - 1], p)

    def slice_tile(grid_lv, shift):
        """My padded tile out of a replicated level grid."""
        rmax, cmax = rows_max >> shift, cols_max >> shift
        padded = jnp.pad(grid_lv, ((0, rmax), (0, cmax)) +
                         ((0, 0),) * (grid_lv.ndim - 2))
        return jax.lax.dynamic_slice(
            padded, (my_row0 >> shift, my_col0 >> shift) +
            (0,) * (grid_lv.ndim - 2),
            (rmax, cmax) + grid_lv.shape[2:])

    # sharded levels l_cut+1 .. L: exchange ±M2L_HALO ghost rows/columns at
    # the valid tile edges, then the identical slab implementation.  Tiles
    # are even-aligned on both axes at every sharded level (plan parity +
    # sharded_depth), so row0=col0=0 anchors the correct parity and the
    # 2-line halo suffices.
    le_prev = None  # my tile's LE at the previous (coarser) level
    if L > l_cut:
        with obs.scope("fmm.l2l"):
            le_prev = slice_tile(le_rep[l_cut], cut_shift)
    for lv in range(l_cut + 1, L + 1):
        if pipeline:
            le_lv = le_m2l[lv]
        else:
            le_lv, halo_bad = sharded_m2l(lv, halo_bad)
        with obs.scope("fmm.l2l"):
            le_lv = le_lv + ex.l2l(le_prev, p)
        le_prev = le_lv
    with obs.scope("fmm.l2l"):
        le_leaf = le_prev if L > l_cut else slice_tile(le_rep[L], 0)

    # ---- evaluation -------------------------------------------------------
    z_eval, m_eval = (z, mask) if zt is None else (zt, mt)
    with obs.scope("fmm.l2p"):
        far = ex.l2p_eval(le_leaf, z_eval, m_eval, my_centers, box_size(L),
                          p, eq.l2p_modes)
    with obs.scope("fmm.p2p"):
        if overlap:
            near = fmm.p2p_tile_overlapped(p2p_slab, z, q, mask,
                                           z_buf, q_buf, m_buf,
                                           my_rows, my_cols, sigma, z_tgt=zt)
        else:
            near = p2p_slab(z_buf, q_buf, m_buf, sigma, zt)
    with obs.scope("fmm.assemble"):
        # padded rows/cols (mask=False) are dropped here
        out = fmm._mask_channels(m_eval, far + near)
        out = _faults.corrupt_tile(out, faults, di)
    if not with_health:
        return out
    # per-device health word (flags only at driver level); the caller
    # reduces the stacked (P, N_FIELDS) output with the merge semantics
    with obs.scope("fmm.health"):
        health = hw.empty()
        health = hw.with_flag(health, hw.F_HALO, halo_bad)
        health = hw.with_flag(health, hw.F_COEFF,
                              jnp.maximum(hw.nonfinite(me[L]),
                                          hw.nonfinite(le_leaf)))
        health = hw.with_flag(health, hw.F_VEL, hw.nonfinite(out, m_eval))
    return out, health


@functools.partial(jax.jit, static_argnames=("p", "mesh", "mesh_axis",
                                             "use_kernels", "plan",
                                             "overlap", "eq", "with_health",
                                             "faults", "pipeline"))
def parallel_fmm_evaluate(tree: Tree, p: int, mesh: Optional[Mesh] = None,
                          mesh_axis: str = "data",
                          use_kernels: bool = False,
                          plan: Optional[Union[SlabPlan, BlockPlan]] = None,
                          overlap: bool = True, eq=None,
                          targets: Optional[Tree] = None,
                          with_health: bool = False,
                          faults: tuple = (), pipeline: bool = True,
                          p2p_halo: Optional[jnp.ndarray] = None):
    """Distributed FMM evaluation of any registered equation, plan-driven.

    ``plan`` maps devices to contiguous parity-even leaf-row bands
    (:class:`SlabPlan`) or row-x-column tiles (:class:`BlockPlan`) — the
    cost-model partitioner's output; ``plan=None`` falls back to the
    uniform equal-count band strawman (``uniform_plan`` handles any device
    count, including non-dividing P, via base/extra parent rows).  The tree
    is resharded into the plan's padded tile layout, evaluated under
    ``shard_map``, and scattered back to standard layout, so the result is
    independent of the plan to f32 roundoff.  Falls back to a 1-device mesh
    when ``mesh`` is None.  ``use_kernels=True`` routes M2L/P2P through the
    same Pallas kernels the serial driver uses (interpret mode off-TPU) on
    both plan kinds.  ``overlap=True`` (default) executes the interior/rim
    pipeline that hides the halo collectives behind tile-interior compute;
    ``overlap=False`` keeps the monolithic exchange-then-compute ordering.
    Both agree to f32 roundoff on both plan kinds and kernel routes.

    ``eq`` selects the registered equation spec (vortex default); the
    drivers consume only the spec.  ``targets`` — a second :class:`Tree`
    at the same level holding passive target points — is resharded by the
    SAME plan and evaluated against the sources' local expansions and near
    field; the output is then per target slot, (n, n, st[, eq.nout]).

    ``with_health=True`` returns ``(out, health)`` with a global
    ``health.N_FIELDS`` int32 health word: non-finite sentinels on the
    exchanged halo buffers, the expansion coefficients, and the masked
    output, computed per device inside the shard_map body and reduced in
    the same program — the guard costs no extra host sync.  ``faults`` is
    the static tuple of active :class:`~repro.core.faults.FaultSpec`s
    (empty = the exact injection-free program).

    ``pipeline=True`` (default) extends the overlap frontier (DESIGN.md
    §12): the cut-level all_gather's first consumption is deferred past
    all sharded-level M2L compute.  ``pipeline=False`` traces exactly the
    pre-§12 ordering (the bit-identical escape hatch).  ``p2p_halo``, when
    given, is the already-exchanged packed particle buffer from
    :func:`parallel_fmm_p2p_prefetch` (the cross-substep double buffer, in
    device-tile layout): the body consumes it instead of issuing its own
    exchange round.
    """
    require_dense(tree, "parallel_fmm_evaluate")
    eq = _eqs.get_equation(eq)
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    P_ = mesh.shape[mesh_axis]
    n = tree.nside
    if tree.level < 2:
        raise ValueError("parallel FMM requires tree level >= 2")
    if targets is None and eq.needs_targets:
        raise ValueError(f"equation {eq.name!r} requires a targets tree")
    if targets is not None and targets.level != tree.level:
        raise ValueError("targets tree level != source tree level")
    if plan is None:
        plan = uniform_plan(tree.level, P_)
    if plan.level != tree.level:
        raise ValueError(f"plan level {plan.level} != tree level {tree.level}")
    if plan.nparts != P_:
        raise ValueError(f"plan has {plan.nparts} bands for {P_} devices")
    block = plan.as_block() if isinstance(plan, SlabPlan) else plan

    rows_max, cols_max = block.rows_max, block.cols_max
    identity = (block.grid[1] == 1 and block.is_uniform
                and P_ * rows_max == n)
    if identity:
        z_sh, q_sh, m_sh = tree.z, tree.q, tree.mask
        t_sh = () if targets is None else (targets.z, targets.mask)
    else:
        with obs.scope("fmm.assemble"):
            src_r, src_c, valid = block.gather_index()
            src_r, src_c = jnp.asarray(src_r), jnp.asarray(src_c)
            v = jnp.asarray(valid)[:, :, None]
            z_sh = jnp.where(v, tree.z[src_r, src_c], 0)
            q_sh = jnp.where(v, tree.q[src_r, src_c], 0)
            m_sh = tree.mask[src_r, src_c] & v
            t_sh = () if targets is None else (
                jnp.where(v, targets.z[src_r, src_c], 0),
                targets.mask[src_r, src_c] & v)

    l_cut = block.level - block.sharded_depth()
    pre = () if p2p_halo is None else (p2p_halo,)
    if pre:
        planes = 4 if eq.q_is_real else 5
        want = (P_ * (rows_max + 2), cols_max + 2, planes, tree.slots)
        if tuple(p2p_halo.shape) != want:
            raise ValueError(f"p2p_halo shape {tuple(p2p_halo.shape)} does "
                             f"not match plan/equation (expected {want})")
    body = functools.partial(_parallel_fmm_body, plan=block, l_cut=l_cut, p=p,
                             sigma=tree.sigma, axis_name=mesh_axis,
                             use_kernels=use_kernels, overlap=overlap, eq=eq,
                             pipeline=pipeline, prefetched=bool(pre),
                             with_health=with_health, faults=faults)
    spec = P(mesh_axis, None, None)
    out_spec = spec if eq.nout == 1 else P(mesh_axis, None, None, None)
    if with_health:
        out_spec = (out_spec, P(mesh_axis))
    # pallas_call has no shard_map replication rule; disable the check on
    # the kernel route (numerics are unaffected — outputs stay sharded).
    pre_spec = (P(mesh_axis, None, None, None),) * len(pre)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec,) * (3 + len(t_sh)) + pre_spec,
                       out_specs=out_spec, check_vma=not use_kernels)
    if with_health:
        w, h = fn(z_sh, q_sh, m_sh, *t_sh, *pre)
        with obs.scope("fmm.health"):
            health = hw.device_combine(h.reshape(P_, hw.N_FIELDS))
    else:
        w = fn(z_sh, q_sh, m_sh, *t_sh, *pre)
    if not identity:
        with obs.scope("fmm.assemble"):
            sct_r, sct_c = block.scatter_index()
            w = w[jnp.asarray(sct_r), jnp.asarray(sct_c)]
    return (w, health) if with_health else w


@functools.partial(jax.jit, static_argnames=("mesh", "mesh_axis", "plan",
                                             "eq"))
def parallel_fmm_p2p_prefetch(tree: Tree, mesh: Optional[Mesh] = None,
                              mesh_axis: str = "data",
                              plan: Optional[Union[SlabPlan,
                                                   BlockPlan]] = None,
                              eq=None) -> jnp.ndarray:
    """Issue ONLY the packed (z, q, mask) P2P halo exchange for ``tree``.

    The cross-substep double buffer (DESIGN.md §12): the RK2 stepper calls
    this the moment substep k+1's rebinned particles exist — while substep
    k's trailing reductions are still pending — and hands the result to
    :func:`parallel_fmm_evaluate` via ``p2p_halo``, which then consumes the
    buffer instead of issuing its own round.  Under an async-collective
    backend the exchange's flight time hides behind everything traced
    between issue and first rim use (the guard reductions, the next
    evaluation's resharding and upward sweep).  The exchanged bytes are
    identical to the inline round — fault injection and the health
    sentinel are applied by the CONSUMER, exactly as on the inline path,
    so recovery semantics don't change.

    Returns the halo'd packed buffer in device-tile layout,
    ``(P * (rows_max + 2), cols_max + 2, planes, slots)``; the plan/mesh
    fallbacks mirror :func:`parallel_fmm_evaluate` so the pair always
    agrees on the layout.
    """
    require_dense(tree, "parallel_fmm_p2p_prefetch")
    eq = _eqs.get_equation(eq)
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    P_ = mesh.shape[mesh_axis]
    if plan is None:
        plan = uniform_plan(tree.level, P_)
    block = plan.as_block() if isinstance(plan, SlabPlan) else plan
    n = tree.nside
    rows_max, cols_max = block.rows_max, block.cols_max
    identity = (block.grid[1] == 1 and block.is_uniform
                and P_ * rows_max == n)
    if identity:
        z_sh, q_sh, m_sh = tree.z, tree.q, tree.mask
    else:
        with obs.scope("fmm.assemble"):
            src_r, src_c, valid = block.gather_index()
            src_r, src_c = jnp.asarray(src_r), jnp.asarray(src_c)
            v = jnp.asarray(valid)[:, :, None]
            z_sh = jnp.where(v, tree.z[src_r, src_c], 0)
            q_sh = jnp.where(v, tree.q[src_r, src_c], 0)
            m_sh = tree.mask[src_r, src_c] & v
    Pr, Pc = block.grid

    def body(z, q, m):
        with obs.scope("fmm.exchange"):
            if eq.q_is_real:
                q = (q.real + 0j).astype(z.dtype)
            di = jax.lax.axis_index(mesh_axis)
            dev = np.arange(Pr * Pc)
            my_rows = jnp.asarray(
                np.asarray(block.rows, np.int32)[dev // Pc])[di]
            my_cols = jnp.asarray(
                np.asarray(block.cols, np.int32)[dev % Pc])[di]
            return _tile_halo(_pack_particles(z, q, m, eq.q_is_real), 1,
                              my_rows, my_cols, mesh_axis, (Pr, Pc))

    spec = P(mesh_axis, None, None)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=P(mesh_axis, None, None, None))
    return fn(z_sh, q_sh, m_sh)


# Named jitted entry points the static-analysis layer lowers and checks
# (repro/analysis: trace contracts, SPMD schedule verifier, retrace
# detector).  Keys are stable names — contracts reference entry points by
# name, so renaming a function here is an API change, not a refactor.
TRACE_ENTRY_POINTS = {
    "parallel_fmm_evaluate": parallel_fmm_evaluate,
    "parallel_fmm_p2p_prefetch": parallel_fmm_p2p_prefetch,
}


def parallel_fmm_velocity(tree: Tree, p: int, mesh: Optional[Mesh] = None,
                          mesh_axis: str = "data",
                          use_kernels: bool = False,
                          plan: Optional[Union[SlabPlan, BlockPlan]] = None,
                          overlap: bool = True, with_health: bool = False,
                          faults: tuple = (), pipeline: bool = True,
                          p2p_halo: Optional[jnp.ndarray] = None):
    """Complex velocity W per slot — the vortex-kernel form of
    :func:`parallel_fmm_evaluate` (the registry's bit-compatible default)."""
    return parallel_fmm_evaluate(tree, p, mesh, mesh_axis, use_kernels,
                                 plan, overlap, eq=_eqs.VORTEX,
                                 with_health=with_health, faults=faults,
                                 pipeline=pipeline, p2p_halo=p2p_halo)
