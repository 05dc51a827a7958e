"""Dynamic load-balanced vortex time stepping (the paper's title, §4),
with guarded execution (DESIGN.md §11).

:class:`VortexStepper` owns the ``(tree, plan)`` pair and closes the
model -> execution -> measurement loop:

  * each RK2 (midpoint) step is ONE jitted device program — FMM velocity,
    half-kick, device-side rebinning (``quadtree.rebuild_tree``), second
    FMM, full kick, rebin — no host round-trip per substep;
  * every ``replan_every`` steps the current leaf occupancy is pulled,
    measured per-device times (when available) are folded into the weights
    via ``partition.measured_rates`` — the same feedback ``rebalance``
    applies to the subtree graph — and a new plan is emitted when the
    modeled Eq-20 bottleneck improves by more than ``replan_tol``;
  * an occupancy guard re-levels the tree on the host *before* any leaf
    box can overflow its slot capacity mid-run;
  * with ``guard=True`` (default) every step also returns an on-device
    health word (``core/health.py``) — NaN/Inf sentinels on velocities,
    coefficients, and exchanged halos; out-of-domain and dropped-particle
    counts; the overflow bit — and a fault walks the bounded
    :class:`RecoveryPolicy` ladder: plain retries -> halved dt -> host
    re-level -> root-box expansion (``quadtree.Domain``) -> plan fallback
    (block -> slab -> uniform) -> the serial jnp reference route ->
    rollback to the last checkpoint -> typed :class:`StepperFaultError`
    carrying a structured :class:`FaultReport`.

Periodic snapshots go through ``checkpoint.manager.CheckpointManager``
(atomic writes, keep-last-k); ``VortexStepper.from_checkpoint`` restores
bit-exact tree/payload state onto ANY device count by rebuilding the plan
from the restored leaf counts (elastic restore).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from .cost_model import ModelParams, array_digest
from . import faults as flt
from . import health as hw
from . import partition as pt
from ..checkpoint.manager import CheckpointManager
from .. import obs
from .fmm import fmm_velocity
from .parallel_fmm import parallel_fmm_p2p_prefetch, parallel_fmm_velocity
from .plan import (BlockPlan, SlabPlan, assignment_from_plan, autotune_plan,
                   candidate_grids, measured_row_scale, plan_from_counts,
                   plan_loads, plan_stats, replan, uniform_plan)
from .quadtree import (Domain, Tree, box_counts, build_tree, choose_level,
                       rebuild_tree, require_dense)


def _velocity(tree, p, mesh, mesh_axis, use_kernels, plan, overlap,
              with_health=False, faults=(), pipeline=True, p2p_halo=None):
    if mesh is None:
        return fmm_velocity(tree, p, use_kernels=use_kernels,
                            with_health=with_health)
    return parallel_fmm_velocity(tree, p, mesh, mesh_axis, use_kernels, plan,
                                 overlap, with_health=with_health,
                                 faults=faults, pipeline=pipeline,
                                 p2p_halo=p2p_halo)


def robust_wall(samples, clip: float = 4.0) -> float:
    """Median/clip outlier filter for wall-clock samples.

    One corrupted sample — a scheduler stall inflating a step, or a garbage
    near-zero timer reading — must not thrash the measured-feedback loop
    (``rebalance`` / replanning).  Samples outside ``[median/clip,
    median*clip]`` are discarded and the median of the survivors is
    returned, so a single outlier in either direction moves the estimate by
    at most one rank."""
    s = np.asarray(list(samples), dtype=np.float64)
    med = float(np.median(s))
    keep = s[(s >= med / clip) & (s <= med * clip)]
    return float(np.median(keep)) if keep.size else med


def clean_wall_samples(records) -> list[float]:
    """Steady-state wall-clock samples from a list of :class:`StepRecord`s.

    Drops every FLAGGED record (replanned, releveled, or recovered — those
    steps paid a host rebuild and/or recovery reruns inside their own
    timer) AND each flagged record's successor: a re-plan, an
    occupancy-guard re-level, and a domain expansion are all ADOPTED after
    their step ran, so the retrace for the new static plan / tree shapes
    lands on the FOLLOWING step's sample.  Without the successor drop one
    retrace-contaminated sample per adoption leaks into the window and
    only :func:`robust_wall`'s clip saves the estimate.
    """
    flagged = [bool(r.replanned or r.releveled or r.recovered)
               for r in records]
    return [r.seconds for i, r in enumerate(records)
            if not flagged[i] and not (i > 0 and flagged[i - 1])]


def host_wallclock_times(stepper: "VortexStepper"):
    """Default ``measured_times_fn``: per-device times from the host-side
    step wall clock.

    The host can only observe the whole step (the bottleneck device);
    attributing that wall time to devices in proportion to their modeled
    load share feeds the measured-feedback plumbing (``measured_row_scale``
    -> ``replan`` -> ``rebalance``) real wall-clock magnitudes every replan
    interval without inventing per-device resolution — the resulting rates
    are uniform, so the re-plan stays count-driven until real per-device
    timers (jax profiler device runtimes / TPU counters — the ROADMAP
    item) replace this hook.  Recompile-dominated samples are excluded via
    :func:`clean_wall_samples`: every adoption that changes the jitted
    step's static shapes — a re-plan, an occupancy-guard re-level, a
    recovery re-level, a domain expansion, a rollback — happens AFTER its
    step ran, so the retrace lands on the FOLLOWING step; both the flagged
    record and its successor are dropped.  The surviving samples go
    through :func:`robust_wall` (median/clip), so one corrupted sample
    can't thrash the replanner.  Returns None until a clean steady-state
    step exists.
    """
    recent = clean_wall_samples(stepper.history)[-6:]
    if not recent:
        return None
    wall = robust_wall(recent)
    # maybe_replan stashes the counts it just pulled; fall back to a fresh
    # pull only when called outside the replan path (no second device sync
    # in the steady-state replan check)
    counts = getattr(stepper, "_counts_cache", None)
    if counts is None:
        counts = stepper.counts()
    loads = plan_loads(stepper.plan, counts, stepper.params)
    peak = max(float(loads.max()), 1e-30)
    return wall * np.asarray(loads, dtype=np.float64) / peak


@functools.partial(jax.jit, static_argnames=("p", "mesh", "mesh_axis",
                                             "use_kernels", "plan",
                                             "overlap", "pipeline", "guard",
                                             "faults"))
def rk2_step(tree: Tree, dt, payload=None, *, p: int, mesh=None,
             mesh_axis: str = "data", use_kernels: bool = False,
             plan: Optional[SlabPlan] = None, overlap: bool = True,
             pipeline: bool = True, guard: bool = False, faults: tuple = ()):
    """One jitted RK2 midpoint step; ``dz/dt = conj(W)`` (W = u - iv).

    ``payload`` is an optional pytree of per-slot (n, n, s) arrays carried
    through both rebinnings (e.g. particle labels or initial radii).
    Returns ``(new_tree, new_payload, ok, occ, health)``: ``ok`` is False
    iff a leaf box overflowed its slots during either rebin and ``occ`` the
    maximum leaf occupancy after the step — both computed inside the one
    device program so the stepper's guards cost no extra host round trip.
    ``guard=True`` additionally assembles the full ``core/health.py`` word
    (driver sentinels merged with out-of-domain counts BEFORE the rebins
    clamp, dropped-particle counts from each rebin, the overflow bit, and
    occupancy); ``guard=False`` returns ``health=None`` and traces the
    exact unguarded program.  ``faults`` is the static tuple of active
    :class:`~repro.core.faults.FaultSpec`s (injected on the first substep;
    empty tuple = the injection-free program, bit for bit).

    ``pipeline=True`` (default) runs the substep pipeline (DESIGN.md §12)
    on sharded meshes: substep 2's packed P2P exchange is ISSUED the
    moment the rebinned midpoint tree exists — before substep 1's trailing
    guard reductions and substep 2's resharding/upward sweep, all of which
    then hide the collective's flight — and its evaluation consumes the
    prefetched buffer.  The gather-overlap stage inside each evaluation is
    gated by the same flag.  The exchanged bytes and every consuming op
    are identical, so the two orderings agree bit-for-bit in value;
    ``pipeline=False`` traces exactly the pre-§12 program (the escape
    hatch the equivalence tests pin).
    """
    require_dense(tree, "rk2_step")
    v1 = _velocity(tree, p, mesh, mesh_axis, use_kernels, plan, overlap,
                   with_health=guard, faults=faults, pipeline=pipeline)
    w1, h1 = v1 if guard else (v1, None)
    with obs.scope("stepper.kick"):
        z_mid = jnp.where(tree.mask, tree.z + 0.5 * dt * jnp.conj(w1),
                          tree.z)
        z_mid = flt.corrupt_positions(z_mid, tree.mask, faults)
    with obs.scope("stepper.health"):
        live0 = tree.mask.sum()
        ood1 = None
        if guard and not pipeline:
            ood1 = hw.out_of_domain_count(z_mid, tree.mask)
    aux = (tree.z, payload) if payload is not None else (tree.z,)
    with obs.scope("stepper.rebin"):
        t_mid, aux, ok1 = rebuild_tree(tree, z_mid, aux=aux)
    z0 = aux[0]

    # cross-substep double buffer (DESIGN.md §12): issue substep 2's packed
    # exchange as soon as the rebinned particles exist, then deliberately
    # order substep 1's trailing guard reduction AFTER the issue — that
    # reduction plus the next evaluation's resharding/upward sweep is the
    # compute window the collective flies through.  Ownership rule: the
    # buffer is read-only from issue to consumption; fault injection and
    # the health sentinel run at the CONSUMER (inside the evaluation), so
    # the guarded paths observe identical data on both orderings.
    p2p_pre = None
    if pipeline and mesh is not None:
        p2p_pre = parallel_fmm_p2p_prefetch(t_mid, mesh=mesh,
                                            mesh_axis=mesh_axis, plan=plan)
    if guard and pipeline:
        with obs.scope("stepper.health"):
            ood1 = hw.out_of_domain_count(z_mid, tree.mask)

    v2 = _velocity(t_mid, p, mesh, mesh_axis, use_kernels, plan, overlap,
                   with_health=guard, faults=faults, pipeline=pipeline,
                   p2p_halo=p2p_pre)
    w2, h2 = v2 if guard else (v2, None)
    with obs.scope("stepper.kick"):
        z_new = jnp.where(t_mid.mask, z0 + dt * jnp.conj(w2), t_mid.z)
    with obs.scope("stepper.health"):
        ood2 = hw.out_of_domain_count(z_new, t_mid.mask) if guard else None
    with obs.scope("stepper.rebin"):
        t_new, aux, ok2 = rebuild_tree(
            t_mid, z_new, aux=aux[1] if payload is not None else None)
    with obs.scope("stepper.health"):
        occ = t_new.mask.sum(axis=-1).max()
        health = None
        if guard:
            health = hw.merge(h1, h2)
            health = hw.with_count(health, hw.F_OOD, ood1 + ood2)
            # a rebin drop is live particles lost to capacity overflow —
            # the count callers would silently lose if they ignored ``ok``
            health = hw.with_count(health, hw.F_DROPPED,
                                   live0 - t_new.mask.sum())
            health = hw.with_flag(health, hw.F_OVERFLOW, ~(ok1 & ok2))
            health = hw.with_flag(health, hw.F_OCC, occ)
        ok = ok1 & ok2
    return t_new, aux, ok, occ, health


# Named jitted entry point for the static-analysis layer (repro/analysis):
# contracts lower "rk2_step" by name (sentinel-free when guard=False, no
# donated buffers — the recovery ladder retries from the intact pre-step
# tree), and the retrace detector monitors its compile cache.
TRACE_ENTRY_POINTS = {"rk2_step": rk2_step}


@dataclasses.dataclass(frozen=True)
class RecoveryPolicy:
    """The recovery ladder's knobs, in escalation order (DESIGN.md §11)."""

    max_retries: int = 1          # rung 1: plain retries (transient faults)
    halve_dt: bool = True         # rung 2: two dt/2 substeps, same interval
    relevel: bool = True          # rung 3: host re-level at fresh capacity
    expand_domain: bool = True    # rung 4: grow the root box (OOD faults)
    domain_margin: float = 0.5    # relative margin of the expanded root box
    plan_fallback: bool = True    # rung 5: block -> slab -> uniform
    reference_route: bool = True  # rung 6: serial jnp route, no kernels
    rollback: bool = True         # rung 7: restore the last checkpoint


@dataclasses.dataclass
class FaultReport:
    """Structured account of an exhausted recovery ladder."""

    step: int                     # 1-based index of the step that faulted
    attempts: list                # [{"rung": str, "health": {field: int}}]
    plan: str                     # plan descriptor at the time of the fault
    level: int
    dt: float

    def __str__(self) -> str:
        rungs = " -> ".join(a["rung"] for a in self.attempts)
        last = self.attempts[-1]["health"] if self.attempts else {}
        bad = {k: v for k, v in last.items()
               if v and k != "max_occupancy"}
        return (f"step {self.step} unrecoverable after [{rungs}]; "
                f"last health {bad}; plan={self.plan} level={self.level} "
                f"dt={self.dt}")


class StepperFaultError(RuntimeError):
    """Raised when every enabled recovery rung failed; carries the report."""

    def __init__(self, report: FaultReport):
        super().__init__(str(report))
        self.report = report


@dataclasses.dataclass
class StepRecord:
    step: int
    seconds: float
    load_balance: float      # Eq (20) min/max on modeled band loads
    replanned: bool
    releveled: bool
    level: int
    recovered: str = ""      # recovery rung that rescued the step ("" = none)
    health: int = 0          # packed health word of the adopted attempt


class VortexStepper:
    """Owns ``(tree, plan)`` and advances the vortex system dynamically.

    ``plan_method``: 'uniform' (strawman), 'model' (a-priori cost-model
    plan), with ``dynamic=True`` adding re-planning from drifted counts and
    measured times.  ``plan_grid=(Pr, Pc)`` schedules a 2-D
    :class:`BlockPlan` tile grid (``Pr * Pc`` must equal the mesh size)
    instead of 1-D row bands; ``plan_grid="auto"`` lets the per-axis grid
    autotuner choose slab vs block at build and every replan.  ``overlap``
    selects the sharded driver's interior/rim overlapped execution.
    ``measured_times_fn(stepper) -> (nparts,) seconds`` is the injection
    point for real per-device timers; dynamic steppers default to
    :func:`host_wallclock_times`.

    Guarded execution: ``guard=True`` (default) runs every step with the
    on-device health word and walks the :class:`RecoveryPolicy` ladder on a
    fault; ``guard=False`` reproduces the pre-guard stepper exactly (only
    the legacy overflow retry remains).  ``faults`` accepts a
    :class:`~repro.core.faults.FaultInjector` for deterministic fault
    injection (tests / chaos drills); None costs nothing.

    Checkpointing: ``checkpoint_dir`` + ``checkpoint_every=k`` snapshots
    (tree, payload, meta) every k adopted steps through
    :class:`CheckpointManager`; the ladder's rollback rung restores the
    last snapshot bit-exact, and :meth:`from_checkpoint` rebuilds a stepper
    — including onto a different device count — from the saved state.

    ``domain`` maps physical coordinates onto the solver's unit square
    (identity by default); the domain-expansion rung grows it when
    particles escape the root box.
    """

    def __init__(self, positions: np.ndarray, gamma: np.ndarray, sigma: float,
                 *, p: int = 12, dt: float = 0.005, mesh=None,
                 mesh_axis: str = "data", use_kernels: bool = False,
                 plan_method: str = "model", dynamic: bool = False,
                 plan_grid=None, overlap: bool = True, pipeline: bool = True,
                 replan_every: int = 4, replan_tol: float = 0.05,
                 target_per_box: float = 8.0, slots_headroom: float = 2.0,
                 occupancy_guard: float = 0.9, cut: Optional[int] = None,
                 payload=None,
                 measured_times_fn: Optional[Callable[["VortexStepper"],
                                                      np.ndarray]] = None,
                 guard: bool = True,
                 policy: Optional[RecoveryPolicy] = None,
                 faults: Optional[flt.FaultInjector] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0, checkpoint_keep: int = 3,
                 domain: Optional[Domain] = None,
                 artifact_cache=None):
        self._init_config(
            p=p, dt=dt, mesh=mesh, mesh_axis=mesh_axis,
            use_kernels=use_kernels, plan_method=plan_method, dynamic=dynamic,
            plan_grid=plan_grid, overlap=overlap, pipeline=pipeline,
            replan_every=replan_every,
            replan_tol=replan_tol, target_per_box=target_per_box,
            slots_headroom=slots_headroom, occupancy_guard=occupancy_guard,
            cut=cut, sigma=sigma, measured_times_fn=measured_times_fn,
            guard=guard, policy=policy, faults=faults,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            checkpoint_keep=checkpoint_keep, domain=domain,
            artifact_cache=artifact_cache)
        self._build_host(np.asarray(positions, np.float64),
                         np.asarray(gamma, np.float64),
                         payload_values=None if payload is None else payload)

    def _init_config(self, *, p, dt, mesh, mesh_axis, use_kernels,
                     plan_method, dynamic, plan_grid, overlap, replan_every,
                     replan_tol, target_per_box, slots_headroom,
                     occupancy_guard, cut, sigma, measured_times_fn, guard,
                     policy, faults, checkpoint_dir, checkpoint_every,
                     checkpoint_keep, domain, pipeline=True,
                     artifact_cache=None):
        self.p, self.dt = p, float(dt)
        # externally-owned artifact cache (serve/fmm_service.ArtifactCache
        # duck type: get(key, builder)); None builds everything locally
        self.artifact_cache = artifact_cache
        self._artifact_keys: dict = {}
        self.mesh, self.mesh_axis = mesh, mesh_axis
        self.use_kernels = use_kernels
        self.plan_method = plan_method
        self.dynamic = dynamic
        self.overlap = overlap
        self.pipeline = bool(pipeline)
        self.plan_grid = plan_grid if plan_grid in (None, "auto") \
            else tuple(plan_grid)
        self.replan_every = max(int(replan_every), 1)
        self.replan_tol = float(replan_tol)
        self.target_per_box = float(target_per_box)
        self.slots_headroom = float(slots_headroom)
        self.occupancy_guard = float(occupancy_guard)
        self._cut = cut
        self.sigma = float(sigma)           # PHYSICAL core size
        self.domain = domain or Domain()
        self.guard = bool(guard)
        self.policy = policy or RecoveryPolicy()
        self.faults = faults
        self.checkpoint_every = int(checkpoint_every)
        self._ckpt = (CheckpointManager(checkpoint_dir, keep=checkpoint_keep)
                      if checkpoint_dir else None)
        self._rolled_back_steps: set[int] = set()
        # dynamic steppers default to the host wall-clock timer so
        # --plan dynamic exercises the full measured-feedback loop with
        # real magnitudes (injected per-device timers override it)
        if measured_times_fn is None and dynamic:
            measured_times_fn = host_wallclock_times
        self.measured_times_fn = measured_times_fn
        self.step_count = 0
        self.history: list[StepRecord] = []

    # -- host-side (re)construction -----------------------------------------

    @property
    def nparts(self) -> int:
        return 1 if self.mesh is None else self.mesh.shape[self.mesh_axis]

    def _min_level(self) -> int:
        # every device needs at least one parent row (2 leaf rows); a 2-D
        # grid only needs that per axis.  "auto" must fit its most
        # demanding *surviving* candidate, so size for the most square
        # factorization (the least demanding per axis) — larger-axis
        # candidates that don't fit are skipped by the autotuner.
        if self.plan_grid == "auto":
            need = max(min(2 * max(g) for g in candidate_grids(self.nparts)),
                       4)
        elif self.plan_grid is not None:
            need = max(2 * max(self.plan_grid), 4)
        else:
            need = max(2 * self.nparts, 4)
        return max(2, math.ceil(math.log2(need)))

    # -- externally-owned artifact cache (session re-entrancy) ---------------

    def _cached(self, key, builder):
        if self.artifact_cache is None:
            return builder()
        return self.artifact_cache.get(key, builder)

    def _plan_key(self, counts) -> tuple:
        return ("plan", array_digest(counts), self.params, self.nparts,
                self.plan_method, self.plan_grid, self.overlap, self.pipeline)

    def _build_plan(self, counts):
        """The deterministic a-priori plan build (cache-keyable — replans
        driven by MEASURED times never go through the cache)."""
        if self.plan_grid == "auto":
            return autotune_plan(counts, self.params, self.nparts,
                                 method=self.plan_method,
                                 overlap=self.overlap,
                                 pipeline=self.pipeline)
        return plan_from_counts(counts, self.params, self.nparts,
                                method=self.plan_method, grid=self.plan_grid)

    def artifact_keys(self) -> dict:
        """{cache_key: live_value} of the artifacts this stepper resolved
        through the external cache — the serving engine re-resolves them by
        key each step (steady state: pure hits) and repopulates an evicted
        entry from the live value."""
        out = {}
        if "tree" in self._artifact_keys:
            out[self._artifact_keys["tree"]] = (self.tree, self.index)
        if "plan" in self._artifact_keys:
            out[self._artifact_keys["plan"]] = self.plan
        return out

    def _build_host(self, positions, gamma, payload_values=None):
        """(Re)bin PHYSICAL particles through the domain map (unit coords,
        scaled sigma/gamma — see :class:`quadtree.Domain`)."""
        size = self.domain.size
        positions = self.domain.to_unit(positions)
        gamma = np.asarray(gamma, np.float64) / size ** 2
        sigma_unit = self.sigma / size
        level = max(choose_level(len(positions), self.target_per_box),
                    self._min_level())
        n = 1 << level
        occ = box_counts(positions, level).max()
        slots = max(int(math.ceil(occ * self.slots_headroom)), 2)
        tree_key = ("tree", array_digest(positions, gamma), level, slots,
                    float(sigma_unit), complex(1.0 / (2j * np.pi)))
        self.tree, self.index = self._cached(
            tree_key, lambda: build_tree(positions, gamma, level, sigma_unit,
                                         slots=slots))
        self._artifact_keys = {"tree": tree_key}
        if payload_values is not None:
            def scatter(v):
                flat = np.zeros((n * n, slots), dtype=np.asarray(v).dtype)
                flat[self.index.box_of_particle,
                     self.index.slot_of_particle] = v
                return jnp.asarray(flat.reshape(n, n, slots))
            self.payload = jax.tree_util.tree_map(scatter, payload_values)
        else:
            self.payload = None
        if self.mesh is not None:
            # the jitted step returns its state replicated over the mesh;
            # place the host-built state the same way, or the second step
            # compiles the step again for the new input sharding
            rep = jax.sharding.NamedSharding(self.mesh,
                                             jax.sharding.PartitionSpec())
            self.tree, self.payload = jax.device_put((self.tree,
                                                      self.payload), rep)
        cut = self._cut if self._cut is not None else min(level - 1, 4)
        self.params = ModelParams(level=level, cut=max(cut, 1), p=self.p,
                                  slots=slots)
        if self.plan_grid not in (None, "auto") and \
                self.plan_grid[0] * self.plan_grid[1] != self.nparts:
            raise ValueError(f"plan_grid {self.plan_grid} has "
                             f"{self.plan_grid[0] * self.plan_grid[1]} tiles"
                             f" for {self.nparts} devices")
        counts = self.index.counts
        plan_key = self._plan_key(counts)
        self.plan = self._cached(plan_key, lambda: self._build_plan(counts))
        self._artifact_keys["plan"] = plan_key
        self.subtree_assign = assignment_from_plan(self.plan, self.params.cut)
        self._cached_lb = plan_stats(self.plan, counts,
                                     self.params)["load_balance"]

    def counts(self) -> np.ndarray:
        with obs.span("stepper.pull"):
            return np.asarray(self.tree.mask.sum(axis=-1))

    def particles(self) -> tuple[np.ndarray, np.ndarray]:
        """(positions, gamma) of the live particles, host-side, PHYSICAL
        coordinates (the inverse of the domain map ``_build_host`` applies;
        an identity domain is bit-transparent)."""
        m = np.asarray(self.tree.mask).reshape(-1)
        z = np.asarray(self.tree.z).reshape(-1)[m]
        q = np.asarray(self.tree.q).reshape(-1)[m]
        pos = self.domain.from_unit(np.stack([z.real, z.imag], axis=1))
        gamma = np.real(q * 2j * np.pi) * self.domain.size ** 2
        return pos, gamma

    def _gather_payload_values(self):
        if self.payload is None:
            return None
        m = np.asarray(self.tree.mask).reshape(-1)
        return jax.tree_util.tree_map(
            lambda a: np.asarray(a).reshape(-1)[m], self.payload)

    def _relevel(self):
        """Host rebuild at a freshly chosen level/capacity (overflow guard)."""
        with obs.span("stepper.relevel"):
            pos, gamma = self.particles()
            self._build_host(pos, gamma,
                             payload_values=self._gather_payload_values())

    def _expand_domain(self, margin: Optional[float] = None):
        """Grow the root box and rebuild — the recovery rung for particles
        escaping the current domain.  The new domain covers the old one and
        is at least twice its size, so the escaping step gains real room."""
        margin = self.policy.domain_margin if margin is None else margin
        pos, gamma = self.particles()
        payload_values = self._gather_payload_values()
        new = Domain.covering(pos, margin=margin, at_least=self.domain)
        if new.size < 2.0 * self.domain.size:
            cx = new.origin[0] + new.size / 2.0
            cy = new.origin[1] + new.size / 2.0
            size = 2.0 * self.domain.size
            new = Domain(origin=(cx - size / 2.0, cy - size / 2.0), size=size)
        self.domain = new
        self._build_host(pos, gamma, payload_values=payload_values)

    # -- checkpointing -------------------------------------------------------

    def save_checkpoint(self):
        """Snapshot (tree, payload, meta) through the checkpoint manager."""
        if self._ckpt is None:
            raise RuntimeError("stepper built without checkpoint_dir")
        trees = {"tree": {"z": self.tree.z, "q": self.tree.q,
                          "mask": self.tree.mask}}
        payload_spec = None
        if self.payload is not None:
            trees["payload"] = self.payload
            if isinstance(self.payload, dict):
                payload_spec = {k: str(np.asarray(v).dtype)
                                for k, v in self.payload.items()}
        meta = {"level": self.params.level, "cut": self.params.cut,
                "slots": self.params.slots, "p": self.p, "dt": self.dt,
                "sigma": self.sigma, "sigma_unit": float(self.tree.sigma),
                "domain_origin": list(self.domain.origin),
                "domain_size": self.domain.size,
                "plan_method": self.plan_method,
                "payload_spec": payload_spec}
        self._ckpt.save(self.step_count, trees, meta)

    @staticmethod
    def _templates_from_meta(meta):
        n, s = 1 << meta["level"], meta["slots"]
        templates = {"tree": {"z": np.zeros((n, n, s), np.complex64),
                              "q": np.zeros((n, n, s), np.complex64),
                              "mask": np.zeros((n, n, s), bool)}}
        if meta.get("payload_spec"):
            templates["payload"] = {
                k: np.zeros((n, n, s), np.dtype(dt))
                for k, dt in meta["payload_spec"].items()}
        return templates

    def _adopt_restored(self, out, meta):
        """Install restored arrays + rebuild the plan from counts (the
        elastic part: any device count works as long as the saved level
        fits its minimum)."""
        t = out["tree"]
        self.tree = Tree(z=jnp.asarray(t["z"]), q=jnp.asarray(t["q"]),
                         mask=jnp.asarray(t["mask"]), level=meta["level"],
                         sigma=meta["sigma_unit"])
        self.payload = None
        if "payload" in out:
            self.payload = jax.tree_util.tree_map(jnp.asarray, out["payload"])
        self.domain = Domain(origin=tuple(meta["domain_origin"]),
                             size=meta["domain_size"])
        self.sigma = meta["sigma"]
        self.params = ModelParams(level=meta["level"], cut=meta["cut"],
                                  p=self.p, slots=meta["slots"])
        self.step_count = meta["step"]
        self._counts_cache = None
        if meta["level"] < self._min_level():
            # saved tree too shallow for this device count: re-level (the
            # only restore path that is not bit-exact — host rebuild)
            self._relevel()
            return
        counts = self.counts()
        plan_key = self._plan_key(counts)
        self.plan = self._cached(plan_key, lambda: self._build_plan(counts))
        # no host tree build on this path — only the plan key is live
        self._artifact_keys = {"plan": plan_key}
        self.subtree_assign = assignment_from_plan(self.plan, self.params.cut)
        self._cached_lb = plan_stats(self.plan, counts,
                                     self.params)["load_balance"]

    def rollback(self, step: Optional[int] = None) -> int:
        """Restore the last (or a given) checkpoint bit-exact; returns the
        restored step index."""
        if self._ckpt is None:
            raise RuntimeError("stepper built without checkpoint_dir")
        self._ckpt.wait()               # never race an in-flight save
        step = self._ckpt.latest_step() if step is None else step
        if step is None:
            raise RuntimeError("no checkpoint to roll back to")
        meta = self._ckpt.load_meta(step)
        out, meta = self._ckpt.restore(self._templates_from_meta(meta),
                                       step=step)
        self._adopt_restored(out, meta)
        return step

    @classmethod
    def from_checkpoint(cls, directory: str, *, mesh=None,
                        mesh_axis: str = "data", step: Optional[int] = None,
                        use_kernels: bool = False, plan_method: str = None,
                        dynamic: bool = False, plan_grid=None,
                        overlap: bool = True, pipeline: bool = True,
                        replan_every: int = 4,
                        replan_tol: float = 0.05,
                        target_per_box: float = 8.0,
                        slots_headroom: float = 2.0,
                        occupancy_guard: float = 0.9,
                        measured_times_fn=None, guard: bool = True,
                        policy: Optional[RecoveryPolicy] = None,
                        faults: Optional[flt.FaultInjector] = None,
                        checkpoint_every: int = 0,
                        checkpoint_keep: int = 3,
                        artifact_cache=None) -> "VortexStepper":
        """Elastic restore: rebuild a stepper from a checkpoint directory,
        onto ANY mesh/device count — tree and payload arrays are restored
        bit-exact (they are device-count independent) and the execution
        plan is rebuilt from the restored leaf counts."""
        mgr = CheckpointManager(directory, keep=checkpoint_keep)
        step = mgr.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {directory}")
        meta = mgr.load_meta(step)
        out, meta = mgr.restore(cls._templates_from_meta(meta), step=step)
        st = cls.__new__(cls)
        st._init_config(
            p=meta["p"], dt=meta["dt"], mesh=mesh, mesh_axis=mesh_axis,
            use_kernels=use_kernels,
            plan_method=plan_method or meta.get("plan_method", "model"),
            dynamic=dynamic, plan_grid=plan_grid, overlap=overlap,
            pipeline=pipeline,
            replan_every=replan_every, replan_tol=replan_tol,
            target_per_box=target_per_box, slots_headroom=slots_headroom,
            occupancy_guard=occupancy_guard, cut=meta["cut"],
            sigma=meta["sigma"], measured_times_fn=measured_times_fn,
            guard=guard, policy=policy, faults=faults,
            checkpoint_dir=directory, checkpoint_every=checkpoint_every,
            checkpoint_keep=checkpoint_keep, domain=None,
            artifact_cache=artifact_cache)
        st._adopt_restored(out, meta)
        return st

    # -- the dynamic loop ----------------------------------------------------

    def maybe_replan(self, measured_times: Optional[np.ndarray] = None,
                     occ: Optional[int] = None) -> str:
        """Re-level if occupancy approaches capacity; re-plan if it pays.

        ``occ`` (max leaf occupancy) is normally read off the jitted step's
        own outputs (``rk2_step`` returns it), so the overflow guard
        triggers no extra device sync; the counts grid is then pulled once
        per replan interval to refresh the reported load balance and (when
        dynamic) drive the re-plan.
        Returns what was adopted: ``"relevel"`` when the occupancy guard
        rebuilt the tree, ``"replan"`` when a new plan was adopted, ``""``
        otherwise — truthiness-compatible with the old bool return, but
        lets :meth:`step` record the correct ``releveled``/``replanned``
        flags (both adoptions retrace on the NEXT step, which
        :func:`clean_wall_samples` relies on)."""
        if occ is None:
            with obs.span("stepper.pull"):
                occ = int(np.asarray(self.tree.mask.sum(axis=-1).max()))
        if occ >= self.occupancy_guard * self.params.slots:
            self._relevel()
            return "relevel"
        counts = self.counts()
        self._counts_cache = counts     # reused by host_wallclock_times
        self._cached_lb = plan_stats(self.plan, counts,
                                     self.params)["load_balance"]
        if not self.dynamic:
            return ""
        if measured_times is None and self.measured_times_fn is not None:
            measured_times = self.measured_times_fn(self)
        new_plan = replan(counts, self.params, self.nparts,
                          prev_plan=self.plan, measured_times=measured_times,
                          method=self.plan_method, grid=self.plan_grid,
                          overlap=self.overlap, pipeline=self.pipeline)
        if new_plan == self.plan:
            return ""
        # adopt when the modeled bottleneck (measured-rate-weighted when
        # times are available) improves by more than the tolerance
        scale = None
        if measured_times is not None:
            scale = measured_row_scale(self.plan, counts, self.params,
                                       measured_times)
        old_max = plan_loads(self.plan, counts, self.params, scale).max()
        new_max = plan_loads(new_plan, counts, self.params, scale).max()
        if new_max > (1.0 - self.replan_tol) * old_max:
            return ""
        self.plan = new_plan
        self._cached_lb = plan_stats(new_plan, counts,
                                     self.params)["load_balance"]
        # keep the paper's 2-D subtree assignment in sync (graph stats /
        # rebalance parity with §4)
        graph = pt.build_subtree_graph(counts, self.params)
        if measured_times is not None:
            self.subtree_assign = pt.rebalance(
                graph, assignment_from_plan(new_plan, self.params.cut),
                self.nparts, measured_times)
        else:
            self.subtree_assign = assignment_from_plan(new_plan,
                                                       self.params.cut)
        return "replan"

    # -- cross-process watchdog hooks (parallel/resilience, DESIGN.md §14) ---

    def modeled_step_work(self) -> float:
        """Eq 13-15 modeled bottleneck of the current plan: the max
        per-partition load.  Pure cost-model units — the resilience layer
        multiplies it by a measured seconds-per-work calibration to seed a
        watchdog deadline before any wall-clock history exists (e.g. the
        first step after a coordinated shrink restart)."""
        counts = getattr(self, "_counts_cache", None)
        if counts is None:
            counts = self.counts()
            self._counts_cache = counts
        return float(plan_loads(self.plan, counts, self.params).max())

    def predicted_step_seconds(self) -> Optional[float]:
        """Robust-filtered steady-state step wall time, or None until a
        clean sample exists.  Same filtering discipline as the replanner:
        flagged records and their retrace-contaminated successors are
        dropped (:func:`clean_wall_samples`), then :func:`robust_wall`
        median/clips the recent window — so one stalled step can't inflate
        (or a garbage timer deflate) the watchdog deadline derived from
        this."""
        recent = clean_wall_samples(self.history)[-8:]
        if not recent:
            return None
        return robust_wall(recent)

    # -- guarded execution ---------------------------------------------------

    def _active_faults(self, attempt: int) -> tuple:
        if self.faults is None:
            return ()
        active = self.faults.active(self.step_count + 1, attempt)
        # teleport magnitudes are PHYSICAL; rk2 runs in unit coordinates,
        # so rescale by the current domain size (root-box expansion can
        # then genuinely cure a sticky teleport that fits the new domain)
        return tuple(dataclasses.replace(f,
                                         magnitude=f.magnitude
                                         / self.domain.size)
                     if f.site == "teleport" else f
                     for f in active)

    def _run_rk2(self, dt, faults=(), plan=None, reference=False):
        """One rk2 attempt from the CURRENT (tree, payload); adopts nothing.

        ``reference=True`` runs the most conservative route: serial mesh,
        pure-jnp slabs, monolithic ordering — the ladder's last compute
        rung.  Returns host-side ``(tree, payload, ok, occ, health)``."""
        with obs.span("stepper.dispatch"):
            if reference:
                out = rk2_step(self.tree, dt, self.payload, p=self.p,
                               mesh=None, use_kernels=False, plan=None,
                               overlap=False, pipeline=False,
                               guard=self.guard, faults=faults)
            else:
                out = rk2_step(
                    self.tree, dt, self.payload, p=self.p, mesh=self.mesh,
                    mesh_axis=self.mesh_axis, use_kernels=self.use_kernels,
                    plan=None if self.mesh is None
                    else (plan if plan is not None else self.plan),
                    overlap=self.overlap, pipeline=self.pipeline,
                    guard=self.guard, faults=faults)
        tree, payload, ok, occ, health = out
        with obs.span("stepper.wait"):
            jax.block_until_ready(tree.z)
        # one span per device-to-host read: their count is the step's syncs
        with obs.span("stepper.pull"):
            ok = bool(ok)
        with obs.span("stepper.pull"):
            occ = int(occ)
        if health is not None:
            with obs.span("stepper.pull"):
                health = np.asarray(health)
        return tree, payload, ok, occ, health

    def _recover(self, first_health: np.ndarray):
        """Walk the recovery ladder for the step that just faulted.

        Returns ``(tree, payload, occ, health, rung, releveled, replanned)``
        with the recovered step's state, or ``(None, ..., "rollback", ...)``
        after a checkpoint rollback (the step did NOT advance), or raises
        :class:`StepperFaultError` once every enabled rung is exhausted.
        """
        pol = self.policy
        attempts = [{"rung": "step", "health": hw.describe(first_health)}]
        saw_ood = int(first_health[hw.F_OOD]) > 0
        attempt = 1

        def run(dt, **kw):
            nonlocal attempt
            f = self._active_faults(attempt)
            attempt += 1
            return self._run_rk2(dt, faults=f, **kw)

        def note(rung, h):
            nonlocal saw_ood
            attempts.append({"rung": rung, "health": hw.describe(h)})
            saw_ood = saw_ood or int(h[hw.F_OOD]) > 0

        # rung 1: bounded plain retries (the transient-fault model: a
        # non-sticky injected fault, a one-off bad collective)
        for r in range(max(pol.max_retries, 0)):
            t = run(self.dt)
            note(f"retry_{r + 1}", t[4])
            if hw.ok(t[4]):
                return t[0], t[1], t[3], t[4], f"retry_{r + 1}", False, False
        # rung 2: halved dt — two half-steps covering the same interval, so
        # a recovered trajectory stays comparable to an unfaulted one
        if pol.halve_dt:
            t1 = run(self.dt / 2.0)
            note("half_dt_1", t1[4])
            if hw.ok(t1[4]):
                saved = (self.tree, self.payload)
                self.tree, self.payload = t1[0], t1[1]
                t2 = run(self.dt / 2.0)
                self.tree, self.payload = saved
                note("half_dt_2", t2[4])
                if hw.ok(t2[4]):
                    return t2[0], t2[1], t2[3], t2[4], "half_dt", False, False
        # rung 3: host re-level at freshly chosen depth/capacity (overflow,
        # capacity-drop faults)
        if pol.relevel:
            self._relevel()
            t = run(self.dt)
            note("relevel", t[4])
            if hw.ok(t[4]):
                return t[0], t[1], t[3], t[4], "relevel", True, False
        # rung 4: root-box expansion (particles escaped the domain)
        if pol.expand_domain and saw_ood:
            self._expand_domain()
            t = run(self.dt)
            note("expand_domain", t[4])
            if hw.ok(t[4]):
                return t[0], t[1], t[3], t[4], "expand_domain", True, False
        # rung 5: plan fallback block -> slab -> uniform (bad plan/exchange)
        if pol.plan_fallback and self.mesh is not None and self.nparts > 1:
            for name, fb in self._fallback_plans():
                t = run(self.dt, plan=fb)
                note(f"plan_{name}", t[4])
                if hw.ok(t[4]):
                    self.plan = fb
                    self.plan_grid = None
                    counts = self.counts()
                    self.subtree_assign = assignment_from_plan(
                        fb, self.params.cut)
                    self._cached_lb = plan_stats(fb, counts,
                                                 self.params)["load_balance"]
                    return t[0], t[1], t[3], t[4], f"plan_{name}", False, True
        # rung 6: the jnp reference route (serial, no kernels, monolithic)
        if pol.reference_route:
            t = run(self.dt, reference=True)
            note("reference", t[4])
            if hw.ok(t[4]):
                return t[0], t[1], t[3], t[4], "reference", False, False
        # rung 7: rollback to the last good checkpoint (once per step)
        fault_step = self.step_count + 1
        if (pol.rollback and self._ckpt is not None
                and fault_step not in self._rolled_back_steps
                and self._ckpt.latest_step() is not None):
            self._rolled_back_steps.add(fault_step)
            self.rollback()
            return None, None, 0, first_health, "rollback", False, False
        raise StepperFaultError(FaultReport(
            step=fault_step, attempts=attempts,
            plan=self.plan.describe(), level=self.params.level, dt=self.dt))

    def _fallback_plans(self):
        """Simpler-plan candidates in escalation order, current plan and
        infeasible geometries excluded (a slab needs 2 leaf rows/device)."""
        out = []
        n = 1 << self.params.level
        if n < 2 * self.nparts:
            return out
        counts = self.counts()
        is_block = isinstance(self.plan, BlockPlan) and self.plan.grid[1] > 1
        if is_block:
            out.append(("slab", plan_from_counts(counts, self.params,
                                                 self.nparts,
                                                 method="model")))
        uni = uniform_plan(self.params.level, self.nparts)
        if uni != self.plan:
            out.append(("uniform", uni))
        return out

    # -- stepping ------------------------------------------------------------

    def step(self) -> StepRecord:
        """Advance one RK2 step; time it; periodically re-plan.

        Guarded steppers check the on-device health word and walk the
        recovery ladder on any fault; a rollback record carries
        ``recovered="rollback"`` and does NOT advance ``step_count``."""
        with obs.span("stepper.step"):
            return self._step()

    def _step(self) -> StepRecord:
        t0 = time.perf_counter()
        recovered, releveled, fb_replanned = "", False, False
        tree, payload, ok, occ, health = self._run_rk2(
            self.dt, faults=self._active_faults(0))
        if self.guard:
            if not hw.ok(health):
                with obs.span("stepper.recover"):
                    (tree, payload, occ, health, recovered, releveled,
                     fb_replanned) = self._recover(health)
                if tree is None:        # rolled back: step did not advance
                    seconds = time.perf_counter() - t0
                    rec = StepRecord(step=self.step_count, seconds=seconds,
                                     load_balance=self._cached_lb,
                                     replanned=False, releveled=False,
                                     level=self.params.level,
                                     recovered="rollback",
                                     health=hw.pack(health))
                    self.history.append(rec)
                    return rec
        elif not ok:
            # legacy (unguarded) overflow path: the old tree is still
            # intact — re-level on the host and redo the step safely.
            releveled = True
            self._relevel()
            tree, payload, ok, occ, health = self._run_rk2(self.dt)
            if not ok:
                raise RuntimeError(
                    "leaf box overflow persists after re-leveling; "
                    "increase slots_headroom or lower target_per_box")
        # the timer covers everything the step actually cost, including a
        # re-level/recovery + recompile when one happened
        seconds = time.perf_counter() - t0
        self.tree, self.payload = tree, payload
        self.step_count += 1
        if self.faults is not None:
            # host-side fault site: corrupt this step's wall-clock sample
            seconds *= self.faults.time_factor(self.step_count)
        replanned = fb_replanned
        self._counts_cache = None       # tree advanced: drop stale counts
        if self.step_count % self.replan_every == 0:
            # occ comes off the step's own outputs (already on host after
            # block_until_ready) — the check itself syncs nothing extra
            with obs.span("stepper.replan"):
                action = self.maybe_replan(occ=int(occ))
            replanned = replanned or action == "replan"
            releveled = releveled or action == "relevel"
        rec = StepRecord(step=self.step_count, seconds=seconds,
                         load_balance=self._cached_lb,
                         replanned=replanned,
                         releveled=releveled or bool(recovered == "relevel"),
                         level=self.params.level, recovered=recovered,
                         health=0 if health is None else hw.pack(health))
        self.history.append(rec)
        if (self._ckpt is not None and self.checkpoint_every
                and self.step_count % self.checkpoint_every == 0):
            with obs.span("stepper.checkpoint"):
                self.save_checkpoint()
        return rec

    def stats(self) -> dict:
        return plan_stats(self.plan, self.counts(), self.params)
