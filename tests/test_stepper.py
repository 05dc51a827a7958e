"""Device-side rebinning + the dynamic RK2 stepper (paper §3 + §4 dynamic).

Pins the acceptance criterion: a jitted RK2 step via ``rebuild_tree`` +
``VortexStepper`` reproduces the host-rebuild loop it replaces to f32
tolerance, overflow is reported (never silently corrupted), and the
occupancy guard re-levels before ``build_tree`` could die mid-run.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.fmm import fmm_velocity
from repro.core.quadtree import (Tree, build_tree, gather_particle_values,
                                 rebuild_tree)
from repro.core.stepper import VortexStepper, rk2_step
from repro.core.vortex import lamb_oseen_particles


def _random_tree(n=500, level=4, slots=12, seed=0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.01, 0.99, (n, 2))
    gamma = rng.normal(size=n)
    tree, index = build_tree(pos, gamma, level, sigma=0.02, slots=slots)
    return tree, index, pos, gamma


# ---------------------------------------------------------------------------
# rebuild_tree: the jit-able build_tree
# ---------------------------------------------------------------------------


def test_rebuild_identity_matches_build_tree():
    tree, index, _, _ = _random_tree()
    new_tree, aux, ok = jax.jit(rebuild_tree)(tree, tree.z)
    assert bool(ok) and aux is None
    assert (np.asarray(new_tree.mask.sum(-1)) == index.counts).all()
    # same multiset of particles per box (slot order may differ)
    for a, b in ((new_tree.z, tree.z), (new_tree.q, tree.q)):
        assert np.allclose(np.sort(np.asarray(a), axis=-1),
                           np.sort(np.asarray(b), axis=-1))


def test_rebuild_moved_matches_host_binning():
    tree, index, pos, gamma = _random_tree(seed=1)
    rng = np.random.default_rng(2)
    pos2 = (pos + rng.normal(0, 0.05, pos.shape)).clip(0.001, 0.999)
    host_tree, host_index = build_tree(pos2, gamma, tree.level, sigma=0.02,
                                       slots=tree.slots)
    n = tree.nside
    newz = np.zeros((n * n, tree.slots), dtype=np.complex64)
    newz[index.box_of_particle, index.slot_of_particle] = \
        pos2[:, 0] + 1j * pos2[:, 1]
    new_tree, _, ok = rebuild_tree(tree, jnp.asarray(newz.reshape(n, n, -1)))
    assert bool(ok)
    assert (np.asarray(new_tree.mask.sum(-1)) == host_index.counts).all()
    assert np.asarray(new_tree.q).sum() == pytest.approx(
        np.asarray(host_tree.q).sum(), rel=1e-5)


def test_rebuild_reports_overflow():
    tree, _, _, _ = _random_tree(slots=None)   # slots == max occupancy
    clumped = jnp.full_like(tree.z, 0.5 + 0.5j)
    overflowed, _, ok = rebuild_tree(tree, clumped)
    assert not bool(ok)
    # capacity is respected even under overflow (surplus dropped, not UB)
    assert int(overflowed.mask.sum()) <= overflowed.slots


def test_rebuild_carries_aux_payload():
    tree, _, _, _ = _random_tree(seed=5)
    labels = jnp.where(tree.mask,
                       jnp.cumsum(tree.mask.reshape(-1)).reshape(tree.mask.shape),
                       0)
    shifted = jnp.where(tree.mask, tree.z + 0.03, tree.z)
    new_tree, (new_labels,), ok = rebuild_tree(tree, shifted, aux=(labels,))
    assert bool(ok)
    # every label survives, attached to its particle
    a = np.sort(np.asarray(labels)[np.asarray(tree.mask)])
    b = np.sort(np.asarray(new_labels)[np.asarray(new_tree.mask)])
    assert (a == b).all()


def _rebuild_by_scatter(tree, new_z, aux=None):
    """The rebin as a scatter of each sorted particle to its slot: the
    formulation ``rebuild_tree`` replaced, kept as its reference."""
    n, s = tree.nside, tree.slots
    N = n * n * s
    z = new_z.reshape(N)
    m = tree.mask.reshape(N)
    ix = jnp.clip((z.real * n).astype(jnp.int32), 0, n - 1)
    iy = jnp.clip((z.imag * n).astype(jnp.int32), 0, n - 1)
    box = jnp.where(m, iy * n + ix, n * n)
    order = jnp.argsort(box)
    sb = box[order]
    idx = jnp.arange(N, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sb[1:] != sb[:-1]])
    slot = idx - jax.lax.cummax(jnp.where(is_start, idx, 0))
    ok = jnp.all((sb == n * n) | (slot < s))
    dest = jnp.where((sb < n * n) & (slot < s), sb * s + slot, N)

    def scatter(vals):
        flat = jnp.zeros((N,), dtype=vals.dtype)
        return flat.at[dest].set(vals.reshape(N)[order], mode="drop") \
                   .reshape(n, n, s)

    new_tree = Tree(z=scatter(z), q=scatter(tree.q), mask=scatter(m),
                    level=tree.level, sigma=tree.sigma)
    new_aux = jax.tree_util.tree_map(scatter, aux) if aux is not None else None
    return new_tree, new_aux, ok


# (level, slots, occupied share of slots, centre and spread of the new
# positions, whether some box must overflow); a centre of None keeps every
# particle in its box, so each box of a full grid holds exactly ``slots``
_REBIN_CASES = {
    "sparse": (4, 8, 0.2, 0.5, 0.25, False),
    "full_boxes": (3, 8, 1.0, None, 0.0, False),
    "crowded": (3, 16, 0.9, 0.5, 0.2, None),
    "overflowing": (5, 4, 0.5, 0.5, 0.04, True),
    "out_of_domain": (2, 32, 0.7, 0.5, 0.9, None),
    "deep": (6, 12, 0.3, 0.3, 0.1, True),
    "no_live_particle": (3, 8, 0.0, 0.5, 0.3, False),
}


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.tobytes()


@pytest.mark.parametrize("with_aux", [False, True], ids=["bare", "aux"])
@pytest.mark.parametrize("case", list(_REBIN_CASES))
def test_rebuild_matches_scatter_reference_bit_for_bit(case, with_aux):
    """The inverse-map placement returns what the per-particle scatter
    wrote, bit for bit: the same first ``s`` particles of each box in the
    same slots, the same zero fill, the same ``ok`` under overflow, and
    out-of-domain positions clamped into the same edge boxes."""
    level, slots, fill, centre, spread, overflows = _REBIN_CASES[case]
    n = 1 << level
    shape = (n, n, slots)
    rng = np.random.default_rng(level * 100 + slots)

    def cplx(loc, scale):
        return (rng.normal(loc, scale, shape)
                + 1j * rng.normal(loc, scale, shape)).astype(np.complex64)

    mask = rng.random(shape) < fill
    tree = Tree(z=jnp.asarray(cplx(0.5, 0.3)), q=jnp.asarray(cplx(0.0, 1.0)),
                mask=jnp.asarray(mask), level=level, sigma=0.01)
    # empty slots hold positions too: the rebin must ignore them
    if centre is None:
        at = (np.arange(n) + 0.5) / n
        new_z = jnp.broadcast_to((at[None, :] + 1j * at[:, None])[..., None],
                                 shape).astype(jnp.complex64)
    else:
        new_z = jnp.asarray(cplx(centre, spread))
    aux = None
    if with_aux:
        aux = {"z0": tree.z,
               "label": jnp.asarray(rng.integers(-9, 9, shape, np.int32)),
               "flag": (jnp.asarray(rng.random(shape) < 0.5),)}

    got = jax.jit(rebuild_tree)(tree, new_z, aux)
    want = jax.jit(_rebuild_by_scatter)(tree, new_z, aux)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert _bits(a) == _bits(b)
    assert got[0].level == level and got[0].sigma == tree.sigma
    if overflows is not None:
        assert bool(got[2]) is not overflows
    if case == "no_live_particle":
        assert not np.asarray(got[0].mask).any()


# ---------------------------------------------------------------------------
# Jitted RK2 == host-rebuild loop (acceptance-pinned)
# ---------------------------------------------------------------------------


def test_jitted_rk2_matches_host_rebuild_loop():
    pos0, gamma0, sigma = lamb_oseen_particles(40)
    p, dt, steps = 10, 0.004, 3
    st = VortexStepper(pos0, gamma0, sigma, p=p, dt=dt,
                       payload={"z0": pos0[:, 0] + 1j * pos0[:, 1]})
    for _ in range(steps):
        st.step()

    # the loop examples/vortex_sim.py used to run: host build_tree twice
    # per RK2 step at the same level / slot capacity
    level, slots = st.params.level, st.params.slots
    pos = pos0.copy()
    for _ in range(steps):
        t, ix = build_tree(pos, gamma0, level, sigma, slots=slots)
        w = gather_particle_values(np.asarray(fmm_velocity(t, p)), ix)
        mid = pos + 0.5 * dt * np.stack([w.real, -w.imag], 1)
        t, ix = build_tree(mid, gamma0, level, sigma, slots=slots)
        w = gather_particle_values(np.asarray(fmm_velocity(t, p)), ix)
        pos = pos + dt * np.stack([w.real, -w.imag], 1)

    # match trajectories via the initial-position payload
    m = np.asarray(st.tree.mask).reshape(-1)
    z_dev = np.asarray(st.tree.z).reshape(-1)[m]
    z0_dev = np.asarray(st.payload["z0"]).reshape(-1)[m]
    dev = z_dev[np.lexsort((z0_dev.imag, z0_dev.real))]
    z0_host = pos0[:, 0] + 1j * pos0[:, 1]
    host = (pos[:, 0] + 1j * pos[:, 1])[np.lexsort((z0_host.imag,
                                                    z0_host.real))]
    assert len(dev) == len(host)
    assert np.abs(dev - host).max() < 5e-5


def test_stepper_orbit_invariant():
    """Lamb-Oseen particles orbit on near-circles through many rebins."""
    pos0, gamma0, sigma = lamb_oseen_particles(40)
    r0 = np.hypot(pos0[:, 0] - 0.5, pos0[:, 1] - 0.5)
    st = VortexStepper(pos0, gamma0, sigma, p=10, dt=0.005,
                       payload={"r0": r0 + 0j})
    for _ in range(4):
        st.step()
    m = np.asarray(st.tree.mask).reshape(-1)
    z = np.asarray(st.tree.z).reshape(-1)[m]
    rr0 = np.asarray(st.payload["r0"]).reshape(-1)[m].real
    r = np.hypot(z.real - 0.5, z.imag - 0.5)
    sel = rr0 > 0.02
    assert np.abs(r[sel] - rr0[sel]).max() < 5e-3


# ---------------------------------------------------------------------------
# Occupancy guard: re-level instead of dying inside build_tree mid-run
# ---------------------------------------------------------------------------


def test_occupancy_guard_relevels_before_overflow():
    pos0, gamma0, sigma = lamb_oseen_particles(40)
    st = VortexStepper(pos0, gamma0, sigma, p=8, dt=0.004,
                       slots_headroom=1.0,       # no slack: occ == slots
                       occupancy_guard=0.9,
                       payload={"z0": pos0[:, 0] + 1j * pos0[:, 1]})
    n_before = int(st.tree.mask.sum())
    level_before = st.params.level
    assert st.maybe_replan() == "relevel"         # guard fires -> re-level
    assert int(st.tree.mask.sum()) == n_before    # no particle lost
    assert st.params.slots >= st.counts().max()
    # payload survived the host rebuild
    z0 = np.asarray(st.payload["z0"]).reshape(-1)
    assert (z0 != 0).sum() == n_before
    assert st.params.level >= level_before


def test_stepper_measured_times_fn_is_wired():
    """The dynamic loop polls the injected per-device timer at replan time
    (the hook real deployments use for heterogeneous pools)."""
    pos0, gamma0, sigma = lamb_oseen_particles(40)
    calls = []

    def timer(stepper):
        calls.append(stepper.step_count)
        return np.ones(stepper.nparts)

    st = VortexStepper(pos0, gamma0, sigma, p=8, dt=0.004, dynamic=True,
                       replan_every=1, measured_times_fn=timer)
    st.step()
    assert calls == [1]


# ---------------------------------------------------------------------------
# Wall-clock sample hygiene for the measured-feedback replanner
# ---------------------------------------------------------------------------


def test_clean_wall_samples_drops_every_retrace_successor():
    """Regression (substep-pipelining PR): ANY adopted tree change —
    replan, occupancy-guard re-level, recovery rung — retraces on the
    FOLLOWING step, so the flagged record AND its successor must both be
    dropped from the feedback window, not only replan successors."""
    from repro.core.stepper import StepRecord, clean_wall_samples

    def rec(step, sec, replanned=False, releveled=False, recovered=""):
        return StepRecord(step=step, seconds=sec, load_balance=1.0,
                          replanned=replanned, releveled=releveled,
                          level=5, recovered=recovered)

    records = [rec(1, 1.0),
               rec(2, 9.0, replanned=True),     # flagged
               rec(3, 9.0),                     # retrace successor
               rec(4, 1.1),
               rec(5, 9.0, releveled=True),     # guard re-level: flagged too
               rec(6, 9.0),                     # its retrace successor
               rec(7, 1.2),
               rec(8, 9.0, recovered="expand_domain"),
               rec(9, 9.0),                     # recovery retrace
               rec(10, 1.3)]
    assert clean_wall_samples(records) == [1.0, 1.1, 1.2, 1.3]
    # flagged-first window: the leading record itself is dropped
    assert clean_wall_samples([rec(1, 9.0, releveled=True),
                               rec(2, 9.0), rec(3, 1.0)]) == [1.0]
    assert clean_wall_samples([]) == []


def test_occupancy_guard_relevel_is_recorded_as_relevel():
    """Regression: the guard's re-level used to come back as a bare True
    and was recorded as ``replanned`` — mislabeling the record and keeping
    its inflated wall sample in the feedback window."""
    pos0, gamma0, sigma = lamb_oseen_particles(40)
    st = VortexStepper(pos0, gamma0, sigma, p=8, dt=0.004,
                       slots_headroom=1.0, occupancy_guard=0.9,
                       dynamic=True, replan_every=1)
    rec = st.step()
    assert rec.releveled and not rec.replanned
    from repro.core.stepper import clean_wall_samples
    assert clean_wall_samples(st.history) == []
