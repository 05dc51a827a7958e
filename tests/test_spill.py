"""The spill layout: a dense grid of ``s`` slots per box plus overflow chunks
of ``s`` slots for the boxes that hold more (``quadtree.Spill``).

A clustered field's fullest boxes no longer set every box's slots; the
tests hold the tree to its invariants, the evaluation to the f64 direct
sum and to the same particles in a dense tree, and a tree with no
overflow to the dense tree it always was."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import equations as eqs
from repro.core import quadtree as qt
from repro.core.fmm import (fmm_evaluate, near_field, p2p_chunk_fn,
                            p2p_slab_fn, spill_near_field)
from repro.core.quadtree import build_tree, gather_particle_values

LEVEL, CAP, P = 5, 4, 12
SIGMA = 0.25 / 2 ** LEVEL       # the mollifier is ~1 beyond one leaf box


def plummer(n=4000, a=0.1, seed=0):
    """A projected Plummer cluster about the square's centre (surface
    density ~ (1 + R^2/a^2)^-2, radius by the inverse mass fraction),
    truncated at R = 0.45: at level 5 its fullest box holds 123."""
    rng = np.random.default_rng(seed)
    m = rng.uniform(0.0, 0.45 ** 2 / (0.45 ** 2 + a * a), n)
    r = a * np.sqrt(m / (1.0 - m))
    t = rng.uniform(0.0, 2.0 * np.pi, n)
    pos = np.stack([0.5 + r * np.cos(t), 0.5 + r * np.sin(t)], axis=1)
    gamma = rng.uniform(0.99, 1.01, n) / n
    return pos, gamma


@pytest.fixture(scope="module")
def cluster():
    pos, gamma = plummer()
    tree, index = build_tree(pos, gamma, LEVEL, SIGMA, slots=CAP)
    full = int(index.counts.max())
    dense, dindex = build_tree(pos, gamma, LEVEL, SIGMA, slots=full)
    return pos, gamma, tree, index, dense, dindex


def test_every_particle_lies_once_in_its_own_box(cluster):
    pos, gamma, tree, index, _, _ = cluster
    n, sp = tree.nside, tree.spill
    assert tree.slots == CAP and sp is not None
    z = pos[:, 0] + 1j * pos[:, 1]
    chunk = index.chunk_of_particle
    spilled = chunk >= 0
    dz = np.asarray(tree.z).reshape(n * n, CAP)
    sz = np.asarray(sp.z)
    got = np.where(spilled, sz[np.maximum(chunk, 0), index.slot_of_particle],
                   dz[index.box_of_particle, index.slot_of_particle])
    np.testing.assert_array_equal(got, z.astype(np.complex64))
    # every live slot is one particle's: as many live slots as particles
    assert int(tree.mask.sum()) + int(sp.mask.sum()) == len(pos)
    assert int(tree.num_particles) == len(pos)
    np.testing.assert_array_equal(qt.box_counts(pos, LEVEL), index.counts)
    assert tree.spill_particles == int(spilled.sum()) == int(sp.mask.sum())
    # each chunk's particles lie in the chunk's box
    box = np.asarray(sp.box)
    bx = np.clip((sz.real * n).astype(int), 0, n - 1)
    by = np.clip((sz.imag * n).astype(int), 0, n - 1)
    live = np.asarray(sp.mask)
    assert ((by * n + bx == box[:, None]) | ~live).all()
    assert (np.diff(box) >= 0).all()                   # chunks by box


def test_the_dense_part_fills_before_any_chunk(cluster):
    _, _, tree, index, _, _ = cluster
    counts = index.counts.reshape(-1)
    dense = np.asarray(tree.mask).reshape(-1, CAP).sum(axis=1)
    np.testing.assert_array_equal(dense, np.minimum(counts, CAP))
    box = np.asarray(tree.spill.box)
    per_box = np.bincount(box, minlength=counts.size)
    np.testing.assert_array_equal(per_box,
                                  -(-np.maximum(counts - CAP, 0) // CAP))
    # only a box's last chunk may be part full, and none is empty
    fill = np.asarray(tree.spill.mask).sum(axis=1)
    last = np.r_[box[1:] != box[:-1], True]
    assert (fill[~last] == CAP).all() and (fill > 0).all()


def test_the_runs_cover_every_neighbouring_pair_with_a_chunk(cluster):
    """Brute force over chunks: every (target, source) pair of chunks in
    neighbouring boxes with a spill chunk in it is covered once by the
    runs, and nothing else but empty dense boxes; a target's runs are
    adjacent, and ``groups`` counts them."""
    _, _, tree, index, _, _ = cluster
    n, sp = tree.nside, tree.spill
    K = sp.chunks
    y0, x0, wy, wx = sp.window
    W = wy * wx
    counts = index.counts.reshape(-1)

    def name(c):                    # chunk index -> ("d", box) | ("s", k)
        if c < W:
            return ("d", int((y0 + c // wx) * n + x0 + c % wx))
        return ("s", int(c - W))

    occupied = np.flatnonzero(counts > 0)
    chunk = [("d", int(b)) for b in occupied] + \
        [("s", k) for k in range(K)]
    box = np.r_[occupied, np.asarray(sp.box)]
    by, bx = np.divmod(box, n)
    near = (np.abs(by[:, None] - by[None, :]) <= 1) & \
        (np.abs(bx[:, None] - bx[None, :]) <= 1)
    spill = np.array([c[0] == "s" for c in chunk])
    want = {(chunk[i], chunk[j]) for i, j in
            zip(*np.nonzero(near & (spill[:, None] | spill[None, :])))}
    tgt, start, length = (np.asarray(a) for a in (sp.run_tgt, sp.run_start,
                                                  sp.run_len))
    got = [(name(t), name(c)) for t, s0, m in zip(tgt, start, length)
           for c in range(s0, s0 + m)]
    assert len(got) == len(set(got)) == sp.pairs == tree.spill_pairs
    empty = {g for g in got if g[1][0] == "d" and counts[g[1][1]] == 0}
    assert set(got) - empty == want
    assert (length >= 1).all() and (length <= sp.run).all()
    # a target's runs are adjacent, in groups of equal run counts
    at = 0
    for runs, targets in sp.groups:
        block = tgt[at:at + runs * targets].reshape(targets, runs)
        assert (block == block[:, :1]).all()
        at += runs * targets
    assert at == len(tgt) and len(set(tgt)) == sum(t for _, t in sp.groups)
    assert tree.spill_pair_evals == len(tgt) * sp.run * CAP * CAP


@pytest.mark.parametrize("route", ["jnp", "kernel"])
def test_spilled_evaluation_matches_direct_sum_and_dense_tree(cluster,
                                                              route):
    """Both routes against the f64 direct sum at 400 particles, and against
    the same particles in a dense tree of the fullest box's slots.  The
    direct-sum bound is the p = 12 series truncation with room (the dense
    tree reads 3e-7); the dense-tree bound is float32 summation order over
    up to 9 x 123 near-field terms a particle (reads about 1e-7)."""
    pos, gamma, tree, index, dense, dindex = cluster
    uk = route == "kernel"
    w = gather_particle_values(
        jax.device_get(fmm_evaluate(tree, P, eq=eqs.VORTEX,
                                    use_kernels=uk)), index)
    wd = gather_particle_values(
        np.asarray(fmm_evaluate(dense, P, eq=eqs.VORTEX, use_kernels=uk)),
        dindex)
    z = pos[:, 0] + 1j * pos[:, 1]
    sel = np.random.default_rng(1).choice(len(z), 400, replace=False)
    exact = eqs.direct_sum(eqs.VORTEX, z[sel], z, gamma, SIGMA)
    rel = lambda a, b: np.linalg.norm(a - b) / np.linalg.norm(b)
    assert rel(w[sel], exact) < 1e-5
    assert rel(w, wd) < 1e-6


def test_spilled_laplace_evaluation_matches_the_dense_tree(cluster):
    """A two-channel equation through the spill path (jnp route): the
    potential and field per particle agree with the dense tree's to
    float32 summation order."""
    pos, gamma, _, _, _, _ = cluster
    eq = eqs.LAPLACE
    tree, index = build_tree(pos, gamma, LEVEL, SIGMA, slots=CAP,
                             charge_scale=eq.charge_scale)
    dense, dindex = build_tree(pos, gamma, LEVEL, SIGMA,
                               slots=int(index.counts.max()),
                               charge_scale=eq.charge_scale)
    w, ws = jax.device_get(fmm_evaluate(tree, P, eq=eq))
    wd = np.asarray(fmm_evaluate(dense, P, eq=eq))
    for c in range(eq.nout):
        got = gather_particle_values((w[..., c], ws[..., c]), index)
        want = gather_particle_values(wd[..., c], dindex)
        assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-6


@pytest.mark.parametrize("run,route", [(1, "jnp"), (1, "kernel"),
                                       (8, "jnp"), (8, "kernel")])
def test_spill_near_field_matches_the_plain_slab_reference(run, route,
                                                           monkeypatch):
    """The dense grid's near field plus the chunk pairs' (runs of one
    source chunk, and runs of eight) against ``p2p_slab_reference`` on the
    same particles in a dense tree: the same pairs, summed in another
    order (float32 bound)."""
    monkeypatch.setattr(qt, "SPILL_RUN", run)
    pos, gamma = plummer(n=1500, a=0.15, seed=3)
    tree, index = build_tree(pos, gamma, 4, SIGMA, slots=CAP)
    assert tree.spill.run == run and max(tree.spill.run_len) <= run
    dense, dindex = build_tree(pos, gamma, 4, SIGMA,
                               slots=int(index.counts.max()))
    uk = route == "kernel"
    near = jax.jit(lambda t: near_field(t, p2p_slab_fn(uk)))
    spill = jax.jit(lambda t: spill_near_field(t, p2p_chunk_fn(uk)))
    add, ws = spill(tree)
    got = gather_particle_values(
        (np.asarray(near(tree) + add), np.asarray(ws)), index)
    want = gather_particle_values(np.asarray(near(dense)), dindex)
    assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-6


@pytest.mark.parametrize("runs,lanes,slots,sigma", [
    (100, 128, 8, SIGMA),      # one block, fewer runs than lanes
    (384, 128, 8, SIGMA),      # whole blocks
    (300, 128, 8, None),       # a partial last block, no mollifier
    (300, 128, 4, SIGMA),      # four slots a chunk
])
def test_p2p_chunk_kernel_matches_the_jnp_reference(runs, lanes, slots,
                                                    sigma):
    """The chunk-pair kernel (interpreted) against ``p2p_chunk_reference``
    on random rows, with runs of 1 to 8 live source chunks and half the
    slots empty: the same pair terms summed in the same order within a
    run (float32 bound)."""
    from repro.kernels import p2p as kp
    from repro.core.fmm import p2p_chunk_reference

    R = qt.SPILL_RUN
    rng = np.random.default_rng(runs + slots)
    tgt = rng.uniform(0.0, 0.01, (runs, 2 * slots)).astype(np.float32)
    src = rng.uniform(0.0, 0.01, (runs, R, 5, slots)).astype(np.float32)
    src[:, :, 4] = rng.uniform(size=(runs, R, slots)) < 0.5
    src = src.reshape(runs, R * 5 * slots)
    run_len = rng.integers(1, R + 1, runs).astype(np.int32)
    got = kp.p2p_chunk_pallas(jnp.asarray(tgt), jnp.asarray(src),
                              jnp.asarray(run_len), sigma=sigma,
                              lanes=lanes, interpret=True)
    want = p2p_chunk_reference(jnp.asarray(tgt), jnp.asarray(src),
                               jnp.asarray(run_len), sigma)
    assert got.shape == want.shape == (2, slots, runs)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * scale


def _lattice(side, level):
    h = 0.8 / (side - 1)
    xs = 0.1 + h * np.arange(side)
    X, Y = np.meshgrid(xs, xs, indexing="xy")
    pos = np.stack([X.ravel(), Y.ravel()], axis=1)
    return pos, np.linspace(0.5, 1.5, len(pos)), level


@pytest.mark.parametrize("level", [8, 10])
def test_a_lattice_tree_has_no_spill_and_is_the_dense_tree(level):
    """The paper's 875^2 lattice at both benchmark levels: the library's
    capacity is the fullest box, nothing spills, and the arrays are the
    plain dense binning's (slot = rank of the particle in its box)."""
    pos, gamma, level = _lattice(875, level)
    tree, index = build_tree(pos, gamma, level, sigma=1e-3)
    n = 1 << level
    assert tree.spill is None and tree.spill_chunks == 0
    assert tree.slots == int(index.counts.max())
    assert (index.chunk_of_particle == -1).all()
    box = np.clip((pos * n).astype(np.int64), 0, n - 1)
    box = box[:, 1] * n + box[:, 0]
    order = np.argsort(box, kind="stable")
    counts = np.bincount(box, minlength=n * n)
    rank = np.arange(len(box)) - (np.cumsum(counts) - counts)[box[order]]
    z = np.zeros((n * n, tree.slots), np.complex64)
    z[box[order], rank] = (pos[order, 0] + 1j * pos[order, 1])
    np.testing.assert_array_equal(np.asarray(tree.z).reshape(n * n, -1), z)
    np.testing.assert_array_equal(
        np.asarray(tree.mask).reshape(n * n, -1), z != 0)


def test_choose_slots_reads_flat_and_clustered_histograms():
    """Flat: a uniform field's fullest box is within the Poisson bound of
    the mean occupied box, and the capacity is that box.  Clustered: the
    capacity is the mean occupied box rounded up to a power of two."""
    rng = np.random.default_rng(0)
    flat = np.bincount(rng.integers(0, 1024, 2000), minlength=1024)
    assert qt.choose_slots(flat) == flat.max()
    pos, _ = plummer()
    counts = qt.box_counts(pos, LEVEL)
    mean = counts.sum() / (counts > 0).sum()
    assert counts.max() > qt.poisson_bound(mean, int((counts > 0).sum()))
    assert mean <= qt.choose_slots(counts) < 2 * mean
    assert qt.choose_slots(np.zeros(16, int)) == 1


@pytest.mark.parametrize("level", [8, 10])
@pytest.mark.parametrize("per_box", [0.5, 1.0, 1.53, 2.0, 3.0])
def test_uniform_fields_do_not_spill_under_choose_slots(level, per_box):
    """A uniform random field, from half a particle a box to three, at
    both of the benchmark's depths: on every seed the pick is the fullest
    box, so nothing spills (the old rule, four times the rounded mean,
    spilled most such fields near two a box)."""
    boxes = 4 ** level
    for seed in range(8):
        rng = np.random.default_rng(seed)
        counts = np.bincount(rng.integers(0, boxes, int(per_box * boxes)),
                             minlength=boxes)
        assert qt.choose_slots(counts) == counts.max()


def test_poisson_bound_is_the_smallest_with_the_tail_below():
    """``boxes * P(X > c) <= tail`` at the bound and not one below it,
    against the tail summed term by term."""
    import math

    def above(mean, c):
        term = total = math.exp(-mean)
        for k in range(1, c + 1):
            term *= mean / k
            total += term
        return 1.0 - total

    for mean, boxes in [(0.5, 65536), (1.95, 65536), (6.16, 120000),
                        (11.7, 65536)]:
        c = qt.poisson_bound(mean, boxes)
        assert boxes * above(mean, c) <= qt.FLAT_TAIL * (1 + 1e-6) + 1e-15
        assert boxes * above(mean, c - 1) > qt.FLAT_TAIL


def test_build_tree_defaults_to_the_fullest_box():
    """With no capacity given, a clustered field's tree is dense: its
    slots are the fullest box and it has no spill chunks, as every path
    (sharded, stepper, passive targets) needs."""
    pos, gamma = plummer()
    tree, index = build_tree(pos, gamma, LEVEL, SIGMA)
    assert tree.spill is None
    assert tree.slots == int(index.counts.max())
    assert (index.chunk_of_particle == -1).all()


def test_a_dense_tree_traces_no_spill_scope(cluster):
    """The compiled program of a tree with no spill chunks names no
    ``fmm.spill`` op; a spilled tree's does."""
    _, _, tree, _, dense, _ = cluster
    text = lambda t: fmm_evaluate.lower(t, 6, eq=eqs.VORTEX).compile() \
        .as_text()
    dense_text = text(dense)
    assert "fmm.p2p" in dense_text and "fmm.spill" not in dense_text
    assert "fmm.spill" in text(tree)


def test_dense_only_paths_refuse_a_spilled_tree(cluster):
    from repro.core.parallel_fmm import parallel_fmm_evaluate
    from repro.core.stepper import rk2_step

    _, _, tree, _, _, _ = cluster
    for call in (lambda: parallel_fmm_evaluate(tree, P),
                 lambda: rk2_step(tree, 0.01, p=P),
                 lambda: qt.rebuild_tree(tree, tree.z),
                 lambda: fmm_evaluate(tree, P, eq=eqs.TRACER, targets=tree)):
        with pytest.raises(ValueError, match="spill"):
            call()


def test_every_op_of_a_spilled_program_has_a_scope(cluster):
    """The spill work adds no op that the program's stage names miss."""
    from test_obs import unscoped_ops

    _, _, tree, _, _, _ = cluster
    text = fmm_evaluate.lower(tree, 6, eq=eqs.VORTEX).compile().as_text()
    assert unscoped_ops(text) == []
