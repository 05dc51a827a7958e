"""Interior/rim overlapped execution == monolithic execution (DESIGN.md §9).

Pins the acceptance criteria of the overlap work: the overlapped sharded
driver (halo collectives issued first, tile interiors computed from local
data while they fly, rim strips stitched from the exchanged buffers)
matches the monolithic exchange-then-compute driver — and the serial
driver — to f32 roundoff on SlabPlan and BlockPlan, with ``use_kernels``
on and off, at P in {4, 6}, including thin 2-row/2-col boundary tiles
where the whole tile is rim.  Also pins the packed single-round P2P
exchange (3 -> 1 collectives) bit-exactly against the three separate
exchanges it replaced, and the overlap-aware cost-model terms.

Multidevice cases run in subprocesses because jax locks the device count
at first init and the rest of the suite must see exactly 1 CPU device.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core.cost_model import ModelParams, comm_overlap_effective
from repro.core.plan import (BlockPlan, SlabPlan, autotune_plan,
                             block_plan_from_counts, candidate_grids,
                             halo_volume, plan_comm_cost, plan_from_counts,
                             plan_score, uniform_plan)
from repro.core.quadtree import build_tree
from repro.core.vortex import lamb_oseen_particles


def _run(body: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", body],
                          capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout


_SLAB_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core.cost_model import ModelParams
    from repro.core.fmm import fmm_velocity
    from repro.core.parallel_fmm import parallel_fmm_velocity
    from repro.core.plan import SlabPlan, plan_from_counts
    from repro.core.quadtree import build_tree
    from repro.core.stepper import VortexStepper
    from repro.core.vortex import lamb_oseen_particles

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    pos, gamma, sigma = lamb_oseen_particles(160)
    tree, index = build_tree(pos, gamma, level=5, sigma=sigma)
    serial = np.asarray(fmm_velocity(tree, p=12))
    params = ModelParams(level=5, cut=4, p=12, slots=tree.slots)
    model = plan_from_counts(index.counts, params, 4, method="model")
    # thin plan: 2-row boundary bands are ALL rim (interior is empty and
    # statically skipped); the strips must cover the whole band
    thin = SlabPlan(level=5, row0=(0, 2, 16, 30), rows=(2, 14, 14, 2))
    for plan in (model, thin):
        for use_kernels in (False, True):
            got = {}
            for overlap in (False, True):
                w = np.asarray(parallel_fmm_velocity(
                    tree, 12, mesh, use_kernels=use_kernels, plan=plan,
                    overlap=overlap))
                err = np.linalg.norm(w - serial) / np.linalg.norm(serial)
                print(f"rows={plan.rows} kernels={use_kernels} "
                      f"overlap={overlap} rel_err={err:.3e}")
                assert err < 1e-5, (plan.rows, use_kernels, overlap, err)
                got[overlap] = w
            d = np.linalg.norm(got[True] - got[False]) / \
                max(np.linalg.norm(got[False]), 1e-30)
            assert d < 1e-6, (plan.rows, use_kernels, d)

    # the grid autotuner drives the stepper end to end under the mesh
    st = VortexStepper(pos, gamma, sigma, p=8, dt=0.004, mesh=mesh,
                       plan_method="model", dynamic=True, plan_grid="auto",
                       replan_every=2)
    for _ in range(2):
        rec = st.step()
    assert rec.step == 2 and rec.seconds > 0
    print("auto plan:", type(st.plan).__name__, st.plan.describe())
    print("OK")
""")


_BLOCK_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=6"
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core.cost_model import ModelParams
    from repro.core.fmm import fmm_velocity
    from repro.core.parallel_fmm import parallel_fmm_velocity
    from repro.core.plan import BlockPlan, block_plan_from_counts
    from repro.core.quadtree import build_tree
    from repro.core.vortex import lamb_oseen_particles

    mesh6 = Mesh(np.array(jax.devices()[:6]), ("data",))
    pos, gamma, sigma = lamb_oseen_particles(160)
    tree, index = build_tree(pos, gamma, level=5, sigma=sigma)
    serial = np.asarray(fmm_velocity(tree, p=12))
    params = ModelParams(level=5, cut=4, p=12, slots=tree.slots)
    b23 = block_plan_from_counts(index.counts, params, (2, 3), method="model")
    # minimum-size 2-row/2-col boundary tiles: whole tiles are rim on both
    # axes and the corner-carrying strips span the entire neighbor tile
    skew = BlockPlan(level=5, row0=(0, 2, 22), rows=(2, 20, 10),
                     col0=(0, 30), cols=(30, 2))
    for plan in (b23, skew):
        for use_kernels in (False, True):
            got = {}
            for overlap in (False, True):
                w = np.asarray(parallel_fmm_velocity(
                    tree, 12, mesh6, use_kernels=use_kernels, plan=plan,
                    overlap=overlap))
                err = np.linalg.norm(w - serial) / np.linalg.norm(serial)
                print(f"rows={plan.rows} cols={plan.cols} "
                      f"kernels={use_kernels} overlap={overlap} "
                      f"rel_err={err:.3e}")
                assert err < 1e-5, (plan.rows, use_kernels, overlap, err)
                got[overlap] = w
            d = np.linalg.norm(got[True] - got[False]) / \
                max(np.linalg.norm(got[False]), 1e-30)
            assert d < 1e-6, (plan.rows, use_kernels, d)
    print("OK")
""")


_PACKED_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import parallel_fmm as pf

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    grid = (2, 2)
    rmax = cmax = 8
    s = 5
    rv, cv = 6, 8          # unequal valid extents exercise the dynamic edges

    def fused(z, q, m):
        buf = pf._tile_halo(pf._pack_particles(z, q, m), 1, rv, cv,
                            "data", grid)
        return pf._unpack_particles(buf, z.dtype)

    def unfused(z, q, m):
        return (pf._tile_halo(z, 1, rv, cv, "data", grid),
                pf._tile_halo(q, 1, rv, cv, "data", grid),
                pf._tile_halo(m, 1, rv, cv, "data", grid))

    spec = P("data", None, None)
    rng = np.random.default_rng(0)
    shape = (4 * rmax, cmax, s)
    z = jnp.asarray(rng.normal(size=shape) + 1j * rng.normal(size=shape),
                    jnp.complex64)
    q = jnp.asarray(rng.normal(size=shape) - 1j * rng.normal(size=shape),
                    jnp.complex64)
    m = jnp.asarray(rng.uniform(size=shape) > 0.4)
    outs = {}
    for name, fn in (("fused", fused), ("unfused", unfused)):
        sm = jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                           out_specs=(spec,) * 3, check_vma=False)
        outs[name] = [np.asarray(a) for a in jax.jit(sm)(z, q, m)]
    for a, b in zip(outs["fused"], outs["unfused"]):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    print("OK")
""")


def test_overlap_matches_monolithic_slab_4dev():
    """Overlapped == monolithic == serial on 4 devices, SlabPlan, both
    kernel routes, thin 2-row boundary bands included; the grid autotuner
    (plan_grid='auto') steps end to end (acceptance-pinned)."""
    _run(_SLAB_BODY)


def test_overlap_matches_monolithic_block_6dev():
    """Overlapped == monolithic == serial on 6 devices, BlockPlan (2x3 and
    thin 2-row/2-col boundary tiles), both kernel routes."""
    _run(_BLOCK_BODY)


def test_packed_p2p_exchange_roundtrip_multidevice():
    """The ONE packed (z, q, mask) exchange reproduces the three separate
    ``_tile_halo`` rounds bit-exactly, including dtype, on a 2x2 grid with
    valid extents smaller than the padded tile."""
    _run(_PACKED_BODY)


def test_pack_unpack_roundtrip_host():
    """_pack_particles / _unpack_particles are a lossless pair (complex64
    components and the bool mask survive the f32 packing exactly)."""
    import jax.numpy as jnp

    from repro.core.parallel_fmm import _pack_particles, _unpack_particles

    rng = np.random.default_rng(7)
    shape = (6, 4, 3)
    z = jnp.asarray(rng.normal(size=shape) + 1j * rng.normal(size=shape),
                    jnp.complex64)
    q = jnp.asarray(rng.normal(size=shape) - 1j * rng.normal(size=shape),
                    jnp.complex64)
    m = jnp.asarray(rng.uniform(size=shape) > 0.5)
    packed = _pack_particles(z, q, m)
    assert packed.shape == (6, 4, 5, 3) and packed.dtype == jnp.float32
    z2, q2, m2 = _unpack_particles(packed, z.dtype)
    assert np.array_equal(np.asarray(z2), np.asarray(z))
    assert np.array_equal(np.asarray(q2), np.asarray(q))
    assert np.array_equal(np.asarray(m2), np.asarray(m))


# ---------------------------------------------------------------------------
# Rim/interior geometry and the overlap-aware cost model (host-side)
# ---------------------------------------------------------------------------


def _lamb_setup(level=5, P=4):
    pos, gamma, sigma = lamb_oseen_particles(120)
    tree, index = build_tree(pos, gamma, level=level, sigma=sigma)
    params = ModelParams(level=level, cut=4, p=10, slots=tree.slots)
    return index.counts, params


def test_interior_extents_and_rim_owners():
    plan = BlockPlan(level=5, row0=(0, 4), rows=(4, 28),
                     col0=(0, 20), cols=(20, 12))
    # w=1 (P2P): interior loses one ring; w=2 (M2L) two; 4-row tiles with
    # w=2 have an EMPTY interior (clamped to 0, the all-rim case)
    assert plan.interior_extents(1) == ((2, 18), (2, 10), (26, 18), (26, 10))
    assert plan.interior_extents(2) == ((0, 16), (0, 8), (24, 16), (24, 8))
    # rim ghost owners (N, S, W, E), -1 at domain edges
    assert plan.rim_owners() == ((-1, 2, -1, 1), (-1, 3, 0, -1),
                                 (0, -1, -1, 3), (1, -1, 2, -1))
    slab = uniform_plan(5, 4)
    assert slab.interior_extents(2) == tuple((4, 28) for _ in range(4))
    assert slab.rim_owners() == ((-1, 1, -1, -1), (0, 2, -1, -1),
                                 (1, 3, -1, -1), (2, -1, -1, -1))


def test_halo_volume_reports_rim_cost():
    counts, params = _lamb_setup()
    plan = plan_from_counts(counts, params, 4, method="model")
    hv = halo_volume(plan, params, executed=True)
    assert hv["rim_m2l_boxes"] > 0 and hv["rim_p2p_boxes"] > 0
    # per sharded level each band recomputes 2w rows of cols_max plus 2w
    # cols of rows_max; the leaf P2P strips are width 1
    block = plan.as_block()
    expect_p2p = sum(2 * (block.rows_max + block.cols_max)
                     for _ in range(4))
    assert hv["rim_p2p_boxes"] == expect_p2p


def test_comm_overlap_effective_residue():
    params = ModelParams(level=5, cut=4, p=10, slots=8)
    assert comm_overlap_effective(100.0, 40.0, params) == 60.0
    assert comm_overlap_effective(100.0, 1000.0, params) == 0.0
    assert comm_overlap_effective(100.0, 1000.0, params, overlap=False) == 100.0
    out = comm_overlap_effective(np.array([10.0, 50.0]),
                                 np.array([20.0, 20.0]), params)
    np.testing.assert_allclose(out, [0.0, 30.0])


def test_plan_comm_cost_overlap_never_exceeds_serial():
    counts, params = _lamb_setup()
    for plan in (plan_from_counts(counts, params, 4, method="model"),
                 block_plan_from_counts(counts, params, (2, 2),
                                        method="model")):
        hidden = plan_comm_cost(plan, counts, params, overlap=True)
        serial = plan_comm_cost(plan, counts, params, overlap=False)
        assert hidden.shape == serial.shape == (4,)
        assert (hidden <= serial + 1e-12).all()
        assert serial.sum() > 0


def test_autotune_plan_picks_min_score_grid():
    counts, params = _lamb_setup()
    best = autotune_plan(counts, params, 4, method="model")
    best_score = plan_score(best, counts, params)
    for Pr, Pc in candidate_grids(4):
        if Pc == 1:
            cand = plan_from_counts(counts, params, 4, method="model")
        else:
            cand = block_plan_from_counts(counts, params, (Pr, Pc),
                                          method="model")
        assert best_score <= plan_score(cand, counts, params) + 1e-9
    # candidate enumeration covers slab and block factorizations
    assert (4, 1) in candidate_grids(4) and (2, 2) in candidate_grids(4)
    assert candidate_grids(6) == [(1, 6), (2, 3), (3, 2), (6, 1)]


def test_block_plan_1d_scale_applies_to_rows():
    """Regression: a 1-D (R,) measured-feedback scale handed to the 2-D
    planner must scale ROWS (column-vector broadcast), not columns —
    matching ``plan_loads`` — so the autotuner's block candidates re-plan
    on the same slowdown field the slab candidates see."""
    counts, params = _lamb_setup()
    R = (1 << params.level) // 2
    scale = np.ones(R)
    scale[: R // 4] = 4.0                    # top rows slowed 4x
    b1 = block_plan_from_counts(counts, params, (2, 2), method="model",
                                cell_weight_scale=scale)
    b2 = block_plan_from_counts(counts, params, (2, 2), method="model",
                                cell_weight_scale=scale[:, None])
    assert b1 == b2
    # the slowed TOP rows shed work: the first row band shrinks vs unscaled
    b0 = block_plan_from_counts(counts, params, (2, 2), method="model")
    assert b1.rows[0] < b0.rows[0], (b1.rows, b0.rows)


def test_replan_auto_with_measured_times_switches_kind():
    """grid='auto' re-plans across plan kinds; measured feedback flows
    through whichever scale shape the previous plan produced."""
    from repro.core.plan import replan

    counts, params = _lamb_setup()
    prev_slab = plan_from_counts(counts, params, 4, method="model")
    out = replan(counts, params, 4, prev_plan=prev_slab,
                 measured_times=np.array([2.0, 1.0, 1.0, 1.0]), grid="auto")
    assert isinstance(out, (SlabPlan, BlockPlan))
    prev_block = block_plan_from_counts(counts, params, (2, 2),
                                        method="model")
    out = replan(counts, params, 4, prev_plan=prev_block,
                 measured_times=np.array([1.0, 1.0, 1.0, 2.0]), grid="auto")
    assert isinstance(out, (SlabPlan, BlockPlan))
