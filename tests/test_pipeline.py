"""Substep-pipelined asynchrony == the serial issue order (DESIGN.md §12).

Pins the acceptance criteria of the pipeline work: the pipelined sharded
driver (cut-level gather issued before the remaining sharded M2L levels,
root-tree sweep deferred to the gather's first consumption, next
substep's packed P2P exchange issued as soon as the rebinned particles
exist) matches the unpipelined driver — and the serial driver — to f32
roundoff on SlabPlan and BlockPlan, with ``use_kernels`` on and off, at
P in {4, 6}; the prefetched-halo route is BIT-exact against the inline
exchange.  Structural pins: the gather's issue depth (compute ops
between issue and first use in the lowered StableHLO, which preserves
trace order) must grow under pipelining while collective counts stay
EQUAL (the prefetch replaces the exchange, never duplicates it), and
degenerate plan axes ship raw-width strips with zero ppermutes on the
single-rank axis.  Fault-injection interplay: a transient halo fault
with an exchange in flight across the substep boundary still recovers
bit-exactly via the plain-retry rung.

Multidevice cases run in subprocesses because jax locks the device count
at first init and the rest of the suite must see exactly 1 CPU device.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.core.cost_model import (ModelParams, comm_overlap_effective,
                                   work_root_tree, work_upward)
from repro.core.fmm import flops_estimate
from repro.core.plan import (block_plan_from_counts, plan_comm_cost,
                             plan_from_counts)
from repro.core.quadtree import build_tree
from repro.core.vortex import lamb_oseen_particles
from repro.launch.hlo_analysis import collective_issue_depths


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
    from repro.core import parallel_fmm as pf
    from repro.core.cost_model import ModelParams
    from repro.core.fmm import fmm_velocity
    from repro.core.plan import SlabPlan, plan_from_counts
    from repro.core.quadtree import build_tree
    from repro.core.stepper import rk2_step
    from repro.core.vortex import lamb_oseen_particles
    from repro.launch.hlo_analysis import collective_issue_depths

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    pos, gamma, sigma = lamb_oseen_particles(160)
    tree, index = build_tree(pos, gamma, level=5, sigma=sigma)
    serial = np.asarray(fmm_velocity(tree, p=12))
    params = ModelParams(level=5, cut=4, p=12, slots=tree.slots)
    model = plan_from_counts(index.counts, params, 4, method="model")
    # thin plan: 2-row boundary bands are ALL rim; the pipeline's deferred
    # root-tree consumption must still see the same gathered cut level
    thin = SlabPlan(level=5, row0=(0, 2, 16, 30), rows=(2, 14, 14, 2))
    for plan in (model, thin):
        for use_kernels in (False, True):
            got = {}
            for pipe in (False, True):
                w = np.asarray(pf.parallel_fmm_velocity(
                    tree, 12, mesh, use_kernels=use_kernels, plan=plan,
                    pipeline=pipe))
                err = np.linalg.norm(w - serial) / np.linalg.norm(serial)
                print(f"rows={plan.rows} kernels={use_kernels} "
                      f"pipeline={pipe} rel_err={err:.3e}")
                assert err < 1e-5, (plan.rows, use_kernels, pipe, err)
                got[pipe] = w
            d = np.linalg.norm(got[True] - got[False]) / \\
                max(np.linalg.norm(got[False]), 1e-30)
            assert d < 1e-6, (plan.rows, use_kernels, d)

    # prefetched-halo route is BIT-exact vs the inline exchange
    pre = pf.parallel_fmm_p2p_prefetch(tree, mesh=mesh, plan=model)
    w_pre = np.asarray(pf.parallel_fmm_velocity(
        tree, 12, mesh, plan=model, pipeline=True, p2p_halo=pre))
    w_inl = np.asarray(pf.parallel_fmm_velocity(
        tree, 12, mesh, plan=model, pipeline=True))
    assert np.array_equal(w_pre, w_inl)

    # full RK2 step: pipelined issue order == pre-pipeline ordering
    outs = {}
    for pipe in (False, True):
        t2 = rk2_step(tree, 1e-4, p=12, mesh=mesh, plan=model,
                      pipeline=pipe)[0]
        outs[pipe] = np.asarray(t2.z)
    assert np.array_equal(outs[True], outs[False])

    # issue-order pin: the cut-level all_gather must be issued with a
    # deeper consumption window under pipelining, at EQUAL collective
    # counts (the prefetch replaces the exchange, never duplicates it)
    depths = {}
    for pipe in (False, True):
        text = jax.jit(lambda tr: pf.parallel_fmm_evaluate(
            tr, 12, mesh=mesh, plan=model, pipeline=pipe)).lower(
                tree).as_text()
        depths[pipe] = collective_issue_depths(text)
    ag_on = max(depths[True]["all_gather"], default=0)
    ag_off = max(depths[False]["all_gather"], default=0)
    assert ag_on > ag_off, (ag_on, ag_off)
    assert len(depths[True]["all_gather"]) == \\
        len(depths[False]["all_gather"])
    assert len(depths[True]["collective_permute"]) == \\
        len(depths[False]["collective_permute"])
    print("gather issue depth:", ag_on, "was", ag_off)
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
    # minimum-size boundary tiles: whole tiles are rim on both axes, so
    # every deferred sharded-M2L level reads ghosts exchanged before the
    # gather was issued
    skew = BlockPlan(level=5, row0=(0, 2, 22), rows=(2, 20, 10),
                     col0=(0, 30), cols=(30, 2))
    for plan in (b23, skew):
        for use_kernels in (False, True):
            got = {}
            for pipe in (False, True):
                w = np.asarray(parallel_fmm_velocity(
                    tree, 12, mesh6, use_kernels=use_kernels, plan=plan,
                    pipeline=pipe))
                err = np.linalg.norm(w - serial) / np.linalg.norm(serial)
                print(f"rows={plan.rows} cols={plan.cols} "
                      f"kernels={use_kernels} pipeline={pipe} "
                      f"rel_err={err:.3e}")
                assert err < 1e-5, (plan.rows, use_kernels, pipe, err)
                got[pipe] = w
            d = np.linalg.norm(got[True] - got[False]) / \\
                max(np.linalg.norm(got[False]), 1e-30)
            assert d < 1e-6, (plan.rows, use_kernels, d)
    print("OK")
""")


_DEGENERATE_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import re
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.core import parallel_fmm as pf

    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    rmax = cmax = 8
    s = 3
    spec = P("data", None, None, None)
    rng = np.random.default_rng(0)
    packed = jnp.asarray(rng.normal(size=(4 * rmax, cmax, 5, s)), jnp.float32)

    def shapes_of(grid):
        fn = lambda x: pf._tile_halo(x, 1, rmax, cmax, "data", grid)
        sm = jax.shard_map(fn, mesh=mesh, in_specs=(spec,),
                           out_specs=spec, check_vma=False)
        text = jax.jit(sm).lower(packed).as_text()
        perm = [l for l in text.splitlines() if "collective_permute" in l]
        widths = set()
        for l in perm:
            for t in re.findall(r"tensor<([0-9]+)x([0-9]+)x[0-9x]*f32", l):
                widths.add(int(t[1]))
        return len(perm), widths

    # 2x2: both axes exchange -> 4 ppermutes; row strips carry the
    # column-extended width (cmax + 2)
    n22, w22 = shapes_of((2, 2))
    assert n22 == 4, n22
    assert cmax + 2 in w22, w22
    # 4x1 slab: the column axis is single-rank -> only the 2 row
    # ppermutes remain and the strips are RAW width (no +2 padding)
    n41, w41 = shapes_of((4, 1))
    assert n41 == 2, n41
    assert w41 == {cmax}, w41
    # 1x4: the row axis is single-rank -> only the 2 column ppermutes
    n14, w14 = shapes_of((1, 4))
    assert n14 == 2, n14

    # value pin: the buffer keeps the padded (rmax+2, cmax+2) shape the
    # consumers index into; only the STRIPS shrank.  Interior of each
    # tile is the tile's own data, untouched, and the degenerate column
    # halo stays zero
    out = np.asarray(jax.jit(jax.shard_map(
        lambda x: pf._tile_halo(x, 1, rmax, cmax, "data", (4, 1)),
        mesh=mesh, in_specs=(spec,), out_specs=spec, check_vma=False))(packed))
    assert out.shape == (4 * (rmax + 2), cmax + 2, 5, s)
    for d in range(4):
        r0 = d * (rmax + 2)
        np.testing.assert_array_equal(
            out[r0 + 1: r0 + 1 + rmax, 1: 1 + cmax],
            np.asarray(packed[d * rmax:(d + 1) * rmax]))
        assert (out[r0: r0 + rmax + 2, 0] == 0).all()
        assert (out[r0: r0 + rmax + 2, cmax + 1] == 0).all()
        # row halos carry the neighbor tiles' edge rows
        if d > 0:
            np.testing.assert_array_equal(
                out[r0, 1: 1 + cmax],
                np.asarray(packed[d * rmax - 1]))
        if d < 3:
            np.testing.assert_array_equal(
                out[r0 + 1 + rmax, 1: 1 + cmax],
                np.asarray(packed[(d + 1) * rmax]))
    print("OK")
""")


_FAULT_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax
    from jax.sharding import Mesh
    from repro.core.stepper import VortexStepper
    from repro.core.faults import FaultInjector, FaultSpec
    from repro.core import health as hw

    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.default_rng(1)
    pos = 0.02 + 0.96 * rng.random((300, 2))
    gamma = rng.standard_normal(300) * 0.1
    KW = dict(sigma=0.02, p=6, dt=0.002, mesh=mesh, pipeline=True)

    def run(faults=None, steps=3):
        st = VortexStepper(pos, gamma, faults=faults, **KW)
        recs = [st.step() for _ in range(steps)]
        return st, recs

    st0, _ = run()
    z0 = np.asarray(st0.tree.z)
    # transient halo corruption lands while substep 2's prefetched
    # exchange is already in flight across the substep boundary; the
    # health word must still merge it in, and the plain-retry rung
    # re-runs the identical pipelined program from the intact pre-step
    # tree -> BIT-exact vs the uninjected pipelined run
    for site in ("halo_nan", "tile_corrupt"):
        st, recs = run(FaultInjector(FaultSpec(site, step=2)))
        assert recs[1].recovered == "retry_1", (site, recs[1])
        assert recs[1].health != 0, site
        assert hw.ok(hw.unpack(recs[1].health)), site
        assert np.array_equal(np.asarray(st.tree.z), z0), site
        assert recs[0].recovered == "" and recs[2].recovered == "", site
    print("OK")
""")


def test_pipeline_matches_unpipelined_slab_4dev():
    """Pipelined == unpipelined == serial on 4 devices, SlabPlan, both
    kernel routes, thin all-rim bands included; prefetched halo bit-exact;
    RK2 step value-identical across issue orders; gather issue-depth and
    equal-collective pins (acceptance-pinned)."""
    _run(_SLAB_BODY)


def test_pipeline_matches_unpipelined_block_6dev():
    """Pipelined == unpipelined == serial on 6 devices, BlockPlan (2x3 and
    thin 2-row/2-col boundary tiles), both kernel routes."""
    _run(_BLOCK_BODY)


def test_degenerate_axis_exchange_is_minimal():
    """Single-rank plan axes ship NO ppermutes and raw-width strips
    (satellite bugfix: slab plans used to pay the column-extended width
    on their row strips); 2x2 keeps the full 4-ppermute exchange."""
    _run(_DEGENERATE_BODY)


def test_pipeline_fault_interplay_recovers_bit_exact():
    """A transient fault injected while the cross-substep exchange is in
    flight still recovers via plain retry, bit-exact — recovery semantics
    are applied at the consumer, not the prefetch site."""
    _run(_FAULT_BODY)


# ---------------------------------------------------------------------------
# Host-side: issue-depth parser and the pipeline-aware cost model
# ---------------------------------------------------------------------------


_TOY_HLO = textwrap.dedent("""
    module @toy {
      func.func public @main(%arg0: tensor<4x8xf32>) -> tensor<4x8xf32> {
        %0 = "stablehlo.all_gather"(%arg0) : (tensor<4x8xf32>) -> tensor<16x8xf32>
        %1 = stablehlo.dot_general %arg0, %arg0, contracting_dims = [1] x [1]
        %2 = stablehlo.add %1, %1 : tensor<4x4xf32>
        %3 = stablehlo.dot_general %2, %2, contracting_dims = [1] x [1]
        %4 = "stablehlo.collective_permute"(%3) : (tensor<4x4xf32>) -> tensor<4x4xf32>
        %5 = stablehlo.dot_general %4, %4, contracting_dims = [1] x [1]
        %6 = stablehlo.slice %0 [0:4, 0:8] : (tensor<16x8xf32>) -> tensor<4x8xf32>
        return %6 : tensor<4x8xf32>
      }
    }
""")


def test_collective_issue_depths_parser():
    d = collective_issue_depths(_TOY_HLO)
    # %0 (all_gather) is first consumed by %6: three dot_generals between
    assert d["all_gather"] == [3]
    # %4 (permute) is consumed by the very next dot_general: depth 0
    assert d["collective_permute"] == [0]
    # elementwise glue (%2 add) never counts toward depth
    d2 = collective_issue_depths(_TOY_HLO, compute=("add",))
    assert d2["all_gather"] == [1]


def _lamb_setup(level=5):
    pos, gamma, sigma = lamb_oseen_particles(120)
    tree, index = build_tree(pos, gamma, level=level, sigma=sigma)
    params = ModelParams(level=level, cut=4, p=10, slots=tree.slots)
    return index.counts, params


def test_pipeline_enlarges_hiding_budget():
    """pipeline=True adds root-tree + upward flops to the hiding budget:
    the comm residue can only shrink, and stays between the overlapped
    and serial prices."""
    counts, params = _lamb_setup()
    for plan in (plan_from_counts(counts, params, 4, method="model"),
                 block_plan_from_counts(counts, params, (2, 2),
                                        method="model")):
        piped = plan_comm_cost(plan, counts, params, overlap=True,
                               pipeline=True)
        plain = plan_comm_cost(plan, counts, params, overlap=True,
                               pipeline=False)
        serial = plan_comm_cost(plan, counts, params, overlap=False)
        assert (piped <= plain + 1e-12).all()
        assert (plain <= serial + 1e-12).all()
        assert serial.sum() > 0


def test_comm_overlap_effective_extra_hide():
    params = ModelParams(level=5, cut=4, p=10, slots=8)
    assert comm_overlap_effective(100.0, 40.0, params) == 60.0
    assert comm_overlap_effective(100.0, 40.0, params, extra_hide=30.0) == 30.0
    assert comm_overlap_effective(100.0, 40.0, params, extra_hide=1e9) == 0.0
    # the extra budget is an overlap feature: serial pricing ignores it
    assert comm_overlap_effective(100.0, 40.0, params, overlap=False,
                                  extra_hide=1e9) == 100.0


def test_work_root_tree_and_upward_terms():
    params = ModelParams(level=6, cut=3, p=10, slots=8)
    rt = work_root_tree(params)
    up = work_upward(params, leaf_boxes=64.0)
    assert rt > 0 and up > 0
    # deeper cut -> more replicated root-tree levels -> more hidden work
    deeper = ModelParams(level=6, cut=4, p=10, slots=8)
    assert work_root_tree(deeper) > rt


def test_flops_estimate_pipeline_census():
    base = flops_estimate(5, 8, 10)
    assert base["gather_overlap_flops"] == 0.0
    assert base["p2p_prefetch_rounds"] == 0.0
    sh = flops_estimate(5, 8, 10, grid=(2, 2), cut=2)
    assert sh["p2p_prefetch_rounds"] == 1.0
    expect = sum(4 ** l for l in range(3, 6)) * 27 * 10 * 10 * 6.0
    assert sh["gather_overlap_flops"] == expect
    # windows, not work: the stage total is unchanged
    assert sh["total"] == base["total"]
