"""``python -m repro.analysis.check`` — run every static-analysis layer.

Sections (each independently skippable):

* ``lint``      — AST rules over ``src/repro`` (:mod:`.lint`)
* ``contracts`` — the trace-contract catalog over the named entry points
  (:mod:`.contracts`): M2L no-staging + fewer-bytes, fused-exchange
  collective counts (2x2 and both degenerate grids), pipelined issue
  depth, guard-free traces, no-donation on ``rk2_step``, no f64 upcasts,
  no host callbacks
* ``schedule``  — the SPMD collective-schedule verifier across every
  device id, both plan kinds, degenerate single-rank axes included
  (:mod:`.schedule`)
* ``retrace``   — the scripted jit-cache session (:mod:`.retrace`)

Exit status is nonzero on any violation; CI runs this as the dedicated
``static-analysis`` job.  ``--json PATH`` writes machine-readable
section summaries.  The process forces 6 host devices BEFORE importing
jax (jax locks the device count at first init) so the 4- and 6-device
meshes both exist; ``--devices N`` lowers the forced count.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

SECTIONS = ("lint", "contracts", "schedule", "retrace")


def _force_devices(n: int) -> None:
    if "jax" in sys.modules:
        return                      # too late; use whatever is configured
    flag = f"--xla_force_host_platform_device_count={n}"
    prev = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in prev:
        os.environ["XLA_FLAGS"] = (prev + " " + flag).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


def _fmm_fixture(level, p, n=2000, charge_scale=None):
    import numpy as np
    from repro.core.quadtree import build_tree

    rng = np.random.default_rng(0)
    pos = rng.uniform(0.02, 0.98, size=(n, 2))
    return build_tree(pos, rng.normal(size=n), level, sigma=0.02,
                      charge_scale=charge_scale)


def _mesh(ndev):
    import numpy as np
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:ndev]), ("data",))


def _plans(tree, index, level, p, ndev, grid):
    from repro.core.cost_model import ModelParams
    from repro.core.plan import block_plan_from_counts, plan_from_counts

    params = ModelParams(level=level, cut=min(4, level - 1), p=p,
                         slots=tree.slots)
    slab = plan_from_counts(index.counts, params, ndev, method="model")
    block = block_plan_from_counts(index.counts, params, grid,
                                   method="model")
    return slab, block


def _fused_exchange(grid, ndev):
    """The packed P2P ``_tile_halo`` round as its own jitted entry — the
    PR-4 fusion pin's exact subject.  Tile extents don't affect the
    collective count, only strip widths, so a small fixed tile is fine."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core import parallel_fmm as pf

    rmax = cmax = 4
    def fused(z, q, m):
        buf = pf._tile_halo(pf._pack_particles(z, q, m), 1, rmax, cmax,
                            "data", grid)
        return pf._unpack_particles(buf, z.dtype)

    spec = P("data", None, None)
    jfn = jax.jit(jax.shard_map(fused, mesh=_mesh(ndev),
                                in_specs=(spec,) * 3,
                                out_specs=(spec,) * 3, check_vma=False))
    shape = (ndev * rmax, cmax, 2)
    z = jnp.ones(shape, jnp.complex64)
    return jfn, (z, z, jnp.ones(shape, bool))


# ---------------------------------------------------------------------------
# sections
# ---------------------------------------------------------------------------


def run_lint_section(args):
    from repro.analysis import lint

    root = os.path.join(os.path.dirname(__file__), "..")
    findings = lint.run_lint(os.path.abspath(root))
    print(lint.format_findings(findings))
    return {"checked": len(lint.DEFAULT_RULES), "violations": len(findings),
            "detail": [str(f) for f in findings]}


def run_contracts_section(args):
    import jax
    from repro.analysis import contracts as C
    from repro.core import expansions as ex
    from repro.core import parallel_fmm as pf
    from repro.core import stepper as stp
    from repro.core.fmm import fmm_velocity
    from repro.kernels import ops as kops

    quick = args.quick
    results = []

    # -- M2L staging/bytes (serial, compiled HLO) ---------------------------
    import numpy as np
    import jax.numpy as jnp
    level, p = (3, 12) if quick else (4, 17)
    n = 1 << level
    rng = np.random.default_rng(0)
    me = jnp.asarray(rng.normal(size=(n, n, p)) +
                     1j * rng.normal(size=(n, n, p)), jnp.complex64)
    lw = lambda f, label: C.Lowered(jax.jit(f), me, label=label)
    kern = lw(lambda g: kops.m2l_apply(g, level, p), "m2l_apply")
    fold = lw(lambda g: ex.m2l_reference(g, level, p), "m2l_reference")
    m40 = lw(lambda g: ex.m2l_masked40(g, level, p), "m2l_masked40")
    staging = [C.no_staging_dim(40 * p), C.no_f64_upcast()]
    results += C.evaluate(kern, staging)
    results += C.evaluate(fold, staging)
    results += C.evaluate(fold, [C.fewer_bytes("folded", "masked40")],
                          pair_with=m40)

    # -- unguarded serial driver + rk2_step (sentinels, donation) -----------
    tree, index = _fmm_fixture(3 if quick else 4, 6)
    drv = C.Lowered(jax.jit(lambda t: fmm_velocity(t, p=6)), tree,
                    label="fmm_velocity")
    results += C.evaluate(drv, [C.sentinel_free(), C.no_host_callback(),
                                C.no_f64_upcast()])
    rk2 = stp.TRACE_ENTRY_POINTS["rk2_step"]
    rk2_low = C.Lowered(rk2, tree, 1e-4, p=6, label="rk2_step[guard=False]")
    results += C.evaluate(rk2_low, [C.sentinel_free(),
                                    C.not_donated("rk2"),
                                    C.no_host_callback()])

    # -- batched serving entries (serve/fmm_service, PR 10) -----------------
    from repro.core import equations as eqs
    from repro.serve import fmm_service as svc
    strees = [_fmm_fixture(3, 6, n=300)[0] for _ in range(2)]
    bz, bq, bm = svc.stack_trees(strees, 2)
    for ep_name, xargs in (("batched_fmm_eval", (bz, bq, bm)),
                           ("batched_fmm_eval_targets",
                            (bz, bq, bm, bz, bm))):
        low = C.Lowered(svc.TRACE_ENTRY_POINTS[ep_name], *xargs,
                        level=3, sigma=0.02, p=6, eq=eqs.VORTEX,
                        label=f"{ep_name}[B2]")
        results += C.evaluate(low, [C.sentinel_free(), C.no_host_callback(),
                                    C.no_f64_upcast()])

    # -- fused packed exchange: 4 ppermutes on 2x2, 2 on degenerate axes ----
    ndev = min(4, args.devices)
    if ndev >= 4:
        for grid, want in (((2, 2), 4), ((4, 1), 2), ((1, 4), 2)):
            jfn, xargs = _fused_exchange(grid, 4)
            low = C.Lowered(jfn, *xargs,
                            label=f"p2p_exchange{grid[0]}x{grid[1]}")
            results += C.evaluate(
                low, [C.collective_count("collective-permute", want)])

        # -- pipelined issue order on the sharded evaluation ----------------
        level, p = (5, 8) if quick else (6, 12)
        tree, index = _fmm_fixture(level, p, n=4000 if quick else 20000)
        slab, _ = _plans(tree, index, level, p, 4, (2, 2))
        mesh = _mesh(4)
        evaluate_ep = pf.TRACE_ENTRY_POINTS["parallel_fmm_evaluate"]
        on = C.Lowered(evaluate_ep, tree, p, mesh, plan=slab,
                       pipeline=True, label="fmm[pipeline=on]")
        off = C.Lowered(evaluate_ep, tree, p, mesh, plan=slab,
                        pipeline=False, label="fmm[pipeline=off]")
        results += C.evaluate(on, [C.issue_depth_grows("all_gather"),
                                   C.min_issue_depth("all_gather",
                                                     8 if quick else 32)],
                              pair_with=off)

    print(C.format_results(results))
    bad = C.violations(results)
    return {"checked": len(results), "violations": len(bad),
            "detail": [str(r) for r in bad]}


def run_schedule_section(args):
    from repro.analysis import schedule as S
    from repro.core import parallel_fmm as pf
    from repro.core import stepper as stp

    reports = []
    level, p = (4, 6) if args.quick else (5, 8)
    tree, index = _fmm_fixture(level, p)
    evaluate_ep = pf.TRACE_ENTRY_POINTS["parallel_fmm_evaluate"]

    cases = []
    if args.devices >= 4:
        slab, block = _plans(tree, index, level, p, 4, (2, 2))
        cases += [("slab_P4", 4, slab), ("block_2x2", 4, block)]
        # degenerate single-rank axes — PR 7's exchange-skip edge
        _, b41 = _plans(tree, index, level, p, 4, (4, 1))
        _, b14 = _plans(tree, index, level, p, 4, (1, 4))
        cases += [("block_4x1", 4, b41), ("block_1x4", 4, b14)]
    if args.devices >= 6:
        _, b23 = _plans(tree, index, level, p, 6, (2, 3))
        cases += [("block_2x3", 6, b23)]
    if args.devices >= 3:
        # shrunken-world mesh (DESIGN.md §14): after a coordinated 4->3
        # shrink the survivors re-lower every module at the odd world
        # size — verify the post-shrink schedule is hang-free too, not
        # just the power-of-two launch configurations
        slab3, _ = _plans(tree, index, level, p, 3, (3, 1))
        cases += [("slab_P3_shrunk", 3, slab3)]

    for label, ndev, plan in cases:
        rep = S.verify_entry(evaluate_ep, tree, p, _mesh(ndev), plan=plan,
                             ndev=ndev, label=f"parallel_fmm[{label}]")
        reports.append(rep)
    if args.devices >= 4:
        slab, _ = _plans(tree, index, level, p, 4, (2, 2))
        rep = S.verify_entry(stp.TRACE_ENTRY_POINTS["rk2_step"], tree, 1e-4,
                             p=p, mesh=_mesh(4), plan=slab, ndev=4,
                             label="rk2_step[slab_P4]")
        reports.append(rep)
        # targets mode — the serving engine's sharded probe-grid lane
        # (serve/fmm_service._run_sharded) runs this exact configuration
        tgt_tree, _ = _fmm_fixture(level, p, n=500)
        rep = S.verify_entry(evaluate_ep, tree, p, _mesh(4), plan=slab,
                             targets=tgt_tree, ndev=4,
                             label="parallel_fmm[slab_P4_targets]")
        reports.append(rep)
    if args.devices >= 3:
        slab3, _ = _plans(tree, index, level, p, 3, (3, 1))
        rep = S.verify_entry(stp.TRACE_ENTRY_POINTS["rk2_step"], tree, 1e-4,
                             p=p, mesh=_mesh(3), plan=slab3, ndev=3,
                             label="rk2_step[slab_P3_shrunk]")
        reports.append(rep)

    bad = [r for r in reports if not r.ok]
    for r in reports:
        print(r.diff_text() if not r.ok else
              f"schedule [{r.label}]: consistent, "
              f"{len(r.schedules[0])} collectives x {r.ndev} devices")
    return {"checked": len(reports), "violations": len(bad),
            "detail": [r.diff_text() for r in bad]}


def run_retrace_section(args):
    from repro.analysis import retrace as R

    events = R.run_session(level=3, p=4)
    events += R.run_serve_session(level=2, p=4)
    bad = [e for e in events if not e.ok]
    for e in events:
        print(f"retrace {e}")
    return {"checked": len(events), "violations": len(bad),
            "detail": [str(e) for e in bad]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.check",
        description="trace contracts + lint + schedule verify + retrace")
    ap.add_argument("--quick", action="store_true",
                    help="smaller fixtures (CI quick tier)")
    ap.add_argument("--devices", type=int, default=6,
                    help="host devices to force (default 6: covers the "
                         "4-dev and 2x3 meshes)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write section summaries as JSON")
    ap.add_argument("--skip", action="append", default=[],
                    choices=SECTIONS, help="skip a section (repeatable)")
    args = ap.parse_args(argv)

    _force_devices(args.devices)

    runners = {"lint": run_lint_section,
               "contracts": run_contracts_section,
               "schedule": run_schedule_section,
               "retrace": run_retrace_section}
    summary, failed = {}, 0
    for name in SECTIONS:
        if name in args.skip:
            summary[name] = {"skipped": True}
            continue
        print(f"==== {name} ====")
        res = runners[name](args)
        summary[name] = res
        failed += res["violations"]
        print(f"---- {name}: {res['checked']} checked, "
              f"{res['violations']} violation(s)\n")

    total_checked = sum(s.get("checked", 0) for s in summary.values())
    print(f"==== total: {total_checked} checks, {failed} violation(s) ====")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
