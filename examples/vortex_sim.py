"""Vortex-method simulation driver (the paper's client application, §3).

Advects Lamb-Oseen vortex particles with their FMM-computed Biot-Savart
velocity (inviscid step, RK2) through :class:`repro.core.stepper.VortexStepper`:
each step is ONE jitted device program (FMM -> half-kick -> device rebin ->
FMM -> full kick -> rebin; no host tree rebuild), executed under the
partition-driven :class:`SlabPlan` of choice:

  --plan uniform   equal-count row bands (the DPMTA-style strawman)
  --plan model     a-priori cost-model bands (paper §4-§5, static)
  --plan dynamic   model bands re-planned from the drifted particle
                   distribution every --replan-every steps (paper's title)

``--plan-grid PrxPc`` (e.g. ``2x3``) schedules a 2-D BlockPlan tile grid
with two-axis halos instead of 1-D row bands; it implies
``--devices Pr*Pc``.  ``--plan-grid auto`` lets the per-axis grid
autotuner choose slab vs block and the (Pr, Pc) factorization from the
cost model (Eq-20 balance + overlap-aware comm residue) at build time and
every replan.  ``--no-overlap`` disables the sharded driver's interior/rim
communication-computation overlap (DESIGN.md §9).

The vorticity field is a steady Euler solution up to core diffusion, so
particles should orbit the vortex center on (nearly) circular paths — the
initial radius is carried through every rebinning as a step payload and
the max radius drift is the correctness invariant.

Run:  PYTHONPATH=src python examples/vortex_sim.py [--steps 10] [--n-side 80]
          [--plan dynamic] [--devices 4]
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dt", type=float, default=0.005)
    ap.add_argument("--n-side", type=int, default=80)
    ap.add_argument("--p", type=int, default=12)
    ap.add_argument("--plan", choices=("uniform", "model", "dynamic"),
                    default="model")
    ap.add_argument("--plan-grid", default=None, metavar="PrxPc|auto",
                    help="2-D BlockPlan device grid, e.g. 2x3 (implies "
                         "--devices Pr*Pc), or 'auto' to let the per-axis "
                         "grid autotuner pick slab vs block and (Pr, Pc) "
                         "from the cost model at every replan")
    ap.add_argument("--devices", type=int, default=1,
                    help="shard over N devices (forces host devices on CPU)")
    ap.add_argument("--replan-every", type=int, default=4)
    ap.add_argument("--no-overlap", action="store_true",
                    help="disable interior/rim comm-compute overlap")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="disable substep pipelining (cross-substep P2P "
                         "prefetch + gather/root-tree overlap); the serial "
                         "issue order of the pre-pipeline driver")
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--debug-nans", action="store_true",
                    help="jax_debug_nans: crash on the first NaN any jitted "
                         "computation produces (guarded recovery is "
                         "disabled so the fault is not masked)")
    ap.add_argument("--no-guard", action="store_true",
                    help="disable the health word + recovery ladder")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot (tree, payload) here every "
                         "--checkpoint-every steps")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore from the latest checkpoint in "
                         "--checkpoint-dir instead of starting fresh")
    args = ap.parse_args()

    plan_grid = None
    if args.plan_grid is not None and args.plan_grid.lower() == "auto":
        plan_grid = "auto"
    elif args.plan_grid is not None:
        try:
            plan_grid = tuple(int(x) for x in args.plan_grid.lower().split("x"))
            assert len(plan_grid) == 2 and min(plan_grid) >= 1
        except (ValueError, AssertionError):
            sys.exit(f"--plan-grid must look like 2x3 or auto, "
                     f"got {args.plan_grid!r}")
        ndev = plan_grid[0] * plan_grid[1]
        if args.devices not in (1, ndev):
            sys.exit(f"--plan-grid {args.plan_grid} needs {ndev} devices, "
                     f"--devices says {args.devices}")
        args.devices = ndev

    if args.devices > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}")

    sys.path.insert(0, "src")
    from repro.configs import backend
    backend.use_compile_cache()
    if args.debug_nans:
        # debug-NaN wants the raw failure, not a recovered one
        backend.set_debug_nan(True)
        args.no_guard = True
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from repro.core.stepper import VortexStepper
    from repro.core.vortex import lamb_oseen_particles

    pos, gamma, sigma = lamb_oseen_particles(args.n_side)
    r0 = np.hypot(pos[:, 0] - 0.5, pos[:, 1] - 0.5)

    mesh = None
    if args.devices > 1:
        if len(jax.devices()) < args.devices:
            sys.exit(f"need {args.devices} devices, have {len(jax.devices())}")
        mesh = Mesh(np.array(jax.devices()[:args.devices]), ("data",))

    common = dict(
        mesh=mesh, use_kernels=args.use_kernels,
        plan_method="uniform" if args.plan == "uniform" else "model",
        dynamic=(args.plan == "dynamic"), plan_grid=plan_grid,
        overlap=not args.no_overlap, pipeline=not args.no_pipeline,
        replan_every=args.replan_every,
        guard=not args.no_guard,
        checkpoint_every=args.checkpoint_every)
    if args.resume:
        if not args.checkpoint_dir:
            sys.exit("--resume needs --checkpoint-dir")
        stepper = VortexStepper.from_checkpoint(args.checkpoint_dir, **common)
        print(f"resumed from step {stepper.step_count} in "
              f"{args.checkpoint_dir}")
    else:
        stepper = VortexStepper(
            pos, gamma, sigma, p=args.p, dt=args.dt,
            checkpoint_dir=args.checkpoint_dir,
            payload={"r0": r0 + 0j}, **common)
    s0 = stepper.stats()
    print(f"plan={args.plan} devices={stepper.nparts} "
          f"level={stepper.params.level} bands={stepper.plan.describe()} "
          f"LB(min/max)={s0['load_balance']:.3f}")

    drift = 0.0
    for step in range(args.steps):
        rec = stepper.step()
        if step % 2 == 1 or step == args.steps - 1:
            m = np.asarray(stepper.tree.mask).reshape(-1)
            z = np.asarray(stepper.tree.z).reshape(-1)[m]
            rr0 = np.asarray(stepper.payload["r0"]).reshape(-1)[m].real
            r = np.hypot(z.real - 0.5, z.imag - 0.5)
            sel = rr0 > 0.02
            drift = np.abs(r[sel] - rr0[sel]).max()
            flags = ("R" if rec.replanned else "") + ("L" if rec.releveled else "")
            print(f"step {rec.step:3d}: max |r - r0| = {drift:.2e}  "
                  f"LB={rec.load_balance:.3f}  {rec.seconds * 1e3:7.1f} ms {flags}")
    assert drift < 5e-3, drift
    print("OK")


if __name__ == "__main__":
    main()
