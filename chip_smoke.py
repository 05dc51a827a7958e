"""Run the FMM's main path once on a TPU and check what comes out.

The deployment is the paper's §7 vortex run, as ``configs/petfmm_vortex``
records it: the 875^2 Lamb-Oseen lattice (765,625 particles), tree level
10, cut level 4, p = 17, with the core size ``lamb_oseen_particles``
returns.  All phases run in this one process, which holds the chip:

  python chip_smoke.py               one chip: evaluate, stepper, serve
  python chip_smoke.py --four-chips  four chips: the sharded evaluator on a
                                     slab 4x1 and a block 2x2 plan against
                                     the one-chip serial result, then two
                                     stepper steps on the 4-device mesh

Each phase prints its compile and steady seconds and the numbers it checks;
the last line is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.  The script exits non-zero, and prints no such line, when JAX
finds no TPU, when a phase raises or when a bound fails.  ``--n-side``,
``--level`` and ``--p`` shrink the run: only a shrunk run executes its
phases off-TPU (a rehearsal, which still ends non-zero).
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.configs.backend import use_compile_cache  # noqa: E402
from repro.configs.petfmm_vortex import CONFIG  # noqa: E402

EVAL_REL_L2 = 5e-4       # vs the sampled f64 direct sum (far-field kernel)
SHARDED_REL_L2 = 1e-5    # sharded vs one-chip serial, same kernels
SAMPLE_TARGETS = 512
SERVE_SIGMA = 0.005      # a core well inside a service job's leaf box


def _check(ok, message: str) -> None:
    """A failed bound ends the run (and survives ``python -O``)."""
    if not ok:
        raise RuntimeError(message)


def _rel_l2(a, b) -> float:
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / np.linalg.norm(np.asarray(b)))


def _timed(fn, reps: int):
    """(result, first-call seconds, mean steady seconds) of ``fn``."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn())
    return out, first, (time.perf_counter() - t0) / reps


def _check_steps(name: str, records) -> None:
    from repro.core import health as hw
    for rec in records:
        clean = hw.ok(hw.unpack(rec.health))
        print(f"{name}: step={rec.step} seconds={rec.seconds:.6f} "
              f"level={rec.level} recovered={rec.recovered!r} "
              f"health_clean={clean}", flush=True)
        _check(clean and rec.recovered == "",
               f"{name} step {rec.step}: health {hw.describe(rec.health)}, "
               f"recovered by {rec.recovered!r}")


def _run_stepper(name: str, pos, gamma, sigma, p: int, steps: int,
                 mesh=None) -> None:
    from repro.core.stepper import VortexStepper
    st = VortexStepper(pos, gamma, sigma, p=p, mesh=mesh, use_kernels=True)
    records = [st.step() for _ in range(steps)]
    steady = records[-1].seconds
    print(f"{name}: level={st.params.level} slots={st.params.slots} "
          f"devices={st.nparts} compile_s={records[0].seconds - steady:.6f} "
          f"steady_s={steady:.6f}", flush=True)
    _check_steps(name, records)


def phase_evaluate(tree, index, z, gamma, sigma, p: int, rng) -> None:
    import jax
    from repro.core import equations as eqs
    from repro.core.fmm import fmm_evaluate, fmm_velocity
    from repro.core.quadtree import gather_particle_values

    sel = rng.choice(len(z), SAMPLE_TARGETS, replace=False)
    ref = eqs.direct_sum(eqs.VORTEX, z[sel], z, gamma, sigma, chunk=16)
    for use_kernels in (True, False):
        route = "kernels" if use_kernels else "jnp"
        t0 = time.perf_counter()
        compiled = fmm_evaluate.lower(tree, p, eq=eqs.VORTEX,
                                      use_kernels=use_kernels).compile()
        compile_s = time.perf_counter() - t0
        custom = compiled.as_text().count("tpu_custom_call")
        # off-TPU the kernels are interpreted: only a rehearsal gets here
        _check(custom or not use_kernels or jax.default_backend() != "tpu",
               "kernel program holds no tpu_custom_call")
        w, first, steady = _timed(
            lambda: fmm_velocity(tree, p, use_kernels=use_kernels), reps=3)
        wp = gather_particle_values(np.asarray(w), index)
        err = _rel_l2(wp[sel], ref)
        print(f"evaluate: route={route} level={tree.level} "
              f"slots={tree.slots} particles={len(z)} p={p} "
              f"tpu_custom_calls={custom} compile_s={compile_s:.6f} "
              f"first_call_s={first:.6f} steady_s={steady:.6f} "
              f"rel_l2={err:.3e}", flush=True)
        _check(np.isfinite(wp).all(), f"{route}: non-finite velocities")
        _check(err <= EVAL_REL_L2, f"{route}: rel_l2 {err:.3e}")


def phase_serve(p: int, rng) -> None:
    from repro.core import equations as eqs
    from repro.serve import fmm_service as svc

    engine = svc.FmmServiceEngine(
        use_kernels=True,
        budget=svc.ServiceBudget(max_job_flops=1e13, max_queue_flops=1e14))
    jobs = {}
    t0 = time.perf_counter()
    for i, equation in enumerate(("vortex", "laplace", "vortex", "laplace")):
        n = 10_000 + 1_000 * i
        job = svc.FmmJob(positions=rng.uniform(0.05, 0.95, size=(n, 2)),
                         strength=rng.normal(size=n), equation=equation,
                         sigma=SERVE_SIGMA, tenant=f"t{i % 2}")
        jobs[engine.submit(job)] = job
    n = 10_000
    sid = engine.submit(svc.FmmJob(
        positions=rng.uniform(0.3, 0.7, size=(n, 2)),
        strength=0.1 * rng.normal(size=n), steps=2, p=p, dt=1e-3,
        sigma=SERVE_SIGMA, tenant="session"))
    records = [rec for _, _, rec in engine.session(sid).stream(2)]
    engine.drain()
    wall = time.perf_counter() - t0
    stats = engine.stats()
    _check(stats["rejected"] == 0, f"serve: rejected jobs: {stats}")
    for jid, job in jobs.items():
        r = engine.result(jid)
        zs = job.positions[:, 0] + 1j * job.positions[:, 1]
        sel = rng.choice(len(zs), SAMPLE_TARGETS, replace=False)
        ref = eqs.direct_sum(job.equation, zs[sel], zs, job.strength,
                             job.sigma)
        out = r.out[sel]
        if job.equation == "laplace":
            # the potential's imaginary part is branch-dependent: compare Re
            errs = [_rel_l2(out[:, 0].real, ref[:, 0].real),
                    _rel_l2(out[:, 1], ref[:, 1])]
        else:
            errs = [_rel_l2(out, ref)]
        print(f"serve: job={jid} equation={job.equation} "
              f"particles={len(zs)} level={r.price.level} "
              f"slots={r.price.slots} p={r.price.p} lane={r.lane} "
              f"batch={r.batch_capacity} latency_s={r.latency_s:.6f} "
              f"rel_l2={','.join(f'{e:.3e}' for e in errs)}", flush=True)
        _check(np.isfinite(r.out).all() and max(errs) <= EVAL_REL_L2,
               f"serve job {jid}: rel_l2 {errs}")
    print(f"serve: wall_s={wall:.6f} admitted={stats['admitted']} "
          f"rejected={stats['rejected']} batches={stats['batches']} "
          f"session_steps={stats['session_steps']}", flush=True)
    _check_steps("serve_session", records)


def phase_four_chips(tree, index, pos, gamma, sigma, p: int, cut: int) -> None:
    import jax
    from jax.sharding import Mesh
    from repro.core import equations as eqs
    from repro.core.cost_model import ModelParams
    from repro.core.fmm import fmm_velocity
    from repro.core.parallel_fmm import (parallel_fmm_evaluate,
                                         parallel_fmm_velocity)
    from repro.core.plan import plan_from_counts

    devices = jax.devices()
    _check(len(devices) >= 4,
           f"--four-chips needs 4 devices, JAX has {len(devices)}")
    mesh = Mesh(np.array(devices[:4]), ("data",))
    mask = np.asarray(tree.mask)
    serial, first, steady = _timed(
        lambda: fmm_velocity(tree, p, use_kernels=True), reps=2)
    serial = np.asarray(serial)[mask]
    print(f"four_chips: serial level={tree.level} slots={tree.slots} "
          f"first_call_s={first:.6f} steady_s={steady:.6f}", flush=True)
    params = ModelParams(level=tree.level, cut=cut, p=p, slots=tree.slots)
    plans = {"slab_4x1": plan_from_counts(index.counts, params, 4),
             "block_2x2": plan_from_counts(index.counts, params, 4,
                                           grid=(2, 2))}
    for name, plan in plans.items():
        t0 = time.perf_counter()
        parallel_fmm_evaluate.lower(tree, p, mesh=mesh, use_kernels=True,
                                    plan=plan, eq=eqs.VORTEX).compile()
        compile_s = time.perf_counter() - t0
        w, first, steady = _timed(
            lambda: parallel_fmm_velocity(tree, p, mesh, use_kernels=True,
                                          plan=plan), reps=3)
        held = {s.device for s in w.addressable_shards}
        _check(held == set(mesh.devices.flat)
               and len(w.addressable_shards) == 4,
               f"{name}: shards on {sorted(str(d) for d in held)}")
        err = _rel_l2(np.asarray(w)[mask], serial)
        print(f"four_chips: plan={name} {plan.describe()} "
              f"compile_s={compile_s:.6f} first_call_s={first:.6f} "
              f"steady_s={steady:.6f} rel_l2_vs_serial={err:.3e}",
              flush=True)
        _check(err <= SHARDED_REL_L2, f"{name}: rel_l2 {err:.3e}")
    _run_stepper("four_chips_stepper", pos, gamma, sigma, p, steps=2,
                 mesh=mesh)


def main(argv=None) -> int:
    full_side = math.isqrt(CONFIG.num_particles)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip sharded phase")
    ap.add_argument("--n-side", type=int, default=full_side)
    ap.add_argument("--level", type=int, default=CONFIG.level)
    ap.add_argument("--p", type=int, default=CONFIG.p)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_compile_cache()
    import jax
    from repro.core.quadtree import build_tree
    from repro.core.vortex import lamb_oseen_particles

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    shrunk = (args.n_side, args.level, args.p) != \
        (full_side, CONFIG.level, CONFIG.p)
    if not on_tpu and not shrunk:
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
                 f"shrink the run with --n-side/--level/--p to rehearse it "
                 f"here")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)

    pos, gamma, sigma = lamb_oseen_particles(
        args.n_side, sigma=CONFIG.sigma, spacing_ratio=CONFIG.spacing_ratio)
    z = pos[:, 0] + 1j * pos[:, 1]
    t0 = time.perf_counter()
    tree, index = build_tree(pos, gamma, args.level, sigma)
    print(f"setup: particles={len(z)} level={args.level} slots={tree.slots} "
          f"p={args.p} sigma={sigma:.6e} "
          f"build_tree_s={time.perf_counter() - t0:.6f}", flush=True)
    rng = np.random.default_rng(args.seed)

    if args.four_chips:
        phase_four_chips(tree, index, pos, gamma, sigma, args.p,
                         CONFIG.cut_level)
    else:
        phase_evaluate(tree, index, z, gamma, sigma, args.p, rng)
        _run_stepper("stepper", pos, gamma, sigma, args.p, steps=3)
        phase_serve(args.p, rng)

    if not on_tpu:
        sys.exit(f"chip_smoke: phases ran on {dev.platform!r}, not a TPU: "
                 f"no result")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
