"""FMM serving launcher: price, admit, and serve a synthetic workload.

The CLI face of ``serve/fmm_service.py`` (DESIGN.md §15): builds an
:class:`~repro.serve.fmm_service.FmmServiceEngine` on N (forced host)
devices, submits a mixed one-shot + trajectory workload, and prints the
per-job prices, admission decisions, latency percentiles, cache
hit/miss counters, and the steady-state jit-entry count.

Run:  PYTHONPATH=src python -m repro.launch.fmm_serve [--devices 2]
          [--jobs 8] [--n 300] [--steps 2] [--max-job-flops 5e9]
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description="FMM-as-a-service smoke/driver")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--jobs", type=int, default=8,
                    help="one-shot jobs per equation wave")
    ap.add_argument("--n", type=int, default=300,
                    help="sources per one-shot job")
    ap.add_argument("--steps", type=int, default=2,
                    help="RK2 steps of the trajectory session (0 disables)")
    ap.add_argument("--p", type=int, default=8)
    ap.add_argument("--sigma", type=float, default=0.02)
    ap.add_argument("--max-job-flops", type=float, default=5e9)
    ap.add_argument("--max-queue-flops", type=float, default=2e10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.devices > 1 and "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}")

    import numpy as np
    import jax
    from jax.sharding import Mesh

    from ..configs.backend import use_compile_cache
    from ..serve import fmm_service as svc

    use_compile_cache()
    ndev = args.devices
    if len(jax.devices()) < ndev:
        sys.exit(f"--devices {ndev} asks for more devices than the "
                 f"{len(jax.devices())} {jax.default_backend()} device(s) "
                 f"present")
    mesh = Mesh(np.array(jax.devices()[:ndev]), ("data",)) if ndev > 1 \
        else None
    engine = svc.FmmServiceEngine(
        mesh=mesh,
        budget=svc.ServiceBudget(max_job_flops=args.max_job_flops,
                                 max_queue_flops=args.max_queue_flops))
    rng = np.random.default_rng(args.seed)
    print(f"== fmm_serve: {ndev} device(s), budget "
          f"max_job={args.max_job_flops:.2g} "
          f"max_queue={args.max_queue_flops:.2g} flops")

    jids = []
    for i in range(args.jobs):
        n = args.n + 4 * (i % 3)
        pos = rng.uniform(0.1, 0.9, size=(n, 2))
        q = rng.normal(size=n)
        job = svc.FmmJob(positions=pos, strength=q,
                         equation="vortex" if i % 2 == 0 else "laplace",
                         p=args.p, sigma=args.sigma, tenant=f"t{i % 3}")
        try:
            jids.append(engine.submit(job))
        except svc.JobRejected as e:
            print(f"   job {i}: REJECTED at "
                  f"{e.price.total_flops:.3g} flops")
    if args.steps:
        pos = rng.uniform(0.3, 0.7, size=(args.n, 2))
        sid = engine.submit(svc.FmmJob(
            positions=pos, strength=0.1 * rng.normal(size=args.n),
            steps=args.steps, p=args.p, dt=1e-3, sigma=args.sigma,
            tenant="session"))
        for i, _pos, rec in engine.session(sid).stream(args.steps):
            print(f"   session step {i}: {rec.seconds * 1e3:.1f} ms")
    engine.drain()

    for jid in jids:
        r = engine.result(jid)
        print(f"   job {jid}: lane={r.lane} cap={r.batch_capacity} "
              f"price={r.price.total_flops:.3g} flops "
              f"(level={r.price.level}, p={r.price.p}, "
              f"slots={r.price.slots}) latency={r.latency_s * 1e3:.1f} ms")
    stats = engine.stats()
    print(f"   admitted={stats['admitted']} deferred={stats['deferred']} "
          f"promoted={stats['promoted']} rejected={stats['rejected']} "
          f"batches={stats['batches']}")
    print(f"   cache={stats['cache']} "
          f"batch_utilization={stats['batch_utilization']:.2f} "
          f"jit_entries={stats['jit_entries']}")
    for lane, l in stats["latency"].items():
        print(f"   latency[{lane}]: p50={l['p50_ms']:.1f} ms "
              f"p99={l['p99_ms']:.1f} ms (n={l['n']})")
    print("== fmm_serve: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
