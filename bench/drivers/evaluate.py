"""Closed loop of FMM evaluations of one tree, back to back.

Serial (``fmm_evaluate``) on one chip, or sharded (``parallel_fmm_velocity``
under the plan the library's autotuner picks) when the traffic says
``"sharded": true``.  Each call ends when its output is ready, as for a
caller that uses the result; the call then gathers the velocities of the
seed's sample of particles and whether the whole output is finite, which
the check compares once the window has closed.
"""
from __future__ import annotations

import numpy as np

from bench import lattice, reference, work


class Cell:
    evals_per_call = 1

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, span):
        import jax
        import jax.numpy as jnp
        from repro.core.quadtree import build_tree

        self._span = span
        self._limit = float(cfg["check"]["rel_l2_limit"])
        level, slots, p = cfg["level"], cfg["slots"], cfg["p"]
        pos, gamma, sigma = lattice.particles(cfg, seed)
        self._z = pos[:, 0] + 1j * pos[:, 1]
        self._gamma, self._sigma = gamma, sigma
        self._level, self._p = level, p
        self._counts = work.box_counts(pos, level)
        tree, index = build_tree(pos, gamma, level, sigma, slots=slots)
        n = 1 << level
        if tree.z.shape != (n, n, slots):
            raise RuntimeError(f"tree {tree.z.shape} is not the "
                               f"configuration's ({n}, {n}, {slots})")
        self._sample = lattice.check_sample(len(pos), cfg["check"]["targets"],
                                            seed)
        flat = (index.box_of_particle[self._sample] * slots
                + index.slot_of_particle[self._sample])
        idx = jnp.asarray(flat, jnp.int32)

        if traffic["sharded"]:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            from repro.core.cost_model import ModelParams
            from repro.core.parallel_fmm import parallel_fmm_velocity
            from repro.core.plan import autotune_plan

            mesh = Mesh(np.array(devices), ("data",))
            plan = autotune_plan(
                index.counts, ModelParams(level=level, cut=cfg["cut_level"],
                                          p=p, slots=slots),
                len(devices), method="model")
            print(f"plan: {plan.describe()}", flush=True)
            # the particles live on the mesh, as a sharded caller's would
            tree = jax.device_put(tree, NamedSharding(mesh, PartitionSpec()))
            self._run = lambda: parallel_fmm_velocity(
                tree, p, mesh, use_kernels=cfg["use_kernels"], plan=plan)
        else:
            from repro.core.fmm import fmm_evaluate

            tree = jax.device_put(tree, devices[0])
            self._run = lambda: fmm_evaluate(
                tree, p, eq="vortex", use_kernels=cfg["use_kernels"])
        self._take = jax.jit(lambda w: (w.reshape(-1)[idx],
                                        jnp.all(jnp.isfinite(w))))
        self._taken = []
        jax.block_until_ready(self._take(jax.block_until_ready(self._run())))

    def call(self) -> None:
        with self._span("dispatch"):
            w = self._run()
        with self._span("block"):
            w.block_until_ready()
        with self._span("check"):
            self._taken.append(self._take(w))

    def release(self) -> None:
        self._got = [(np.asarray(v), bool(ok)) for v, ok in self._taken]
        del self._run, self._take, self._taken

    def check(self):
        z = self._z
        ref = reference.velocity_f64(z[self._sample], z, self._gamma,
                                     self._sigma)
        errs = [reference.rel_l2(v, ref) for v, _ in self._got]
        failed = sum(1 for _, ok in self._got if not ok)
        worst = float(np.max(errs))          # NaN if any call read NaN
        print(f"check: calls={len(errs)} rel_l2_min={float(np.min(errs))!r} "
              f"rel_l2_max={worst!r} nonfinite_calls={failed}", flush=True)
        return {"rel_l2": (worst, self._limit)}, failed

    def work(self) -> dict:
        return {"counts": self._counts, "level": self._level, "p": self._p}


def control(cfg: dict, traffic: dict, seed: int,
            variant: str = reference.CONTROL_DTYPE) -> dict:
    """The check's numbers for the reference put in the program's place,
    computed one precision below the configuration's (bfloat16, in the
    ``reference.CONTROLS`` variant named)."""
    pos, gamma, sigma = lattice.particles(cfg, seed)
    z = pos[:, 0] + 1j * pos[:, 1]
    s = lattice.check_sample(len(pos), cfg["check"]["targets"], seed)
    ref = reference.velocity_f64(z[s], z, gamma, sigma)
    low = reference.velocity_device(
        z[s], z, gamma, sigma, dtype=reference.CONTROL_DTYPE,
        f32_differences=reference.CONTROLS[variant])
    return {"rel_l2": reference.rel_l2(low, ref)}
