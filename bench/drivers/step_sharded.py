"""Closed loop of vortex-method RK2 steps on a mesh of the cell's chips.

``VortexStepper(mesh=Mesh(chips), plan_grid=...)`` with the traffic's plan
grid (``"auto"``: the autotuner picks slab or block tiles at build and at
every replan).  The stepper's state is replicated over the mesh, so every
chip rebins every particle; the evaluations are sharded under the plan.
Everything else -- the set-up step, the window's calls, the check against
a reference RK2 step and the rebin's guarantees, the control -- is the
one-chip step cell's (``step.py``).
"""
from __future__ import annotations

import time

import numpy as np

from bench import lattice, work
from bench.drivers import step

control = step.control


class Cell(step.Cell):

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, span):
        from jax.sharding import Mesh
        from repro.core.stepper import VortexStepper

        t0 = time.perf_counter()
        self._span = span
        self._cfg = cfg
        pos, gamma, sigma = lattice.particles(cfg, seed)
        self._gamma, self._sigma, self._seed = gamma, sigma, seed
        self._counts = work.box_counts(pos, cfg["level"])
        st = VortexStepper(pos, gamma, sigma, p=cfg["p"], dt=cfg["dt"],
                           mesh=Mesh(np.array(devices), ("data",)),
                           plan_grid=traffic["plan_grid"],
                           use_kernels=cfg["use_kernels"],
                           target_per_box=cfg["target_per_box"],
                           slots_headroom=cfg["slots_headroom"])
        if (st.params.level, st.params.slots) != (cfg["level"], cfg["slots"]):
            raise RuntimeError(
                f"the stepper chose level {st.params.level} and "
                f"{st.params.slots} slots, not the configuration's "
                f"{cfg['level']} and {cfg['slots']}")
        print(f"plan: {st.plan.describe()}", flush=True)
        t1 = time.perf_counter()
        self._first_ok = self._clean(st.step())
        st.counts()          # warms the occupancy pull of the replan check
        print(f"cell: stepper_s={t1 - t0!r} "
              f"first_step_s={time.perf_counter() - t1!r}", flush=True)
        self._st = st
        self._before = st.tree
        self._records = []
