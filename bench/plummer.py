"""The benchmark's clustered particles: a Plummer cluster projected onto the
plane, seeded.

The projected Plummer model (Plummer 1911, MNRAS 71, 460) has surface
density ``Sigma(R) ~ (1 + R^2/a^2)^-2``, so the mass fraction inside ``R``
is ``m = R^2 / (R^2 + a^2)``; a radius is drawn by inverting it,
``R = a sqrt(m / (1 - m))``, as Aarseth, Henon & Wielen (1974) do for the
sphere, with ``m`` uniform below the fraction inside the truncation
radius.  The positions come from the configuration's fixed ``field_seed``,
so every run seed has the same boxes and shapes, as the lattice's do; the
run seed perturbs the circulations and draws the particles whose answers
are checked.  Kept here, not in the program, so that a change to the
program cannot change the data it is measured on.
"""
from __future__ import annotations

import numpy as np

from bench import lattice


def plummer_positions(n: int, a: float, r_max: float, center, seed: int
                      ) -> np.ndarray:
    """``(n, 2)`` positions of a projected Plummer cluster of scale radius
    ``a`` about ``center``, truncated at ``r_max``."""
    rng = np.random.default_rng(int(seed))
    m = rng.uniform(0.0, r_max * r_max / (r_max * r_max + a * a), n)
    r = a * np.sqrt(m / (1.0 - m))
    theta = rng.uniform(0.0, 2.0 * np.pi, n)
    return np.stack([center[0] + r * np.cos(theta),
                     center[1] + r * np.sin(theta)], axis=1)


def particles(cfg: dict, seed: int):
    """``(positions (N, 2), circulations (N,), sigma)``: the configuration's
    cluster, each circulation ``(1 + jitter u_i) / N``, ``u_i`` uniform in
    [-1, 1) from the run seed."""
    pl = cfg["plummer"]
    n = int(cfg["particles"])
    pos = plummer_positions(n, pl["a"], pl["r_max"], pl["center"],
                            pl["field_seed"])
    rng, _ = lattice._streams(seed)
    gamma = (1.0 + cfg["gamma_jitter"] * rng.uniform(-1.0, 1.0, n)) / n
    return pos, gamma, float(cfg["sigma"])
