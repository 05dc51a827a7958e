"""The benchmark's particles: the paper's Lamb-Oseen lattice, seeded.

``lamb_oseen_particles`` is a copy of ``repro.core.vortex``'s generator
(PetFMM §7.1), kept here so that a change to the program cannot change the
data it is measured on.  The seed perturbs the circulations and draws the
particles whose answers are checked; it never moves a particle, so the box
counts, the level and the slots -- every shape the cell compiles -- are the
same for every seed.
"""
from __future__ import annotations

import numpy as np


def lamb_oseen_omega(r, gamma0: float, nu: float, t: float):
    """Vorticity of the Lamb-Oseen vortex (paper Eq 16)."""
    return gamma0 / (4.0 * np.pi * nu * t) * np.exp(-r * r / (4.0 * nu * t))


def lamb_oseen_particles(m_side: int, gamma0: float = 1.0, nu: float = 5e-4,
                         t: float = 4.0, spacing_ratio: float = 0.8,
                         sigma: float = 0.02, extent: float = 0.8,
                         x0: float = 0.5, y0: float = 0.5):
    """``(positions (N, 2), circulations (N,), sigma)`` on an
    ``m_side x m_side`` lattice covering ``extent`` of the unit square;
    circulation = vorticity x cell area, spacing h = ``spacing_ratio`` x
    sigma, both scaled down together when the lattice would not fit."""
    h = sigma * spacing_ratio
    span = (m_side - 1) * h
    scale = 1.0
    if span > extent:
        scale = extent / span
        h *= scale
        span = extent
    xs = x0 - span / 2 + h * np.arange(m_side)
    ys = y0 - span / 2 + h * np.arange(m_side)
    X, Y = np.meshgrid(xs, ys, indexing="xy")
    r = np.sqrt((X - x0) ** 2 + (Y - y0) ** 2)
    w = lamb_oseen_omega(r, gamma0, nu, t)
    gamma = (w * h * h).ravel()
    pos = np.stack([X.ravel(), Y.ravel()], axis=1)
    return pos, gamma, sigma * scale


def _streams(seed: int):
    perturb, sample = np.random.SeedSequence(int(seed)).spawn(2)
    return np.random.default_rng(perturb), np.random.default_rng(sample)


def particles(cfg: dict, seed: int):
    """The configuration's lattice with seeded circulations:
    ``gamma_i (1 + jitter u_i)``, ``u_i`` uniform in [-1, 1)."""
    lat = cfg["lattice"]
    pos, gamma, sigma = lamb_oseen_particles(
        lat["side"], gamma0=lat["gamma0"], nu=lat["nu"], t=lat["t"],
        spacing_ratio=lat["spacing_ratio"], sigma=lat["sigma"],
        extent=lat["extent"], x0=lat["center"][0], y0=lat["center"][1])
    rng, _ = _streams(seed)
    gamma = gamma * (1.0 + cfg["gamma_jitter"] * rng.uniform(-1.0, 1.0,
                                                             gamma.shape))
    return pos, gamma, sigma


def check_sample(n: int, k: int, seed: int) -> np.ndarray:
    """The ``k`` particles, of ``n``, whose answers the check compares."""
    _, rng = _streams(seed)
    return np.sort(rng.choice(n, size=min(k, n), replace=False))
