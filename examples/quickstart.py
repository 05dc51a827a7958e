"""Quickstart: evaluate the velocity of N vortex particles with the FMM.

Builds a Lamb-Oseen vortex (the paper's §7 test case), runs the full FMM
(upward sweep, M2L, L2L, evaluation) and compares against the O(N^2)
direct Biot-Savart sum and the analytical solution.

Run:  PYTHONPATH=src python examples/quickstart.py [--n-side 150] [--p 17]
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.configs.backend import use_compile_cache
from repro.core.fmm import fmm_velocity
from repro.core.quadtree import build_tree, choose_level, gather_particle_values
from repro.core.vortex import direct_sum, lamb_oseen_particles, lamb_oseen_velocity


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-side", type=int, default=120)
    ap.add_argument("--p", type=int, default=17)
    ap.add_argument("--use-kernels", action="store_true",
                    help="route M2L/P2P through the Pallas kernels "
                         "(interpreted off-TPU)")
    args = ap.parse_args()
    use_compile_cache()

    pos, gamma, sigma = lamb_oseen_particles(args.n_side)
    n = len(pos)
    level = choose_level(n, target_per_box=8)
    print(f"N = {n} particles, tree level {level}, p = {args.p}, sigma = {sigma:.4f}")

    tree, index = build_tree(pos, gamma, level, sigma)
    t0 = time.perf_counter()
    w = np.asarray(fmm_velocity(tree, args.p, use_kernels=args.use_kernels))
    t_fmm = time.perf_counter() - t0
    w_at = gather_particle_values(w, index)

    t0 = time.perf_counter()
    exact = direct_sum(pos[:, 0] + 1j * pos[:, 1], gamma, sigma)
    t_dir = time.perf_counter() - t0

    err = np.linalg.norm(w_at - exact) / np.linalg.norm(exact)
    print(f"FMM time    : {t_fmm:.3f} s  (includes jit compile on first call)")
    print(f"direct time : {t_dir:.3f} s")
    print(f"relative L2 error vs direct sum: {err:.3e}")

    # against the analytical Lamb-Oseen field (nu*t from the initializer)
    u_a, v_a = lamb_oseen_velocity(pos[:, 0], pos[:, 1], 1.0, 5e-4, 4.0)
    u_f, v_f = np.real(w_at), -np.imag(w_at)
    mask = np.abs(u_a) + np.abs(v_a) > 1e-3
    err_a = (np.linalg.norm((u_f - u_a)[mask]) + np.linalg.norm((v_f - v_a)[mask])) / \
            (np.linalg.norm(u_a[mask]) + np.linalg.norm(v_a[mask]))
    print(f"relative error vs analytical Lamb-Oseen: {err_a:.3e} "
          f"(discretization-limited)")
    assert err < 1e-3
    print("OK")


if __name__ == "__main__":
    main()
