"""Closed loop of FMM evaluations of one clustered tree, back to back.

The configuration's Plummer cluster (``bench/plummer.py``) in a tree whose
dense capacity the library picks from the occupancy histogram
(``quadtree.choose_slots``); the configuration states the pick and set-up
asserts it.  The crowded boxes' overflow lies in spill chunks, so
``fmm_evaluate`` returns the dense grid's output and the chunks'.  Each
call ends when both are ready; it then gathers the velocities of the
seed's sample of particles, at least ``check.spilled_targets`` of them in
spill chunks, and whether every output is finite, which the check compares
once the window has closed.
"""
from __future__ import annotations

import numpy as np

from bench import lattice, plummer, reference, work


def _sample(cfg: dict, seed: int, n: int) -> np.ndarray:
    return lattice.check_sample(n, cfg["check"]["targets"], seed)


class Cell:
    evals_per_call = 1

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, span):
        import jax
        import jax.numpy as jnp
        from repro.core.fmm import fmm_evaluate
        from repro.core.quadtree import build_tree, choose_slots

        self._span = span
        self._limit = float(cfg["check"]["rel_l2_limit"])
        level, p = cfg["level"], cfg["p"]
        pos, gamma, sigma = plummer.particles(cfg, seed)
        self._z = pos[:, 0] + 1j * pos[:, 1]
        self._gamma, self._sigma = gamma, sigma
        self._level, self._p = level, p
        self._counts = work.box_counts(pos, level)
        slots = choose_slots(self._counts)
        if slots != cfg["slots"]:
            raise RuntimeError(f"the library picks {slots} slots, not the "
                               f"configuration's {cfg['slots']}")
        tree, index = build_tree(pos, gamma, level, sigma, slots=slots)
        print(f"work: spilled_particles={tree.spill_particles} "
              f"spill_chunks={tree.spill_chunks} "
              f"chunk_pairs={tree.spill_pairs} "
              f"spill_pair_evals={tree.spill_pair_evals}", flush=True)
        self._sample = _sample(cfg, seed, len(pos))
        chunk = index.chunk_of_particle[self._sample]
        spilled = chunk >= 0
        if spilled.sum() < cfg["check"]["spilled_targets"]:
            raise RuntimeError(f"the sample holds {int(spilled.sum())} "
                               f"spilled particles, fewer than "
                               f"{cfg['check']['spilled_targets']}")
        slot = index.slot_of_particle[self._sample]
        at_spill = jnp.asarray(spilled)
        i_dense = jnp.asarray(index.box_of_particle[self._sample] * slots
                              + slot, jnp.int32)
        i_spill = jnp.asarray(np.where(spilled, chunk, 0) * slots + slot,
                              jnp.int32)

        tree = jax.device_put(tree, devices[0])
        self._run = lambda: fmm_evaluate(tree, p, eq="vortex",
                                         use_kernels=cfg["use_kernels"])
        self._take = jax.jit(lambda w: (
            jnp.where(at_spill, w[1].reshape(-1)[i_spill],
                      w[0].reshape(-1)[i_dense]),
            jnp.all(jnp.isfinite(w[0])) & jnp.all(jnp.isfinite(w[1]))))
        self._taken = []
        jax.block_until_ready(self._take(jax.block_until_ready(self._run())))

    def call(self) -> None:
        import jax
        with self._span("dispatch"):
            w = self._run()
        with self._span("block"):
            jax.block_until_ready(w)
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
    pos, gamma, sigma = plummer.particles(cfg, seed)
    z = pos[:, 0] + 1j * pos[:, 1]
    s = _sample(cfg, seed, len(pos))
    ref = reference.velocity_f64(z[s], z, gamma, sigma)
    low = reference.velocity_device(
        z[s], z, gamma, sigma, dtype=reference.CONTROL_DTYPE,
        f32_differences=reference.CONTROLS[variant])
    return {"rel_l2": reference.rel_l2(low, ref)}
