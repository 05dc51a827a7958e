"""Closed loop of vortex-method RK2 steps (``VortexStepper.step``).

Set-up builds the stepper from the seed's particles and takes its first
step through the same ``step()`` the window calls.  Each window call holds
on to the state it starts from (a reference, not a copy: the stepper
donates nothing), so once the window has closed the check follows the
window's last step with a reference RK2 step from that same state, the
strengths being the bench's own.  Every step of the window is held to the
stepper's own health word: a step that is not clean, or that a rung of the
recovery ladder rescued, counts as failed.  The last step's start and end
are held to what a rebin guarantees: every particle kept, each in its own
leaf box, every charge carried unchanged.
"""
from __future__ import annotations

import time

import numpy as np

from bench import lattice, reference, work

HEALTH_FAULT_BITS = 0xFFFFFF      # flags, out-of-domain and dropped counts


def _host_tree(tree):
    return (np.asarray(tree.z), np.asarray(tree.q), np.asarray(tree.mask))


def misbinned(z, mask) -> int:
    """Live slots whose particle does not lie in the slot's leaf box."""
    n = z.shape[0]
    iy, ix = np.meshgrid(np.arange(n), np.arange(n), indexing="ij")
    bx = np.clip(np.floor(z.real * n).astype(np.int64), 0, n - 1)
    by = np.clip(np.floor(z.imag * n).astype(np.int64), 0, n - 1)
    return int((mask & ((bx != ix[:, :, None]) | (by != iy[:, :, None])))
               .sum())


def nearest(z, q, mask, points):
    """For each point, the live particle nearest to it among the 3x3 leaf
    boxes around the point's box: ``(positions, charges)``."""
    n, _, s = z.shape
    zp = np.pad(z, ((1, 1), (1, 1), (0, 0)))
    qp = np.pad(q, ((1, 1), (1, 1), (0, 0)))
    mp = np.pad(mask, ((1, 1), (1, 1), (0, 0)))
    bx = np.clip(np.floor(points.real * n).astype(np.int64), 0, n - 1)
    by = np.clip(np.floor(points.imag * n).astype(np.int64), 0, n - 1)
    rows = by[:, None, None] + np.arange(3)[None, :, None]
    cols = bx[:, None, None] + np.arange(3)[None, None, :]
    cz = zp[rows, cols].reshape(len(points), 9 * s)
    cq = qp[rows, cols].reshape(len(points), 9 * s)
    cm = mp[rows, cols].reshape(len(points), 9 * s)
    d = np.where(cm, np.abs(cz - points[:, None]), np.inf)
    k = np.argmin(d, axis=1)
    r = np.arange(len(points))
    return cz[r, k], cq[r, k]


class Cell:
    evals_per_call = 2

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices, span):
        from repro.core.stepper import VortexStepper

        t0 = time.perf_counter()
        self._span = span
        self._cfg = cfg
        pos, gamma, sigma = lattice.particles(cfg, seed)
        self._gamma, self._sigma, self._seed = gamma, sigma, seed
        self._counts = work.box_counts(pos, cfg["level"])
        st = VortexStepper(pos, gamma, sigma, p=cfg["p"], dt=cfg["dt"],
                           use_kernels=cfg["use_kernels"],
                           target_per_box=cfg["target_per_box"],
                           slots_headroom=cfg["slots_headroom"])
        if (st.params.level, st.params.slots) != (cfg["level"], cfg["slots"]):
            raise RuntimeError(
                f"the stepper chose level {st.params.level} and "
                f"{st.params.slots} slots, not the configuration's "
                f"{cfg['level']} and {cfg['slots']}")
        t1 = time.perf_counter()
        self._first_ok = self._clean(st.step())
        st.counts()          # warms the occupancy pull of the replan check
        print(f"cell: stepper_s={t1 - t0!r} "
              f"first_step_s={time.perf_counter() - t1!r}", flush=True)
        self._st = st
        self._before = st.tree
        self._records = []

    @staticmethod
    def _clean(rec) -> bool:
        return (rec.health & HEALTH_FAULT_BITS) == 0 and rec.recovered == ""

    def call(self) -> None:
        with self._span("step_host"):
            self._before = self._st.tree
            self._records.append(self._st.step())

    def release(self) -> None:
        st = self._st
        self._last_in = _host_tree(self._before)
        self._final = _host_tree(st.tree)
        del self._before
        self._level_after = (st.params.level, st.params.slots)
        del self._st

    def check(self):
        cfg = self._cfg
        q_all = (self._gamma * (1.0 / (2j * np.pi))).astype(np.complex64)
        # the last step's start: the program's positions, and for each
        # particle the bench's own float64 strength, found by its charge
        zb, qb, mb = self._last_in
        z_in = zb[mb].astype(np.complex128)
        q_in = qb[mb]
        order = np.argsort(q_all.imag)
        at = np.searchsorted(q_all.imag[order], q_in.imag).clip(
            0, len(order) - 1)
        gamma_in = self._gamma[order[at]]
        s = lattice.check_sample(len(z_in), cfg["check"]["targets"],
                                 self._seed)
        z1_ref = reference.rk2_step(z_in, gamma_in, self._sigma, cfg["dt"], s)
        disp = z1_ref - z_in[s]
        expect = z_in[s] + disp
        z, q, mask = self._final
        zm, qm = nearest(z, q, mask, expect)
        rel = float(np.linalg.norm(zm - expect) / np.linalg.norm(disp))
        mismatched = int((qm != q_in[s]).sum())
        lost = misbin = changed = 0
        for z_, q_, m_ in (self._last_in, self._final):
            lost += abs(len(self._gamma) - int(m_.sum()))
            misbin += misbinned(z_, m_)
            changed += int(not np.array_equal(np.sort(q_[m_]),
                                              np.sort(q_all)))
        failed = sum(1 for r in self._records if not self._clean(r))
        print(f"check: first_step_clean={self._first_ok} "
              f"window_steps={len(self._records)} unclean_steps={failed} "
              f"replanned={sum(r.replanned for r in self._records)} "
              f"releveled={sum(r.releveled for r in self._records)} "
              f"level_slots_after={self._level_after}", flush=True)
        limit = float(cfg["check"]["rel_l2_limit"])
        return {"rel_l2": (rel if self._first_ok else float("nan"), limit),
                "mismatched": (float(mismatched), 0.0),
                "lost": (float(lost), 0.0),
                "misbinned": (float(misbin), 0.0),
                "charges_changed": (float(changed), 0.0)}, failed

    def work(self) -> dict:
        return {"counts": self._counts, "level": self._cfg["level"],
                "p": self._cfg["p"]}


def control(cfg: dict, traffic: dict, seed: int,
            variant: str = reference.CONTROL_DTYPE) -> dict:
    """The check's numbers for the reference step put in the program's
    place, computed one precision below the configuration's (bfloat16, in
    the ``reference.CONTROLS`` variant named), from the bench's particles."""
    pos, gamma, sigma = lattice.particles(cfg, seed)
    z0 = pos[:, 0] + 1j * pos[:, 1]
    s = lattice.check_sample(len(pos), cfg["check"]["targets"], seed)
    ref = reference.rk2_step(z0, gamma, sigma, cfg["dt"], s)
    low = reference.rk2_step(
        z0, gamma, sigma, cfg["dt"], s, dtype=reference.CONTROL_DTYPE,
        f32_differences=reference.CONTROLS[variant])
    return {"rel_l2": float(np.linalg.norm(low - ref)
                            / np.linalg.norm(ref - z0[s]))}
