"""The benchmark's copies of the generator and the reference agree with the
program's originals, and its control fails where the reference passes."""
import copy

import numpy as np
import pytest

from bench import harness, lattice, reference

CFG = harness.load_json("bench/configs/vortex765k-L10.json")


def test_lattice_copy_matches_the_programs_generator():
    from repro.core.vortex import lamb_oseen_particles

    for side in (875, 31):
        got = lattice.lamb_oseen_particles(side)
        want = lamb_oseen_particles(side)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)
    cfg = copy.deepcopy(CFG)
    cfg["gamma_jitter"] = 0.0
    pos, gamma, sigma = lattice.particles(cfg, 7)
    want = lamb_oseen_particles(875)
    np.testing.assert_array_equal(pos, want[0])
    np.testing.assert_array_equal(gamma, want[1])
    assert sigma == want[2] == CFG["sigma_as_generated"]


def test_seed_changes_strengths_and_sample_never_positions():
    cfg = copy.deepcopy(CFG)
    cfg["lattice"]["side"] = 40
    a = lattice.particles(cfg, 1)
    b = lattice.particles(cfg, 2 ** 31 + 5)
    np.testing.assert_array_equal(a[0], b[0])
    assert not np.array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[1], lattice.particles(cfg, 1)[1])
    ratio = a[1] / lattice.lamb_oseen_particles(40)[1]
    assert np.all(np.abs(ratio - 1.0) <= cfg["gamma_jitter"])
    s1 = lattice.check_sample(1600, 64, 1)
    assert len(set(s1)) == 64
    assert not np.array_equal(s1, lattice.check_sample(1600, 64, 2))


def _small(side=30, seed=3):
    cfg = copy.deepcopy(CFG)
    cfg["lattice"]["side"] = side
    pos, gamma, sigma = lattice.particles(cfg, seed)
    return pos[:, 0] + 1j * pos[:, 1], gamma, sigma


def test_direct_sum_copy_matches_the_programs_oracle():
    from repro.core import equations as eqs

    z, gamma, sigma = _small()
    tgt = z[::7]
    want = eqs.direct_sum(eqs.VORTEX, tgt, z, gamma, sigma)
    got = reference.velocity_f64(tgt, z, gamma, sigma, threads=2)
    assert reference.rel_l2(got, want) < 1e-12


def test_device_sum_in_float32_agrees_and_its_bfloat16_control_fails():
    z, gamma, sigma = _small()
    tgt = z[::5]
    ref = reference.velocity_f64(tgt, z, gamma, sigma, threads=2)
    f32 = reference.velocity_device(tgt, z, gamma, sigma, block=32)
    bf16 = reference.velocity_device(tgt, z, gamma, sigma, dtype="bfloat16",
                                     block=32)
    limit = CFG["check"]["rel_l2_limit"]
    assert reference.rel_l2(f32, ref) < limit / 50
    assert reference.rel_l2(bf16, ref) > limit          # not correct


def test_the_pairs_control_keeps_float32_differences():
    """The ``bfloat16_pairs`` variant rounds only the pair arithmetic: it
    lies between the float32 sum and the all-bfloat16 control."""
    z, gamma, sigma = _small()
    tgt = z[::5]
    ref = reference.velocity_f64(tgt, z, gamma, sigma, threads=2)
    err = {name: reference.rel_l2(reference.velocity_device(
               tgt, z, gamma, sigma, dtype=reference.CONTROL_DTYPE, block=32,
               f32_differences=diff), ref)
           for name, diff in reference.CONTROLS.items()}
    f32 = reference.rel_l2(reference.velocity_device(tgt, z, gamma, sigma,
                                                     block=32), ref)
    assert f32 * 10 < err["bfloat16_pairs"] < err["bfloat16"]


def test_rk2_reference_and_its_control():
    z, gamma, sigma = _small(side=24)
    sample = np.arange(0, len(z), 11)
    dt = 0.005
    # the plain float64 step over every particle, written out
    w0 = reference.velocity_f64(z, z, gamma, sigma, threads=2)
    zm = z + 0.5 * dt * np.conj(w0)
    want = z[sample] + dt * np.conj(
        reference.velocity_f64(zm[sample], zm, gamma, sigma, threads=2))
    disp = want - z[sample]
    got = reference.rk2_step(z, gamma, sigma, dt, sample)
    assert np.linalg.norm(got - want) / np.linalg.norm(disp) < 1e-6
    ctrl = reference.rk2_step(z, gamma, sigma, dt, sample, dtype="bfloat16")
    limit = harness.load_json(
        "bench/configs/vortex765k-L8.json")["check"]["rel_l2_limit"]
    assert np.linalg.norm(ctrl - want) / np.linalg.norm(disp) > limit
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(ctrl, want, rtol=0, atol=1e-9)
