"""Whole runs of the serial cells on the CPU, at a small size, with the
timed path broken underneath: the check has to come out not correct."""
import time

import jax
import pytest

from bench import harness
from conftest import tiny_config

EVAL = "vortex765k-L10.eval"
STEP = "vortex765k-L8.step"


def _run(cell, monkeypatch=None, patch=None):
    jax.clear_caches()              # a patch must reach a fresh trace
    if patch is not None:
        monkeypatch.setattr(*patch)
    return harness.run(cell, 2 ** 31 + 17, 0.2, False, time.perf_counter(),
                       require_chip=False, config=tiny_config(cell))


@pytest.fixture(scope="module")
def fmm():
    from repro.core import fmm
    return fmm


@pytest.fixture(scope="module")
def stepper():
    from repro.core import stepper
    return stepper


def test_sound_evaluations_are_correct():
    r = _run(EVAL)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "eval_s"}


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_broken_evaluations_are_not_correct(fault, fmm, monkeypatch):
    orig = fmm.fmm_evaluate

    def broken(*a, **k):
        w = orig(*a, **k)
        if fault == "answer_altered":
            return w * 1.01
        return w.at[w.shape[0] // 2:].set(0)

    r = _run(EVAL, monkeypatch, (fmm, "fmm_evaluate", broken))
    assert not r["correct"]


def test_sound_steps_are_correct():
    r = _run(STEP)
    assert r["correct"] and r["failed"] == 0
    assert set(r["checks"]) == {"rel_l2", "mismatched", "lost", "misbinned",
                                "charges_changed"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        stepper, monkeypatch):
    orig = stepper.rk2_step

    def unchanged(tree, dt, payload=None, **kw):
        _, _, ok, occ, health = orig(tree, dt, payload, **kw)
        return tree, payload, ok, occ, health

    r = _run(STEP, monkeypatch, (stepper, "rk2_step", unchanged))
    assert not r["correct"]


def test_a_fault_after_the_first_step_is_not_correct(stepper, monkeypatch):
    """Set-up's step is sound; every later step moves each particle 1% too
    far, so only the window's steps are wrong."""
    orig = stepper.rk2_step
    calls = []

    def overshoot(tree, dt, payload=None, **kw):
        calls.append(dt)
        return orig(tree, dt * (1.01 if len(calls) > 1 else 1.0), payload,
                    **kw)

    r = _run(STEP, monkeypatch, (stepper, "rk2_step", overshoot))
    assert len(calls) > 1
    assert not r["correct"]
    assert r["checks"]["rel_l2"]["value"] > r["checks"]["rel_l2"]["limit"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_broken_step_velocities_are_not_correct(fault, stepper, monkeypatch):
    orig = stepper._velocity

    def broken(*a, **k):
        out = orig(*a, **k)
        w, h = out if isinstance(out, tuple) else (out, None)
        if fault == "answer_altered":
            w = w * 1.01
        else:
            w = w.at[w.shape[0] // 2:].set(0)
        return w if h is None else (w, h)

    r = _run(STEP, monkeypatch, (stepper, "_velocity", broken))
    assert not r["correct"]


def test_a_traced_run_reports_the_cells_per_layer_metrics(monkeypatch):
    """The CPU has no TPU plane to trace and no published peaks, so the
    reduction is handed a small synthetic trace and the v5e's peaks; the
    rest of the traced run is real."""
    from bench import peaks, trace_events as te

    v5e = peaks.peak("TPU v5 lite")
    monkeypatch.setattr(peaks, "peak", lambda kind: v5e)

    def fake_load(log_dir):
        ops = [te.Op("p2p_pallas_slab.1", 0, 800, "custom-call"),
               te.Op("m2l_pallas_slab.2", 800, 900, "custom-call")]
        return te.Trace([te.Device(ops)], [te.Op("block", 0, 1000)],
                        (0, 1000))

    monkeypatch.setattr(te, "load", fake_load)
    r = harness.run(EVAL, 5, 0.2, True, time.perf_counter(),
                    require_chip=False, config=tiny_config(EVAL))
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device",
                       "breakdown", "checks"]
    assert set(r["metrics"]) == {"idle_share.eval", "mfu.eval",
                                 "p2p_roofline.eval", "m2l_roofline.eval"}
    assert r["metrics"]["idle_share.eval"]["value"] == pytest.approx(10.0)
    assert r["device"]["busy_s"] == pytest.approx(900e-9)
    assert r["breakdown"]["idle_gaps"] == [["block", pytest.approx(1e-7)]]
