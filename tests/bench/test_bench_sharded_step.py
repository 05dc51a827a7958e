"""A whole run of the four-chip stepper cell on four CPU devices, at a small
size: correct as it stands, not correct when a step returns its state
unchanged.  JAX fixes its device count when it starts, so this runs in a
child.  And the reader of the stepper's exposed collectives, on synthetic
events."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness, peaks
from bench.trace_events import Device, Op, Trace

CHILD = r"""
import json, time
import jax
from bench import harness
from conftest import tiny_config
from repro.core import stepper

CELL = "vortex765k-L8.step.4chip"
orig = stepper.rk2_step


def unchanged(tree, dt, payload=None, **kw):
    _, _, ok, occ, health = orig(tree, dt, payload, **kw)
    return tree, payload, ok, occ, health


out = {}
for name in ("sound", "unchanged"):
    if name == "unchanged":
        jax.clear_caches()
        stepper.rk2_step = unchanged
    out[name] = harness.run(CELL, 2 ** 31 + 101, 0.2, False,
                            time.perf_counter(), require_chip=False,
                            config=tiny_config(CELL))
print("RESULTS " + json.dumps(out))
"""


def test_sharded_step_cell_is_correct_and_fails_on_an_unchanged_state():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([here, harness.ROOT,
                                           os.path.join(harness.ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULTS ")][-1]
    out = json.loads(line[len("RESULTS "):])
    sound = out["sound"]
    assert sound["correct"] and sound["failed"] == 0
    assert sound["device"]["count"] == 4
    assert set(sound["checks"]) == {"rel_l2", "mismatched", "lost",
                                    "misbinned", "charges_changed"}
    assert not out["unchanged"]["correct"]


def _inputs(tr, calls):
    return harness.LayerInputs(trace=tr, calls=calls, evals=2 * calls,
                               counts=np.ones((4, 4), np.int64), level=2,
                               p=4, peak=peaks.peak("TPU v5 lite"), chips=4)


@pytest.mark.parametrize("module, read", [
    ("jit_rk2_step(7)", True),                  # the stepper's program
    ("jit_parallel_fmm_evaluate(1)", False),    # another program
])
def test_rk2_collective_reader_takes_the_steppers_collectives(module, read):
    """Per step, the time a collective of ``rk2_step`` is in flight and
    no other op runs, averaged over the chips; nothing read where the
    collectives lie in another program."""
    dev = Device([Op("fusion.1", 0, 1_000_000, "fusion"),
                  Op("collective-permute-start.3", 500_000, 500_010,
                     "collective-permute-start"),
                  Op("collective-permute-done.3", 3_999_990, 4_000_000,
                     "collective-permute-done"),
                  Op("fusion.2", 3_500_000, 4_000_000, "fusion")],
                 modules=[Op(module, 0, 5_000_000)])
    tr = Trace([dev, dev], [], (0, 5_000_000))
    rd = harness.load_module(
        harness.reader_file("rk2_collective_ms.step"), "t")
    got = rd.read(_inputs(tr, calls=2))
    # in flight from 0.5 to 4.0 ms, and no other op runs from 1.0 to 3.5:
    # 2.5 ms on each chip, over 2 steps
    assert got == (pytest.approx(1.25) if read else None)
