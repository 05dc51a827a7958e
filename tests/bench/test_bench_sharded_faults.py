"""A whole run of the four-chip cell on four CPU devices, at a small size:
correct as it stands, not correct with the exchange between chips left
out.  JAX fixes its device count when it starts, so this runs in a child."""
import json
import os
import subprocess
import sys

from bench import harness

CHILD = r"""
import json, sys, time
import jax, jax.numpy as jnp
from bench import harness
from conftest import tiny_config
from repro.core import parallel_fmm

CELL = "vortex765k-L10.eval.4chip"


def no_exchange(x, width, rows_valid, cols_valid, axis_name, grid):
    w, trail = width, x.shape[2:]
    buf = jnp.zeros((x.shape[0] + 2 * w, x.shape[1] + 2 * w) + trail, x.dtype)
    return jax.lax.dynamic_update_slice(buf, x, (w, w) + (0,) * len(trail))


out = {}
for name in ("sound", "no_exchange"):
    if name == "no_exchange":
        jax.clear_caches()
        parallel_fmm._tile_halo = no_exchange
    r = harness.run(CELL, 987654321, 0.2, False, time.perf_counter(),
                    require_chip=False, config=tiny_config(CELL))
    out[name] = r
print("RESULTS " + json.dumps(out))
"""


def test_sharded_cell_is_correct_and_fails_without_the_exchange():
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
    assert out["sound"]["correct"] and out["sound"]["device"]["count"] == 4
    assert out["sound"]["failed"] == 0
    assert not out["no_exchange"]["correct"]
