"""``bench/run.py`` refuses to measure where it cannot: no TPU, or a
checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

from bench import harness

ARGS = ["--workload", "vortex765k-L10.eval", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def _run(root, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(env_extra)
    return subprocess.run([sys.executable, os.path.join("bench", "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    try:
        json.loads(last)
    except ValueError:
        return True
    return False


def test_exits_nonzero_without_a_result_on_the_cpu():
    proc = _run(harness.ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)
    assert "no TPU" in proc.stderr


def test_exits_nonzero_in_a_checkout_of_the_benchmark_alone(tmp_path):
    bm = harness.load_benchmark()
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for p in bm["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert _no_result(proc)
