"""chip_smoke.py off the chip: it must fail, and print no result line.

The driver-facing contract: with no TPU the script exits non-zero and never
prints ``"ok": true``.  At full size it refuses at once; shrunk, it runs
every phase here as a rehearsal (interpreted kernels) and still fails.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=ROOT)


def test_full_size_refuses_without_a_tpu(tmp_path):
    proc = _run([], tmp_path, timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_shrunk_rehearsal_runs_every_phase_then_fails(tmp_path):
    proc = _run(["--n-side", "24", "--level", "3", "--p", "6"], tmp_path,
                timeout=600)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert "not a TPU" in proc.stderr, proc.stderr[-2000:]
    for line in ("evaluate: route=kernels", "evaluate: route=jnp",
                 "stepper: step=3", "serve: wall_s", "serve_session: step=2"):
        assert line in proc.stdout, (line, proc.stdout[-2000:])
