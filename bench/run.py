r"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> \
                         --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell
asks for.  The last line of standard output is the result, one JSON
object; the numbers the check compared, each beside its limit, are the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.

JAX's persistent compilation cache is where ``JAX_COMPILATION_CACHE_DIR``
points, or else the fixed ``<checkout>/.jax_cache``, so only a cell's first
run in a checkout compiles.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the checkout's root (for ``bench``) and ``src`` in place of this
    # script's own directory
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    # the TPU runtime's logs go under this run's temporary directory, not
    # a fixed path shared with other runs
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    # cache every program, however quick to compile, so that a warm run's
    # set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    import repro  # noqa: F401  (the system under test must be here)
    from bench import harness

    try:
        harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                    T_START)
    except harness.SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
