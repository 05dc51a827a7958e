"""The benchmark's tests import ``bench`` from the checkout's root and the
program from ``src``, however pytest was started."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


def tiny_config(cell: str, side: int = 36, p: int = 12) -> dict:
    """The cell's configuration cut to a lattice of ``side**2`` particles
    for a whole run on the CPU, at the depth and slots that lattice needs
    (the stepper's own choice for a stepping cell)."""
    import copy
    import math

    from bench import harness, lattice, work
    from repro.core.quadtree import choose_level

    bm = harness.load_benchmark()
    name = harness.find(bm["workloads"], cell, "workload")["config"]
    cfg = copy.deepcopy(harness.load_json(
        harness.find(bm["configs"], name, "config")["file"]))
    cfg["lattice"]["side"] = side
    cfg["particles"] = side * side
    cfg["p"] = p
    cfg["check"]["targets"] = 64
    pos, _, _ = lattice.particles(cfg, 0)
    if "dt" in cfg:
        cfg["level"] = max(choose_level(len(pos), cfg["target_per_box"]), 2)
        cfg["slots"] = max(int(math.ceil(
            work.box_counts(pos, cfg["level"]).max()
            * cfg["slots_headroom"])), 2)
    else:
        cfg["level"], cfg["cut_level"] = 4, 2
        cfg["slots"] = int(work.box_counts(pos, 4).max())
    return cfg
