"""The clustered cell (``plummer765k-L10.eval``) on the CPU at a small size:
its particles, a whole run through the harness, a run with the spill
chunks' near field left out, and the reader of ``spill_ms``."""
import copy
import time

import jax
import numpy as np
import pytest

from bench import harness, plummer, work
from bench.trace_events import Device, Op, Trace

CELL = "plummer765k-L10.eval"


def _config() -> dict:
    bm = harness.load_benchmark()
    return harness.load_json(harness.find(bm["configs"], "plummer765k-L10",
                                          "config")["file"])


def tiny_config(n=4000, level=5) -> dict:
    """The cell's configuration cut to ``n`` particles of a wider cluster
    at level 5, p 12, with the library's capacity for them; sigma keeps
    the mollifier at float32 one beyond a leaf box, as the cell's does."""
    from repro.core.quadtree import choose_slots

    cfg = copy.deepcopy(_config())
    cfg.update(particles=n, level=level, cut_level=2, p=12,
               sigma=(0.25 / 2 ** level))
    cfg["plummer"]["a"] = 0.1
    cfg["check"].update(targets=64, spilled_targets=16)
    pos, _, _ = plummer.particles(cfg, 0)
    cfg["slots"] = choose_slots(work.box_counts(pos, level))
    return cfg


def _run(trace=False):
    jax.clear_caches()              # a patch must reach a fresh trace
    return harness.run(CELL, 2 ** 31 + 29, 0.2, trace, time.perf_counter(),
                       require_chip=False, config=tiny_config())


def test_the_cluster_is_seeded_by_its_field_seed_alone():
    cfg = tiny_config()
    a, ga, sa = plummer.particles(cfg, 1)
    b, gb, _ = plummer.particles(cfg, 2)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(ga, gb)
    assert abs(ga.sum() - 1.0) < 0.01 and sa == cfg["sigma"]
    r = np.hypot(a[:, 0] - 0.5, a[:, 1] - 0.5)
    assert r.max() <= cfg["plummer"]["r_max"]
    # half the mass inside the scale radius, as R^2 / (R^2 + a^2) says
    # (less the truncation's few per cent)
    assert 0.45 < (r < cfg["plummer"]["a"]).mean() < 0.55


def test_the_configuration_states_the_librarys_pick():
    """At the cell's own size: the library's capacity is the stated slots
    (the driver asserts it on every run), and the cluster spills."""
    from repro.core.quadtree import choose_slots

    cfg = _config()
    pos, _, _ = plummer.particles(cfg, 0)
    counts = work.box_counts(pos, cfg["level"])
    assert choose_slots(counts) == cfg["slots"] == 8
    assert counts.max() == 132 and (counts == 0).mean() > 0.88


def test_sound_clustered_evaluations_are_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"setup_s", "eval_s"}
    assert r["checks"]["rel_l2"]["value"] < 1e-4


def test_spilled_particles_left_out_of_the_near_field_are_not_correct(
        monkeypatch):
    from repro.core import fmm

    def dropped(tree, chunk_fn):
        sp = tree.spill
        n, s = tree.nside, tree.slots
        return (jax.numpy.zeros((n, n, s), tree.z.dtype),
                jax.numpy.zeros((sp.chunks, s), tree.z.dtype))

    monkeypatch.setattr(fmm, "spill_near_field", dropped)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["rel_l2"]["value"] > r["checks"]["rel_l2"]["limit"]


def test_spill_ms_reads_the_ops_under_the_spill_scope():
    """The reader against the scopes of a spilled program this process
    holds: one op under ``fmm.spill`` and one under another scope."""
    from bench import scopes
    from repro.core.fmm import fmm_evaluate
    from repro.core.quadtree import build_tree

    reader = harness.load_module(harness.reader_file("spill_ms.eval"), "r")
    cfg = tiny_config(n=1500, level=4)
    pos, gamma, sigma = plummer.particles(cfg, 0)
    tree, _ = build_tree(pos, gamma, 4, sigma, slots=cfg["slots"])
    assert tree.spill is not None
    jax.block_until_ready(fmm_evaluate(tree, 6, eq="vortex"))
    found = scopes.live_scopes()["jit_fmm_evaluate"]
    spill = next(k for k, v in found.items() if v == "fmm.spill")
    other = next(k for k, v in found.items() if v == "fmm.m2l")
    dev = Device([Op(spill, 0, 3000), Op(other, 3000, 10000)],
                 [Op("jit_fmm_evaluate(1)", 0, 10000)])
    inputs = harness.LayerInputs(
        trace=Trace([dev], [], (0, 10000)), calls=2, evals=2,
        counts=work.box_counts(pos, 4), level=4, p=6, peak={}, chips=1)
    assert reader.read(inputs) == pytest.approx(3000 / 1e6 / 2)
    dev.ops = dev.ops[1:]
    assert reader.read(inputs) is None
