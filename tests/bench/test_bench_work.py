"""The roofline yardstick against brute force, and its blindness to how the
program lays the work out."""
import numpy as np
import pytest

from bench import lattice, work


def _lattice(side=23, level=3):
    pos, _, _ = lattice.lamb_oseen_particles(side)
    return pos, level


def test_p2p_pairs_match_a_brute_force_count():
    pos, level = _lattice()
    n = 1 << level
    b = np.clip((pos * n).astype(int), 0, n - 1)
    near = (np.abs(b[:, None, 0] - b[None, :, 0]) <= 1) & \
        (np.abs(b[:, None, 1] - b[None, :, 1]) <= 1)
    np.fill_diagonal(near, False)
    counts = work.box_counts(pos, level)
    assert counts.sum() == len(pos)
    assert work.p2p_pairs(counts) == int(near.sum())


def _interaction_list(level, iy, ix, bounded):
    """Boxes well separated from (iy, ix) whose parents neighbour its
    parent, by brute force over the grid."""
    n = 1 << level
    out = 0
    for jy in range(-2 * n, 3 * n):
        for jx in range(-2 * n, 3 * n):
            if bounded and not (0 <= jy < n and 0 <= jx < n):
                continue
            near = max(abs(jy - iy), abs(jx - ix)) <= 1
            parents = max(abs(jy // 2 - iy // 2), abs(jx // 2 - ix // 2)) <= 1
            out += parents and not near
    return out


def test_m2l_count_is_27_translations_a_box_of_levels_2_to_L():
    level, p = 3, 5
    # every box of an unbounded grid has exactly 27; boxes at the domain's
    # edge have fewer, so the count is the dense driver's full list
    for lv in (2, 3):
        n = 1 << lv
        assert {_interaction_list(lv, iy, ix, False)
                for iy in range(n) for ix in range(n)} == {27}
        assert all(_interaction_list(lv, iy, ix, True) <= 27
                   for iy in range(n) for ix in range(n))
    flops, nbytes = work.m2l_cost(level, p)
    boxes = 4 ** 2 + 4 ** 3
    assert flops == boxes * 27 * p * p * work.CMAC
    assert nbytes == boxes * p * work.BYTES_PER_COEFF * 2


def test_work_reads_no_slots_or_padding():
    """Trees built with different slot counts (and so different lane
    padding) bin the same particles into the same boxes: the work the
    rooflines count is the same."""
    from repro.core.quadtree import build_tree

    pos, level = _lattice()
    gamma = np.ones(len(pos))
    costs = set()
    for slots in (None, 64, 128):
        _, index = build_tree(pos, gamma, level, 0.01, slots=slots)
        np.testing.assert_array_equal(index.counts,
                                      work.box_counts(pos, level))
        costs.add((work.p2p_cost(index.counts), work.m2l_cost(level, 17),
                   work.evaluation_flops(index.counts, level, 17)))
    assert len(costs) == 1


def test_least_time_is_the_larger_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.least_seconds(1000.0, 50.0, peak) == pytest.approx(10.0)
    assert work.least_seconds(100.0, 50.0, peak) == pytest.approx(5.0)
