"""Ahead-of-time TPU v5e compiles of the FMM's main path, without a chip.

The only test file that describes the chip.  The TPU compiler is installed
with JAX and compiles for a described, unattached topology, so these tests
catch what the Pallas interpreter never checks — tiling rules on the halo
windows, VMEM bounds, HBM fit — at the paper's widths (level 10, p = 17,
lane padding on) and at the slot count the stepper picks for the 875^2
lattice.  Nothing runs: they say nothing about results or times.

The topology is described inside a module-scoped fixture (never while a
module is imported): only one process at a time may load the TPU library,
and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import equations as eqs
from repro.core.fmm import fmm_evaluate
from repro.core.quadtree import Tree
from repro.kernels import m2l as km
from repro.kernels import ops
from repro.kernels import p2p as kp

P = 17                # the paper's expansion order
EVAL_LEVEL = 10       # the paper's tree, 4 slots per leaf
STEP_LEVEL, STEP_SLOTS = 8, 50   # VortexStepper's choice for 765,625 particles


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("cls,rows,cols", [
    ("tile", 1 << (EVAL_LEVEL - 1), 1 << (EVAL_LEVEL - 1)),  # serial grid
    ("rim_row", 1, 1 << (EVAL_LEVEL - 2)),     # 2x2 plan tile's row strip
])
def test_m2l_kernel_compiles_for_v5e(one_chip, cls, rows, cols):
    """Parent-plane launch (rows, cols) at p = 17, 4p padded to 128 lanes;
    the rim strip carries two ghost rows and columns per side."""
    block = ops.autotune_block(rows, cols)
    assert ops._shape_class(rows, cols) == cls
    halo, col_halo = (2, 0) if cls == "tile" else (2, 2)
    me = _struct((2 * rows + 2 * halo, 2 * cols + 2 * col_halo, P),
                 jnp.complex64, one_chip)
    text = _compiled_text(
        lambda x: km.m2l_pallas_slab(x, EVAL_LEVEL, P, halo=halo,
                                     col_halo=col_halo, block=block,
                                     interpret=False, lane_pad=True), me)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cls,rows,cols,slots", [
    ("tile", 1 << EVAL_LEVEL, 1 << EVAL_LEVEL, 4),       # evaluate, level 10
    ("rim_col", 1 << (STEP_LEVEL - 2), 1, STEP_SLOTS),   # stepper rim strip
])
def test_p2p_kernel_compiles_for_v5e(one_chip, cls, rows, cols, slots):
    """Leaf launch (rows, cols) with +-1 ghosts, slots padded to 128."""
    block = ops.autotune_block(rows, cols)
    assert ops._shape_class(rows, cols) == cls
    shape = (rows + 2, cols + 2, slots)
    args = (_struct(shape, jnp.complex64, one_chip),
            _struct(shape, jnp.complex64, one_chip),
            _struct(shape, jnp.bool_, one_chip))
    text = _compiled_text(
        lambda z, q, m: kp.p2p_pallas_slab(z, q, m, sigma=1e-3, block=block,
                                           interpret=False, lane_pad=True),
        *args)
    assert "tpu_custom_call" in text


def test_fmm_velocity_kernel_program_compiles_for_v5e(one_chip, monkeypatch):
    """The whole serial kernel-route program at a small level.  The kernel
    wrappers ask the (CPU) default backend whether to interpret; steer
    them to the compiled kernels here."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    level, slots = 5, 8
    n = 1 << level
    tree = Tree(z=_struct((n, n, slots), jnp.complex64, one_chip),
                q=_struct((n, n, slots), jnp.complex64, one_chip),
                mask=_struct((n, n, slots), jnp.bool_, one_chip),
                level=level, sigma=1e-3)
    text = fmm_evaluate.lower(tree, P, eq=eqs.VORTEX,
                              use_kernels=True).compile().as_text()
    assert text.count("tpu_custom_call") >= 2       # M2L levels + P2P
