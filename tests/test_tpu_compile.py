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
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import obs
from repro.core import equations as eqs
from repro.core.fmm import fmm_evaluate
from repro.core.quadtree import SPILL_RUN, Tree, build_tree
from repro.core.stepper import rk2_step
from repro.kernels import m2l as km
from repro.kernels import ops
from repro.kernels import p2p as kp

P = 17                # the paper's expansion order
EVAL_LEVEL = 10       # the paper's tree, 4 slots per leaf
# the Plummer cell's spill layout at level 10: dense capacity 8, 703,001
# runs of its near field
SPILL_SLOTS, SPILL_RUNS = 8, 703001
STEP_LEVEL, STEP_SLOTS = 8, 50   # VortexStepper's choice for 765,625 particles
TILE = 1 << (EVAL_LEVEL - 1)     # leaf tile of the level-10 grid, 2x2 plan
# collective-permutes of the four-chip step below: all the evaluations'
# halo exchanges; the rebin runs on replicated arrays and adds none
STEP_4CHIP_PERMUTES = 56


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


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """A mesh of the described topology's four chips."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices), ("data",))


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


@pytest.mark.parametrize("rows,cols,slots", [
    (1 << EVAL_LEVEL, 1 << EVAL_LEVEL, 4),               # evaluate, level 10
    (1 << STEP_LEVEL, 1 << STEP_LEVEL, STEP_SLOTS),      # stepper, level 8
    (TILE - 2, TILE - 2, 4),                             # 2x2 plan interior
    (1, TILE, 4),                                        # its row rim strip
    (TILE, 1, 4),                                        # its column rim strip
])
def test_p2p_kernel_compiles_for_v5e(one_chip, rows, cols, slots):
    """Leaf launch (rows, cols) with +-1 ghosts at the default block, the
    box columns padded to 128 lanes.  Every operand and result of the
    kernel keeps the slots on its leading axis, rounded up to a chunk and
    never padded to 128, and the longer box axis on the lanes."""
    shape = (rows + 2, cols + 2, slots)
    args = (_struct(shape, jnp.complex64, one_chip),
            _struct(shape, jnp.complex64, one_chip),
            _struct(shape, jnp.bool_, one_chip))
    text = _compiled_text(
        lambda z, q, m: kp.p2p_pallas_slab(z, q, m, sigma=1e-3,
                                           block=kp.P2P_BLOCK,
                                           interpret=False, lane_pad=True),
        *args)
    calls = [ln for ln in text.splitlines()
             if "p2p_kernel" in ln and " custom-call(" in ln]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    dims = [tuple(map(int, d.split(",")))
            for d in re.findall(r"f32\[([\d,]+)\]", calls[0])]
    assert len(dims) == 2 + 5 + 2          # targets, sources, outputs
    for d in dims:                         # (.., slots, box rows, box cols)
        assert slots <= d[-3] < 2 * slots
        assert d[-1] == -(-max(rows, cols) // 128) * 128


def test_p2p_chunk_kernel_compiles_for_v5e(one_chip):
    """The spill layout's chunk-pair kernel at the Plummer cell's shapes:
    one custom call that takes the gathered rows as they are (no XLA
    transpose or lane pad before it) and returns the runs on the lanes,
    the slots on the sublanes, never padded to 128."""
    width = 5 * SPILL_RUN * SPILL_SLOTS
    tgt = _struct((SPILL_RUNS, 2 * SPILL_SLOTS), jnp.float32, one_chip)
    src = _struct((SPILL_RUNS, width), jnp.float32, one_chip)
    run_len = _struct((SPILL_RUNS,), jnp.int32, one_chip)
    text = _compiled_text(
        lambda t, s, n: kp.p2p_chunk_pallas(t, s, n, sigma=1.5e-4,
                                            interpret=False),
        tgt, src, run_len)
    calls = [ln for ln in text.splitlines()
             if "p2p_chunk_kernel" in ln and " custom-call(" in ln]
    assert len(calls) == 1 and "tpu_custom_call" in calls[0]
    dims = {tuple(map(int, d.split(",")))
            for d in re.findall(r"[fs]32\[([\d,]+)\]", calls[0])}
    assert dims == {(2, SPILL_SLOTS, SPILL_RUNS),
                    (SPILL_RUNS, 2 * SPILL_SLOTS), (SPILL_RUNS, width),
                    (1, SPILL_RUNS)}
    assert not re.search(r"\b(pad|transpose)\b", text)


def _spill_tree(one_chip, level=5, slots=8):
    """The shapes of a small clustered tree with spill chunks."""
    import numpy as np

    rng = np.random.default_rng(0)
    pos = np.clip(rng.normal(0.5, 0.05, (3000, 2)), 0.0, 0.999)
    tree, _ = build_tree(pos, rng.uniform(0.9, 1.1, 3000), level, 1e-3,
                         slots=slots)
    assert tree.spill is not None
    return jax.tree_util.tree_map(
        lambda a: _struct(a.shape, a.dtype, one_chip), tree)


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


def _kernel_body(match) -> str:
    """A Mosaic kernel's serialized module, printed without its source
    locations (which carry the name scopes, as HLO metadata does)."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(match.group(1)))
        return module.operation.get_asm(enable_debug_info=False)


def _small_tree(one_chip, level=5, slots=8):
    n = 1 << level
    return Tree(z=_struct((n, n, slots), jnp.complex64, one_chip),
                q=_struct((n, n, slots), jnp.complex64, one_chip),
                mask=_struct((n, n, slots), jnp.bool_, one_chip),
                level=level, sigma=1e-3)


def test_kernels_keep_their_names_for_v5e(one_chip, monkeypatch):
    """The P2P and M2L custom calls take their ``pallas_call`` names, which
    hold the words the trace's kernel readers select by; no other custom
    call of the program holds either word."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    text = fmm_evaluate.lower(_small_tree(one_chip), P, eq=eqs.VORTEX,
                              use_kernels=True).compile().as_text()
    calls = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = [^\n]*? custom-call\(",
                       text, re.M)
    named = {c.split(".")[0] for c in calls if "p2p" in c or "m2l" in c}
    assert named == {"p2p_kernel", "m2l_kernel"}


def test_a_spilled_tree_compiles_with_the_chunk_kernel_for_v5e(
        one_chip, monkeypatch):
    """A spilled tree's whole kernel-route program: the chunk-pair kernel
    joins the dense P2P and M2L kernels, its name holding the word the P2P
    roofline selects by."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    text = fmm_evaluate.lower(_spill_tree(one_chip), P, eq=eqs.VORTEX,
                              use_kernels=True).compile().as_text()
    calls = re.findall(r"^\s+(?:ROOT )?%([\w.\-]+) = [^\n]*? custom-call\(",
                       text, re.M)
    named = {c.split(".")[0] for c in calls if "p2p" in c or "m2l" in c}
    assert named == {"p2p_kernel", "p2p_chunk_kernel", "m2l_kernel"}


@pytest.mark.parametrize("program", ["fmm_evaluate", "rk2_step"])
def test_stage_scopes_change_only_metadata_for_v5e(one_chip, monkeypatch,
                                                   program):
    """Compiled for the chip with and without ``obs.scope``, the programs
    differ in their metadata alone."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    tree = _small_tree(one_chip, level=4)

    def compiled():
        jax.clear_caches()
        if program == "fmm_evaluate":
            lowered = fmm_evaluate.lower(tree, P, eq=eqs.VORTEX,
                                         use_kernels=True)
        else:
            lowered = rk2_step.lower(tree, 0.005, p=P, use_kernels=True,
                                     guard=True)
        text = lowered.compile().as_text()
        body = [ln for ln in text.splitlines()
                if ln.startswith(("HloModule", "%", "ENTRY", " ", "}"))]
        body = re.sub(r",? metadata=\{[^{}]*\}", "", "\n".join(body))
        return re.sub(r'"body":"([^"]+)"', _kernel_body, body), text

    shipped, shipped_text = compiled()
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    bare, bare_text = compiled()
    assert "fmm.p2p/" in shipped_text and "fmm.p2p/" not in bare_text
    assert shipped == bare


def _opcode(line):
    """The opcode of one HLO instruction line and the text of its result
    type, or None for a line that is no instruction."""
    m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?[\]})]) ([a-z][\w\-]*)\(", line)
    return (m.group(2), m.group(1)) if m else None


def test_rebin_places_particles_without_a_scatter_for_v5e(one_chip,
                                                           monkeypatch):
    """The stepper's rebin at level 8 with 50 slots compiles to sorts and
    gathers alone: no instruction under ``stepper.rebin``, fused or not,
    is a scatter (which the chip runs one update at a time), nor a sort
    carrying a ``pred`` operand (what a scattered mask compiles to)."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    tree = _small_tree(one_chip, level=STEP_LEVEL, slots=STEP_SLOTS)
    text = rk2_step.lower(tree, 0.005, p=P, use_kernels=True,
                          guard=True).compile().as_text()
    rebin = [_opcode(ln) for ln in text.splitlines()
             if "/stepper.rebin/" in ln]
    rebin = [op for op in rebin if op is not None]
    ops_seen = {op for op, _ in rebin}
    assert {"sort", "gather"} <= ops_seen
    assert "scatter" not in ops_seen
    assert not [t for op, t in rebin if op == "sort" and "pred[" in t]


def test_four_chip_step_runs_its_collectives_in_rk2_step_for_v5e(
        four_chips, monkeypatch):
    """The stepper on a 2x2 block plan compiles to one program,
    ``jit_rk2_step``, holding the evaluations' halo exchanges as
    asynchronous collective-permutes: where the benchmark's
    ``rk2_collective_ms`` reader looks for them on the chip."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core.cost_model import ModelParams
    from repro.core.plan import block_plan_from_counts

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    level, slots = 5, 16
    rng = np.random.default_rng(0)
    tree, index = build_tree(rng.uniform(0.05, 0.95, (6000, 2)),
                             np.full(6000, 1 / 6000), level, 1e-3,
                             slots=slots)
    plan = block_plan_from_counts(
        index.counts, ModelParams(level=level, cut=2, p=8, slots=slots),
        (2, 2))
    rep = NamedSharding(four_chips, PartitionSpec())
    tree = jax.tree_util.tree_map(lambda a: _struct(a.shape, a.dtype, rep),
                                  tree)
    text = rk2_step.lower(tree, 0.005, p=8, mesh=four_chips,
                          use_kernels=True, plan=plan,
                          guard=True).compile().as_text()
    assert re.match(r"HloModule jit_rk2_step\b", text)
    starts = re.findall(r"^\s+%collective-permute-start(?:\.\d+)? = ",
                        text, re.M)
    dones = re.findall(r"^\s+%collective-permute-done(?:\.\d+)? = ",
                       text, re.M)
    assert len(starts) == len(dones) == STEP_4CHIP_PERMUTES
