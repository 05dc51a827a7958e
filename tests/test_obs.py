"""The stage scopes of ``repro.obs`` change nothing but metadata, and cover
every op of the programs they name.

Each program is compiled on the CPU at a tiny level (the kernel route in
the Pallas interpreter) twice: as shipped, and with ``obs.scope`` a null
context.  With the ``metadata={...}`` attributes and the stack-frame tables
stripped, the two HLO texts must be identical.  The sharded evaluator and
the sharded stepper compile on four virtual CPU devices in a subprocess,
since JAX fixes the device count at its first start.
"""
import contextlib
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import equations as eqs
from repro.core.fmm import fmm_evaluate
from repro.core.quadtree import Tree
from repro.core.stepper import rk2_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
META = re.compile(r",? metadata=\{[^{}]*\}")
SCOPE = re.compile(r'op_name="[^"]*?\b(?:fmm|stepper)\.[a-z0-9_]+(?:/|")')
# opcodes XLA adds or keeps without a stage of their own: arguments,
# tuples, constants, and the copies it inserts for loop state and layouts
ALLOWED = {"parameter", "tuple", "get-tuple-element", "bitcast", "constant",
           "copy"}
TRACED = 'op_name="jit('      # metadata of an op JAX traced, not XLA made


def _tree(level: int, slots: int) -> Tree:
    n = 1 << level
    s = lambda dt: jax.ShapeDtypeStruct((n, n, slots), dt)  # noqa: E731
    return Tree(z=s(jnp.complex64), q=s(jnp.complex64), mask=s(jnp.bool_),
                level=level, sigma=1e-3)


def _serial(name: str) -> str:
    if name == "fmm_evaluate":
        lowered = fmm_evaluate.lower(_tree(3, 8), 6, eq=eqs.VORTEX,
                                     use_kernels=True, with_health=True)
    else:
        lowered = rk2_step.lower(_tree(3, 8), 0.005, p=6, use_kernels=True,
                                 guard=True)
    return lowered.compile().as_text()


_SHARDED = textwrap.dedent("""
    import contextlib, json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh
    from repro import obs
    from repro.core.parallel_fmm import parallel_fmm_evaluate
    from repro.core.plan import uniform_block_plan
    from repro.core.quadtree import Tree
    from repro.core.stepper import rk2_step

    assert len(jax.devices()) == 4
    mesh = Mesh(np.array(jax.devices()), ("data",))
    plan = uniform_block_plan(4, (2, 2))
    s = lambda dt: jax.ShapeDtypeStruct((16, 16, 8), dt)
    tree = Tree(z=s(jnp.complex64), q=s(jnp.complex64), mask=s(jnp.bool_),
                level=4, sigma=1e-3)

    def texts():
        jax.clear_caches()
        ev = parallel_fmm_evaluate.lower(tree, 6, mesh, use_kernels=True,
                                         plan=plan, with_health=True)
        st = rk2_step.lower(tree, 0.005, p=6, mesh=mesh, use_kernels=True,
                            plan=plan, guard=True)
        return {"parallel_fmm_evaluate": ev.compile().as_text(),
                "rk2_step_sharded": st.compile().as_text()}

    shipped = texts()
    obs.scope = lambda name: contextlib.nullcontext()
    print(json.dumps({"shipped": shipped, "unscoped": texts()}))
""")


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _SHARDED], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _program(text: str) -> str:
    """The HLO without metadata: every computation, no stack-frame tables."""
    body = [ln for ln in text.splitlines()
            if ln.startswith(("HloModule", "%", "ENTRY", " ", "}"))]
    return META.sub("", "\n".join(body))


def _computations(text: str) -> dict[str, str]:
    return {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.\-]+) \(.*?^\}", text, re.M | re.S)}


def unscoped_ops(text: str) -> list[str]:
    """Instructions the device executes (those outside fused and reducer
    computations) that JAX traced but no program scope names: neither their
    own ``op_name`` nor, for a fusion, any ``op_name`` inside it.  An
    instruction whose metadata XLA made (no ``jit(...)`` path) is left out,
    and so are the ``ALLOWED`` opcodes."""
    comps = _computations(text)
    nested = set(re.findall(r"\b(?:calls|to_apply)=%([\w.\-]+)", text))
    out = []
    for name, comp in comps.items():
        if name in nested:
            continue
        for line in comp.splitlines()[1:]:
            m = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = .*?\s([\w\-]+)\(", line)
            if not m or m.group(2) in ALLOWED or SCOPE.search(line):
                continue
            callee = re.search(r"\bcalls=%([\w.\-]+)", line)
            inner = comps[callee.group(1)] if callee else ""
            if SCOPE.search(inner):
                continue
            if TRACED in line or TRACED in inner:
                out.append(line.strip()[:160])
    return out


def _with_null_scope(monkeypatch, fn, *args):
    monkeypatch.setattr(obs, "scope", lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        return fn(*args)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


@pytest.mark.parametrize("name", ["fmm_evaluate", "rk2_step"])
def test_scopes_change_only_metadata(name, monkeypatch):
    shipped = _serial(name)
    unscoped = _with_null_scope(monkeypatch, _serial, name)
    assert SCOPE.search(shipped) and not SCOPE.search(unscoped)
    assert _program(shipped) == _program(unscoped)


@pytest.mark.parametrize("name", ["parallel_fmm_evaluate",
                                  "rk2_step_sharded"])
def test_sharded_scopes_change_only_metadata(name, sharded):
    shipped, unscoped = sharded["shipped"][name], sharded["unscoped"][name]
    assert SCOPE.search(shipped) and not SCOPE.search(unscoped)
    assert _program(shipped) == _program(unscoped)


@pytest.mark.parametrize("name", ["fmm_evaluate", "rk2_step"])
def test_every_op_of_the_serial_programs_has_a_scope(name):
    assert unscoped_ops(_serial(name)) == []


@pytest.mark.parametrize("name", ["parallel_fmm_evaluate",
                                  "rk2_step_sharded"])
def test_every_op_of_the_sharded_programs_has_a_scope(name, sharded):
    assert unscoped_ops(sharded["shipped"][name]) == []


def test_unscoped_ops_finds_an_op_that_lost_its_scope():
    text = textwrap.dedent("""\
        %fused (p: f32[4]) -> f32[4] {
          ROOT %n = f32[4] negate(%p), metadata={op_name="jit(f)/neg"}
        }

        ENTRY %main (x: f32[4]) -> f32[4] {
          %x = f32[4] parameter(0), metadata={op_name="x"}
          %a = f32[4] add(%x, %x), metadata={op_name="jit(f)/fmm.p2m/add"}
          %f = f32[4] fusion(%a), kind=kLoop, calls=%fused
          %c = f32[4] copy(%f)
          %b = f32[4] broadcast(%c), metadata={op_name="broadcast.9"}
          ROOT %m = f32[4] multiply(%b, %b), metadata={op_name="jit(f)/mul"}
        }
        """)
    assert [ln.split(" =")[0] for ln in unscoped_ops(text)] == ["%f",
                                                                "ROOT %m"]

