"""The program-scope reduction (``bench/scopes.py``) and the readers built
on it, on synthetic HLO and event lists, and on what the CPU backend gives
for the program itself."""
import glob
import os

import jax
import numpy as np
import pytest

from bench import harness, peaks, scopes
from bench.trace_events import Device, Op, Trace

NEW_READERS = ("unscoped_share", "expansion_ms", "p2p_staging_ms",
               "rebin_scope_ms")
OLD_READERS = ("idle_share", "mfu", "p2p_roofline", "m2l_roofline",
               "rebin_ms", "exposed_collective_ms")

HLO = """\
HloModule jit_rk2_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %a = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(rk2_step)/jit(fmm_evaluate)/fmm.p2m/add" stack_frame_id=3}
  %b = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(rk2_step)/jit(fmm_evaluate)/fmm.p2m/mul"}
  ROOT %c = f32[8]{0} negate(%b), metadata={op_name="jit(rk2_step)/jit(fmm_evaluate)/fmm.l2p/neg"}
}

ENTRY %main.2 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="tree.z"}
  %fusion.1 = f32[8]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1
  %p2p_kernel.1 = f32[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(rk2_step)/jit(fmm_evaluate)/fmm.p2p/pallas_call"}
  %pad.3 = f32[10]{0} pad(%p2p_kernel.1), metadata={op_name="jit(rk2_step)/jit(fmm_evaluate)/fmm.p2p/pad"}
  %sort.2 = f32[10]{0} sort(%pad.3), metadata={op_name="jit(rk2_step)/stepper.rebin/jit(argsort)/sort"}
  %copy.4 = f32[10]{0} copy(%sort.2)
  ROOT %fusion.7 = f32[8]{0} fusion(%copy.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(rk2_step)/stepper.kick/add"}
}
"""


def test_innermost_takes_the_last_program_scope_of_the_path():
    assert scopes.innermost(
        "jit(rk2_step)/stepper.kick/jit(fmm_evaluate)/fmm.p2p/pad") \
        == "fmm.p2p"
    assert scopes.innermost("jit(rk2_step)/stepper.rebin/jit(argsort)/sort") \
        == "stepper.rebin"
    assert scopes.innermost("jit(parallel_fmm_evaluate)/shard_map") is None
    assert scopes.innermost("jit(rk2_step)/stepper_x/add") is None
    assert scopes.innermost("tree.z") is None


def test_hlo_scopes_reads_each_instruction_and_fills_in_fusions():
    got = scopes.hlo_scopes(HLO)
    assert got["x.1"] is None and got["copy.4"] is None
    assert got["p2p_kernel.1"] == "fmm.p2p" and got["pad.3"] == "fmm.p2p"
    assert got["sort.2"] == "stepper.rebin"
    # no metadata of its own: the scope most fused instructions name
    assert got["fusion.1"] == "fmm.p2m"
    # its own metadata wins over what it fuses
    assert got["fusion.7"] == "stepper.kick"
    assert got["a"] == "fmm.p2m" and got["c"] == "fmm.l2p"


def test_module_names_drop_the_run_id():
    assert scopes.module_name("jit_rk2_step(12)") == "jit_rk2_step"
    assert scopes.module_name("jit_rk2_step") == "jit_rk2_step"


def _op(name, s, e, kind="fusion"):
    return Op(name, s, e, kind)


FOUND = {"jit_rk2_step": scopes.hlo_scopes(HLO)}


def _device(t0=0):
    """One run of ``jit_rk2_step`` from ``t0``: each instruction once, and
    an op of another program that holds the same instruction name."""
    ops = [_op("fusion.1", t0, t0 + 10), _op("p2p_kernel.1", t0 + 10,
                                             t0 + 60, "custom-call"),
           _op("pad.3", t0 + 60, t0 + 65, "pad"),
           _op("sort.2", t0 + 65, t0 + 85, "sort"),
           _op("copy.4", t0 + 85, t0 + 90, "copy"),
           _op("fusion.7", t0 + 90, t0 + 100),
           _op("fusion.1", t0 + 110, t0 + 120)]
    mods = [_op("jit_rk2_step(3)", t0, t0 + 100, ""),
            _op("jit_other(4)", t0 + 105, t0 + 125, "")]
    return Device(ops, mods)


def test_op_scopes_keys_each_op_by_the_program_run_it_starts_in():
    got = [(o.name, s) for o, s in scopes.op_scopes(_device(), FOUND)]
    assert got == [("fusion.1", "fmm.p2m"), ("p2p_kernel.1", "fmm.p2p"),
                   ("pad.3", "fmm.p2p"), ("sort.2", "stepper.rebin"),
                   ("copy.4", None), ("fusion.7", "stepper.kick"),
                   ("fusion.1", None)]


def _inputs(tr, calls=1, evals=1):
    return harness.LayerInputs(trace=tr, calls=calls, evals=evals,
                               counts=np.ones((4, 4), np.int64), level=2,
                               p=4, peak=peaks.peak("TPU v5 lite"), chips=1)


def _reader(name):
    return harness.load_module(harness.reader_file(name + ".x"), "t")


def test_scope_readers_on_a_synthetic_trace(monkeypatch):
    monkeypatch.setattr(scopes, "live_scopes", lambda: FOUND)
    # two chips, the second one shifted: a mean over chips
    tr = Trace([_device(0), _device(1000)], [], (0, 1200))
    inputs = _inputs(tr, calls=1, evals=2)
    # busy 110 ns a chip; unscoped: copy.4 (5) and other's fusion.1 (10)
    assert _reader("unscoped_share").read(inputs) == pytest.approx(
        100 * 15 / 110)
    # fmm.p2m fusion.1 (10 ns) over 2 evaluations
    assert _reader("expansion_ms").read(inputs) == pytest.approx(5e-6)
    # fmm.p2p outside the kernel: pad.3 (5 ns)
    assert _reader("p2p_staging_ms").read(inputs) == pytest.approx(2.5e-6)
    # stepper.rebin: sort.2 (20 ns) in the one step
    assert _reader("rebin_scope_ms").read(inputs) == pytest.approx(2e-5)


def test_scope_time_is_clipped_to_the_window(monkeypatch):
    monkeypatch.setattr(scopes, "live_scopes", lambda: FOUND)
    tr = Trace([_device(0)], [], (70, 1200))
    assert _reader("rebin_scope_ms").read(_inputs(tr)) == pytest.approx(
        1.5e-5)


@pytest.mark.parametrize("name", NEW_READERS)
def test_scope_readers_read_nothing_from_a_program_without_scopes(
        name, monkeypatch):
    """The program as it was before its stages were named: no reader
    raises, and each leaves its metric out."""
    bare = {"jit_rk2_step": {k: None for k in FOUND["jit_rk2_step"]}}
    for found in ({}, bare):
        monkeypatch.setattr(scopes, "live_scopes", lambda: found)
        tr = Trace([_device(0)], [], (0, 200))
        assert _reader(name).read(_inputs(tr)) is None


def _old_names(op):
    """The kernels' custom calls as they were named before ``name=``."""
    for new, old in (("p2p_kernel", "p2p_pallas_slab"),
                     ("m2l_kernel", "m2l_pallas_slab")):
        if op.name.startswith(new):
            return Op(old + op.name[len(new):], op.start, op.end, op.kind,
                      op.dims)
    return op


@pytest.mark.parametrize("name", OLD_READERS)
def test_readers_select_the_same_ops_under_the_kernels_new_names(name):
    flat, grid = ((4096,),), ((8, 8, 64),)
    ops = [Op("p2p_kernel.1", 0, 500_000, "custom-call", grid),
           Op("m2l_kernel.3", 500_000, 600_000, "custom-call", grid),
           Op("m2l_kernel.4", 600_000, 650_000, "custom-call", grid),
           Op("sort.2", 700_000, 900_000, "sort", flat * 2),
           Op("collective-permute-start.1", 900_000, 900_010,
              "collective-permute-start"),
           Op("collective-permute-done.1", 990_000, 1_000_000,
              "collective-permute-done")]
    mods = [_op("jit_rk2_step(1)", 0, 950_000, ""),
            _op("jit_parallel_fmm_evaluate(2)", 950_000, 1_100_000, "")]
    counts = np.ones((8, 8), np.int64)

    def read(device_ops):
        tr = Trace([Device(device_ops, mods)], [], (0, 1_100_000))
        inputs = harness.LayerInputs(trace=tr, calls=1, evals=2,
                                     counts=counts, level=3, p=4,
                                     peak=peaks.peak("TPU v5 lite"), chips=1)
        return _reader(name).read(inputs)

    got = read(ops)
    assert got is not None
    assert got == read([_old_names(o) for o in ops])


def test_span_gaps_name_each_idle_stretch_by_the_innermost_span():
    ops = [_op("a", 0, 10), _op("b", 30, 40), _op("c", 60, 100)]
    spans = [Op("stepper.step", 5, 95), Op("stepper.wait", 5, 30),
             Op("stepper.pull", 42, 44), Op("stepper.replan", 45, 55)]
    got = scopes.span_gaps(ops, spans, 0, 100)
    # idle: [10, 30) in wait; [40, 60) in step, pull [42, 44), replan
    # [45, 55)
    assert got == {"stepper.wait": 20, "stepper.step": 8,
                   "stepper.pull": 2, "stepper.replan": 10}
    assert scopes.span_gaps(ops, [], 0, 100) == {"none": 40}


@pytest.fixture(scope="module")
def stepped(tmp_path_factory):
    """Three steps of a small stepper with the profiler on: re-plan checks
    every second step."""
    from repro.core.stepper import VortexStepper

    rng = np.random.default_rng(0)
    pos = rng.uniform(0.1, 0.9, (400, 2))
    st = VortexStepper(pos, 1e-3 * rng.normal(size=400), 0.02, p=6,
                       replan_every=2)
    st.step()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(log_dir)
    records = [st.step() for _ in range(3)]
    jax.profiler.stop_trace()
    assert not any(r.recovered for r in records)
    return st, scopes.host_spans(log_dir)


def test_the_stepper_writes_its_host_spans(stepped):
    st, spans = stepped
    names = [s.name for s in spans]
    assert names.count("stepper.step") == 3
    assert names.count("stepper.dispatch") == 3
    assert names.count("stepper.wait") == 3
    # ok, occ and the health word every step; the counts at the re-plan
    # checks of steps 2 and 4 (steps 2 to 4 are traced)
    assert names.count("stepper.pull") == 3 * 3 + 2
    assert names.count("stepper.replan") == 2
    steps = [s for s in spans if s.name == "stepper.step"]
    for s in spans:
        assert any(t.start <= s.start and s.end <= t.end for t in steps)


def test_live_scopes_reads_the_programs_the_process_holds():
    from repro.core.fmm import fmm_evaluate
    from repro.core.quadtree import build_tree

    rng = np.random.default_rng(1)
    tree, _ = build_tree(rng.uniform(0.1, 0.9, (300, 2)),
                         rng.normal(size=300), 3, 0.02, slots=24)
    jax.block_until_ready(fmm_evaluate(tree, 6, eq="vortex",
                                       use_kernels=True))
    found = scopes.live_scopes()["jit_fmm_evaluate"]
    named = set(found.values()) - {None}
    assert {"fmm.p2m", "fmm.m2l", "fmm.l2p", "fmm.p2p",
            "fmm.assemble"} <= named
    assert named <= {"fmm.p2m", "fmm.m2m", "fmm.m2l", "fmm.l2l", "fmm.l2p",
                     "fmm.p2p", "fmm.assemble", "fmm.health"}


def test_host_spans_reads_only_the_programs_spans(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:window"):
        with jax.profiler.TraceAnnotation("fmm:stepper.pull"):
            jax.block_until_ready(jax.numpy.ones(3) + 1)
    jax.profiler.stop_trace()
    assert glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)
    assert [s.name for s in scopes.host_spans(str(tmp_path))] == [
        "stepper.pull"]


def test_trace_report_summarizes_scopes_spans_and_gaps():
    from bench import trace_report

    dev = _device(0)
    spans = [Op("stepper.step", 0, 140), Op("stepper.pull", 102, 104),
             Op("stepper.pull", 125, 126)]
    tr = Trace([dev], [Op("step_host", 0, 150)], (0, 150))
    got = trace_report.summarize(tr, spans, FOUND, calls=1)
    assert got["scope_ms_per_call"] == pytest.approx({
        "fmm.p2p": 55e-6, "stepper.rebin": 20e-6, "none": 15e-6,
        "fmm.p2m": 10e-6, "stepper.kick": 10e-6})
    # idle [100, 110) and [120, 150): pulls 3 ns, the step 27, none 10
    assert got["idle_ms_per_call_by_span"] == pytest.approx({
        "stepper.step": 27e-6, "none": 10e-6, "stepper.pull": 3e-6})
    assert got["spans_per_call"] == {"stepper.pull": 2, "stepper.step": 1}
    assert got["gaps_over_1ms"] == 0
    assert got["ms_per_call_of_them_outside_spans"] == 0
    assert got["longest_gaps_ms_chip_span_module_before_after"][0] == (
        30e-6, 0, "step_host", "jit_rk2_step(3)", None)


def test_trace_report_measures_what_no_span_covers_in_long_gaps(
        monkeypatch):
    from bench import trace_report

    monkeypatch.setattr(trace_report, "MIN_GAP_NS", 5)
    tr = Trace([_device(0)], [], (0, 150))
    got = trace_report.summarize(tr, [Op("stepper.step", 0, 140)], FOUND,
                                 calls=1)
    # gaps [100, 110) and [120, 150); the step span ends at 140
    assert got["gaps_over_1ms"] == 2
    assert got["ms_per_call_of_them_outside_spans"] == pytest.approx(1e-5)
