"""The trace reduction on synthetic event lists."""
import numpy as np
import pytest

from bench import harness, peaks, trace_events as te, work
from bench.trace_events import Device, Op, Trace


def test_parse_hlo_names_opcodes_and_dims_as_the_chip_writes():
    cases = {
        "%p2p_pallas_slab.1 = (f32[1024,1024,128]{2,1,0:T(8,128)}, "
        "f32[1024,1024,128]{2,1,0:T(8,128)}) custom-call(f32[1026,1032,128]"
        "{2,1,0:T(8,128)} %pad.40), custom_call_target=\"tpu_custom_call\"":
            ("p2p_pallas_slab.1", "custom-call",
             ((1024, 1024, 128), (1024, 1024, 128))),
        "%custom-call.4 = c64[1024,1024,4]{2,1,0:T(8,128)} custom-call("
        "f32[1024,1024,4]{2,1,0:T(8,128)} %a)": (
            "custom-call.4", "custom-call", ((1024, 1024, 4),)),
        "%fusion.17 = (f32[3276800]{0:T(1024)S(1)}, f32[3276800]{0:T(1024)"
        "S(1)}) fusion(f32[3276800]{0:T(1024)S(1)} %broadcast.65.clone.6), "
        "kind=kCustom": ("fusion.17", "fusion", ((3276800,), (3276800,))),
        "%fusion.13 = pred[3276800]{0:T(1024)(128)(4,1)S(1)} fusion(pred"
        "[3276800]{0:T(1024)(128)(4,1)S(1)} %custom-call.54)": (
            "fusion.13", "fusion", ((3276800,),)),
        "%sort.2 = (s32[3276800]{0:T(1024)}, s32[3276800]{0:T(1024)S(1)}) "
        "sort(s32[3276800]{0:T(1024)S(1)} %fusion.55), dimensions={0}": (
            "sort.2", "sort", ((3276800,), (3276800,))),
        "%copy.533 = c64[1024,1024,4]{2,1,0:T(8,128)} copy(c64[1024,1024,4]"
        "{1,2,0:T(4,128)} %tree_q.1)": ("copy.533", "copy", ((1024, 1024, 4),)),
        "%x = s32[]{:T(128)} add(s32[] %a, s32[] %b)": ("x", "add", ((),)),
        "ThreadpoolListener::StartRegion": (
            "ThreadpoolListener::StartRegion", "", ()),
    }
    for text, want in cases.items():
        assert te.parse_hlo(text) == want


def test_union_merges_overlaps_and_clips_to_the_window():
    iv = [(0, 10), (5, 20), (30, 40), (39, 45), (50, 60)]
    assert te.union(iv, 2, 55) == [(2, 20), (30, 45), (50, 55)]
    assert te.total(te.union(iv, 2, 55)) == 18 + 15 + 5


def _op(name, s, e, kind=""):
    return Op(name, s, e, kind)


def test_busy_gaps_and_idle_share():
    ops = [_op("a", 0, 40), _op("b", 30, 50), _op("c", 70, 90)]
    assert te.busy_ns(ops, 0, 100) == 70
    assert te.gaps(ops, 0, 100) == [(50, 70), (90, 100)]
    tr = Trace([Device(ops), Device([_op("d", 0, 100)])], [], (0, 100))
    # device 0 busy 70 of 100, device 1 busy 100: mean busy 0.85
    assert te.mean_busy_s(tr) == pytest.approx(85e-9)
    reader = harness.load_module(harness.reader_file("idle_share.x"), "t")
    assert reader.read(_inputs(tr)) == pytest.approx(15.0)


def test_collective_flights_run_from_start_to_done():
    ops = [_op("collective-permute-start.5", 10, 11,
               "collective-permute-start"),
           _op("fusion.1", 11, 20, "fusion"),
           _op("collective-permute-start.6", 12, 13,
               "collective-permute-start"),
           _op("collective-permute-done.5", 29, 30,
               "collective-permute-done"),
           _op("all-gather.2", 40, 60, "all-gather"),
           _op("collective-permute-done.6", 70, 71,
               "collective-permute-done")]
    assert sorted(te.collective_flights(ops)) == [(10, 30), (12, 71),
                                                  (40, 60)]
    assert not te.is_collective(ops[1])


def test_exposed_time_is_what_no_other_op_covers():
    flights = [(10, 30), (25, 60)]
    others = [_op("fusion.1", 0, 15), _op("fusion.2", 40, 50)]
    # collectives under way in [10, 60); others hide [10, 15) and [40, 50)
    assert te.exposed_ns(flights, others, 0, 100) == 50 - 5 - 10
    assert te.exposed_ns(flights, others, 20, 45) == 25 - 5


def test_kernel_ops_match_custom_calls_by_word():
    ops = [_op("p2p_pallas_slab.1", 0, 5, "custom-call"),
           _op("m2l_pallas_slab.9", 5, 6, "custom-call"),
           _op("p2p_fusion.3", 6, 7, "fusion"),
           _op("custom-call.4", 7, 8, "custom-call")]
    assert [o.name for o in te.kernel_ops(ops, "p2p")] == ["p2p_pallas_slab.1"]
    assert [o.name for o in te.kernel_ops(ops, "m2l")] == ["m2l_pallas_slab.9"]


def test_ops_are_assigned_to_the_program_run_they_start_in():
    dev = Device([_op("sort.1", 5, 8, "sort"), _op("sort.2", 25, 28, "sort")],
                 modules=[_op("jit_rk2_step(1)", 0, 10),
                          _op("jit_other(2)", 20, 30)])
    assert [o.name for o in dev.in_module(dev.ops, "rk2_step")] == ["sort.1"]


def test_breakdown_names_gaps_by_the_host_span_open_in_them():
    ops = [_op("p2p_pallas_slab.1", 0, 60, "custom-call"),
           _op("fusion.1", 60, 70, "fusion"),
           _op("p2p_pallas_slab.1", 80, 95, "custom-call")]
    spans = [_op("block", 0, 72), _op("dispatch", 72, 82),
             _op("check", 95, 100)]
    tr = Trace([Device(ops)], spans, (0, 100))
    b = te.breakdown(tr)
    assert b["device_ops"][0] == ["p2p_pallas_slab.1 custom-call",
                                  pytest.approx(75e-9)]
    assert b["idle_gaps"] == [["dispatch", pytest.approx(10e-9)],
                              ["check", pytest.approx(5e-9)]]


def _inputs(tr, evals=1, counts=None, level=2, p=4):
    counts = np.ones((4, 4), np.int64) if counts is None else counts
    return harness.LayerInputs(trace=tr, calls=evals, evals=evals,
                               counts=counts, level=level, p=p,
                               peak=peaks.peak("TPU v5 lite"), chips=1)


def test_roofline_readers_divide_least_time_by_kernel_time():
    counts = np.arange(16, dtype=np.int64).reshape(4, 4)
    least = work.least_seconds(*work.p2p_cost(counts),
                               peaks.peak("TPU v5 lite"))
    kernel_ns = int(round(least * 1e9 * 200))       # 0.5% of its roofline
    tr = Trace([Device([_op("p2p_pallas_slab.1", 0, kernel_ns,
                            "custom-call")])], [], (0, kernel_ns))
    p2p = harness.load_module(harness.reader_file("p2p_roofline.x"), "t")
    m2l = harness.load_module(harness.reader_file("m2l_roofline.x"), "t")
    assert p2p.read(_inputs(tr, counts=counts)) == pytest.approx(0.5,
                                                                 rel=1e-3)
    assert m2l.read(_inputs(tr, counts=counts)) is None


def test_readers_that_find_nothing_return_nothing():
    tr = Trace([Device([_op("fusion.1", 0, 10, "fusion")])], [], (0, 10))
    for name in ("rebin_ms.x", "exposed_collective_ms.x", "p2p_roofline.x"):
        mod = harness.load_module(harness.reader_file(name), "t")
        assert mod.read(_inputs(tr)) is None


def test_rebin_reader_takes_the_steppers_flat_slot_ops():
    flat, grid = ((4096,),), ((8, 8, 64),)
    dev = Device([Op("sort.1", 0, 2_000_000, "sort", flat * 2),
                  Op("fusion.17", 2_000_000, 3_000_000, "fusion", flat),
                  Op("fusion.3", 3_000_000, 5_000_000, "fusion", grid),
                  Op("reduce.1", 5_000_000, 5_500_000, "reduce", ((),)),
                  Op("fusion.9", 7_000_000, 9_000_000, "fusion", flat)],
                 modules=[_op("jit_rk2_step(1)", 0, 6_000_000),
                          _op("jit_other(2)", 6_000_000, 10_000_000)])
    tr = Trace([dev], [], (0, 10_000_000))
    rebin = harness.load_module(harness.reader_file("rebin_ms.step"), "t")
    # counts of 8 x 8 boxes: flat arrays of >= 64 slots are the rebin's
    got = rebin.read(_inputs(tr, evals=2, counts=np.ones((8, 8), np.int64)))
    assert got == pytest.approx(1.5)


def test_exposed_collective_reader():
    dev = Device([_op("fusion.1", 0, 1_000_000, "fusion"),
                  _op("collective-permute-start.3", 500_000, 500_010,
                      "collective-permute-start"),
                  _op("collective-permute-done.3", 2_999_990, 3_000_000,
                      "collective-permute-done")],
                 modules=[_op("jit_parallel_fmm_evaluate(1)", 0, 4_000_000)])
    tr = Trace([dev, dev], [], (0, 4_000_000))
    exp = harness.load_module(
        harness.reader_file("exposed_collective_ms.sharded_eval"), "t")
    assert exp.read(_inputs(tr, evals=1)) == pytest.approx(2.0)
