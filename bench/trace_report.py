r"""Where a traced window of one cell went, by the program's own names.

    python3 bench/trace_report.py --workload <cell> --seed <n> \
                                  --seconds <s> [--keep <dir>]

from the root of a checkout, on the chips the cell asks for.  It sets the
cell up as ``bench/run.py`` does, traces one window, and prints one JSON
object: the device milliseconds per call of each program scope
(``bench/scopes.py``), the unscoped ops that take 0.5% of busy time or more,
the device idle time per call under each of the program's ``fmm:`` host
spans, the count of each span per call, the idle gaps over 1 ms and how
much of them no program span covers, the longest idle gaps with the
harness span and the program runs (``XLA Modules``) on either side of
each, and every per-layer metric the harness would report.

Unlike ``bench/run.py`` it keeps the trace (under ``--keep``, else a
temporary directory it deletes), so the program's host spans, which the
harness's readers do not see, can be read too.  It decides nothing: the
benchmark's numbers come from ``bench/run.py`` alone.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MIN_GAP_NS = 1_000_000
MIN_UNSCOPED = 0.005


def summarize(tr, spans, found, calls: int) -> dict:
    """The report's numbers for a loaded trace ``tr``, the program's host
    spans and the scopes ``found`` (``scopes.live_scopes()``)."""
    from bench import scopes, trace_events

    lo, hi = tr.window
    ndev = len(tr.devices)
    by_scope = collections.Counter()
    unscoped = collections.Counter()
    gaps = collections.Counter()
    big = uncovered = 0
    longest = []
    for k, d in enumerate(tr.devices):
        for op, scope in scopes.op_scopes(d, found):
            ns = trace_events.busy_ns([op], lo, hi)
            by_scope[scope or "none"] += ns
            if scope is None:
                unscoped[op.label()] += ns
        gaps.update(scopes.span_gaps(d.ops, spans, lo, hi))
        for s, e in trace_events.gaps(d.ops, lo, hi):
            if e - s > MIN_GAP_NS:
                big += 1
                uncovered += scopes.span_gaps([], spans, s, e).get("none", 0)
            before = [m.name for m in d.modules if m.end <= s]
            after = [m.name for m in d.modules if m.start >= e]
            longest.append(((e - s) / 1e6, k,
                            trace_events.label((s, e), tr.spans),
                            before[-1] if before else None,
                            after[0] if after else None))
    longest.sort(key=lambda g: -g[0])
    busy = trace_events.mean_busy_s(tr) * 1e9 * ndev
    per_call = 1e6 * ndev * calls
    return {
        "calls": calls, "window_s": tr.window_ns / 1e9,
        "busy_s": trace_events.mean_busy_s(tr),
        "scope_ms_per_call": {k: v / per_call
                              for k, v in by_scope.most_common()},
        "unscoped_ops_ms_per_call": {k: v / per_call
                                     for k, v in unscoped.most_common()
                                     if v >= MIN_UNSCOPED * busy},
        "idle_ms_per_call_by_span": {k: v / per_call
                                     for k, v in gaps.most_common()},
        "spans_per_call": {k: v / calls for k, v in sorted(
            collections.Counter(s.name for s in spans
                                if lo <= s.start < hi).items())},
        "gaps_over_1ms": big,
        "ms_per_call_of_them_outside_spans": uncovered / per_call,
        "longest_gaps_ms_chip_span_module_before_after": longest[:5]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    sys.path[:1] = [ROOT, os.path.join(ROOT, "src")]
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import harness, peaks, scopes, trace_events

    bm = harness.load_benchmark()
    cell = harness.find(bm["workloads"], args.workload, "workload")
    cfg = harness.load_json(harness.find(bm["configs"], cell["config"],
                                         "config")["file"])
    traffic = harness.load_json(harness.traffic_file(cell["traffic"]))
    devices = jax.devices()[:int(cell["chips"])]
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench: the cell needs {cell['chips']} TPU chips",
              file=sys.stderr)
        return 2
    driver = harness.load_module(harness.driver_file(traffic["driver"]),
                                 "driver")
    cellrun = driver.Cell(cfg, traffic, args.seed, devices, harness.span)
    log_dir = args.keep or tempfile.mkdtemp(prefix="bench-report-")
    calls = 0
    jax.profiler.start_trace(log_dir)
    with harness.span(trace_events.WINDOW_SPAN):
        t0 = time.perf_counter()
        while calls == 0 or time.perf_counter() - t0 < args.seconds:
            cellrun.call()
            calls += 1
    jax.profiler.stop_trace()
    # the readers run where the harness runs them: after the cell's state
    # is dropped
    work = cellrun.work()
    cellrun.release()
    gc.collect()
    try:
        tr = trace_events.load(log_dir)
        spans = scopes.host_spans(log_dir)
    finally:
        if args.keep is None:
            shutil.rmtree(log_dir, ignore_errors=True)
    inputs = harness.LayerInputs(
        trace=tr, calls=calls, evals=calls * cellrun.evals_per_call,
        counts=work["counts"], level=work["level"], p=work["p"],
        peak=peaks.peak(devices[0].device_kind), chips=len(devices))
    out = {"workload": args.workload,
           **summarize(tr, spans, scopes.live_scopes(), calls),
           "metrics": {m["name"]: harness.load_module(
               harness.reader_file(m["name"]), "reader").read(inputs)
               for m in harness.cell_metrics(bm, args.workload,
                                             "per_layer")}}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
