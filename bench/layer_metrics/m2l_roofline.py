"""The M2L kernel's share of its roofline, in percent: the least time of
the window's M2L work (``work.m2l_cost``: 27 p x p translations per box of
levels 2..L, each expansion read and written once) over the summed device
time of the kernel's events, custom calls with ``m2l`` in their name
(``m2l_pallas_slab.<k>`` today)."""
from bench import trace_events, work


def read(inputs):
    ops = [o for d in inputs.trace.devices
           for o in trace_events.kernel_ops(d.ops, "m2l")]
    if not ops:
        return None
    least = work.least_seconds(*work.m2l_cost(inputs.level, inputs.p),
                               inputs.peak)
    return 100.0 * least * inputs.evals / (sum(o.dur for o in ops) / 1e9)
