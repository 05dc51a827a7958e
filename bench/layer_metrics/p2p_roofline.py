"""The P2P kernel's share of its roofline, in percent: the least time of
the window's near-field work (``work.p2p_cost``: real pairs, each particle
read and written once) over the summed device time of the kernel's events,
custom calls with ``p2p`` in their name (``p2p_pallas_slab.<k>`` today)."""
from bench import trace_events, work


def read(inputs):
    ops = [o for d in inputs.trace.devices
           for o in trace_events.kernel_ops(d.ops, "p2p")]
    if not ops:
        return None
    least = work.least_seconds(*work.p2p_cost(inputs.counts), inputs.peak)
    return 100.0 * least * inputs.evals / (sum(o.dur for o in ops) / 1e9)
