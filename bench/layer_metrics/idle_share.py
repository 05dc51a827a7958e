"""Device idle share of the traced window, in percent: 1 - (the union of
the device's op intervals / the window), averaged over the chips."""
from bench import trace_events


def read(inputs):
    tr = inputs.trace
    return 100.0 * (1.0 - trace_events.mean_busy_s(tr) / (tr.window_ns / 1e9))
