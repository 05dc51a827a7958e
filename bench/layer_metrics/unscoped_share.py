"""Share of the window's device busy time, in percent, in ops that run
under no program scope (``bench/scopes.py``): how much of the device's
work the program's own stage names leave unnamed, summed over the chips."""
from bench import scopes, trace_events


def read(inputs):
    ms = scopes.stage_ms(inputs.trace, lambda op, scope: scope is None)
    if ms is None:
        return None
    return 100.0 * ms / (trace_events.mean_busy_s(inputs.trace) * 1e3)
