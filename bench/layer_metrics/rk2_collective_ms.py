"""Device milliseconds per step in which a collective of the sharded
stepper's program (``rk2_step`` on a mesh: the evaluations' halo
collective-permutes and cut-level all-gather) is under way and no other op
runs, averaged over the chips.  A collective is under way from its
``-start`` op to its ``-done`` op in ``XLA Ops``.  A stepper on one chip
runs no collective, and then the metric is left out."""
from bench import trace_events


def read(inputs):
    lo, hi = inputs.trace.window
    found, exposed = False, 0
    for d in inputs.trace.devices:
        flights = trace_events.collective_flights(
            d.in_module(d.ops, "rk2_step"))
        found = found or bool(flights)
        others = [o for o in d.ops if not trace_events.is_collective(o)]
        exposed += trace_events.exposed_ns(flights, others, lo, hi)
    if not found:
        return None
    return exposed / len(inputs.trace.devices) / 1e6 / inputs.calls
