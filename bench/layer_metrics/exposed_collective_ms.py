"""Device milliseconds per evaluation in which a collective of the sharded
evaluator (the halo collective-permutes, the cut-level all-gather) is under
way and no other op runs, averaged over the chips.  A collective is under
way from its ``-start`` op to its ``-done`` op in ``XLA Ops``."""
from bench import trace_events


def read(inputs):
    lo, hi = inputs.trace.window
    found, exposed = False, 0
    for d in inputs.trace.devices:
        flights = trace_events.collective_flights(
            d.in_module(d.ops, "parallel_fmm"))
        found = found or bool(flights)
        others = [o for o in d.ops if not trace_events.is_collective(o)]
        exposed += trace_events.exposed_ns(flights, others, lo, hi)
    if not found:
        return None
    return exposed / len(inputs.trace.devices) / 1e6 / inputs.evals
