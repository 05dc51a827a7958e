"""Device milliseconds per evaluation in the spill layout's work, the ops
whose innermost program scope is ``fmm.spill`` (``bench/scopes.py``): the
spill chunks' P2M and L2P, and the gathers, chunk-pair kernel and
reduction of their near field, averaged over the chips.  No op runs under
that scope where no box overflows the dense capacity, and then the metric
is left out."""
from bench import scopes


def read(inputs):
    ms = scopes.stage_ms(inputs.trace,
                         lambda op, scope: scope == "fmm.spill")
    return ms / inputs.evals if ms else None
