"""Device milliseconds per evaluation in the expansion stages, the ops the
program scopes ``fmm.p2m``, ``fmm.m2m``, ``fmm.l2l`` and ``fmm.l2p``
(``bench/scopes.py``), averaged over the chips.  No roofline sees them."""
from bench import scopes


def read(inputs):
    ms = scopes.stage_ms(inputs.trace,
                         lambda op, scope: scope in scopes.EXPANSION)
    return None if ms is None else ms / inputs.evals
