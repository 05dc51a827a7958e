"""Device milliseconds per evaluation in the near-field stage outside the
P2P kernel: ops the program scopes ``fmm.p2p`` (``bench/scopes.py``) that
are not the kernel's custom call (the halo pad, the lane padding, the
output slice), averaged over the chips.  ``p2p_roofline`` does not see
this time."""
from bench import scopes, trace_events


def _staging(op, scope) -> bool:
    return scope == "fmm.p2p" and not trace_events.kernel_ops([op], "p2p")


def read(inputs):
    ms = scopes.stage_ms(inputs.trace, _staging)
    return None if ms is None else ms / inputs.evals
