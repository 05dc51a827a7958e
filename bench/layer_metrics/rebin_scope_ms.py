"""Device milliseconds per step in the ops the program scopes
``stepper.rebin`` (``bench/scopes.py``: both ``quadtree.rebuild_tree``
calls of ``rk2_step``), averaged over the chips.  The scope's twin of
``rebin_ms``, which selects the same work by result shape."""
from bench import scopes


def read(inputs):
    ms = scopes.stage_ms(inputs.trace,
                         lambda op, scope: scope == "stepper.rebin")
    return None if ms is None else ms / inputs.calls
