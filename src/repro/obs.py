"""Stage names on the profiler's clock.

``scope(name)`` names the device work traced inside it: ``jax.named_scope``
writes the name into each HLO instruction's ``op_name`` metadata, so a
profiler trace of the chip can attribute every op to its FMM or stepper
stage.  It costs nothing at run time and leaves the compiled program as it
is, apart from that metadata.

``span(name)`` is a host span, ``jax.profiler.TraceAnnotation("fmm:" +
name)``: it lands in the same trace, on the same clock, as the device ops,
so an idle gap on the chip can be read against what the host was doing.
With no profiler running it costs about a microsecond.

Names in use (a reader of the trace depends on them):

  device scopes  ``fmm.p2m``, ``fmm.m2m``, ``fmm.m2l``, ``fmm.l2l``,
                 ``fmm.l2p``, ``fmm.p2p``, ``fmm.assemble``, ``fmm.health``,
                 ``fmm.exchange``; ``stepper.kick``, ``stepper.rebin``,
                 ``stepper.health``; ``fmm.spill``, innermost under
                 ``fmm.p2m``, ``fmm.l2p``, ``fmm.p2p`` and
                 ``fmm.assemble``, the spill chunks' work (a dense tree
                 traces none)
  host spans     ``fmm:stepper.step``, ``.dispatch``, ``.wait``, ``.pull``,
                 ``.replan``, ``.relevel``, ``.recover``, ``.checkpoint``
  counters       ``Tree.spill_particles``, ``.spill_chunks``,
                 ``.spill_pairs``, ``.spill_pair_evals``: static counts of
                 a tree's spill layout (``core/quadtree.py``)
"""
from __future__ import annotations

import jax

SPAN_PREFIX = "fmm:"


def scope(name: str):
    """Device-side stage scope (HLO ``op_name`` metadata only)."""
    return jax.named_scope(name)


def span(name: str):
    """Host span ``fmm:<name>`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
