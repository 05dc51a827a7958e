"""The program's own stage names in a profiler trace.

The program names its device stages with ``jax.named_scope`` (``fmm.p2m``,
``fmm.p2p``, ``stepper.rebin``, ...): each HLO instruction's ``op_name``
metadata then holds a path such as ``jit(rk2_step)/jit(fmm_evaluate)/
fmm.p2p/pad``, and the op's stage is the innermost such name.  Its host
spans are ``jax.profiler.TraceAnnotation`` events named ``fmm:<span>``.

A device op in the trace (``trace_events.Op``) carries its HLO instruction
name and the program run it starts in (``XLA Modules``).  The scopes are
read from the HLO of the executables the process holds
(``client.live_executables()``), keyed by module and instruction name: the
harness reads the trace file and deletes it before the readers run, and
the parsed ops keep no metadata.  A fusion whose own metadata names no
scope takes the scope most of its fused instructions name.

Everything below but ``live_scopes`` and ``host_spans`` is pure functions
over HLO text and event lists, checked on synthetic input.  Where the
program has no scopes, as before they were added, every reader built on
these functions returns ``None``.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

from bench import trace_events

SPAN_PREFIX = "fmm:"
EXPANSION = ("fmm.p2m", "fmm.m2m", "fmm.l2l", "fmm.l2p")

_SCOPE = re.compile(r"(?:^|/)((?:fmm|stepper)\.[A-Za-z0-9_]+)(?=/|$)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_RUN_ID = re.compile(r"\(\d+\)$")


def innermost(op_name: str) -> str | None:
    """The last ``fmm.*`` or ``stepper.*`` element of an ``op_name`` path."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict[str, str | None]:
    """Instruction name -> innermost program scope, for every instruction
    of an HLO module's text.  An instruction that names none and calls a
    fused computation takes the most common scope inside it."""
    own: dict[str, str | None] = {}
    calls: dict[str, str] = {}
    members: dict[str, list[str]] = collections.defaultdict(list)
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = innermost(op.group(1)) if op else None
        members[comp].append(name)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
    out = dict(own)
    for name, callee in calls.items():
        if out[name] is None:
            inner = collections.Counter(own[i] for i in members[callee]
                                        if own[i] is not None)
            if inner:
                out[name] = inner.most_common(1)[0][0]
    return out


def module_name(event_name: str) -> str:
    """``jit_fmm_evaluate(12)`` -> ``jit_fmm_evaluate``."""
    return _RUN_ID.sub("", event_name)


def live_scopes() -> dict[str, dict[str, str | None]]:
    """Module name -> ``hlo_scopes`` of every executable this process
    holds; empty where the backend cannot give its HLO."""
    import jax
    out: dict[str, dict[str, str | None]] = {}
    try:
        executables = jax.devices()[0].client.live_executables()
    except (AttributeError, RuntimeError):
        return out
    for exe in executables:
        try:
            modules = exe.hlo_modules()
        except RuntimeError:
            continue
        for mod in modules:
            out.setdefault(mod.name, {}).update(hlo_scopes(mod.to_string()))
    return out


def op_scopes(device: trace_events.Device, scopes) -> list:
    """``(op, scope)`` for each op of ``device``: the scope its instruction
    has in the program run it starts in, ``None`` if it has none."""
    runs = sorted(device.modules, key=lambda m: m.start)
    starts = [m.start for m in runs]
    out = []
    for o in device.ops:
        k = bisect.bisect_right(starts, o.start) - 1
        mod = module_name(runs[k].name) if k >= 0 and o.start < runs[k].end \
            else None
        out.append((o, scopes.get(mod, {}).get(o.name)))
    return out


def has_scopes(trace: trace_events.Trace, scopes) -> bool:
    """Whether any op of the trace ran under a program scope."""
    return any(s is not None for d in trace.devices
               for _, s in op_scopes(d, scopes))


def scope_ms(trace: trace_events.Trace, scopes, keep) -> float:
    """Device milliseconds in the window, mean over chips, of the ops for
    which ``keep(op, scope)`` holds."""
    lo, hi = trace.window
    per_dev = [trace_events.busy_ns([o for o, s in op_scopes(d, scopes)
                                     if keep(o, s)], lo, hi)
               for d in trace.devices]
    return sum(per_dev) / len(per_dev) / 1e6


def stage_ms(trace: trace_events.Trace, keep) -> float | None:
    """``scope_ms`` under the scopes of this process's executables, or
    ``None`` where no op of the trace has a program scope."""
    found = live_scopes()
    if not has_scopes(trace, found):
        return None
    return scope_ms(trace, found, keep)


# --- host spans -------------------------------------------------------------

def host_spans(log_dir: str) -> list[trace_events.Op]:
    """The program's ``fmm:`` host spans of the trace under ``log_dir``,
    prefix stripped, by start."""
    import jax
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = [trace_events.Op(e.name[len(SPAN_PREFIX):], int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda s: s.start)


def span_gaps(ops, spans, lo: int, hi: int) -> dict[str, int]:
    """Device idle nanoseconds in ``[lo, hi]``, by the innermost host span
    open in them (of the open spans, the one that started last, the
    shorter of two that started together); ``"none"`` where no span is
    open."""
    out: dict[str, int] = collections.defaultdict(int)
    for gs, ge in trace_events.gaps(ops, lo, hi):
        cuts = sorted({gs, ge} | {t for s in spans for t in (s.start, s.end)
                                  if gs < t < ge})
        for a, b in zip(cuts, cuts[1:]):
            open_ = [s for s in spans if s.start <= a and b <= s.end]
            name = max(open_, key=lambda s: (s.start, -s.end)).name \
                if open_ else "none"
            out[name] += b - a
    return dict(out)

