"""Profiler trace -> device op intervals and host spans, and their reductions.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU
each chip is a plane ``/device:TPU:<k>`` whose line ``XLA Ops`` holds one
event per executed HLO instruction, named by the instruction's text
(``%p2p_pallas_slab.1 = (f32[...]) custom-call(...)``); ``XLA Modules``
holds one event per program run.  (``Async XLA Ops``, the spans of
asynchronous copies and collectives, appears on the first chip only and is
not read.)  The harness's own
host spans are ``jax.profiler.TraceAnnotation`` events named
``bench:<span>`` on a host plane, on the same clock.

The reductions below are plain interval arithmetic over ``(start, end)``
nanoseconds, so they can be checked on synthetic event lists.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench:"
WINDOW_SPAN = "window"
COLLECTIVE_KINDS = ("all-gather", "collective-permute", "all-reduce",
                    "all-to-all", "reduce-scatter")


@dataclasses.dataclass(frozen=True)
class Op:
    """One event: ``name`` is the HLO instruction name (``fusion.3``) or
    the span name, ``kind`` the HLO opcode (``custom-call``, ``sort``),
    ``dims`` the dimensions of each array the instruction returns."""
    name: str
    start: int
    end: int
    kind: str = ""
    dims: tuple = ()

    @property
    def dur(self) -> int:
        return self.end - self.start

    def label(self) -> str:
        shapes = ", ".join("[" + ",".join(map(str, d)) + "]"
                           for d in self.dims)
        return f"{self.name} {self.kind} {shapes}".strip()


@dataclasses.dataclass
class Device:
    ops: list[Op]                      # ``XLA Ops``
    modules: list[Op] = dataclasses.field(default_factory=list)

    def in_module(self, ops, pattern: str) -> list[Op]:
        """The ``ops`` that start inside a run of a program whose name
        matches ``pattern``."""
        runs = [m for m in self.modules if re.search(pattern, m.name)]
        return [o for o in ops
                if any(m.start <= o.start < m.end for m in runs)]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    spans: list[Op]                    # host spans, prefix stripped
    window: tuple[int, int]

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


_HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = ")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def parse_hlo(text: str) -> tuple[str, str, tuple]:
    """``(name, opcode, result dims)`` of an ``XLA Ops`` event's HLO text;
    an event that is not HLO text keeps its text as name, with no opcode
    and no dims."""
    m = _HLO.match(text)
    if not m:
        return text, "", ()
    rest = text[m.end():]
    if rest.startswith("("):               # tuple shape: skip to its close
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                result, rest = rest[:i + 1], rest[i + 1:]
                break
    else:
        result, _, rest = rest.partition(" ")
    dims = tuple(tuple(int(x) for x in d.split(",") if x)
                 for d in _SHAPE.findall(result))
    kind = re.match(r"\s*([\w\-]+)", rest)
    return m.group("name"), kind.group(1) if kind else "", dims


def _ops(line) -> list[Op]:
    out = []
    for e in line.events:
        name, kind, dims = parse_hlo(e.name)
        out.append(Op(name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                      kind, dims))
    return sorted(out, key=lambda o: o.start)


def load(log_dir: str) -> Trace:
    """The trace that ``jax.profiler.start_trace(log_dir)`` wrote."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices, spans = {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            dev = Device(_ops(lines["XLA Ops"]) if "XLA Ops" in lines
                         else [])
            if "XLA Modules" in lines:
                dev.modules = [Op(e.name, int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                               for e in lines["XLA Modules"].events]
            devices[int(m.group(1))] = dev
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [Op(e.name[len(SPAN_PREFIX):], int(e.start_ns),
                             int(e.start_ns + e.duration_ns))
                          for e in ln.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {SPAN_PREFIX}{WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    if not devices:
        raise RuntimeError("the trace holds no /device:TPU plane")
    return Trace([devices[k] for k in sorted(devices)],
                 sorted((s for s in spans if s.name != WINDOW_SPAN),
                        key=lambda s: s.start),
                 (windows[0].start, windows[0].end))


# --- interval arithmetic ----------------------------------------------------

def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged ``(start, end)`` intervals, clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def spans_of(ops) -> list[tuple[int, int]]:
    return [(o.start, o.end) for o in ops]


def busy_ns(ops, lo: int, hi: int) -> int:
    """Time in ``[lo, hi]`` during which at least one op runs."""
    return total(union(spans_of(ops), lo, hi))


def gaps(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of ``[lo, hi]``: where no op runs."""
    out, t = [], lo
    for s, e in union(spans_of(ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def exposed_ns(targets, others, lo: int, hi: int) -> int:
    """Time in which some interval of ``targets`` is open and no op of
    ``others`` runs."""
    covered = union(targets, lo, hi)
    hidden = 0
    for s, e in union(spans_of(others), lo, hi):
        for cs, ce in covered:
            hidden += max(0, min(e, ce) - max(s, cs))
    return total(covered) - hidden


def kernel_ops(ops, word: str) -> list[Op]:
    """The custom calls (Pallas kernels) whose name contains ``word``."""
    return [o for o in ops if o.kind == "custom-call" and word in o.name]


def collective_flights(ops) -> list[tuple[int, int]]:
    """Intervals in which a collective is under way: each asynchronous
    ``<kind>-start.<k>`` to its ``<kind>-done.<k>``, and each synchronous
    collective op itself."""
    out, open_ = [], {}
    for o in sorted(ops, key=lambda o: o.start):
        if not is_collective(o):
            continue
        base, _, tail = o.name.partition(".")
        if base.endswith("-start"):
            open_[(base[:-len("-start")], tail)] = o.start
        elif base.endswith("-done"):
            start = open_.pop((base[:-len("-done")], tail), o.start)
            out.append((start, o.end))
        else:
            out.append((o.start, o.end))
    return out


def is_collective(op: Op) -> bool:
    return any(op.kind.startswith(k) or op.name.startswith(k)
               for k in COLLECTIVE_KINDS)


def label(interval, spans) -> str:
    """The host span that overlaps ``interval`` most, or ``none``."""
    s, e = interval
    best, name = 0, "none"
    for sp in spans:
        ov = min(e, sp.end) - max(s, sp.start)
        if ov > best:
            best, name = ov, sp.name
    return name


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (seconds per device, summed over
    the window; each named with its opcode and result dimensions) and the
    longest idle gaps, each named by the host span open during it."""
    lo, hi = trace.window
    ndev = len(trace.devices)
    per_op: dict[str, float] = {}
    idle = []
    for dev in trace.devices:
        for o in dev.ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                per_op[o.label()] = per_op.get(o.label(), 0.0) + d / 1e9 / ndev
        idle += [(e - s, label((s, e), trace.spans))
                 for s, e in gaps(dev.ops, lo, hi)]
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle.sort(key=lambda g: -g[0])
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[name, d / 1e9] for d, name in idle[:top]]}


def mean_busy_s(trace: Trace) -> float:
    lo, hi = trace.window
    return sum(busy_ns(d.ops, lo, hi) for d in trace.devices) \
        / len(trace.devices) / 1e9
