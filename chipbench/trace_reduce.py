"""From a profiler trace (``.xplane.pb``) to intervals, per-name device time
and gaps.

A trace holds planes (one per device, some for the host), each with lines
of events. On a device plane one line carries the operations (nested: a
``while`` holds the operations of its body) and one the programs
("modules") that were started. Which planes and lines those are is data of
the peaks table (``trace`` of the device's entry), not of this file.

All times are nanoseconds on the profiler's clock. The harness puts its own
spans (``chipbench.window``, ``chipbench.call``, ``chipbench.between_calls``)
on that clock with ``jax.profiler.TraceAnnotation``; the reduction reads the
window and the calls from them, so nothing depends on matching the host's
clock to the profiler's.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "chipbench."


@dataclass(frozen=True)
class Event:
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


Trace = Dict[str, Dict[str, List[Event]]]  # plane name -> line name -> events

_OPCODE = re.compile(r"[})\]] ([a-z][\w\-]*)\(")


def short_name(name: str) -> str:
    """A TPU trace names an operation by its whole HLO line, ``%copy =
    f32[...] copy(f32[...] %xb.1)``: keep the result's name and the
    operation, ``%copy copy``. Any other name is kept as it is."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    op = _OPCODE.search(rest)
    return f"{head} {op.group(1)}" if op else head


def _planes(data) -> Trace:
    trace: Trace = {}
    for plane in data.planes:
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                events.append(Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)))
    return trace


def load(path: str) -> Trace:
    """Read one ``.xplane.pb``, or the newest one under a profiler log
    directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return _planes(ProfileData.from_file(path))


def load_text(text: str) -> Trace:
    """Read an XSpace written as a text proto (the recorded trace the tests
    keep)."""
    from jax.profiler import ProfileData

    return _planes(ProfileData.from_text_proto(text))


# -- interval arithmetic -------------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in merged if min(b, hi) > max(a, lo)]


def length(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def gaps(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What ``merged`` (sorted, disjoint) leaves uncovered of [lo, hi]."""
    out, at = [], lo
    for a, b in clip(merged, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of ``a`` that ``b`` does not cover (both sorted, disjoint)."""
    out: List[Interval] = []
    for lo, hi in a:
        out.extend(gaps(b, lo, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Time by event name with each event's children taken out of it: an
    operation that holds others (a ``while``, a ``call``) keeps only what
    its body does not account for."""
    out: Dict[str, float] = {}
    stack: List[list] = []  # [event, time covered by children]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end <= upto:
            ev, covered = stack.pop()
            out[ev.name] = out.get(ev.name, 0.0) + max(ev.dur - covered, 0.0)
            if stack:
                stack[-1][1] += ev.dur

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        close(ev.start)
        stack.append([ev, 0.0])
    close(float("inf"))
    return out


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that hold no other event: the operations that run, without
    the ``while`` or ``call`` that holds them."""
    out: List[Event] = []
    ordered = sorted(events, key=lambda e: (e.start, -e.end))
    for i, ev in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or nxt.start >= ev.end or nxt.end > ev.end:
            out.append(ev)
    return out


# -- the reduced trace ---------------------------------------------------------


@dataclass
class Device:
    plane: str
    ops: List[Event]
    modules: List[Event]
    busy: List[Interval]  # union of the operations' intervals


@dataclass
class Reduced:
    window: Interval
    calls: List[Interval]
    spans: List[Event]  # the harness's own spans, by start
    devices: List[Device]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        lo, hi = self.window
        return sum(length(clip(d.busy, lo, hi)) for d in self.devices) / len(self.devices) / 1e9

    def module_time(self, pattern: str) -> float:
        """Nanoseconds, summed over devices, of the programs in the window
        whose name matches ``pattern``."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return sum(
            min(e.end, hi) - max(e.start, lo)
            for d in self.devices for e in d.modules
            if rx.search(e.name) and e.end > lo and e.start < hi
        )

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The operations that took most device time in the window, as
        (name, seconds summed over devices), children taken out."""
        lo, hi = self.window
        total: Dict[str, float] = {}
        for d in self.devices:
            inside = [e for e in d.ops if e.end > lo and e.start < hi]
            for name, ns in self_times(inside).items():
                total[name] = total.get(name, 0.0) + ns
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [(short_name(name), ns / 1e9) for name, ns in ranked]

    def top_gaps(self, n: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of the first device in the window, each
        named by the harness span that covers most of it."""
        lo, hi = self.window
        found = sorted(gaps(self.devices[0].busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
        return [(self._span_over(a, b), (b - a) / 1e9) for a, b in found]

    def _span_over(self, a: float, b: float) -> str:
        best, most = "outside_any_span", 0.0
        for s in self.spans:
            covered = min(s.end, b) - max(s.start, a)
            if s.name != SPAN_PREFIX + "window" and covered > most:
                best, most = s.name, covered
        return best


def reduce(trace: Trace, rule: dict) -> Optional[Reduced]:
    """Pick the device planes and their lines by ``rule`` (``plane``,
    ``ops_line``, ``modules_line``: regular expressions) and the harness's
    spans from every other plane. None where the trace holds no device plane
    or no window span."""
    plane_rx, ops_rx = re.compile(rule["plane"]), re.compile(rule["ops_line"])
    mod_rx = re.compile(rule["modules_line"])
    devices, spans = [], []
    for pname in sorted(trace):
        lines = trace[pname]
        if plane_rx.search(pname):
            ops = [e for ln, evs in lines.items() if ops_rx.search(ln) for e in evs]
            mods = [e for ln, evs in lines.items() if mod_rx.search(ln) for e in evs]
            if ops:
                devices.append(Device(pname, ops, mods, union((e.start, e.end) for e in ops)))
        for evs in lines.values():
            spans.extend(e for e in evs if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda e: e.start)
    windows = [s for s in spans if s.name == SPAN_PREFIX + "window"]
    if not devices or not windows:
        return None
    calls = [(s.start, s.end) for s in spans if s.name == SPAN_PREFIX + "call"]
    return Reduced((windows[0].start, windows[0].end), calls, spans, devices)
