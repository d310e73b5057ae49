"""The program's own spans and kernel names, read beside the device trace.

``heat_tpu.telemetry`` records a span around each phase of a user call
(``heat_tpu.<entry>.<phase>``, phases ``prepare``, ``launch``, ``readback``,
``wrap``) while a profile is being taken, on ``time.perf_counter_ns``: the
clock of ``loadgen.Call.t0/t1``. The harness's own spans lie in the trace on
the profiler's clock. The **join** puts each program span on the profiler's
clock with the offset of the harness call that holds it
(``trace.calls[i].start - window.calls[i].t0``; a lone call whose offset a
stalled thread spoiled takes its neighbours'), cuts the window's host
timeline into segments named by the innermost open span, and splits every
chip's idle intervals over them: ``idle_ms.<phase>`` and ``idle_ms.harness``.

A device plane's clock is not the host planes': on the v5e a program was
seen to start 0.5-0.8 ms *before* the ``launch`` span that enqueued it
(PERF.md, Findings, PR 24). Causality bounds the lag of each chip's line
from both sides: no benchmarked program starts before its ``launch`` span
does, and none ends after the harness call that waited for it returned. Each
chip's intervals are moved by the least lag inside those bounds (0 where 0
is allowed); bounds that contradict each other make the join unsound.

The kernels' events are found by the names the program gives its
``pallas_call``s (``lloyd_update``, ``euclid_tile``), whatever number the
compiler appends.

A program without spans or without those names (a parent commit) gives
``None`` everywhere: nothing here raises for what is not there.
"""

from __future__ import annotations

import bisect
import re
import statistics
from typing import Dict, List, Optional, Tuple

from chipbench import trace_reduce as tr_

PHASES = ("prepare", "launch", "readback", "wrap")
PROGRAM = "heat_tpu."
MAX_OFFSET_STEP_NS = 50_000.0
LLOYD_KERNEL = re.compile(r"^%lloyd_update(\.\d+)? ")
CDIST_KERNEL = re.compile(r"^%euclid_tile(\.\d+)? ")

Segment = Tuple[float, float, str]  # start, end, the innermost span's name


# -- the program's spans --------------------------------------------------------


def recorded_spans() -> Optional[List[dict]]:
    """The span records of the program in this process, or None where the
    program keeps none (``heat_tpu.telemetry.spans`` came with PR 24)."""
    try:
        from heat_tpu import telemetry

        return [s for s in telemetry.spans() if s.get("kind") == "span"]
    except (ImportError, AttributeError):
        return None


def dropped_spans() -> float:
    from heat_tpu import telemetry

    return telemetry.get_registry().counters.get("spans_dropped", 0)


def phase_of(name: str) -> Optional[str]:
    """``prepare`` for ``heat_tpu.kmeans.fit.prepare``; None for a root span
    or another program's name."""
    last = name.rsplit(".", 1)[-1]
    return last if name.startswith(PROGRAM) and last in PHASES else None


def bucket_of(name: str) -> str:
    """Which number a segment's idle time goes to."""
    if not name or name.startswith(tr_.SPAN_PREFIX):
        return "harness"
    return phase_of(name) or "root_only"


# -- the join -------------------------------------------------------------------


def offsets(calls, trace_calls, max_step_ns: float = MAX_OFFSET_STEP_NS):
    """``(joined, why, repaired)``: per harness call, profiler clock minus
    host clock (ns), or a reason why the two sides cannot be joined.

    The harness enters its annotation and then reads the host's clock; a
    thread stalled between the two leaves one call's offset off by the stall
    while its neighbours agree to a microsecond. Such a call (more than
    ``max_step_ns`` from the median of the five offsets around it) takes that
    median; more than one in a hundred of them (one is always allowed) is no
    stall any more, and the join is unsound."""
    if len({c.client for c in calls}) > 1:
        return None, "more than one client: calls overlap on the host", 0
    if len(calls) != len(trace_calls):
        return None, f"{len(calls)} calls timed, {len(trace_calls)} chipbench.call spans traced", 0
    if not calls:
        return None, "no call in the window", 0
    host = sorted(calls, key=lambda c: c.t0)
    raw = [t[0] - c.t0 * 1e9 for c, t in zip(host, sorted(trace_calls))]
    out, repaired = [], 0
    for i, o in enumerate(raw):
        near = statistics.median(raw[max(0, i - 2): i + 3])
        repaired += abs(o - near) > max_step_ns
        out.append(near if abs(o - near) > max_step_ns else o)
    if repaired > max(1, len(raw) // 100):
        step = max(abs(b - a) for a, b in zip(raw, raw[1:]))
        return None, f"offsets of neighbouring calls differ by {step / 1e3:.1f} us", repaired
    return list(zip(host, out)), None, repaired


def on_profiler_clock(spans: List[dict], joined) -> List[tr_.Event]:
    """Each program span that began inside a harness call, as an event on
    the profiler's clock."""
    out, i = [], 0
    for s in sorted(spans, key=lambda s: s["t0_ns"]):
        while i < len(joined) and joined[i][0].t1 * 1e9 < s["t0_ns"]:
            i += 1
        if i < len(joined) and joined[i][0].t0 * 1e9 <= s["t0_ns"]:
            off = joined[i][1]
            out.append(tr_.Event(s["name"], s["t0_ns"] + off, s["t1_ns"] + off))
    return out


def segments(events: List[tr_.Event], lo: float, hi: float) -> List[Segment]:
    """Cut [lo, hi] into consecutive pieces named by the innermost event
    open there ("" where none is). The events of one thread nest; a child is
    held to its parent's end, so a microsecond of clock offset cannot leave
    a piece named twice."""
    out: List[Segment] = []
    stack: List[Tuple[float, str]] = []  # (end, name that idle time goes under)
    at = lo

    def piece(upto: float) -> None:
        nonlocal at
        upto = min(upto, hi)
        if upto > at:
            out.append((at, upto, stack[-1][1] if stack else ""))
            at = upto

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            piece(stack[-1][0])
            stack.pop()

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        if e.end <= lo or e.start >= hi:
            continue
        close(e.start)
        piece(e.start)
        end, name = e.end, e.name
        if stack:
            end = min(end, stack[-1][0])
            # a span that is no phase (another subsystem's) inside a phase
            # belongs to that phase
            if phase_of(stack[-1][1]) and not phase_of(name):
                name = stack[-1][1]
        stack.append((end, name))
    close(hi)
    piece(hi)
    return out


def split(intervals, segs: List[Segment]) -> Dict[str, float]:
    """Length of ``intervals`` (sorted, disjoint) under each segment name."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in intervals:
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            covered = min(b, segs[k][1]) - max(a, segs[k][0])
            if covered > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + covered
            k += 1
    return out


def name_at(segs: List[Segment], t: float) -> str:
    lo, hi = 0, len(segs)
    while lo < hi:
        mid = (lo + hi) // 2
        if segs[mid][1] <= t:
            lo = mid + 1
        else:
            hi = mid
    return segs[lo][2] if lo < len(segs) and segs[lo][0] <= t else ""


def _in_window(tr, events):
    lo, hi = tr.window
    return [e for e in events if e.end > lo and e.start < hi]


def lag_bounds(tr, device, launches, modules_rx):
    """(least, most) nanoseconds by which this chip's line runs ahead of the
    host's: the i-th program named ``modules_rx`` belongs to the i-th
    ``launch`` span, starts no earlier than it and ends no later than the
    harness call around it. None where the two do not pair up."""
    mods = sorted((m for m in _in_window(tr, device.modules) if modules_rx.search(m.name)),
                  key=lambda m: m.start)
    if not mods or len(mods) != len(launches):
        return None
    calls = sorted(tr.calls)
    starts = [c[0] for c in calls]
    least, most = float("-inf"), float("inf")
    for l, m in zip(launches, mods):
        least = max(least, l.start - m.start)
        i = bisect.bisect_right(starts, l.start) - 1
        if i >= 0 and l.start < calls[i][1]:
            most = min(most, calls[i][1] - m.end)
    return least, most


def _join(reading) -> Optional[Dict[str, float]]:
    """ms a call, mean over the chips, of device idle time by bucket (the four
    phases, ``harness``, ``root_only``); None, and a note, where unsound."""
    tr, notes = reading.trace, reading.notes
    if tr is None or not tr.calls:
        return None
    spans = recorded_spans()
    if spans is None:
        return None  # the program has no span buffer: nothing to say

    def unsound(why: str) -> None:
        notes["idle_join"] = why

    joined, why, repaired = offsets(reading.window.calls, tr.calls)
    if why is not None:
        return unsound(why)
    if dropped_spans():
        return unsound(f"the span buffer dropped {dropped_spans():.0f} records")
    mine = on_profiler_clock(spans, joined)
    if not mine:
        return unsound("no program span inside a call")
    launches = sorted((e for e in mine if phase_of(e.name) == "launch"), key=lambda e: e.start)
    modules_rx = re.compile(reading.config.get("roofline_modules", "$^"))
    lags, bounds = [], []
    for d in tr.devices:
        b = lag_bounds(tr, d, launches, modules_rx)
        if b is not None and b[0] > b[1]:
            return unsound(f"{d.plane}: a program starts {b[0] / 1e3:.1f} us before its launch "
                           f"span and ends {-b[1] / 1e3:.1f} us after its call returned")
        bounds.append(b)
        lags.append(0.0 if b is None else min(max(0.0, b[0]), b[1]))

    # the host's segments reach past the window on both sides, so that a
    # chip's moved intervals keep every nanosecond of their length
    lo, hi = tr.window
    reach = max(abs(x) for x in lags) + 1.0
    harness = [s for s in tr.spans if s.name != tr_.SPAN_PREFIX + "window"]
    segs = segments(harness + mine, lo - reach, hi + reach)
    per_ms = len(tr.devices) * len(tr.calls) * 1e6

    by_name: Dict[str, float] = {}
    for d, lag in zip(tr.devices, lags):
        moved = [(a + lag, b + lag) for a, b in tr_.gaps(d.busy, lo, hi)]
        for name, ns in split(moved, segs).items():
            by_name[name] = by_name.get(name, 0.0) + ns / per_ms
    ms = dict.fromkeys(PHASES + ("harness", "root_only"), 0.0)
    for name, v in by_name.items():
        ms[bucket_of(name)] += v

    first, lag = tr.devices[0], lags[0]
    longest = sorted(tr_.gaps(first.busy, lo, hi), key=lambda g: g[0] - g[1])[:10]
    started: Dict[str, int] = {}
    for e in first.modules:
        if lo <= e.start < hi:
            name = name_at(segs, e.start + lag) or "outside_any_span"
            started[name] = started.get(name, 0) + 1
    offs = [o for _, o in joined]
    notes.update(
        idle_under_root_only_ms=ms["root_only"],
        idle_ms_per_call=sum(ms.values()),
        idle_ms_by_span={k or "outside_any_span": v for k, v in sorted(by_name.items())},
        longest_gaps=[
            [max(split([(g[0] + lag, g[1] + lag)], segs).items(), key=lambda kv: kv[1])[0]
             or "outside_any_span", (g[1] - g[0]) / 1e6]
            for g in longest
        ],
        programs_started_under={k: v / len(tr.calls) for k, v in sorted(started.items())},
        spans_per_call=len(mine) / len(tr.calls),
        join_offset_spread_us=(max(offs) - min(offs)) / 1e3,
        join_calls_repaired=repaired,
        device_lag_us=[x / 1e3 for x in lags],
        device_lag_bounds_us=[None if b is None else [b[0] / 1e3, b[1] / 1e3] for b in bounds],
    )
    return ms


def idle_ms(reading, bucket: str) -> Optional[float]:
    """Per call, mean over the chips: device idle time under the spans of
    ``bucket`` (a phase, or ``harness``). None where the join is not sound;
    ``reading.notes['idle_join']`` then says why."""
    if not hasattr(reading, "_idle_ms"):
        reading._idle_ms = _join(reading)
    return None if reading._idle_ms is None else reading._idle_ms[bucket]


# -- kernels by name --------------------------------------------------------------


def kernel_events(tr, device, rx) -> List[tr_.Event]:
    return [e for e in _in_window(tr, device.ops) if rx.search(e.name)]


def kernel_ms(reading, rx, per: str) -> Optional[float]:
    """Device time of the events named ``rx``: their mean duration
    (``per='event'``) or their sum a call (``per='call'``), over the chips."""
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    found = [e for d in tr.devices for e in kernel_events(tr, d, rx)]
    if not found:
        return None
    total = sum(e.dur for e in found)
    n = len(found) if per == "event" else len(tr.devices) * len(tr.calls)
    return total / n / 1e6


def kernel_events_per_call(reading, rx) -> Optional[float]:
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    n = sum(len(kernel_events(tr, d, rx)) for d in tr.devices)
    return n / len(tr.devices) / len(tr.calls) if n else None


def around_kernel(reading, rx) -> Optional[Dict[str, float]]:
    """Per call, mean over the chips, in ms: of the programs that the
    configuration's ``roofline_modules`` names, the device-busy time
    ``before`` the first kernel event, ``after`` the last one, ``between``
    them outside any kernel event, the kernels' own (``kernel``) and the
    whole ``program``. Programs without a kernel event are left out."""
    tr = reading.trace
    if tr is None or not tr.calls:
        return None
    mod_rx = re.compile(reading.config["roofline_modules"])
    out = dict.fromkeys(("before", "after", "between", "kernel", "program"), 0.0)
    seen = False
    for d in tr.devices:
        kernels = kernel_events(tr, d, rx)
        for m in _in_window(tr, d.modules):
            if not mod_rx.search(m.name):
                continue
            mine = [k for k in kernels if m.start <= k.start < m.end]
            if not mine:
                continue
            seen = True
            first, last = min(k.start for k in mine), max(k.end for k in mine)
            busy = lambda a, b: tr_.length(tr_.clip(d.busy, a, b))
            kernel = sum(k.dur for k in mine)
            out["before"] += busy(m.start, first)
            out["after"] += busy(last, m.end)
            out["between"] += busy(first, last) - kernel
            out["kernel"] += kernel
            out["program"] += m.dur
    if not seen:
        return None
    scale = len(tr.devices) * len(tr.calls) * 1e6
    return {k: v / scale for k, v in out.items()}
