"""heat_tpu.telemetry — runtime observability for distributed ops.

The reference framework's communication was explicit (every byte moved
through a hand-written MPI call, reference heat/core/communication.py), so
observability came for free by reading the source. On TPU the collectives
are emitted invisibly by XLA from sharding annotations; this package is the
measurement substrate that makes them visible again:

* a process-global :class:`Telemetry` registry — counters plus a JSON-lines
  event sink — enabled via :func:`enable` or ``HEAT_TPU_TELEMETRY=1``
  (sink path via ``HEAT_TPU_TELEMETRY_SINK``);
* an op/**span** API (``with span("resplit", bytes=...)``), on when
  telemetry is enabled *or* a ``jax.profiler`` session is live. A recorded
  span enters a ``jax.profiler.TraceAnnotation`` of its name (so it lies in
  the profile, on the profiler's clock, beside the device lines) and leaves
  one record in a bounded buffer (:func:`spans`). Enabled explicitly, spans
  also `jax.block_until_ready` their registered outputs before stopping
  the clock, so a span measures device work, not Python dispatch; under a
  profile alone they never block (a profile shows the program as it runs
  without one);
* **compile-time accounting** kept separate from execute time:
  :func:`measure_compile` times the AOT ``jit(f).lower(...).compile()``
  path for pure jitted functions, and :class:`CompileWatcher` accumulates
  the XLA trace/lower/backend-compile durations (via `jax.monitoring`)
  that occur inside arbitrary host-side code — the same quantities the AOT
  path measures, attributed to a first call;
* an analytic **collective cost model** (:mod:`.collectives`) giving
  bytes-on-the-wire for relayouts and the hand-scheduled kernels;
* an **HLO collective auditor** (:mod:`.hlo`) that closes the
  predicted-vs-emitted loop: lower-and-compile a jitted computation,
  parse the ground-truth collectives XLA emitted, and flag drift against
  the analytic prediction (``audit=`` on resplit/qr/cdist, or globally
  via ``HEAT_TPU_HLO_AUDIT=1``);
* per-device **memory watermarks** (:mod:`.memory`);
* a :mod:`.report` summarizer aggregating events into the JSON shape the
  benchmark harness emits;
* a :mod:`.trace` exporter turning the event stream into
  Chrome-trace/Perfetto JSON (:func:`export_trace`), plus a
  ``python -m heat_tpu.telemetry.audit`` CLI.

Off (the default: telemetry disabled and no profiler session), every hook
compiles down to one module-flag check plus, for ``span()``, one
``TraceAnnotation.is_enabled()`` call: ``span()`` returns a shared no-op
context manager, call sites skip field construction, and no listener work
is done — the overhead budget is "not measurable" (<2% on the tier-1
suite, pinned by the acceptance run).
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import defaultdict, deque
from typing import Any, Dict, IO, Iterable, List, Optional, Union

import jax

from heat_tpu import _knobs as knobs

from . import collectives  # noqa: F401  (re-exported submodule)

__all__ = [
    "Telemetry",
    "CompileWatcher",
    "enable",
    "disable",
    "enabled",
    "flush",
    "get_registry",
    "span",
    "spans",
    "trace_event",
    "op_cost",
    "measure_compile",
    "collectives",
    "hlo",
    "memory",
    "report",
    "trace",
    "export_trace",
]

# Module-level fast path: every instrumentation site guards on this single
# boolean, so the disabled overhead is one attribute load + branch.
_ENABLED = False

_REGISTRY: Optional["Telemetry"] = None
_REGISTRY_LOCK = threading.Lock()

# Span nesting is tracked per thread (spans opened on worker threads must
# not see each other as parents).
_STATE = threading.local()


def _stack() -> list:
    s = getattr(_STATE, "stack", None)
    if s is None:
        s = _STATE.stack = []
    return s


class Telemetry:
    """Process-global registry: counters, high-water marks, and an event
    stream with an optional JSON-lines sink.

    Events are dicts with at least ``ts`` (unix seconds), ``kind`` and
    ``name``; spans add ``seconds``, ``depth``, ``parent`` and their user
    fields. The in-memory list and the sink receive identical records.
    Spans reach this stream only in an explicit session (:func:`enable`);
    every recorded span, under a profile too, is in the bounded buffer that
    :func:`spans` reads.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = defaultdict(float)
        self.watermarks: Dict[str, float] = {}
        self.events: List[dict] = []
        self._sink: Optional[IO[str]] = None
        self._sink_path: Optional[str] = None
        self._owns_sink = False

    # -- sink ----------------------------------------------------------------

    def attach_sink(self, sink: Union[str, IO[str]]) -> None:
        """Attach a JSONL sink: a path (opened in append mode, owned and
        closed by the registry) or any writable text file object."""
        self.close_sink()
        if isinstance(sink, (str, os.PathLike)):
            self._sink = open(sink, "a")
            self._sink_path = os.fspath(sink)
            self._owns_sink = True
        else:
            self._sink = sink
            self._sink_path = getattr(sink, "name", None)
            self._owns_sink = False

    def close_sink(self) -> None:
        if self._sink is not None and self._owns_sink:
            try:
                self._sink.close()
            except OSError:
                pass
        self._sink = None
        self._sink_path = None
        self._owns_sink = False

    @property
    def sink_path(self) -> Optional[str]:
        return self._sink_path

    # -- recording -----------------------------------------------------------

    def emit(self, kind: str, name: str, **fields: Any) -> dict:
        """Record one event (and write it to the sink, if attached)."""
        ev = {"ts": time.time(), "kind": kind, "name": name}
        ev.update(fields)
        with self._lock:
            self.events.append(ev)
            if self._sink is not None:
                try:
                    self._sink.write(json.dumps(ev, default=str) + "\n")
                    self._sink.flush()
                except (OSError, ValueError):
                    # a dead sink must never take the workload down —
                    # detach it fully (close an owned handle, clear the
                    # path) so no fd leaks and snapshot() stops naming a
                    # sink that no longer records
                    if self._owns_sink:
                        try:
                            self._sink.close()
                        except OSError:
                            pass
                    self._sink = None
                    self._sink_path = None
                    self._owns_sink = False
        return ev

    def add(self, counter: str, delta: float = 1.0) -> None:
        with self._lock:
            self.counters[counter] += delta

    def high_water(self, key: str, value: float) -> None:
        """Record ``value`` if it exceeds the stored mark for ``key``."""
        with self._lock:
            if value > self.watermarks.get(key, float("-inf")):
                self.watermarks[key] = value

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": dict(self.counters),
                "watermarks": dict(self.watermarks),
                "n_events": len(self.events),
                "sink": self._sink_path,
            }

    def clear(self, kinds: Optional[Iterable[str]] = None) -> None:
        """Drop counters, watermarks and in-memory events (the sink file, if
        any, is left as-is — it is an append-only log). With ``kinds``,
        drop only in-memory events of those kinds and keep everything else
        — e.g. ``clear(kinds=("span",))`` discards warmup spans while
        preserving the ``compile`` and ``collective_trace`` records that
        only fire while a program is first traced."""
        with self._lock:
            if kinds is not None:
                drop = set(kinds)
                self.events[:] = [
                    e for e in self.events if e.get("kind") not in drop
                ]
                return
            self.counters.clear()
            self.watermarks.clear()
            self.events.clear()


def get_registry() -> Telemetry:
    global _REGISTRY
    if _REGISTRY is None:
        with _REGISTRY_LOCK:
            if _REGISTRY is None:
                _REGISTRY = Telemetry()
    return _REGISTRY


# -- enable / disable ---------------------------------------------------------


def enabled() -> bool:
    """Whether telemetry is recording. Instrumentation sites branch on this
    before building field dicts, so the disabled cost is one check."""
    return _ENABLED


def enable(sink: Union[str, IO[str], None] = None) -> Telemetry:
    """Turn recording on. ``sink`` (or ``HEAT_TPU_TELEMETRY_SINK``) names a
    JSONL file to stream events to; with neither, events accumulate in
    memory only. Returns the registry."""
    global _ENABLED
    reg = get_registry()
    if sink is None:
        sink = knobs.raw("HEAT_TPU_TELEMETRY_SINK") or None
    if sink is not None:
        try:
            reg.attach_sink(sink)
        except OSError as e:
            # same contract as a sink dying mid-run: telemetry must never
            # take the workload down (enable() runs at `import heat_tpu`
            # when HEAT_TPU_TELEMETRY=1) — record in memory only
            import warnings

            warnings.warn(
                f"heat_tpu.telemetry: cannot open sink {sink!r} ({e}); "
                "recording in memory only"
            )
    _install_monitoring_listener()
    _install_atexit()
    _ENABLED = True
    return reg


def disable() -> None:
    """Turn recording off and close an owned sink. Counters and in-memory
    events are kept (call ``get_registry().clear()`` to drop them)."""
    global _ENABLED
    _ENABLED = False
    get_registry().close_sink()


# -- crash safety --------------------------------------------------------------
# Counters and watermarks live only in process memory: a hard abort used to
# lose them entirely (events stream to the sink per emit, but the aggregate
# state did not). flush() writes one "final" record carrying the full
# counter/watermark snapshot; it runs at interpreter exit (atexit, installed
# by enable()) and on every resilience escalation (guard.py), so the state
# of a dying run is on disk before the stack unwinds.

_atexit_installed = False


def flush(reason: str = "flush") -> Optional[dict]:
    """Write a ``final`` event carrying the current counter/watermark
    snapshot to the registry (and hence the JSONL sink, which is flushed
    per emit). Safe to call repeatedly; no-op when disabled."""
    if not _ENABLED:
        return None
    reg = get_registry()
    snap = reg.snapshot()
    return reg.emit(
        "final", reason,
        counters=snap["counters"], watermarks=snap["watermarks"],
    )


def _install_atexit() -> None:
    global _atexit_installed
    if _atexit_installed:
        return
    import atexit

    atexit.register(_atexit_flush)
    _atexit_installed = True


def _atexit_flush() -> None:  # pragma: no cover — exercised via subprocess
    try:
        if _ENABLED and get_registry()._sink is not None:
            flush("atexit")
        get_registry().close_sink()
    except Exception:
        pass


# -- span API -----------------------------------------------------------------


class _NoopSpan:
    """Shared do-nothing span returned while nothing records."""

    __slots__ = ()
    recording = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add_fields(self, **fields):
        return self

    def output(self, value):
        return value


_NOOP_SPAN = _NoopSpan()


# Span records: a bounded buffer, oldest dropped first. 32,768 records hold
# a 20 s profile of a 2.5 ms call at four spans a call; a drop is counted
# (registry counter ``spans_dropped``), never silent.
SPAN_BUFFER = 32768
_SPANS: "deque[dict]" = deque(maxlen=SPAN_BUFFER)
_SPANS_LOCK = threading.Lock()
_SPAN_IDS = itertools.count(1)  # next() is atomic under the GIL

_profiling = jax.profiler.TraceAnnotation.is_enabled


def _keep(record: dict) -> None:
    with _SPANS_LOCK:
        dropped = len(_SPANS) == _SPANS.maxlen
        _SPANS.append(record)
    if dropped:
        get_registry().add("spans_dropped", 1)


def spans(clear: bool = False) -> List[dict]:
    """The recorded spans, oldest first (at most ``SPAN_BUFFER``; the
    registry counter ``spans_dropped`` says how many older ones went).
    ``clear=True`` empties the buffer after reading it."""
    with _SPANS_LOCK:
        out = list(_SPANS)
        if clear:
            _SPANS.clear()
    return out


class Span:
    """A timed region of host code.

    On entry it enters a ``jax.profiler.TraceAnnotation`` of the same name;
    on exit it leaves one record in the span buffer (:func:`spans`):
    ``name``, ``t0_ns``/``t1_ns`` (``time.perf_counter_ns``), ``id``,
    ``parent_id``, ``root_id`` (the outermost open span of the thread: the
    spans of one user call share it), ``tid``, the older ``seconds``,
    ``depth``, ``parent``, ``start_ts`` and the user fields.

    With telemetry enabled explicitly the span also has the older
    async-correct semantics: register device outputs with :meth:`output`
    and the span calls ``jax.block_until_ready`` on them **before** stopping
    the clock, so ``seconds`` covers the dispatched device work; the record
    also goes to the registry's event stream and counters. A span that
    records only because a profile is being taken never blocks. Compile
    time is deliberately NOT separated here (a span times what actually
    happened); use :func:`measure_compile`/:class:`CompileWatcher` for the
    compile/execute split.
    """

    __slots__ = (
        "name", "fields", "id", "parent_id", "root_id",
        "_explicit", "_outputs", "_annotation", "_t0_ns", "_wall0",
    )
    recording = True  # the shared no-op span says False

    def __init__(self, name: str, fields: Dict[str, Any]):
        self.name = name
        self.fields = fields
        self.id = next(_SPAN_IDS)
        self.parent_id: Optional[int] = None
        self.root_id = self.id
        self._explicit = _ENABLED
        self._outputs: List[Any] = []
        self._annotation = jax.profiler.TraceAnnotation(name)
        self._t0_ns = 0
        self._wall0 = 0.0

    def add_fields(self, **fields: Any) -> "Span":
        self.fields.update(fields)
        return self

    def output(self, value):
        """Register a device value to block on at exit (telemetry enabled
        explicitly; otherwise nothing is registered); returns it."""
        if self._explicit:
            self._outputs.append(value)
        return value

    def __enter__(self) -> "Span":
        stack = _stack()
        if stack:
            self.parent_id, self.root_id = stack[-1].id, stack[-1].root_id
        stack.append(self)
        # wall-clock start recorded alongside the perf_counter duration
        # clock: deriving the start as `ts - seconds` would mix the two
        # clocks and break nesting containment in the trace export at
        # µs scale (trace.py anchors slices on start_ts)
        self._wall0 = time.time()
        self._annotation.__enter__()
        self._t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None and self._outputs:
            jax.block_until_ready(self._outputs)
        t1_ns = time.perf_counter_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        dt = (t1_ns - self._t0_ns) / 1e9
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        parent = stack[-1].name if stack else None
        old = {"seconds": dt, "start_ts": self._wall0}
        if exc_type is None:
            kind = "span"
            old.update(depth=len(stack), parent=parent)
        else:
            kind = "span_error"
            old["error"] = repr(exc)
        _keep({
            "ts": time.time(), "kind": kind, "name": self.name, **old,
            "id": self.id, "parent_id": self.parent_id, "root_id": self.root_id,
            "tid": threading.get_ident(), "t0_ns": self._t0_ns, "t1_ns": t1_ns,
            **self.fields,
        })
        if self._explicit:
            # the event stream and the counters are for the readers of an
            # explicit session (report, trace export, the JSONL sink)
            reg = get_registry()
            if exc_type is None:
                reg.add(f"span.{self.name}.count", 1)
                reg.add(f"span.{self.name}.seconds", dt)
                b = self.fields.get("bytes")
                if b:
                    reg.add(f"span.{self.name}.bytes", b)
            reg.emit(kind, self.name, **old, **self.fields)
        return False


def span(name: str, **fields: Any):
    """Open a span (context manager). Records while telemetry is enabled or
    a ``jax.profiler`` session is live; otherwise returns a shared no-op
    object — zero allocation, fields ignored."""
    if not (_ENABLED or _profiling()):
        return _NOOP_SPAN
    return Span(name, fields)


def op_cost(cost_fn, *cost_args, audit: bool = False, use_global: bool = True):
    """Shared preamble for instrumented op sites; returns
    ``(cost, fields, do_audit)``:

    * ``cost`` — the analytic :class:`~.collectives.CollectiveCost`,
      computed only when recording or auditing will consume it (None on
      the cold path, preserving the one-flag-check disabled contract);
    * ``fields`` — the span field dict (``cost.as_fields()`` when
      recording, ``{}`` otherwise);
    * ``do_audit`` — whether this call should run the HLO audit:
      explicit ``audit=True``, plus the global ``HEAT_TPU_HLO_AUDIT``
      opt-in unless ``use_global=False`` (the ``_relayout`` primitive
      opts out so an op-level audit is never doubled).

    Every instrumented site goes through here so the flag semantics live
    in ONE place — a new op site cannot silently pick a diverged variant.
    """
    do_audit = audit or (use_global and hlo.audit_enabled())
    cost = cost_fn(*cost_args) if (_ENABLED or do_audit) else None
    fields = cost.as_fields() if (_ENABLED and cost is not None) else {}
    return cost, fields, do_audit


def trace_event(name: str, **fields: Any) -> None:
    """Record that a collective was *traced* (a `shard_map`/jit cache miss
    compiled a program containing it). Fired from the communication layer's
    collective wrappers — trace-time only, so a hot cached program emits
    nothing. No-op when disabled."""
    if not _ENABLED:
        return
    reg = get_registry()
    reg.add(f"traced.{name}", 1)
    reg.emit("collective_trace", name, **fields)


# -- compile-time accounting --------------------------------------------------

# jax.monitoring has no unregister API, so one process-lifetime listener is
# installed on first use and gated on the enabled flag / active watchers.
_MONITORING_PREFIX = "/jax/core/compile/"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_listener_installed = False
_ACTIVE_WATCHERS: List["CompileWatcher"] = []


def _install_monitoring_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    try:
        from jax import monitoring as _monitoring

        _monitoring.register_event_duration_secs_listener(_on_duration_event)
        _listener_installed = True
    except Exception:  # pragma: no cover — very old jax without monitoring
        pass


def _on_duration_event(name: str, secs: float, **kw) -> None:
    if not name.startswith(_MONITORING_PREFIX):
        return
    stage = name[len(_MONITORING_PREFIX):]  # e.g. "backend_compile_duration"
    for w in _ACTIVE_WATCHERS:
        w._record(stage, secs)
    if not _ENABLED:
        return
    reg = get_registry()
    reg.add(f"compile.{stage}", secs)
    if name == _BACKEND_COMPILE_EVENT:
        reg.emit("compile", "backend_compile", seconds=secs)


class CompileWatcher:
    """Accumulate XLA compile-pipeline durations (jaxpr trace, MLIR
    lowering, backend compile — the same stages ``jit(f).lower(x).compile()``
    runs ahead of time) that occur while the context is open.

    For host-side thunks that cannot be AOT-lowered as a whole (e.g. a
    benchmark ``fit()`` mixing device ops with host logic), wrapping the
    first call in a watcher yields the compile seconds *separately* from
    the wall clock, instead of the reference harness's compile+execute
    blend. Works whether or not telemetry recording is enabled.
    """

    def __init__(self):
        self.stages: Dict[str, float] = defaultdict(float)
        self.counts: Dict[str, int] = defaultdict(int)
        self.events = 0

    @property
    def seconds(self) -> float:
        """Total compile-pipeline seconds observed (all stages)."""
        return sum(self.stages.values())

    @property
    def backend_seconds(self) -> float:
        return self.stages.get("backend_compile_duration", 0.0)

    @property
    def backend_compiles(self) -> int:
        """Number of backend-compile events in the window — i.e. how many
        distinct XLA programs were built (the fusion microbenchmark's
        dispatch-count oracle: an N-op chain fused into one program shows
        1 here where eager shows ~N)."""
        return self.counts.get("backend_compile_duration", 0)

    def _record(self, stage: str, secs: float) -> None:
        self.stages[stage] += secs
        self.counts[stage] += 1
        self.events += 1

    def __enter__(self) -> "CompileWatcher":
        _install_monitoring_listener()
        _ACTIVE_WATCHERS.append(self)
        return self

    def __exit__(self, *exc):
        try:
            _ACTIVE_WATCHERS.remove(self)
        except ValueError:
            pass
        return False


def measure_compile(fn, *args, **kwargs):
    """AOT-compile ``fn(*args, **kwargs)`` and time it: returns
    ``(seconds, compiled)`` where ``compiled`` is the executable from
    ``jit(fn).lower(...).compile()``. The clock covers trace + lower +
    backend compile and **no execution** — the honest ``compile_seconds``
    for a pure jittable function (first-full-call timing, by contrast,
    blends in one execution). Emits a ``compile`` event when enabled.

    ``fn`` may be a plain callable or an already-jitted function.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kwargs).compile()
    dt = time.perf_counter() - t0
    if _ENABLED:
        get_registry().emit(
            "compile", getattr(fn, "__name__", repr(fn)), seconds=dt, mode="aot"
        )
    return dt, compiled


# memory/report/hlo/trace/cluster import the registry machinery above,
# so they load last.
from . import memory  # noqa: E402,F401
from . import report  # noqa: E402,F401
from . import hlo  # noqa: E402,F401
from . import trace  # noqa: E402,F401
from . import cluster  # noqa: E402,F401

export_trace = trace.export_trace
SLO = cluster.SLO
summarize_cluster = cluster.summarize_cluster

# Environment activation: HEAT_TPU_TELEMETRY=1 turns recording on at import
# (heat_tpu/__init__ imports this package, so `import heat_tpu` suffices).
if knobs.raw("HEAT_TPU_TELEMETRY", "").strip().lower() in (
    "1", "true", "yes", "on",
):
    enable()
