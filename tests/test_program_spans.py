"""The span API on the two benchmarked paths: when it records, what a record
holds, that the phases tile their root, that every record has its
``TraceAnnotation`` in the profile on the same instant, and that none of it
changes a result.

A CPU run: counts, structure and clocks. None of the times is a device
metric.
"""

import collections
import tempfile

import jax
import numpy as np
import pytest

import heat_tpu as ht
from chipbench import loadgen, program_spans, trace_reduce
from heat_tpu import telemetry

FIT = ["heat_tpu.kmeans.fit.prepare", "heat_tpu.kmeans.fit.launch",
       "heat_tpu.kmeans.fit.readback", "heat_tpu.kmeans.fit.wrap"]
CDIST = ["heat_tpu.cdist.prepare", "heat_tpu.cdist.launch", "heat_tpu.cdist.wrap"]
MIX = {"loop": "closed", "clients": 1}


@pytest.fixture(autouse=True)
def _quiet_again():
    was_on = telemetry.enabled()
    telemetry.spans(clear=True)
    yield
    if not was_on:
        telemetry.disable()
    telemetry.spans(clear=True)
    telemetry.get_registry().counters.pop("spans_dropped", None)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(24)
    x = ht.array(rng.normal(size=(40000, 16)).astype(np.float32), split=0)
    init = rng.normal(size=(4, 16)).astype(np.float32)
    return x, init


def _fit(data):
    x, init = data
    return ht.cluster.KMeans(n_clusters=4, init=ht.array(init), max_iter=30, tol=-1.0).fit(x)


def _cdist(data):
    return ht.spatial.cdist(data[0][:8000], quadratic_expansion=True)


class _Profile:
    """A profiler session as the harness's ``--trace 1`` run takes it."""

    def __enter__(self):
        self.dir = tempfile.TemporaryDirectory(prefix="spans_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir.name, profiler_options=options)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        self.trace = trace_reduce.load(self.dir.name)
        self.dir.cleanup()
        return False


def test_off_gives_the_shared_noop_and_keeps_nothing(data):
    assert not telemetry.enabled() and not jax.profiler.TraceAnnotation.is_enabled()
    assert telemetry.span("heat_tpu.cdist") is telemetry.span("anything", bytes=3)
    _fit(data), _cdist(data)
    assert telemetry.spans() == []


@pytest.mark.parametrize("enable,profile", [(True, False), (False, True), (True, True)],
                         ids=["enabled", "profile", "enabled-and-profile"])
@pytest.mark.parametrize("path,phases", [(_fit, FIT), (_cdist, CDIST)], ids=["fit", "cdist"])
def test_on_records_phases_that_tile_their_root(data, path, phases, enable, profile):
    path(data)  # compile outside the spans
    if enable:
        telemetry.enable()
    telemetry.spans(clear=True)
    events_before = len(telemetry.get_registry().events)

    def one_call(i):
        out = path(data)
        jax.block_until_ready(out.larray if path is _cdist else out.labels_.larray)

    annotate = jax.profiler.TraceAnnotation
    if profile:
        with _Profile() as prof:
            window = loadgen.drive(MIX, 0.4, one_call, lambda r: None, annotate)
    else:
        window = loadgen.drive(MIX, 0.4, one_call, lambda r: None, annotate)
    recs = [s for s in telemetry.spans() if s["name"].startswith("heat_tpu.")]
    roots = [s for s in recs if s["name"] == phases[0].rsplit(".", 1)[0]]
    assert len(roots) == len(window.calls) >= 1
    for root in roots:
        assert root["parent_id"] is None and root["root_id"] == root["id"] and root["depth"] == 0
        kids = sorted((s for s in recs if s["parent_id"] == root["id"]), key=lambda s: s["t0_ns"])
        assert [k["name"] for k in kids] == phases
        for k in kids:
            assert k["root_id"] == root["id"] and k["tid"] == root["tid"] and k["depth"] == 1
            assert k["parent"] == root["name"] and k["kind"] == "span"
            assert root["t0_ns"] <= k["t0_ns"] <= k["t1_ns"] <= root["t1_ns"]
            assert k["seconds"] == pytest.approx((k["t1_ns"] - k["t0_ns"]) / 1e9)
        assert all(a["t1_ns"] <= b["t0_ns"] for a, b in zip(kids, kids[1:])), "consecutive"
    # how little of a call the root keeps to itself is a time: the chip's traced runs read it (``idle_ms.*``), no CPU test
    # the initial centres a fit is given pass through ht.array: a root of its own
    if path is _fit:
        arrays = [s for s in recs if s["name"] == "heat_tpu.array.prepare"]
        assert len(arrays) == len(roots) and all(s["parent_id"] is None for s in arrays)
    legacy = [e for e in telemetry.get_registry().events[events_before:]
              if e.get("name") == roots[0]["name"]]
    assert bool(legacy) == enable, "the event stream is the explicit session's alone"

    if not profile:
        return
    # every record lies in the profile as a TraceAnnotation of its name; put
    # on the profiler's clock by the harness call around it, it is the same
    # instant to 50 us
    host = {p: lines for p, lines in prof.trace.items() if "host" in p.lower()}
    traced = sorted((e for lines in host.values() for evs in lines.values() for e in evs
                     if e.name.startswith("heat_tpu.")), key=lambda e: e.start)
    calls = [(e.start, e.end) for lines in host.values() for evs in lines.values() for e in evs
             if e.name == "chipbench.call"]
    joined, why, _ = program_spans.offsets(window.calls, calls)
    assert why is None
    mapped = sorted(program_spans.on_profiler_clock(recs, joined), key=lambda e: e.start)
    assert [e.name for e in mapped] == [e.name for e in traced] and len(mapped) == len(recs)
    apart = sorted(max(abs(m.start - t.start), abs(m.end - t.end)) for m, t in zip(mapped, traced))
    # a wrong join moves every span; a busy test host stalls the thread
    # between a span's clock reading and its annotation now and then
    assert apart[len(apart) // 2] < 50e3 and apart[int(len(apart) * 0.8)] < 50e3, apart[-5:]


def test_output_blocks_only_in_an_explicit_session(monkeypatch):
    waited = []
    monkeypatch.setattr(jax, "block_until_ready", lambda xs: waited.append(len(xs)))
    with _Profile():
        with telemetry.span("heat_tpu.test.launch") as sp:
            assert sp.output("value") == "value"
    assert waited == [], "a profile shows the program as it runs without one"
    telemetry.enable()
    with telemetry.span("heat_tpu.test.launch") as sp:
        sp.output("value")
    assert waited == [1]


def test_the_buffer_drops_the_oldest_and_counts_it(monkeypatch):
    monkeypatch.setattr(telemetry, "_SPANS", collections.deque(maxlen=4))
    telemetry.enable()
    for i in range(6):
        with telemetry.span(f"s{i}"):
            pass
    assert [s["name"] for s in telemetry.spans()] == ["s2", "s3", "s4", "s5"]
    assert telemetry.get_registry().counters["spans_dropped"] == 2
    assert [s["name"] for s in telemetry.spans(clear=True)] == ["s2", "s3", "s4", "s5"]
    assert telemetry.spans() == []


def test_a_failed_span_is_kept_as_an_error():
    telemetry.enable()
    with pytest.raises(TypeError):
        ht.spatial.cdist("not an array")
    kinds = {s["name"]: s["kind"] for s in telemetry.spans()}
    assert kinds == {"heat_tpu.cdist.prepare": "span_error", "heat_tpu.cdist": "span_error"}


def test_results_are_the_same_bits_with_spans_on_and_off(data):
    off_fit, off_d = _fit(data), _cdist(data)
    telemetry.enable()
    with _Profile():
        on_fit, on_d = _fit(data), _cdist(data)
    assert telemetry.spans()
    assert np.array_equal(np.asarray(off_d.larray), np.asarray(on_d.larray))
    assert np.array_equal(np.asarray(off_fit.cluster_centers_.larray),
                          np.asarray(on_fit.cluster_centers_.larray))
    assert np.array_equal(np.asarray(off_fit.labels_.larray), np.asarray(on_fit.labels_.larray))
    assert off_fit.inertia_ == on_fit.inertia_ and off_fit.n_iter_ == on_fit.n_iter_ == 30
