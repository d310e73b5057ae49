"""The join of the program's spans with the device trace, and the eleven
readers on it: on hand-made traces with numbers worked by hand, on traces
recorded on the TPU v5e in PR 24 (after the kernels got their names) and cut
to two calls, and end to end on the CPU through a tiny manifest of this
PR's own (``tiny_spans/``: the tiny cells and parts, plus the new metrics)."""

import json
import os
import types

import pytest

from chipbench import loadgen, manifest, program_spans as ps, run, trace_reduce as tr
from chipbench.trace_reduce import Event as E

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_spans")
RULE = {"plane": "^/device:TPU:[0-9]+$", "ops_line": "^XLA Ops$", "modules_line": "^XLA Modules$"}
IDLE = ["idle_ms.prepare", "idle_ms.launch", "idle_ms.readback", "idle_ms.wrap", "idle_ms.harness"]
HOST_AHEAD = 10_000  # ns: the host's clock reads this much more than the profiler's

KERNEL = ('%lloyd_update{} = (f32[128,64]{{1,0:T(8,128)S(1)}}, f32[8,128]{{1,0:T(8,128)S(1)}}) '
          'custom-call(s32[1]{{0:T(128)}} %get-tuple-element.155), custom_call_target="tpu_custom_call"')
TILE = ('%euclid_tile.1 = f32[40960,40960]{1,0:T(8,128)S(1)} custom-call(f32[1,1]{1,0} %bitcast, '
        'f32[40960,64]{1,0} %pad.0), custom_call_target="tpu_custom_call"')


def _spans_of_a_fit(at, first_id):
    """One KMeans call as the program records it, on the profiler's clock:
    the initial centres through ``ht.array``, then the fit and its phases."""
    rows = [("heat_tpu.array.prepare", 110, 150, None), ("heat_tpu.kmeans.fit", 160, 880, None),
            ("heat_tpu.kmeans.fit.prepare", 170, 300, 1), ("heat_tpu.kmeans.fit.launch", 300, 340, 1),
            ("heat_tpu.kmeans.fit.readback", 350, 850, 1), ("heat_tpu.kmeans.fit.wrap", 860, 875, 1)]
    return [
        {"kind": "span", "name": n, "id": first_id + i, "root_id": first_id + (1 if p else i),
         "parent_id": first_id + p if p else None,
         "t0_ns": a + at + HOST_AHEAD, "t1_ns": b + at + HOST_AHEAD}
        for i, (n, a, b, p) in enumerate(rows)
    ]


def _calls(*intervals):
    return [loadgen.Call(i, 0, (a + HOST_AHEAD) / 1e9, (b + HOST_AHEAD) / 1e9)
            for i, (a, b) in enumerate(intervals)]


def _kmeans_handmade():
    """A window of 2000 ns, two fits 900 ns apart, two chips.

    Chip 0 runs a small program inside ``prepare`` (180-200), the fit's
    program (320-800: a copy of 40, a ``while`` of 400 that holds three
    kernel events of 100 with 10 between them, a last fusion of 40) and a
    convert inside ``wrap`` (865-870). Chip 1 runs the fit's program alone,
    its kernels 110 long and back to back, and its line runs ahead of the
    host: the program is recorded from 290, 10 ns before the ``launch`` span
    that enqueued it begins."""
    def chip0(at):
        ops = [E("%iota.1 = s32[8]{0} iota()", 180 + at, 200 + at),
               E("%copy = f32[16,64]{1,0} copy(f32[16,64]{0,1} %xb.1)", 320 + at, 360 + at),
               E("%while.2 = (f32[8,64]{1,0}) while((f32[8,64]{1,0}) %tuple)", 360 + at, 760 + at)]
        ops += [E(KERNEL.format(".3"), s + at, s + 100 + at) for s in (370, 480, 590)]
        ops += [E("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %lloyd_update.3)", 760 + at, 800 + at),
                E("%convert.5 = s64[16]{0} convert(s32[16]{0} %labels)", 865 + at, 870 + at)]
        mods = [E("jit_iota(1)", 180 + at, 200 + at), E("jit_lloyd_fit_pallas(2)", 320 + at, 800 + at),
                E("jit_convert_element_type(3)", 865 + at, 870 + at)]
        return ops, mods

    def chip1(at):
        ops = [E("%copy.5 = f32[16,64]{1,0} copy(f32[16,64]{0,1} %xb.1)", 290 + at, 330 + at),
               E("%while.1 = (f32[8,64]{1,0}) while((f32[8,64]{1,0}) %tuple)", 330 + at, 730 + at)]
        ops += [E(KERNEL.format(".1"), s + at, s + 110 + at) for s in (340, 450, 560)]
        ops += [E("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %lloyd_update_helper.2)", 730 + at, 770 + at)]
        return ops, [E("jit_lloyd_fit_pallas(2)", 290 + at, 770 + at)]

    def plane(chip):
        (o1, m1), (o2, m2) = chip(0), chip(900)
        return {"XLA Ops": o1 + o2, "XLA Modules": m1 + m2}

    trace = {
        "/device:TPU:0": plane(chip0),
        "/device:TPU:1": plane(chip1),
        "/host:CPU": {"main": [E("chipbench.window", 0, 2000), E("chipbench.call", 100, 900),
                               E("chipbench.between_calls", 900, 1000), E("chipbench.call", 1000, 1800),
                               E("chipbench.between_calls", 1800, 1850)]},
    }
    spans = _spans_of_a_fit(0, 1) + _spans_of_a_fit(900, 7)
    return trace, spans, _calls((100, 900), (1000, 1800))


def _cdist_handmade():
    """A window of 1300 ns, two distance matrices 600 ns apart, one chip:
    each program (160-560) is a pad of 10, a hole of 5, the kernel's event
    of 125 and a slice of 260."""
    def call(at):
        ops = [E("%pad.0 = f32[40960,64]{1,0} pad(f32[40000,18]{1,0} %x, f32[] %c)", 160 + at, 170 + at),
               E(TILE, 175 + at, 300 + at),
               E("%slice.0 = f32[40000,40000]{1,0} slice(f32[40960,40960]{1,0} %euclid_tile.1)", 300 + at, 560 + at)]
        spans = [("heat_tpu.cdist", 110, 200), ("heat_tpu.cdist.prepare", 115, 140),
                 ("heat_tpu.cdist.launch", 145, 190), ("heat_tpu.cdist.wrap", 192, 197)]
        return ops, [E("jit__euclid_pallas_jit(7)", 160 + at, 560 + at)], [
            {"kind": "span", "name": n, "t0_ns": a + at + HOST_AHEAD, "t1_ns": b + at + HOST_AHEAD}
            for n, a, b in spans
        ]

    (o1, m1, s1), (o2, m2, s2) = call(0), call(600)
    trace = {
        "/device:TPU:0": {"XLA Ops": o1 + o2, "XLA Modules": m1 + m2},
        "/host:CPU": {"main": [E("chipbench.window", 0, 1300), E("chipbench.call", 100, 600),
                               E("chipbench.between_calls", 600, 650), E("chipbench.call", 700, 1200),
                               E("chipbench.between_calls", 1200, 1250)]},
    }
    return trace, s1 + s2, _calls((100, 600), (700, 1200))


def _reading(monkeypatch, trace, spans, calls, modules, dropped=0):
    monkeypatch.setattr(ps, "recorded_spans", lambda: spans)
    monkeypatch.setattr(ps, "dropped_spans", lambda: dropped)
    return types.SimpleNamespace(
        trace=tr.reduce(trace, RULE) if isinstance(trace, dict) else trace,
        window=types.SimpleNamespace(calls=calls), config={"roofline_modules": modules}, notes={},
    )


def _read(reading, name):
    return manifest.load(REPO).module("metrics", name).read(reading)


def test_segments_name_each_piece_by_the_innermost_span():
    events = [E("chipbench.call", 10, 100), E("heat_tpu.cdist", 20, 90), E("heat_tpu.cdist.prepare", 30, 50),
              E("resplit", 35, 40), E("heat_tpu.cdist.launch", 50, 95)]  # a child held to its parent's end
    assert ps.segments(events, 0, 120) == [
        (0, 10, ""), (10, 20, "chipbench.call"), (20, 30, "heat_tpu.cdist"),
        (30, 35, "heat_tpu.cdist.prepare"), (35, 40, "heat_tpu.cdist.prepare"),  # another subsystem's span
        (40, 50, "heat_tpu.cdist.prepare"), (50, 90, "heat_tpu.cdist.launch"),
        (90, 100, "chipbench.call"), (100, 120, ""),
    ]
    segs = ps.segments(events, 0, 120)
    assert ps.split([(5, 25), (45, 55)], segs) == {
        "": 5, "chipbench.call": 10, "heat_tpu.cdist": 5, "heat_tpu.cdist.prepare": 5, "heat_tpu.cdist.launch": 5}
    assert ps.name_at(segs, 37) == "heat_tpu.cdist.prepare" and ps.name_at(segs, 500) == ""
    assert [ps.bucket_of(n) for n in ("", "chipbench.between_calls", "heat_tpu.cdist", "ring_cdist",
                                      "heat_tpu.array.prepare", "heat_tpu.kmeans.fit.readback")] == [
        "harness", "harness", "root_only", "root_only", "prepare", "readback"]


def test_idle_readers_on_the_handmade_fit(monkeypatch):
    r = _reading(monkeypatch, *_kmeans_handmade(), "lloyd_fit")
    got = {name: _read(r, name) for name in IDLE}
    # per call and chip, worked in _kmeans_handmade's terms (ns -> ms):
    # chip 0 idles 300 under prepare, 40 launch, 100 readback, 20 wrap,
    # 50 under the root alone, 480 under the harness (990 = 2000 - 1010 busy);
    # chip 1, moved 10 ns later, 340, 0, 140, 30, 50, 480 (1040)
    assert got == pytest.approx({
        "idle_ms.prepare": 160e-6, "idle_ms.launch": 10e-6, "idle_ms.readback": 60e-6,
        "idle_ms.wrap": 12.5e-6, "idle_ms.harness": 240e-6})
    assert r.notes["idle_under_root_only_ms"] == pytest.approx(25e-6)
    per_call = _read(r, "device_idle_share") / 100 * r.trace.window_s * 1e3 / len(r.trace.calls)
    assert sum(got.values()) + r.notes["idle_under_root_only_ms"] == pytest.approx(per_call)
    assert r.notes["idle_ms_per_call"] == pytest.approx(507.5e-6)
    # chip 1's program is recorded 10 ns before its launch span begins and
    # may lag by up to 130 (its call returns at 900, the program ends at 770)
    assert r.notes["device_lag_us"] == pytest.approx([0.0, 0.010])
    assert r.notes["device_lag_bounds_us"] == [pytest.approx([-0.020, 0.100]), pytest.approx([0.010, 0.130])]
    assert r.notes["programs_started_under"] == {
        "heat_tpu.kmeans.fit.prepare": 1.0, "heat_tpu.kmeans.fit.launch": 1.0, "heat_tpu.kmeans.fit.wrap": 1.0}
    assert r.notes["spans_per_call"] == 6.0
    # chip 0's longest gaps, each under the span that holds most of it
    assert r.notes["longest_gaps"][:3] == [
        ["outside_any_span", pytest.approx(230e-6)],  # 1770-2000: 150 after the last between_calls
        ["chipbench.between_calls", pytest.approx(210e-6)],  # 870-1080
        ["outside_any_span", pytest.approx(180e-6)],  # 0-180: 100 before the first call
    ]
    assert r.notes["idle_ms_by_span"]["heat_tpu.array.prepare"] == pytest.approx(40e-6)
    assert "idle_join" not in r.notes


def test_kernel_readers_on_the_handmade_fit(monkeypatch):
    r = _reading(monkeypatch, *_kmeans_handmade(), "lloyd_fit")
    assert _read(r, "lloyd_kernel_ms") == pytest.approx(105e-6)  # six of 100, six of 110
    assert _read(r, "lloyd_kernel_events") == 3.0
    # before the first kernel: copy 40 + 10 of the while, on both chips
    assert _read(r, "lloyd_prologue_ms") == pytest.approx(50e-6)
    # after the last: chip 0 70 of the while + fusion 40, chip 1 60 + 40
    assert _read(r, "lloyd_epilogue_ms") == pytest.approx(105e-6)
    assert r.notes["lloyd_program_ms"] == pytest.approx(480e-6)
    assert r.notes["lloyd_between_kernels_ms"] == pytest.approx(10e-6)  # chip 0: 2 x 10; chip 1: none
    total = 3 * _read(r, "lloyd_kernel_ms") + _read(r, "lloyd_prologue_ms") + _read(r, "lloyd_epilogue_ms")
    assert total + r.notes["lloyd_between_kernels_ms"] == pytest.approx(r.notes["lloyd_program_ms"])
    assert _read(r, "cdist_kernel_ms") is None and _read(r, "cdist_repack_ms") is None


def test_readers_on_the_handmade_distance_matrix(monkeypatch):
    r = _reading(monkeypatch, *_cdist_handmade(), "euclid|_local_dist|cdist")
    assert _read(r, "cdist_kernel_ms") == pytest.approx(125e-6)
    assert _read(r, "cdist_repack_ms") == pytest.approx(270e-6)  # pad 10 + slice 260; the hole is neither
    assert r.notes["cdist_program_ms"] == pytest.approx(400e-6)
    got = {name: _read(r, name) for name in IDLE}
    assert got == pytest.approx({
        "idle_ms.prepare": 25e-6, "idle_ms.launch": 20e-6, "idle_ms.readback": 0.0,
        "idle_ms.wrap": 0.0, "idle_ms.harness": 200e-6})
    assert r.notes["idle_under_root_only_ms"] == pytest.approx(10e-6)
    assert sum(got.values()) + 10e-6 == pytest.approx((1300 - 2 * 395) / 2 / 1e6)
    assert r.notes["device_lag_us"] == [0.0]
    for name in ("lloyd_kernel_ms", "lloyd_kernel_events", "lloyd_prologue_ms", "lloyd_epilogue_ms"):
        assert _read(r, name) is None


def _all_none_with(reading, part_of_note):
    assert [_read(reading, name) for name in IDLE] == [None] * 5
    assert part_of_note in reading.notes["idle_join"]
    assert "idle_under_root_only_ms" not in reading.notes


def test_a_join_that_is_not_sound_gives_no_number(monkeypatch):
    trace, spans, calls = _kmeans_handmade()
    _all_none_with(_reading(monkeypatch, trace, spans, calls[:1], "lloyd_fit"),
                   "1 calls timed, 2 chipbench.call spans traced")
    late = [calls[0], loadgen.Call(1, 0, calls[1].t0 + 1e-3, calls[1].t1 + 1e-3)]
    _all_none_with(_reading(monkeypatch, trace, spans, late, "lloyd_fit"),
                   "offsets of neighbouring calls differ by 1000.0 us")
    two = [calls[0], loadgen.Call(1, 1, calls[1].t0, calls[1].t1)]
    _all_none_with(_reading(monkeypatch, trace, spans, two, "lloyd_fit"), "more than one client")
    _all_none_with(_reading(monkeypatch, trace, spans, calls, "lloyd_fit", dropped=3),
                   "the span buffer dropped 3 records")
    _all_none_with(_reading(monkeypatch, trace, [], calls, "lloyd_fit"), "no program span inside a call")
    # a program recorded before its launch span AND past the end of its call
    trace["/device:TPU:1"]["XLA Modules"] = [E("jit_lloyd_fit_pallas(2)", 290, 950),
                                             E("jit_lloyd_fit_pallas(2)", 1190, 1670)]
    _all_none_with(_reading(monkeypatch, trace, spans, calls, "lloyd_fit"),
                   "/device:TPU:1: a program starts 0.0 us before its launch span and ends 0.1 us after")
    # the kernels' readers do not need the join
    r = _reading(monkeypatch, _kmeans_handmade()[0], spans, calls[:1], "lloyd_fit")
    assert _read(r, "lloyd_kernel_events") == 3.0


def test_a_lone_stalled_call_takes_its_neighbours_offset():
    """A thread stalled between the harness's annotation and its clock
    reading spoils one call's offset; its neighbours still agree."""
    traced = [(1e6 * i, 1e6 * i + 9e5) for i in range(12)]  # a call a millisecond
    calls = _calls(*traced)
    stalled = loadgen.Call(5, 0, calls[5].t0 + 300e-6, calls[5].t1)  # the clock was read 300 us late
    joined, why, repaired = ps.offsets(calls[:5] + [stalled] + calls[6:], traced)
    assert why is None and repaired == 1
    assert [o for _, o in joined] == pytest.approx([-HOST_AHEAD] * 12)
    also = loadgen.Call(9, 0, calls[9].t0 + 80e-6, calls[9].t1)
    joined, why, repaired = ps.offsets(calls[:5] + [stalled] + calls[6:9] + [also] + calls[10:], traced)
    assert joined is None and repaired == 2 and "differ by 300.0 us" in why


def test_a_program_without_spans_or_names_reads_nothing(monkeypatch):
    """The parent commit under this PR's benchmark files: no span buffer, the
    compiler's names on the kernels. Every new reader gives None, none raises."""
    from heat_tpu import telemetry

    trace, _, calls = _kmeans_handmade()
    for plane in ("/device:TPU:0", "/device:TPU:1"):
        trace[plane]["XLA Ops"] = [E(e.name.replace("%lloyd_update", "%body"), e.start, e.end)
                                   for e in trace[plane]["XLA Ops"]]
    monkeypatch.delattr(telemetry, "spans")
    r = types.SimpleNamespace(trace=tr.reduce(trace, RULE), window=types.SimpleNamespace(calls=calls),
                              config={"roofline_modules": "lloyd_fit"}, notes={})
    names = IDLE + ["lloyd_kernel_ms", "lloyd_kernel_events", "lloyd_prologue_ms", "lloyd_epilogue_ms",
                    "cdist_kernel_ms", "cdist_repack_ms"]
    assert [_read(r, name) for name in names] == [None] * 11 and r.notes == {}
    untraced = types.SimpleNamespace(trace=None, window=r.window, config=r.config, notes={})
    assert [_read(untraced, name) for name in names] == [None] * 11


def test_tiny_spans_is_the_tiny_manifest_with_entries_added():
    """``tiny/BENCHMARK.json`` is the accepted benchmark's file and not this
    PR's to edit, so the rehearsal has a manifest of its own: the same cells
    on the same parts, and the eleven metrics at the end of ``per_layer``."""
    with open(os.path.join(HERE, "tiny", "BENCHMARK.json")) as f:
        old = json.load(f)
    new = manifest.load(TINY).doc  # load() validates
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    assert new["paths"] == ["../tiny/parts"]
    assert [dict(c, file=c["file"].replace("../tiny/", "")) for c in new["configs"]] == old["configs"]
    assert all(new[k] == old[k] for k in ("command", "run_seconds", "workloads", "end_to_end"))
    assert new["per_layer"][:len(old["per_layer"])] == old["per_layer"]
    added = new["per_layer"][len(old["per_layer"]):]
    drop = lambda m: {k: v for k, v in m.items() if k != "workloads"}
    assert [drop(m) for m in added] == [drop(m) for m in real["per_layer"][-11:]]


@pytest.mark.parametrize("workload, spans_per_call, phases", [
    ("tiny-kmeans", 6.0, IDLE),  # array.prepare, the fit, its four phases
    ("tiny-cdist", 4.0, [n for n in IDLE if n != "idle_ms.readback"]),  # the root, its three phases
])
def test_the_traced_cpu_run_reports_the_span_metrics(capsys, workload, spans_per_call, phases):
    """End to end through ``run.py`` and ``tiny_spans/``: the program's
    real spans, a real profile. (A CPU run: the numbers are no device
    metrics; that they add up is what is checked.)"""
    import heat_tpu as ht

    try:
        rc = run.main(["--workload", workload, "--seed", "2400000007", "--seconds", "0.3",
                       "--trace", "1"], root=TINY)
    finally:
        ht.use_comm(None)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0
    metrics = {k: v["value"] for k, v in lines[-1]["metrics"].items()}
    notes = next(l["notes"] for l in lines if "notes" in l)
    assert set(phases) <= set(metrics), notes.get("idle_join")
    device = lines[-1]["device"]
    idle_per_call = (device["window_s"] - device["busy_s"]) * 1e3 / lines[-1]["attempted"]
    assert sum(metrics[n] for n in phases) + notes["idle_under_root_only_ms"] == pytest.approx(idle_per_call, rel=1e-6)
    assert notes["spans_per_call"] == spans_per_call
    assert metrics["idle_ms.prepare"] > 0 and len(notes["longest_gaps"]) == 10
    # no kernel of those names runs on a CPU: those readers leave their metric out
    assert not {"lloyd_kernel_ms", "lloyd_kernel_events", "lloyd_prologue_ms", "lloyd_epilogue_ms",
                "cdist_kernel_ms", "cdist_repack_ms"} & set(metrics)


# -- recorded on the chip ---------------------------------------------------------


def _recorded(monkeypatch, name, cell):
    """A traced run of PR 24 on one TPU v5 lite, cut to the window's first
    two calls: the trace (text proto) and, beside it, the program's span
    records and the calls' host times."""
    parts = manifest.load(REPO)
    peak = parts.table("peaks")["TPU v5 lite"]
    with open(os.path.join(HERE, f"recorded_spans_{name}_v5e.txt")) as f:
        raw = tr.load_text(f.read())
    with open(os.path.join(HERE, f"recorded_spans_{name}_v5e.json")) as f:
        rec = json.load(f)
    r = _reading(monkeypatch, tr.reduce(raw, peak["trace"]), rec["spans"],
                 [loadgen.Call(*c) for c in rec["calls"]], None)
    r.config, r.parts, r.peak, r.chips = parts.config(parts.cell(cell)), parts, peak, 1
    return raw, r


def _annotations_agree(raw, r):
    """Every span record, put on the profiler's clock through the harness
    call around it, is its own TraceAnnotation in the trace to 50 us."""
    joined, why, _ = ps.offsets(r.window.calls, r.trace.calls)
    assert why is None
    mapped = sorted(ps.on_profiler_clock(ps.recorded_spans(), joined), key=lambda e: e.start)
    traced = sorted((e for e in raw["/host:CPU"]["main"] if e.name.startswith("heat_tpu.")),
                    key=lambda e: e.start)
    assert [e.name for e in mapped] == [e.name for e in traced]
    worst = max(max(abs(m.start - t.start), abs(m.end - t.end)) for m, t in zip(mapped, traced))
    assert worst < 50e3
    return len(mapped)


def test_recorded_fit_names_its_kernel_and_its_gaps(monkeypatch):
    raw, r = _recorded(monkeypatch, "kmeans", "kmeans-fit-1chip")
    ops = raw["/device:TPU:0"]["XLA Ops"]
    kernels = [e for e in ops if ps.LLOYD_KERNEL.search(e.name)]
    assert len(kernels) == 60 and {tr.short_name(e.name) for e in kernels} == {"%lloyd_update.3 custom-call"}
    assert _annotations_agree(raw, r) == 12  # array.prepare, the fit, four phases; two calls
    assert _read(r, "lloyd_kernel_events") == 30.0
    assert _read(r, "lloyd_kernel_ms") == pytest.approx(sum(e.dur for e in kernels) / 60 / 1e6)
    assert _read(r, "lloyd_kernel_ms") == pytest.approx(45.635997, abs=1e-6)
    assert _read(r, "lloyd_prologue_ms") == pytest.approx(20.005995, abs=1e-6)  # %copy of X, 19.99 of it
    assert _read(r, "lloyd_epilogue_ms") == pytest.approx(15.127870, abs=1e-6)
    # the fit's program a call: 30 kernels, what lies before and after them,
    # and 0.04 ms of the loop's small operations between them
    fits = [e for e in raw["/device:TPU:0"]["XLA Modules"] if e.name.startswith("jit_lloyd_fit_pallas(")]
    assert r.notes["lloyd_program_ms"] == pytest.approx(sum(e.dur for e in fits) / 2 / 1e6)
    parts_sum = 30 * _read(r, "lloyd_kernel_ms") + _read(r, "lloyd_prologue_ms") + _read(r, "lloyd_epilogue_ms")
    assert parts_sum + r.notes["lloyd_between_kernels_ms"] == pytest.approx(r.notes["lloyd_program_ms"])
    assert r.notes["lloyd_between_kernels_ms"] == pytest.approx(0.0393765, abs=1e-6)
    assert parts_sum == pytest.approx(r.notes["lloyd_program_ms"], rel=0.02)

    got = {name: _read(r, name) for name in IDLE}
    assert got == pytest.approx({
        "idle_ms.prepare": 2.284143, "idle_ms.launch": 0.267685, "idle_ms.readback": 2.316370,
        "idle_ms.wrap": 0.082810, "idle_ms.harness": 0.457995}, abs=1e-6)
    assert r.notes["idle_under_root_only_ms"] == pytest.approx(0.145271, abs=1e-6)
    per_call = _read(r, "device_idle_share") / 100 * r.trace.window_s * 1e3 / 2
    assert sum(got.values()) + r.notes["idle_under_root_only_ms"] == pytest.approx(per_call)
    # the longest gap of a fit lies where the host reads n_iter and inertia back
    assert r.notes["longest_gaps"][0] == ["heat_tpu.kmeans.fit.readback", pytest.approx(3.724444)]
    assert r.notes["device_lag_us"] == [0.0]  # 0 lies inside the bounds of this session
    assert r.notes["device_lag_bounds_us"][0] == pytest.approx([-235.891, 2464.143], abs=1e-3)
    assert r.notes["spans_per_call"] == 6.0
    # what the benchmark had reads what it read: the kernel's new name moves nothing
    assert _read(r, "lloyd_roofline") == pytest.approx(11.5886, abs=1e-4)
    assert _read(r, "launches_per_call") == 7.0
    assert _read(r, "cdist_kernel_ms") is None


def test_recorded_distance_matrix_names_its_kernel_and_its_gaps(monkeypatch):
    raw, r = _recorded(monkeypatch, "cdist", "cdist-susy-1chip")
    kernels = [e for e in raw["/device:TPU:0"]["XLA Ops"] if ps.CDIST_KERNEL.search(e.name)]
    assert [tr.short_name(e.name) for e in kernels] == ["%euclid_tile.1 custom-call"] * 2
    assert _annotations_agree(raw, r) == 8
    assert _read(r, "cdist_kernel_ms") == pytest.approx(sum(e.dur for e in kernels) / 2 / 1e6)
    assert _read(r, "cdist_kernel_ms") == pytest.approx(10.3793675, abs=1e-6)
    assert _read(r, "cdist_repack_ms") == pytest.approx(19.7773485, abs=1e-6)  # the slice is 19.74 of it
    assert _read(r, "cdist_kernel_ms") + _read(r, "cdist_repack_ms") == pytest.approx(
        r.notes["cdist_program_ms"], rel=0.01)
    got = {name: _read(r, name) for name in IDLE}
    assert got == pytest.approx({
        "idle_ms.prepare": 0.153125, "idle_ms.launch": 0.104139, "idle_ms.readback": 0.0,
        "idle_ms.wrap": 0.0, "idle_ms.harness": 1.221812}, abs=1e-6)
    per_call = _read(r, "device_idle_share") / 100 * r.trace.window_s * 1e3 / 2
    assert sum(got.values()) + r.notes["idle_under_root_only_ms"] == pytest.approx(per_call)
    # the chip waits longest after the program's spans have closed: the
    # harness's block_until_ready has not returned yet
    assert r.notes["longest_gaps"][0] == ["chipbench.call", pytest.approx(1.579004)]
    assert r.notes["programs_started_under"] == {"heat_tpu.cdist.launch": 1.0}
    assert _read(r, "cdist_roofline") == pytest.approx(25.936, abs=1e-3)
    assert _read(r, "lloyd_kernel_events") is None
