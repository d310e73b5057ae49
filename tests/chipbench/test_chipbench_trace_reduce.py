"""The reduction from a trace to busy and idle time, per-name time and gaps:
on a hand-made trace with numbers worked by hand, and on a trace recorded
on the TPU v5e in PR 23 and cut to a few hundred events."""

import os
import types

import pytest

from chipbench import manifest, trace_reduce as tr
from chipbench.trace_reduce import Event as E

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
RULE = {"plane": "^/device:TPU:[0-9]+$", "ops_line": "^XLA Ops$", "modules_line": "^XLA Modules$"}


def test_interval_arithmetic():
    merged = tr.union([(5, 9), (0, 2), (1, 3), (9, 10), (20, 20)])
    assert merged == [(0, 3), (5, 10)]
    assert tr.length(merged) == 8
    assert tr.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert tr.gaps(merged, -1, 12) == [(-1, 0), (3, 5), (10, 12)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]


def test_self_times_take_children_out_of_their_parent():
    ops = [E("while", 0, 100), E("kernel", 10, 40), E("fusion", 40, 45), E("kernel", 50, 90),
           E("inner", 60, 70), E("tail", 100, 130)]
    assert tr.self_times(ops) == {"while": 25, "kernel": 60, "fusion": 5, "inner": 10, "tail": 30}
    assert [e.name for e in tr.leaves(ops)] == ["kernel", "fusion", "inner", "tail"]


def _handmade():
    """Two chips; a window of 1000 ns with two calls. Chip 0 is busy 100-400
    (a while holding a kernel and an all-reduce) and 600-900; chip 1 is busy
    100-500 and 600-900, its all-reduce 450-500 alone on the core."""
    def device(ar):
        return {
            "XLA Ops": [E("while.1", 100, ar[1]), E("lloyd_kernel", 100, 380), E("all-reduce.7", *ar),
                        E("while.1", 600, 900), E("lloyd_kernel", 600, 880), E("all-reduce.7", 880, 900)],
            "XLA Modules": [E("jit_lloyd_fit_pallas_sharded(1)", 100, ar[1]), E("jit_convert(2)", ar[1], ar[1]),
                            E("jit_lloyd_fit_pallas_sharded(1)", 600, 900)],
            "Steps": [E("0", 0, 1000)],
        }
    return {
        "/device:TPU:0": device((380, 400)),
        "/device:TPU:1": device((450, 500)),
        "/host:CPU": {"main": [E("chipbench.window", 0, 1000), E("chipbench.call", 50, 420),
                               E("chipbench.between_calls", 420, 560), E("chipbench.call", 560, 950),
                               E("other", 0, 5000)]},
    }


def test_reduce_handmade_trace():
    r = tr.reduce(_handmade(), RULE)
    assert [d.plane for d in r.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert r.window == (0, 1000) and r.calls == [(50, 420), (560, 950)]
    assert r.window_s == pytest.approx(1e-6)
    # busy: chip 0 300 + 300, chip 1 400 + 300 -> mean 650 ns
    assert r.busy_s == pytest.approx(650e-9)
    # programs named lloyd_fit: chip 0 300 + 300, chip 1 400 + 300
    assert r.module_time("lloyd_fit") == 1300
    assert r.module_time("no_such_program") == 0
    top = dict(r.top_ops())
    assert list(top)[0] == "lloyd_kernel"
    assert top["lloyd_kernel"] == pytest.approx(2 * (280 + 280) / 1e9)
    # chip 1's while holds 70 ns that no child accounts for (380-450)
    assert top["while.1"] == pytest.approx(70e-9)
    gaps = r.top_gaps()
    assert gaps[0] == ("chipbench.between_calls", pytest.approx(200e-9))  # 400-600 on chip 0
    # 0-100 and 900-1000: half of each lies in a call, the rest in no span
    assert [g[0] for g in gaps[1:]] == ["chipbench.call", "chipbench.call"]
    assert sum(g[1] for g in gaps) == pytest.approx(400e-9)


def test_reduce_needs_a_device_plane_and_a_window():
    t = _handmade()
    assert tr.reduce({k: v for k, v in t.items() if "TPU" not in k}, RULE) is None
    t["/host:CPU"]["main"] = [e for e in t["/host:CPU"]["main"] if e.name != "chipbench.window"]
    assert tr.reduce(t, RULE) is None


def _reading(reduced, config=None, chips=2):
    parts = manifest.load(REPO)
    return types.SimpleNamespace(
        trace=reduced, parts=parts, config=config or {}, chips=chips, notes={},
        peak=parts.table("peaks")["TPU v5 lite"],
    )


def test_per_layer_readers_on_the_handmade_trace():
    parts = manifest.load(REPO)
    r = _reading(tr.reduce(_handmade(), RULE))
    read = lambda name: parts.module("metrics", name).read(r)
    assert read("device_idle_share") == pytest.approx(35.0)
    # 3 programs start on each chip inside the window, 2 calls
    assert read("launches_per_call") == pytest.approx(1.5)
    # call 1 lasts 370 with 300/320 busy inside (chip 1 is cut at 420), call 2
    # 390 with 300: mean of (370-310) and (390-300), in ms
    assert read("host_ms_per_call") == pytest.approx((60 + 90) / 2 / 1e6)
    # chip 0's all-reduces run inside nothing else: 20 + 20; chip 1: 50 + 20
    assert read("collective_exposed_ms") == pytest.approx((40 + 70) / 2 / 2 / 1e6)
    for name in ("device_idle_share", "launches_per_call", "host_ms_per_call",
                 "collective_exposed_ms", "lloyd_roofline", "cdist_roofline"):
        assert parts.module("metrics", name).read(_reading(None)) is None


def test_roofline_share_on_the_handmade_trace():
    parts = manifest.load(REPO)
    config = {"rows": 819, "features": 25, "n_clusters": 1, "max_iter": 0, "roofline_modules": "lloyd_fit"}
    # one pass: 819 * 25 * 4 + 819 * 8 = 88,452 bytes over 2 chips at 819 GB/s
    # = 54 ns a call; 2 calls over (1300 / 2 chips) = 650 ns of programs
    r = _reading(tr.reduce(_handmade(), RULE), config)
    assert parts.module("metrics", "lloyd_roofline").read(r) == pytest.approx(100 * 2 * 54 / 650)
    assert r.notes == {"lloyd_roofline_bound": "bandwidth"}


def test_short_name_keeps_result_and_operation():
    long = ('%body.3 = (f32[128,64]{1,0:T(8,128)S(1)}, f32[8,128]{1,0:T(8,128)S(1)}) custom-call('
            's32[1]{0:T(128)} %get-tuple-element.155, f32[8]{0} %all-reduce.7), custom_call_target="tpu_custom_call"')
    assert tr.short_name(long) == "%body.3 custom-call"
    assert tr.short_name("%copy = f32[16,64]{1,0:T(8,128)} copy(f32[16,64]{0,1:T(8,128)} %xb.1)") == "%copy copy"
    assert tr.short_name("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={}") == "%all-reduce.7 all-reduce"
    assert tr.short_name("all-reduce.7") == "all-reduce.7"
    parts = manifest.load(REPO)
    coll = parts.module("metrics", "collective_exposed_ms").COLLECTIVE
    assert not coll.search(tr.short_name(long)), "an operand's name does not make a collective"


@pytest.fixture(scope="module")
def recorded():
    """``KMeans.fit`` on 2^24 x 64, one TPU v5 lite, PR 23's first chip
    call: the traced window's first two calls (387 device events)."""
    with open(os.path.join(HERE, "recorded_kmeans_v5e.txt")) as f:
        return tr.load_text(f.read())


def test_recorded_trace_reduces_to_the_numbers_worked_from_it(recorded):
    rule = manifest.load(REPO).table("peaks")["TPU v5 lite"]["trace"]
    dev = recorded["/device:TPU:0"]
    assert {k: len(v) for k, v in dev.items()} == {"XLA Modules": 13, "XLA Ops": 366, "Async XLA Ops": 8}
    r = tr.reduce(recorded, rule)
    assert len(r.devices) == 1 and len(r.calls) == 2
    assert r.window_s == pytest.approx(2.823144595, abs=1e-9)
    # each fit is one program of 1.4042 s; six small ones go before it
    fits = [e for e in dev["XLA Modules"] if e.name.startswith("jit_lloyd_fit_pallas(")]
    assert [round(e.dur) for e in fits] == [1404189775, 1404275072]
    assert r.module_time("lloyd_fit") == pytest.approx(1404189775 + 1404275072)
    # the operations nest on one line, so what they take without their
    # children adds up to the time in which any of them ran
    ops = dev["XLA Ops"]
    assert sum(tr.self_times(ops).values()) == pytest.approx(tr.length(tr.union((e.start, e.end) for e in ops)))
    assert r.busy_s == pytest.approx(2.8111414, abs=1e-9)
    kernels = [e for e in ops if tr.short_name(e.name) == "%body.3 custom-call"]
    assert len(kernels) == 60  # 30 iterations a fit
    name, seconds = r.top_ops()[0]
    assert name == "%body.3 custom-call"
    assert seconds == pytest.approx(sum(e.dur for e in kernels) / 1e9) == pytest.approx(2.738107155, abs=1e-9)
    # the gaps are what the busy time leaves of the window; the longest ones
    # fall inside a call, before the fit's program starts
    gaps = tr.gaps(r.devices[0].busy, *r.window)
    assert tr.length(gaps) / 1e9 == pytest.approx(2.823144595 - 2.8111414, abs=1e-9)
    assert r.top_gaps(2) == [("chipbench.call", pytest.approx(0.003979318)),
                             ("chipbench.call", pytest.approx(0.003869207))]


def test_readers_on_the_recorded_trace(recorded):
    parts = manifest.load(REPO)
    cell = parts.cell("kmeans-fit-1chip")
    peak = parts.table("peaks")["TPU v5 lite"]
    r = types.SimpleNamespace(
        trace=tr.reduce(recorded, peak["trace"]), parts=parts, config=parts.config(cell),
        chips=1, peak=peak, notes={},
    )
    read = lambda name: parts.module("metrics", name).read(r)
    assert read("device_idle_share") == pytest.approx(100 * (1 - 2.8111414 / 2.823144595))
    assert read("launches_per_call") == 6.5  # 7 programs a call; the cut ends before call 2's last
    # 133,278,203,904 bytes a fit at 819 GB/s = 0.162733 s, over 1.4042 s of program
    assert read("lloyd_roofline") == pytest.approx(100 * 2 * 0.1627328 / 2.808464847, rel=1e-5)
    assert r.notes["lloyd_roofline_bound"] == "bandwidth"
    assert read("collective_exposed_ms") == 0.0
    assert 5.0 < read("host_ms_per_call") < 7.0
