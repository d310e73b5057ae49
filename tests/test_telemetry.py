"""heat_tpu.telemetry — enable/disable semantics, JSONL event schema, span
nesting and async-correct timing, AOT compile-vs-execute split, and the
collective byte accounting validated against the analytic volumes
(telemetry/collectives.py; the redistribution arithmetic of
arXiv:2112.01075 §2). Runs on the conftest CPU mesh (8 devices by default,
swept by scripts/run_ci.sh — byte expectations are computed from the live
mesh size, not hard-coded)."""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry as tm
from heat_tpu.core.communication import get_comm
from heat_tpu.telemetry import collectives as tcoll

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def telem(tmp_path):
    """Telemetry enabled with a fresh JSONL sink; always disabled + cleared
    afterwards so the rest of the suite runs on the no-op path."""
    sink = tmp_path / "events.jsonl"
    reg = tm.enable(str(sink))
    reg.clear()
    yield reg, sink
    tm.disable()
    reg.clear()


class TestEnableDisable:
    def test_disabled_by_default_and_noop(self):
        assert not tm.enabled()
        reg = tm.get_registry()
        before = len(reg.events)
        s = tm.span("nothing", bytes=123)
        with s as sp:
            sp.output(jnp.ones(2))
            sp.add_fields(extra=1)
        # the disabled span is one shared object — zero per-call allocation
        assert s is tm.span("something_else")
        assert len(reg.events) == before
        tm.trace_event("all_gather")
        assert len(reg.events) == before
        assert "traced.all_gather" not in reg.counters

    def test_enable_disable_cycle(self, tmp_path):
        reg = tm.enable(str(tmp_path / "s.jsonl"))
        try:
            assert tm.enabled()
            assert reg.sink_path == str(tmp_path / "s.jsonl")
        finally:
            tm.disable()
        assert not tm.enabled()
        assert reg.sink_path is None

    def test_disabled_resplit_emits_nothing(self):
        reg = tm.get_registry()
        reg.clear()
        x = ht.array(np.arange(32, dtype=np.float32).reshape(8, 4), split=0)
        x.resplit(1)
        assert [e for e in reg.events if e["kind"] == "span"] == []


class TestEventSchemaAndSink:
    def test_jsonl_schema(self, telem):
        reg, sink = telem
        with tm.span("alpha", bytes=10, collective="none"):
            pass
        tm.trace_event("psum", axis="proc")
        lines = [json.loads(l) for l in sink.read_text().splitlines() if l]
        assert len(lines) >= 2
        for ev in lines:
            assert isinstance(ev["ts"], float)
            assert isinstance(ev["kind"], str)
            assert isinstance(ev["name"], str)
        span_ev = next(e for e in lines if e["kind"] == "span")
        assert span_ev["name"] == "alpha"
        assert span_ev["bytes"] == 10
        assert span_ev["seconds"] >= 0
        assert {"depth", "parent"} <= set(span_ev)
        trace_ev = next(e for e in lines if e["kind"] == "collective_trace")
        assert trace_ev["name"] == "psum" and trace_ev["axis"] == "proc"
        # the sink and the in-memory stream carry identical records
        assert len(reg.events) == len(lines)

    def test_load_events_roundtrip(self, telem):
        reg, sink = telem
        with tm.span("one"):
            pass
        evs = tm.report.load_events(str(sink))
        assert [e["name"] for e in evs if e["kind"] == "span"] == ["one"]

    def test_counters_accumulate(self, telem):
        reg, _ = telem
        with tm.span("op", bytes=100):
            pass
        with tm.span("op", bytes=50):
            pass
        assert reg.counters["span.op.count"] == 2
        assert reg.counters["span.op.bytes"] == 150
        assert reg.counters["span.op.seconds"] > 0

    def test_clear_by_kind_keeps_other_records(self, telem):
        # the harness drops warmup spans this way — the compile and
        # collective-trace events (which only fire during warmup) and the
        # counters/watermarks must survive
        reg, _ = telem
        with tm.span("op", bytes=100):
            pass
        reg.emit("compile", "backend_compile", seconds=0.5)
        reg.high_water("live_bytes.total", 42)
        reg.clear(kinds=("span",))
        kinds = [e["kind"] for e in reg.events]
        assert "span" not in kinds
        assert "compile" in kinds
        assert reg.counters["span.op.count"] == 1
        assert reg.watermarks["live_bytes.total"] == 42
        reg.clear()
        assert not reg.events and not reg.counters and not reg.watermarks


class TestSpanNesting:
    def test_parent_and_depth(self, telem):
        reg, _ = telem
        with tm.span("outer"):
            with tm.span("inner"):
                pass
        spans = [e for e in reg.events if e["kind"] == "span"]
        inner, outer = spans  # inner exits (and is recorded) first
        assert inner["name"] == "inner"
        assert inner["parent"] == "outer" and inner["depth"] == 1
        assert outer["parent"] is None and outer["depth"] == 0

    def test_span_blocks_on_outputs(self, telem):
        reg, _ = telem
        x = jnp.ones((256, 256))
        f = jax.jit(lambda a: a @ a)
        f(x).block_until_ready()  # compile outside the span
        with tm.span("gemm") as sp:
            sp.output(f(x))
        ev = [e for e in reg.events if e["kind"] == "span"][-1]
        # the async dispatch returns in ~µs; a recorded duration at least
        # covers the host->ready wait (no strict lower bound on CPU, just
        # assert the span recorded a finite, nonnegative clock)
        assert ev["seconds"] >= 0

    def test_span_survives_exception(self, telem):
        reg, _ = telem
        with pytest.raises(ValueError):
            with tm.span("boom"):
                raise ValueError("x")
        errs = [e for e in reg.events if e["kind"] == "span_error"]
        assert len(errs) == 1 and errs[0]["name"] == "boom"
        # stack unwound: a follow-up span is top-level again
        with tm.span("after"):
            pass
        after = [e for e in reg.events if e["kind"] == "span"][-1]
        assert after["parent"] is None and after["depth"] == 0


class TestCompileSplit:
    def test_measure_compile_is_aot(self, telem):
        reg, _ = telem

        def f(x):
            return (x @ x.T).sum()

        x = jnp.ones((32, 32), jnp.float32)
        secs, compiled = tm.measure_compile(f, x)
        assert secs > 0
        # the AOT executable runs without recompiling
        out = compiled(x)
        np.testing.assert_allclose(np.asarray(out), 32.0 * 32 * 32)
        evs = [e for e in reg.events
               if e["kind"] == "compile" and e.get("mode") == "aot"]
        assert len(evs) == 1 and evs[0]["seconds"] == pytest.approx(secs)

    def test_compile_watcher_splits_compile_from_execute(self):
        # works with telemetry disabled — the harness uses it unconditionally
        @jax.jit
        def g(x):
            return jnp.tanh(x * 3.0).sum()

        x = jnp.ones((64,), jnp.float32)
        with tm.CompileWatcher() as first:
            g(x).block_until_ready()
        with tm.CompileWatcher() as second:
            g(x).block_until_ready()
        assert first.seconds > 0
        assert first.stages["backend_compile_duration"] > 0
        # cached second call: no backend compile attributed to it
        assert second.stages.get("backend_compile_duration", 0.0) == 0.0
        assert second.seconds < first.seconds


class TestCollectiveCostModel:
    def test_relayout_cases(self):
        b = 64 * 64 * 4
        assert tcoll.relayout_cost((64, 64), 4, 0, 0, 8).kind == "none"
        assert tcoll.relayout_cost((64, 64), 4, 0, 1, 1).kind == "none"
        c = tcoll.relayout_cost((64, 64), 4, None, 0, 8)
        assert c.kind == "local-slice" and c.bytes == 0
        c = tcoll.relayout_cost((64, 64), 4, 0, None, 8)
        assert c.kind == "all-gather" and c.bytes == b * 7
        c = tcoll.relayout_cost((64, 64), 4, 0, 1, 8)
        assert c.kind == "all-to-all" and c.bytes == b * 7 // 8
        assert c.as_fields() == {
            "collective": "all-to-all", "bytes": b * 7 // 8, "steps": 1
        }

    def test_kernel_costs(self):
        c = tcoll.tsqr_cost(64, 8, 4, 8)
        assert c.kind == "all-gather" and c.bytes == 8 * 7 * 8 * 8 * 4
        c = tcoll.ring_cdist_cost(16, 8, 4, 8)
        assert c.kind == "ppermute-ring" and c.steps == 8
        assert c.bytes == 8 * 8 * math.ceil(16 / 8) * 8 * 4
        c = tcoll.gram_ring_cost(64, 16, 4, 8)
        assert c.bytes > 0 and c.steps == 8
        for fn in (tcoll.tsqr_cost, tcoll.gram_ring_cost):
            assert fn(64, 8, 4, 1).kind == "none"
        assert tcoll.ring_cdist_cost(16, 8, 4, 1).kind == "none"


class TestByteAccounting:
    """Instrumented ops report the analytic wire volumes (computed from the
    live mesh size, so the run_ci.sh size sweep stays green)."""

    def test_resplit_all_to_all_volume(self, telem):
        reg, _ = telem
        p = get_comm().size
        xn = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
        x = ht.array(xn, split=0)
        reg.clear()
        y = x.resplit(1)
        np.testing.assert_allclose(y.numpy(), xn)
        spans = [e for e in reg.events
                 if e["kind"] == "span" and e["name"] == "resplit"]
        assert len(spans) == 1
        ev = spans[0]
        if p > 1:
            assert ev["collective"] == "all-to-all"
            assert ev["bytes"] == 64 * 64 * 4 * (p - 1) // p
        else:
            assert ev["collective"] == "none" and ev["bytes"] == 0
        assert ev["old_split"] == 0 and ev["new_split"] == 1
        # the inner relayout primitive nests under the op span
        inner = [e for e in reg.events
                 if e["kind"] == "span" and e["name"] == "relayout"]
        assert len(inner) == 1 and inner[0]["parent"] == "resplit"

    def test_ring_cdist_volume(self, telem):
        reg, _ = telem
        p = get_comm().size
        if p == 1:
            pytest.skip("ring kernel needs a >1-position mesh")
        rng = np.random.default_rng(0)
        xn = rng.standard_normal((16, 8)).astype(np.float32)
        yn = rng.standard_normal((12, 8)).astype(np.float32)
        x = ht.array(xn, split=0)
        y = ht.array(yn, split=0)
        reg.clear()
        d = ht.spatial.cdist(x, y, ring=True)
        ref = np.sqrt(((xn[:, None, :] - yn[None, :, :]) ** 2).sum(-1))
        np.testing.assert_allclose(d.numpy(), ref, atol=1e-4)
        spans = [e for e in reg.events
                 if e["kind"] == "span" and e["name"] == "ring_cdist"]
        assert len(spans) == 1
        ev = spans[0]
        # the default double-buffered schedule peels the final dead hop
        # (p-1 hops); HEAT_TPU_RING_OVERLAP=0 restores the p-hop serial
        # kernel (core/relayout_planner.ring_overlap)
        from heat_tpu.core import relayout_planner

        hops = p - 1 if relayout_planner.ring_overlap() else p
        assert ev["collective"] == "ppermute-ring" and ev["steps"] == hops
        assert ev["bytes"] == p * hops * math.ceil(12 / p) * 8 * 4

    def test_tsqr_volume(self, telem):
        reg, _ = telem
        p = get_comm().size
        if p == 1:
            pytest.skip("TSQR kernel needs a >1-position mesh")
        rng = np.random.default_rng(1)
        an = rng.standard_normal((64, 8)).astype(np.float32)
        a = ht.array(an, split=0)
        reg.clear()
        q, r = ht.linalg.qr(a)
        np.testing.assert_allclose((q @ r).numpy(), an, atol=1e-4)
        spans = [e for e in reg.events
                 if e["kind"] == "span" and e["name"] == "tsqr"]
        assert len(spans) == 1
        k1 = min(math.ceil(64 / p), 8)
        assert spans[0]["collective"] == "all-gather"
        assert spans[0]["bytes"] == p * (p - 1) * k1 * 8 * 4

    def test_traced_collective_events(self, telem):
        reg, _ = telem
        comm = get_comm()
        if comm.size == 1:
            pytest.skip("collective wrappers need a >1-position mesh")
        xn = np.arange(comm.padded_size(8), dtype=np.float32)
        xs = jax.device_put(xn, comm.sharding(0, 1))
        reg.clear()
        out = jax.shard_map(
            lambda v: comm.psum(jnp.sum(v)),
            mesh=comm.mesh,
            in_specs=comm.spec(0, 1),
            out_specs=jax.sharding.PartitionSpec(),
        )(xs)
        assert float(out) == pytest.approx(xn.sum())
        assert reg.counters.get("traced.psum", 0) >= 1
        names = [e["name"] for e in reg.events
                 if e["kind"] == "collective_trace"]
        assert "psum" in names


class TestMemoryWatermark:
    def test_watermark_snapshot_and_event(self, telem):
        reg, _ = telem
        keep = ht.array(np.ones((32, 32), dtype=np.float32), split=0)
        snap = tm.memory.watermark("unit")
        assert snap["total"] > 0 and snap["arrays"] > 0
        assert sum(snap["per_device"].values()) == snap["total"]
        evs = [e for e in reg.events if e["kind"] == "memory"]
        assert len(evs) == 1 and evs[0]["name"] == "unit"
        assert reg.watermarks["live_bytes.total"] >= snap["total"] or \
            reg.watermarks["live_bytes.total"] == snap["total"]
        del keep

    def test_probe_works_disabled(self):
        # plain probe: no event, but a usable snapshot
        reg = tm.get_registry()
        before = len(reg.events)
        snap = tm.memory.watermark("quiet")
        assert snap["total"] >= 0
        assert len(reg.events) == before


class TestReport:
    def test_summarize_shape(self):
        events = [
            {"kind": "span", "name": "resplit", "seconds": 0.5,
             "bytes": 100, "collective": "all-to-all"},
            {"kind": "span", "name": "resplit", "seconds": 0.25, "bytes": 50},
            {"kind": "span", "name": "tsqr", "seconds": 0.1, "bytes": 7},
            # nested primitive under an op span: same cost, same window —
            # must NOT become a second phase row (double-counting)
            {"kind": "span", "name": "relayout", "seconds": 0.5,
             "bytes": 100, "depth": 1, "parent": "resplit"},
            {"kind": "compile", "name": "backend_compile", "seconds": 0.125},
            {"kind": "compile", "name": "f", "seconds": 0.25, "mode": "aot"},
            {"kind": "collective_trace", "name": "psum"},
            {"kind": "collective_trace", "name": "psum"},
            {"kind": "memory", "name": "w", "total": 10},
        ]
        s = tm.report.summarize(events, watermarks={"live_bytes.total": 123})
        assert s["phases"]["resplit"] == {
            "calls": 2, "execute_seconds": 0.75, "bytes_moved": 150,
            "collective": "all-to-all",
        }
        assert s["phases"]["tsqr"]["bytes_moved"] == 7
        assert "relayout" not in s["phases"]
        assert s["compile_seconds"] == pytest.approx(0.375)
        assert s["compile_events"] == 2
        assert s["traced_collectives"] == {"psum": 2}
        assert s["peak_live_bytes"] == 123
        assert s["events"] == len(events)

    def test_bench_fields_gated(self, telem):
        with tm.span("op", bytes=5):
            pass
        fields = tm.report.bench_fields()
        assert "telemetry" in fields
        assert fields["telemetry"]["phases"]["op"]["bytes_moved"] == 5
        tm.disable()
        assert tm.report.bench_fields() == {}


class TestEnvActivation:
    def test_env_var_enables_and_streams_jsonl(self, tmp_path):
        """HEAT_TPU_TELEMETRY=1 turns recording on at import and streams
        span events (with analytic bytes) to HEAT_TPU_TELEMETRY_SINK."""
        sink = tmp_path / "ev.jsonl"
        code = (
            "import heat_tpu as ht, numpy as np\n"
            "assert ht.telemetry.enabled()\n"
            "x = ht.array(np.arange(64, dtype=np.float32).reshape(16, 4),"
            " split=0)\n"
            "y = x.resplit(1)\n"
            "print('DEVICES', ht.core.communication.get_comm().size)\n"
        )
        env = dict(os.environ)
        env.update({
            "HEAT_TPU_TELEMETRY": "1",
            "HEAT_TPU_TELEMETRY_SINK": str(sink),
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        })
        r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-800:]
        evs = tm.report.load_events(str(sink))
        spans = [e for e in evs if e["kind"] == "span"
                 and e["name"] == "resplit"]
        assert len(spans) == 1
        assert spans[0]["bytes"] == 16 * 4 * 4 * 3 // 4  # all-to-all, p=4
        assert spans[0]["collective"] == "all-to-all"
