"""heat_tpu.telemetry.hlo — the ground-truth XLA collective auditor.

Two layers, mirroring the module's tolerance-to-XLA-noise design:

* **golden-HLO fixtures** — literal optimized-HLO instruction lines (as
  emitted by the baked XLA on the CPU backend) pin the parser grammar:
  opcodes, tuple-form all-to-all, literal and iota replica_groups,
  source_target_pairs, async start/done pairs, and the wire-byte models;
* **live oracles** — `lower().compile()` on the conftest CPU mesh checks
  that resplit(0→1) really emits exactly the predicted all-to-all (the CI
  drift oracle), and that TSQR / ring-cdist / CholeskyQR2 audits agree
  with the analytic model. These recompute expectations from the live
  mesh size, so the run_ci.sh size sweep stays green.
"""

import json

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry as tm
from heat_tpu.core.communication import get_comm
from heat_tpu.telemetry import collectives as tcoll
from heat_tpu.telemetry import hlo


@pytest.fixture
def telem(tmp_path):
    sink = tmp_path / "events.jsonl"
    reg = tm.enable(str(sink))
    reg.clear()
    hlo.clear()
    yield reg, sink
    tm.disable()
    reg.clear()
    hlo.clear()


@pytest.fixture
def fresh_audits():
    """Audit state isolated (no telemetry needed — audits record locally)."""
    hlo.clear()
    yield
    hlo.disable_audit()
    hlo.clear()


# -- golden-HLO parser fixtures ----------------------------------------------
# Literal lines captured from `jit(...).lower(...).compile().as_text()` on
# the CPU backend; the parser must survive exactly this grammar.

GOLDEN_ALL_GATHER = (
    "ROOT %all-gather = f32[64,32]{1,0} all-gather(f32[8,32]{1,0} %param), "
    "channel_id=1, replica_groups=[1,8]<=[8], dimensions={0}, "
    "use_global_device_ids=true"
)

GOLDEN_ALL_TO_ALL_TUPLE = (
    "%all-to-all.1 = (f32[4,1,8]{2,1,0}, f32[4,1,8]{2,1,0}, "
    "f32[4,1,8]{2,1,0}, f32[4,1,8]{2,1,0}) all-to-all("
    "f32[4,1,8]{2,1,0} %bitcast_slice_fusion.3, "
    "f32[4,1,8]{2,1,0} %bitcast_slice_fusion.2, "
    "f32[4,1,8]{2,1,0} %bitcast_slice_fusion.1, "
    "f32[4,1,8]{2,1,0} %bitcast_slice_fusion), "
    "channel_id=1, replica_groups={{0,1,2,3}}"
)

GOLDEN_PERMUTE = (
    "%collective-permute.1 = f32[8,32]{1,0} collective-permute("
    "f32[8,32]{1,0} %get-tuple-element.11), channel_id=1, "
    "source_target_pairs={{0,1},{1,2},{2,3},{3,0}}, "
    'metadata={op_name="jit(ring)/jit(main)/jit(shmap_body)/while/body/'
    'ppermute" source_file="distance.py" source_line=30}'
)

GOLDEN_ALL_REDUCE = (
    "ROOT %all-reduce.1 = f32[8,32]{1,0} all-reduce(f32[8,32]{1,0} %param), "
    "channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, "
    "use_global_device_ids=true, to_apply=%region_0.4"
)

GOLDEN_REDUCE_SCATTER = (
    "%reduce-scatter = f32[1,32]{1,0} reduce-scatter(f32[8,32]{1,0} %p), "
    "channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, "
    "to_apply=%add"
)

# consumer of a collective result: must NOT parse as a collective
GOLDEN_GTE = (
    "%get-tuple-element.1 = f32[4,1,8]{2,1,0} get-tuple-element("
    "(f32[4,1,8]{2,1,0}, f32[4,1,8]{2,1,0}) %all-to-all.1), index=0"
)

GOLDEN_ASYNC_PAIR = (
    "%all-gather-start = (f32[8,32]{1,0}, f32[64,32]{1,0}) all-gather-start("
    "f32[8,32]{1,0} %p), channel_id=1, replica_groups=[1,8]<=[8], "
    "dimensions={0}\n"
    "%all-gather-done = f32[64,32]{1,0} all-gather-done("
    "(f32[8,32]{1,0}, f32[64,32]{1,0}) %all-gather-start)"
)


# jax 0.9.0's XLA prints operands as bare names (no types): recorded from
# `jit(shard_map(...)).lower(x).compile().as_text()` on the 8-device CPU
# mesh. Operand bytes must come from the defining instructions' result
# types — parsed as written, every collective would read in_bytes == 0.
GOLDEN_UNTYPED_MODULE = """\
ENTRY %main.6_spmd (param.1: f32[8,32]) -> f32[1,256] {
  %param.1 = f32[8,32]{1,0} parameter(0), sharding={devices=[8,1]<=[8]}
  %wrapped_slice = f32[1,32]{1,0} fusion(%param.1), kind=kLoop, calls=%wrapped_slice_computation
  %wrapped_slice.1 = f32[1,32]{1,0} fusion(%param.1), kind=kLoop, calls=%wrapped_slice_computation.1
  %all-to-all = (f32[1,32]{1,0}, f32[1,32]{1,0}) all-to-all(%wrapped_slice, /*index=1*/%wrapped_slice.1), channel_id=1, replica_groups={{0,1},{2,3},{4,5},{6,7}}, metadata={op_name="jit(f)/shard_map/all_to_all" stack_frame_id=3}
  %get-tuple-element = f32[1,32]{1,0} get-tuple-element(%all-to-all), index=0
  %multiply_bitcast_fusion = f32[1,256]{1,0} fusion(%get-tuple-element), kind=kLoop, calls=%fused_computation
  %psum_invariant.7 = f32[1,256]{1,0} all-reduce(%multiply_bitcast_fusion), channel_id=2, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%region_0.0
  %all_gather.3 = f32[8,256]{1,0} all-gather(%psum_invariant.7), channel_id=3, replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}, use_global_device_ids=true
  %ppermute.3 = f32[8,256]{1,0} collective-permute(%all_gather.3), channel_id=4, source_target_pairs={{0,1},{1,2},{2,3},{3,4},{4,5},{5,6},{6,7},{7,0}}
  ROOT %reduce_scatter.7 = f32[1,256]{1,0} reduce-scatter(%ppermute.3), channel_id=5, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, dimensions={0}, to_apply=%region_1.0
}
"""


class TestParserGoldens:
    def test_untyped_operands_resolve_through_their_definitions(self):
        recs = {c.op: c for c in hlo.parse_hlo(GOLDEN_UNTYPED_MODULE)}
        assert set(recs) == {
            "all-to-all", "all-reduce", "all-gather", "collective-permute",
            "reduce-scatter",
        }
        f32 = 4
        a2a = recs["all-to-all"]
        assert a2a.in_bytes == 2 * 32 * f32          # two (1, 32) operands
        assert a2a.wire_bytes == a2a.in_bytes * 1 * 8 // 2
        ar = recs["all-reduce"]
        assert ar.in_bytes == 256 * f32
        assert ar.wire_bytes == 2 * 256 * f32 * 7
        assert recs["all-gather"].in_bytes == 256 * f32
        assert recs["all-gather"].wire_bytes == 8 * 256 * f32 * 7
        cp = recs["collective-permute"]
        assert cp.in_bytes == 8 * 256 * f32
        assert cp.wire_bytes == 8 * cp.in_bytes      # one payload per pair
        rs = recs["reduce-scatter"]
        assert rs.in_bytes == 8 * 256 * f32
        assert rs.wire_bytes == rs.in_bytes * 7

    def test_all_gather_iota_groups(self):
        (c,) = hlo.parse_hlo(GOLDEN_ALL_GATHER)
        assert c.op == "all-gather"
        assert c.dtype == "f32"
        assert c.shapes == ((64, 32),)
        assert c.in_bytes == 8 * 32 * 4
        assert c.out_bytes == 64 * 32 * 4
        assert c.group_size == 8 and c.n_participants == 8
        # every device receives the 7/8 of the result it does not hold
        assert c.wire_bytes == 64 * 32 * 4 * 7

    def test_all_to_all_tuple_form(self):
        (c,) = hlo.parse_hlo(GOLDEN_ALL_TO_ALL_TUPLE)
        assert c.op == "all-to-all"
        assert c.group_size == 4
        assert c.groups == ((0, 1, 2, 3),)
        # per-participant payload: 4 tuple operands of (4,1,8) f32
        assert c.in_bytes == 4 * 4 * 1 * 8 * 4
        assert c.wire_bytes == c.in_bytes * 3  # keeps its own 1/4

    def test_collective_permute_pairs(self):
        (c,) = hlo.parse_hlo(GOLDEN_PERMUTE)
        assert c.op == "collective-permute"
        assert c.groups == ((0, 1), (1, 2), (2, 3), (3, 0))
        assert c.in_bytes == 8 * 32 * 4
        assert c.wire_bytes == 4 * 8 * 32 * 4  # one payload per pair
        assert "ppermute" in c.op_name

    def test_all_reduce_and_reduce_scatter(self):
        (ar,) = hlo.parse_hlo(GOLDEN_ALL_REDUCE)
        assert ar.op == "all-reduce"
        assert ar.wire_bytes == 2 * 8 * 32 * 4 * 7  # ring: 2·B·(g−1)
        (rs,) = hlo.parse_hlo(GOLDEN_REDUCE_SCATTER)
        assert rs.op == "reduce-scatter"
        assert rs.wire_bytes == 8 * 32 * 4 * 7

    def test_consumer_lines_do_not_match(self):
        assert hlo.parse_hlo(GOLDEN_GTE) == []

    def test_async_pair_counts_once(self):
        recs = hlo.parse_hlo(GOLDEN_ASYNC_PAIR)
        assert [c.op for c in recs] == ["all-gather"]
        (c,) = recs
        # the start's tuple result aliases the operand buffer — the wire
        # model must count only the gathered result, identical to the
        # sync form (TPU emits the async pair by default, so an overcount
        # here would flag spurious byte-drift on every TPU audit)
        assert c.out_bytes == 64 * 32 * 4
        assert c.wire_bytes == 64 * 32 * 4 * 7

    def test_whole_module_scan(self):
        text = "\n".join([
            "HloModule jit_f, entry_computation_layout={...}",
            "ENTRY %main {",
            GOLDEN_ALL_TO_ALL_TUPLE,
            GOLDEN_GTE,
            GOLDEN_PERMUTE,
            "}",
        ])
        audit = hlo.CollectiveAudit(hlo.parse_hlo(text), n_devices=4)
        assert audit.counts() == {"all-to-all": 1, "collective-permute": 1}
        assert audit.total_wire() == sum(c.wire_bytes for c in audit.collectives)


class TestCompare:
    def _audit(self, text):
        return hlo.CollectiveAudit(hlo.parse_hlo(text), n_devices=8)

    def test_matching_prediction_ok(self):
        audit = self._audit(GOLDEN_ALL_GATHER)
        pred = tcoll.CollectiveCost("all-gather", 64 * 32 * 4 * 7)
        rep = hlo.compare(audit, pred)
        assert rep.ok and rep.drifts == []
        assert rep.emitted_bytes == rep.predicted_bytes

    def test_byte_drift_flagged(self):
        audit = self._audit(GOLDEN_ALL_GATHER)
        pred = tcoll.CollectiveCost("all-gather", 64 * 32 * 4 * 7 * 3)
        rep = hlo.compare(audit, pred, tolerance=0.1)
        assert not rep.ok
        assert [d.reason for d in rep.drifts] == ["byte-drift"]

    def test_tolerance_absorbs_padding_noise(self):
        audit = self._audit(GOLDEN_ALL_GATHER)
        pred = tcoll.CollectiveCost("all-gather", int(64 * 32 * 4 * 7 * 1.05))
        assert hlo.compare(audit, pred, tolerance=0.1).ok

    def test_missing_collective(self):
        audit = self._audit(GOLDEN_ALL_GATHER)
        pred = tcoll.CollectiveCost("all-to-all", 1000)
        rep = hlo.compare(audit, pred)
        reasons = {d.reason for d in rep.drifts}
        assert "missing-collective" in reasons
        assert "unexpected-collective" in reasons  # the stray all-gather

    def test_unexpected_collective_on_none_prediction(self):
        audit = self._audit(GOLDEN_ALL_GATHER)
        rep = hlo.compare(audit, tcoll.CollectiveCost("none", 0))
        assert not rep.ok
        assert [d.reason for d in rep.drifts] == ["unexpected-collective"]

    def test_clean_program_vs_none_prediction(self):
        audit = self._audit("")
        assert hlo.compare(audit, tcoll.CollectiveCost("none", 0)).ok
        assert hlo.compare(audit, tcoll.CollectiveCost("local-slice", 0)).ok

    def test_ring_steps_scaling(self):
        audit = self._audit(GOLDEN_PERMUTE)
        per_exec = 4 * 8 * 32 * 4
        pred = tcoll.CollectiveCost("ppermute-ring", per_exec * 4, steps=4)
        rep = hlo.compare(audit, pred)
        assert rep.ok and rep.emitted_bytes == per_exec * 4

    def test_compound_kind(self):
        audit = self._audit(GOLDEN_PERMUTE + "\n" + GOLDEN_ALL_GATHER)
        total = 4 * 8 * 32 * 4 * 4 + 64 * 32 * 4 * 7
        pred = tcoll.CollectiveCost(
            "ppermute-ring+all-gather", total, steps=4
        )
        assert hlo.compare(audit, pred).ok


class TestAuditCall:
    def test_never_raises(self, fresh_audits):
        def broken():
            raise RuntimeError("lowering exploded")

        with pytest.warns(UserWarning, match="audit of 'x' failed"):
            assert hlo.audit_call("x", broken) is None

    def test_memoized_on_key(self, fresh_audits):
        calls = []

        def build():
            import jax
            import jax.numpy as jnp

            calls.append(1)
            return jax.jit(lambda v: v + 1), (jnp.ones(4),)

        hlo.audit_call("memo", build, key=("memo", 4))
        hlo.audit_call("memo", build, key=("memo", 4))
        assert len(calls) == 1
        assert len([r for r in hlo.recent() if r.site == "memo"]) == 2


class TestResplitDriftOracle:
    """The CI drift oracle (ISSUE 2 satellite): resplit(0→1) on the 1×N
    CPU mesh emits exactly the predicted all-to-all — live
    ``lower().compile()`` parse, expectations from the live mesh size."""

    def test_resplit_0_to_1_emits_exactly_one_all_to_all(self, fresh_audits):
        p = get_comm().size
        if p == 1:
            pytest.skip("a 1-position mesh emits no collectives")
        xn = np.arange(64 * 64, dtype=np.float32).reshape(64, 64)
        x = ht.array(xn, split=0)
        y = x.resplit(1, audit=True)
        np.testing.assert_allclose(y.numpy(), xn)
        rec = hlo.last_audit("resplit")
        assert rec is not None and rec.report is not None
        # exactly the predicted primitive — nothing more, nothing less
        assert rec.audit.counts() == {"all-to-all": 1}
        assert rec.report.ok, rec.report.summary()
        # the compare target is the padded physical program XLA lowered
        pad = -(-64 // p) * p
        pred = tcoll.relayout_cost((pad, pad), 4, 0, 1, p)
        assert rec.report.predicted_bytes == pred.bytes
        assert abs(rec.report.emitted_bytes - pred.bytes) <= 0.1 * pred.bytes

    def test_padded_shape_does_not_false_flag(self, fresh_audits):
        # the (7,5)/4-mesh case from review: mesh divides neither dim, XLA
        # moves the doubly-padded buffer — the schedule is exactly as
        # predicted and the audit must say so (no spurious byte-drift)
        p = get_comm().size
        if p == 1:
            pytest.skip("a 1-position mesh emits no collectives")
        x = ht.array(np.ones((7, 5), dtype=np.float32), split=0)
        x.resplit(1, audit=True)
        rec = hlo.last_audit("resplit")
        assert rec.audit.counts() == {"all-to-all": 1}
        assert rec.report.ok, rec.report.summary()

    def test_resplit_to_replicated_emits_all_gather(self, fresh_audits):
        p = get_comm().size
        if p == 1:
            pytest.skip("a 1-position mesh emits no collectives")
        x = ht.array(np.ones((64, 32), dtype=np.float32), split=0)
        x.resplit(None, audit=True)
        rec = hlo.last_audit("resplit")
        assert rec.audit.counts() == {"all-gather": 1}
        assert rec.report.ok, rec.report.summary()

    def test_global_flag_audits_without_kwarg(self, fresh_audits):
        p = get_comm().size
        if p == 1:
            pytest.skip("a 1-position mesh emits no collectives")
        hlo.enable_audit()
        x = ht.array(np.ones((32, 16), dtype=np.float32), split=0)
        x.resplit(1)
        rec = hlo.last_audit("resplit")
        assert rec is not None and rec.audit.counts() == {"all-to-all": 1}

    def test_audit_events_reach_summary(self, telem):
        reg, _ = telem
        p = get_comm().size
        if p == 1:
            pytest.skip("a 1-position mesh emits no collectives")
        x = ht.array(np.ones((32, 16), dtype=np.float32), split=0)
        x.resplit(1, audit=True)
        evs = [e for e in reg.events if e["kind"] == "hlo_audit"]
        assert len(evs) == 1 and evs[0]["name"] == "resplit"
        assert evs[0]["ok"] and evs[0]["drift"] == 0
        s = tm.report.summarize()
        sec = s["hlo_collectives"]
        assert sec["audits"] == 1 and sec["drift"] == 0
        assert sec["sites"]["resplit"]["instructions"] == {"all-to-all": 1}


class TestKernelAudits:
    def test_tsqr_audit(self, fresh_audits):
        p = get_comm().size
        if p == 1:
            pytest.skip("TSQR kernel needs a >1-position mesh")
        an = np.random.default_rng(1).standard_normal((64, 8)).astype(np.float32)
        q, r = ht.linalg.qr(ht.array(an, split=0), audit=True)
        np.testing.assert_allclose((q @ r).numpy(), an, atol=1e-4)
        rec = hlo.last_audit("tsqr")
        assert rec.audit.counts().get("all-gather", 0) >= 1
        assert rec.report.ok, rec.report.summary()

    def test_ring_cdist_audit(self, fresh_audits):
        p = get_comm().size
        if p == 1:
            pytest.skip("ring kernel needs a >1-position mesh")
        rng = np.random.default_rng(2)
        x = ht.array(rng.standard_normal((16, 8)).astype(np.float32), split=0)
        y = ht.array(rng.standard_normal((16, 8)).astype(np.float32), split=0)
        ht.spatial.cdist(x, y, ring=True, audit=True)
        rec = hlo.last_audit("ring_cdist")
        assert rec.audit.counts() == {"collective-permute": 1}
        assert rec.report.ok, rec.report.summary()

    def test_cholqr_gram_ring_audit(self, fresh_audits):
        p = get_comm().size
        if p == 1:
            pytest.skip("CholeskyQR2 kernel needs a >1-position mesh")
        an = np.random.default_rng(3).standard_normal((64, 16)).astype(np.float32)
        ht.linalg.qr(ht.array(an, split=1), audit=True)
        rec = hlo.last_audit("cholqr_gram_ring")
        counts = rec.audit.counts()
        assert counts.get("collective-permute", 0) >= 1
        assert counts.get("all-gather", 0) >= 1
        assert rec.report.ok, rec.report.summary()


class TestAuditCLI:
    def test_cli_reports_zero_drift(self, capsys):
        from heat_tpu.telemetry import audit as audit_cli

        was_enabled = tm.enabled()
        try:
            rc = audit_cli.main(
                ["ht.resplit(ht.random.randn(32, 16, split=0), 1)"]
            )
        finally:
            if not was_enabled:
                tm.disable()
                tm.get_registry().clear()
            hlo.disable_audit()
            hlo.clear()
        out = json.loads(capsys.readouterr().out)
        p = get_comm().size
        if p > 1:
            assert rc == 0 and out["ok"]
            assert out["n_audits"] >= 1
            sites = [a["site"] for a in out["audits"]]
            assert "resplit" in sites
        else:
            # zero audits must NOT report success — nothing was verified
            assert rc == 1 and not out["ok"]
            assert "error" in out
