"""heat_tpu.autotune (ISSUE 11): search space from the knob registry,
analytic pruning ordered by the collective cost model, measured trials
that never pick worse than default, error-budget refusal of lossy modes,
DB round-trip + foreign-record rejection, second-process zero-trial warm
start, and the default-off dispatch guarantee."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import _knobs as knobs
from heat_tpu import autotune as at
from heat_tpu import telemetry as tm
from heat_tpu.autotune import cost, db, space, trials
from heat_tpu.core import collective_prec
from heat_tpu.core import program_cache as pc
from heat_tpu.telemetry import collectives as cost_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEARCH_PLAN = ["HEAT_TPU_RELAYOUT_PLAN"]
SEARCH_PREC = ["HEAT_TPU_COLLECTIVE_PREC"]


@pytest.fixture(autouse=True)
def _clean():
    at.reset()
    knobs.clear_overrides()
    yield
    at.reset()
    knobs.clear_overrides()
    tm.disable()
    tm.get_registry().clear()


def _resplit_workload(n=256, f=32, seed=0):
    rng = np.random.default_rng(seed)
    x = ht.array(rng.standard_normal((n, f)).astype(np.float32), split=0)
    return x, (lambda: x.resplit(1).larray)


# -- knob overlay (the adoption mechanism) ------------------------------------


class TestKnobOverlay:
    def test_override_wins_over_env_and_restores(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_FUSION_DEPTH", "32")
        assert knobs.get("HEAT_TPU_FUSION_DEPTH") == 32
        with knobs.overlay({"HEAT_TPU_FUSION_DEPTH": "8"}):
            assert knobs.get("HEAT_TPU_FUSION_DEPTH") == 8
            assert knobs.raw("HEAT_TPU_FUSION_DEPTH") == "8"
        assert knobs.get("HEAT_TPU_FUSION_DEPTH") == 32

    def test_overlay_nests_and_restores_absence(self):
        assert knobs.raw("HEAT_TPU_RELAYOUT_PLAN") is None
        with knobs.overlay({"HEAT_TPU_RELAYOUT_PLAN": "chunked"}):
            with knobs.overlay({"HEAT_TPU_RELAYOUT_PLAN": "alltoall"}):
                assert knobs.get("HEAT_TPU_RELAYOUT_PLAN") == "alltoall"
            assert knobs.get("HEAT_TPU_RELAYOUT_PLAN") == "chunked"
        assert knobs.raw("HEAT_TPU_RELAYOUT_PLAN") is None

    def test_unregistered_override_rejected(self):
        with pytest.raises(KeyError):
            knobs.set_override("HEAT_TPU_NOT_A_KNOB", "1")

    def test_every_consumer_sees_tuned_values(self):
        """The overlay rides the registry's one read choke point, so the
        modules that parse knobs themselves see tuned values live."""
        from heat_tpu.core import fusion, relayout_planner

        with knobs.overlay({
            "HEAT_TPU_RELAYOUT_PLAN": "monolithic",
            "HEAT_TPU_FUSION_DEPTH": "4",
            "HEAT_TPU_COLLECTIVE_PREC": "bf16",
        }):
            assert relayout_planner.mode() == "monolithic"
            assert fusion.depth_cap() == 4
            assert collective_prec.mode() == "bf16"


# -- tunable metadata (search space declared next to the knob) ----------------


class TestTunableMetadata:
    def test_declared_search_spaces_are_sane(self):
        tun = knobs.tunables()
        assert len(tun) >= 12
        for name, k in tun.items():
            t = k.tunable
            assert t.kind in ("exact", "lossy", "neutral"), name
            assert t.values and all(
                isinstance(v, str) and v for v in t.values
            ), name
            if t.kind == "lossy":
                assert t.exact_value in t.values, name
            if k.type == "enum":
                assert set(t.values) <= set(k.choices), name

    def test_lossy_classes_cover_the_accuracy_frontier_knobs(self):
        for name in ("HEAT_TPU_COLLECTIVE_PREC", "HEAT_TPU_SERVE_EXACT"):
            assert knobs.REGISTRY[name].tunable.kind == "lossy", name
        for name in ("HEAT_TPU_RELAYOUT_PLAN", "HEAT_TPU_FUSION_DEPTH",
                     "HEAT_TPU_RING_OVERLAP"):
            assert knobs.REGISTRY[name].tunable.kind == "exact", name

    def test_autotune_knobs_registered(self):
        for name in ("HEAT_TPU_AUTOTUNE", "HEAT_TPU_TUNE_DB",
                     "HEAT_TPU_AUTOTUNE_TRIALS", "HEAT_TPU_AUTOTUNE_BUDGET",
                     "HEAT_TPU_CI_SKIP_AUTOTUNE"):
            assert name in knobs.REGISTRY, name
        assert knobs.get("HEAT_TPU_AUTOTUNE") is False  # default-off


# -- candidate lattice --------------------------------------------------------


class TestSpace:
    def test_default_config_is_candidate_zero(self):
        cfgs = space.candidates(SEARCH_PLAN)
        assert cfgs[0] == {"HEAT_TPU_RELAYOUT_PLAN": "auto"}
        assert len(cfgs) == 4

    def test_lossy_pinned_without_budget(self):
        cfgs = space.candidates(SEARCH_PLAN + SEARCH_PREC)
        assert all(
            c["HEAT_TPU_COLLECTIVE_PREC"] == "off" for c in cfgs
        )
        cfgs = space.candidates(
            SEARCH_PLAN + SEARCH_PREC, error_budget=0.01
        )
        assert {c["HEAT_TPU_COLLECTIVE_PREC"] for c in cfgs} == {
            "off", "bf16", "int8", "blockwise"
        }

    def test_env_value_joins_the_lattice(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_FUSION_DEPTH", "12")
        cfgs = space.candidates(["HEAT_TPU_FUSION_DEPTH"])
        assert cfgs[0] == {"HEAT_TPU_FUSION_DEPTH": "12"}
        assert {c["HEAT_TPU_FUSION_DEPTH"] for c in cfgs} == {
            "12", "4", "8", "16", "32", "64"
        }

    def test_exact_variant_and_lossy_shift(self):
        base = space.default_config(SEARCH_PREC + SEARCH_PLAN)
        assert space.exact_variant(base)["HEAT_TPU_COLLECTIVE_PREC"] == "off"
        shifted = dict(base, HEAT_TPU_COLLECTIVE_PREC="int8")
        assert space.is_lossy_shift(shifted, base)
        exact_shift = dict(base, HEAT_TPU_RELAYOUT_PLAN="chunked")
        assert not space.is_lossy_shift(exact_shift, base)

    def test_untunable_knob_rejected(self):
        with pytest.raises(ValueError, match="tunable"):
            space.candidates(["HEAT_TPU_TELEMETRY"])


# -- analytic pruning ---------------------------------------------------------


class TestCostPruning:
    def test_pruning_order_matches_the_analytic_model(self):
        """The offline rank over precision modes must be EXACTLY the
        collective cost model's byte ordering for the same signature."""
        gshape, itemsize, p = (4096, 256), 4, 4
        fn = cost.relayout_cost_fn(gshape, itemsize, 0, 1, p)
        cfgs = [
            {"HEAT_TPU_RELAYOUT_PLAN": "alltoall",
             "HEAT_TPU_COLLECTIVE_PREC": m}
            for m in ("off", "bf16", "int8", "blockwise")
        ]
        ranked = cost.rank(cfgs, fn)
        got = [cfg["HEAT_TPU_COLLECTIVE_PREC"] for _, _, cfg in ranked]
        expected = sorted(
            ("off", "bf16", "int8", "blockwise"),
            key=lambda m: cost_model.relayout_cost(
                gshape, itemsize, 0, 1, p, precision=m
            ).bytes,
        )
        assert got == expected
        # and the predicted numbers ARE the model's numbers
        for c, _, cfg in ranked:
            m = cfg["HEAT_TPU_COLLECTIVE_PREC"]
            assert c == cost_model.relayout_cost(
                gshape, itemsize, 0, 1, p, precision=m
            ).bytes

    def test_prune_always_keeps_default_first(self):
        fn = cost.relayout_cost_fn((4096, 256), 4, 0, 1, 4)
        cfgs = space.candidates(SEARCH_PREC + SEARCH_PLAN,
                                error_budget=0.01)
        kept = cost.prune(cfgs, fn, keep=3)
        assert kept[0] == cfgs[0]
        assert len(kept) == 3

    def test_temp_model_marks_infeasible(self):
        """A budget below even the chunked temp need prices to inf —
        the memory_analysis-calibrated feasibility gate."""
        fn = cost.relayout_cost_fn((4096, 256), 4, 0, 1, 4, budget=1)
        c = fn({"HEAT_TPU_RELAYOUT_PLAN": "monolithic",
                "HEAT_TPU_COLLECTIVE_PREC": "off"})
        assert c == float("inf")

    def test_no_model_measures_everything(self):
        cfgs = space.candidates(SEARCH_PLAN)
        assert cost.prune(cfgs, None, keep=2) == cfgs


# -- trial machinery ----------------------------------------------------------


class TestTrials:
    def test_robust_median_rejects_outliers(self):
        assert trials.robust_median([1.0, 1.01, 0.99, 1.0, 50.0]) == 1.0
        assert trials.robust_median([2.0]) == 2.0

    def test_digest_is_bit_and_dtype_exact(self):
        a = np.arange(6, dtype=np.float32)
        assert trials.digest(a) == trials.digest(a.copy())
        assert trials.digest(a) != trials.digest(a.astype(np.float64))
        assert trials.digest(a) != trials.digest(a.reshape(2, 3))
        b = a.copy()
        b[3] = np.nextafter(b[3], np.inf)
        assert trials.digest(a) != trials.digest(b)

    def test_max_rel_err(self):
        ref = np.array([0.0, 2.0, -4.0])
        out = ref + np.array([0.0, 0.0, 0.04])
        assert trials.max_rel_err(out, ref) == pytest.approx(0.01)
        assert trials.max_rel_err(np.zeros(2), np.zeros(3)) == float("inf")


# -- persistent tuning DB -----------------------------------------------------


class TestTuneDB:
    def _record(self, key, site="resplit", mesh=None):
        return {
            "schema": db.SCHEMA, "key": key, "site": site,
            "signature": "sig", "mesh": mesh or db.mesh_fingerprint(),
            "config": {"HEAT_TPU_RELAYOUT_PLAN": "alltoall"},
            "baseline_wall": 1.0, "tuned_wall": 0.5, "created": 0.0,
        }

    def test_key_is_stable_and_signature_sensitive(self):
        mesh = db.mesh_fingerprint()
        k1 = db.tune_key("resplit", ((256, 32), 0, 1), mesh)
        assert k1 == db.tune_key("resplit", ((256, 32), 0, 1), mesh)
        assert k1 != db.tune_key("resplit", ((256, 33), 0, 1), mesh)
        other = dict(mesh, devices=mesh["devices"] + 1)
        assert k1 != db.tune_key("resplit", ((256, 32), 0, 1), other)

    def test_round_trip(self, tmp_path):
        d = db.TuneDB(str(tmp_path / "db"))
        key = db.tune_key("resplit", "sig")
        path = d.store(self._record(key))
        assert os.path.basename(path) == f"{key}.json"
        rec = d.lookup(key)
        assert rec is not None and rec["site"] == "resplit"
        assert [r["key"] for r in d.records()] == [key]

    def test_corrupt_record_cleanly_rejected(self, tmp_path):
        d = db.TuneDB(str(tmp_path / "db"))
        os.makedirs(d.path)  # the dir is otherwise created on first store
        key = db.tune_key("resplit", "sig")
        with open(os.path.join(d.path, f"{key}.json"), "w") as f:
            f.write('{"schema": 1, "key": TRUNCATED')
        assert d.lookup(key) is None
        assert list(d.records()) == []

    def test_foreign_records_cleanly_rejected(self, tmp_path):
        d = db.TuneDB(str(tmp_path / "db"))
        os.makedirs(d.path)  # the dir is otherwise created on first store
        mesh = db.mesh_fingerprint()
        # wrong mesh topology
        foreign = dict(mesh, devices=mesh["devices"] + 1)
        key = db.tune_key("resplit", "sig", foreign)
        rec = self._record(key, mesh=foreign)
        with open(os.path.join(d.path, f"{key}.json"), "w") as f:
            json.dump(rec, f)
        assert d.lookup(key) is None
        # schema drift
        key2 = db.tune_key("reduce", "sig")
        rec2 = dict(self._record(key2, site="reduce"), schema=db.SCHEMA + 1)
        with open(os.path.join(d.path, f"{key2}.json"), "w") as f:
            json.dump(rec2, f)
        assert d.lookup(key2) is None
        # key/filename mismatch (a renamed record is foreign)
        key3 = db.tune_key("serve", "sig")
        with open(os.path.join(d.path, f"{key3}.json"), "w") as f:
            json.dump(self._record(key), f)
        assert d.lookup(key3) is None
        assert list(d.records()) == []

    def test_store_refuses_unregistered_config_knobs(self, tmp_path):
        d = db.TuneDB(str(tmp_path / "db"))
        key = db.tune_key("resplit", "sig")
        rec = self._record(key)
        rec["config"] = {"HEAT_TPU_NOT_A_KNOB": "1"}
        with pytest.raises(ValueError, match="invalid tuning record"):
            d.store(rec)

    def test_open_db_env(self, tmp_path, monkeypatch):
        assert db.open_db() is None or os.environ.get("HEAT_TPU_TUNE_DB")
        monkeypatch.setenv("HEAT_TPU_TUNE_DB", str(tmp_path / "envdb"))
        d = db.open_db()
        assert d is not None and d.path == str(tmp_path / "envdb")


# -- the tuner ----------------------------------------------------------------


class TestTune:
    def test_winner_never_worse_than_default(self, tmp_path):
        """The default config is measured under the same protocol as
        every challenger and wins ties, so tuned_wall <= baseline_wall
        by construction."""
        x, work = _resplit_workload()
        res = at.tune(
            "resplit", work, signature=("r", x.shape, 0, 1),
            search=SEARCH_PLAN, trials_per_config=2,
            db_dir=str(tmp_path / "db"),
            cost_fn=cost.relayout_cost_fn(x.shape, 4, 0, 1,
                                          ht.get_comm().size),
        )
        assert not res.from_db and res.trials_run > 0
        rec = res.record
        assert rec["tuned_wall"] <= rec["baseline_wall"]
        assert rec["validation"] == "digest" and rec["max_rel_err"] == 0.0
        # the winner is adopted into the overlay
        assert at.adopted()["resplit"] == res.config

    def test_db_hit_skips_trials_and_adopts(self, tmp_path):
        x, work = _resplit_workload()
        kwargs = dict(
            signature=("r", x.shape, 0, 1), search=SEARCH_PLAN,
            trials_per_config=2, db_dir=str(tmp_path / "db"),
        )
        first = at.tune("resplit", work, **kwargs)
        at.reset()
        second = at.tune("resplit", work, **kwargs)
        assert second.from_db and second.trials_run == 0
        assert second.config == first.config
        assert at.adopted()["resplit"] == first.config

    def test_db_hit_respects_callers_tighter_budget(self, tmp_path):
        """A persisted LOSSY winner is only a hit when the current
        caller's budget covers its measured error: a tighter budget (or
        none at all — exact-only) discards the hit and re-tunes, so a
        record tuned under a loose budget can never violate a later
        caller's stated contract. The lossy record is planted directly
        so the gate is exercised regardless of which mode wins the
        measured race on this host."""
        budget = 1.05 / 127
        x, work = _resplit_workload()
        sig = ("rh", x.shape, 0, 1)
        mesh = db.mesh_fingerprint()
        key = db.tune_key("resplit", sig, mesh)
        d = db.TuneDB(str(tmp_path / "db"))
        d.store({
            "schema": db.SCHEMA, "key": key, "site": "resplit",
            "signature": repr(sig), "mesh": mesh,
            "config": {"HEAT_TPU_COLLECTIVE_PREC": "int8"},
            "default_config": {"HEAT_TPU_COLLECTIVE_PREC": "off"},
            "baseline_wall": 1.0, "tuned_wall": 0.5, "speedup": 2.0,
            "trials": 4, "configs_measured": 2, "lattice": 4,
            "error_budget": budget, "max_rel_err": 0.004,
            "validation": "allclose", "created": 0.0,
        })
        kwargs = dict(signature=sig, search=SEARCH_PREC,
                      trials_per_config=2, db_dir=d.path)
        # a budget covering the record's measured error hits: zero trials
        first = at.tune("resplit", work, error_budget=budget, **kwargs)
        assert first.from_db and first.trials_run == 0
        assert first.config == {"HEAT_TPU_COLLECTIVE_PREC": "int8"}
        at.reset()
        # tighter budget: must NOT warm-start — re-tunes under it
        # (persist=False keeps the lossy record in place for the probes)
        second = at.tune("resplit", work, error_budget=1e-12,
                         persist=False, **kwargs)
        assert not second.from_db and second.trials_run > 0
        assert second.record["validation"] == "digest"
        at.reset()
        # no budget at all (exact-only caller): same refusal
        third = at.tune("resplit", work, persist=False, **kwargs)
        assert not third.from_db
        assert third.record["validation"] == "digest"

    def test_unopenable_db_degrades_to_in_memory_tuning(self, tmp_path):
        """An unopenable HEAT_TPU_TUNE_DB (a path component is a plain
        file) degrades to in-memory tuning — the winner is measured and
        adopted, never a crash (db.py contract, same as warm_start)."""
        blocker = tmp_path / "blocker"
        blocker.write_text("not a directory")
        x, work = _resplit_workload()
        res = at.tune(
            "resplit", work, signature=("ro", x.shape, 0, 1),
            search=SEARCH_PLAN, trials_per_config=2,
            db_dir=str(blocker / "db"),
        )
        assert not res.from_db and res.trials_run > 0
        assert at.adopted()["resplit"] == res.config

    def test_concurrent_tunes_serialize_on_the_module_lock(self, tmp_path):
        """tune() holds the module tune lock through its measured
        section, so two concurrent tunes can never interleave their
        candidate overlays (the docstring promise)."""
        x, work = _resplit_workload()
        seen = []

        def spying_work():
            seen.append(at._TUNE_LOCK.locked())
            return work()

        res = at.tune(
            "resplit", spying_work, signature=("rs", x.shape, 0, 1),
            search=SEARCH_PLAN, trials_per_config=2,
            db_dir=str(tmp_path / "db"),
        )
        assert not res.from_db
        assert seen and all(seen)

    def test_error_budget_refuses_lossy_modes(self, tmp_path):
        """With a budget tighter than any quantized mode's error, every
        lossy candidate is rejected and the winner stays exact."""
        reg = tm.enable()
        reg.clear()
        try:
            x, work = _resplit_workload()
            res = at.tune(
                "resplit", work, signature=("rb", x.shape, 0, 1),
                search=SEARCH_PREC, error_budget=1e-12,
                trials_per_config=2, db_dir=str(tmp_path / "db"),
            )
            assert res.config["HEAT_TPU_COLLECTIVE_PREC"] == "off"
            assert reg.counters["autotune.rejected_budget"] >= 1
            assert res.record["validation"] == "digest"
        finally:
            tm.disable()

    def test_budgeted_lossy_pick_is_within_budget(self, tmp_path):
        budget = 1.05 / 127  # the int8 single-hop bound the CI gate pins
        x, work = _resplit_workload()
        res = at.tune(
            "resplit", work, signature=("rl", x.shape, 0, 1),
            search=SEARCH_PREC, error_budget=budget,
            trials_per_config=2, db_dir=str(tmp_path / "db"),
        )
        rec = res.record
        assert rec["tuned_wall"] <= rec["baseline_wall"]
        assert rec["max_rel_err"] <= budget
        assert rec["error_budget"] == budget

    def test_exact_site_pin_beats_tuned_overlay(self):
        """An adopted lossy overlay must not leak into exact-semantics
        sites: the per-call precision='off' pin wins (HL003 contract),
        so sort stays bit-identical under a tuned int8 overlay."""
        rng = np.random.default_rng(3)
        xn = rng.standard_normal((64, 8)).astype(np.float32)
        x = ht.array(xn, split=0)

        def sorted_digest():
            vals, idx = ht.sort(x, axis=0)
            return trials.digest((vals.numpy(), idx.numpy()))

        ref = sorted_digest()
        at._adopt("resplit", {"HEAT_TPU_COLLECTIVE_PREC": "int8"})
        assert collective_prec.mode() == "int8"  # overlay is live...
        assert collective_prec.resolve("off") == "off"  # ...pin wins
        assert sorted_digest() == ref

    def test_broken_candidate_is_disqualified_not_fatal(self, tmp_path):
        reg = tm.enable()
        reg.clear()
        try:
            calls = {"n": 0}

            def work():
                calls["n"] += 1
                if knobs.get("HEAT_TPU_RELAYOUT_PLAN") == "chunked":
                    raise RuntimeError("boom")
                return np.ones(3)

            res = at.tune(
                "flaky", work, signature="f", search=SEARCH_PLAN,
                trials_per_config=2, db_dir=str(tmp_path / "db"),
            )
            assert res.config["HEAT_TPU_RELAYOUT_PLAN"] != "chunked"
            assert reg.counters["autotune.rejected_error"] == 1
        finally:
            tm.disable()


# -- telemetry: counters / events / summarize / trace -------------------------


class TestTelemetry:
    def test_live_and_offline_summaries_agree(self, tmp_path):
        """report.summarize()'s offline event replay must reconstruct
        the SAME autotune block as the live counters (the PR-5
        resilience reconciliation, applied to the new subsystem)."""
        reg = tm.enable()
        reg.clear()
        try:
            x, work = _resplit_workload()
            kwargs = dict(
                signature=("rt", x.shape, 0, 1), search=SEARCH_PLAN,
                trials_per_config=2, db_dir=str(tmp_path / "db"),
            )
            at.tune("resplit", work, **kwargs)
            at.reset()
            at.tune("resplit", work, **kwargs)  # db hit path too
            live = tm.report.summarize()["autotune"]
            offline = tm.report.summarize(list(reg.events))["autotune"]
            assert live == offline
            for key in ("trials", "picks", "stores", "db_misses",
                        "db_hits", "adopted"):
                assert live.get(key, 0) >= 1, (key, live)
        finally:
            tm.disable()

    def test_trace_gets_an_autotune_track(self):
        reg = tm.enable()
        reg.clear()
        try:
            at._emit("resplit", "pick", config={"k": "v"})
            rows = tm.trace.to_trace_events(reg.events)
            marks = [r for r in rows if r.get("cat") == "autotune"]
            assert marks and marks[0]["ph"] == "i"
            tid = marks[0]["tid"]
            names = [r for r in rows if r.get("name") == "thread_name"
                     and r["tid"] == tid]
            assert names and names[0]["args"]["name"] == "autotune"
        finally:
            tm.disable()

    def test_untuned_summary_shape_unchanged(self):
        reg = tm.enable()
        reg.clear()
        try:
            assert "autotune" not in tm.report.summarize()
        finally:
            tm.disable()


# -- dispatch integration -----------------------------------------------------


class TestDispatchIntegration:
    def test_default_off_is_the_pr10_dispatch_path(self, monkeypatch):
        """HEAT_TPU_AUTOTUNE=0: one flag check on the miss path, no DB
        reads, no autotune counters, no new compiles (CompileWatcher +
        counter oracle)."""
        monkeypatch.delenv("HEAT_TPU_AUTOTUNE", raising=False)

        def boom(*a, **k):  # any DB open under the off flag is a bug
            raise AssertionError("tuning DB consulted while disarmed")

        monkeypatch.setattr(at.db, "open_db", boom)
        reg = tm.enable()
        reg.clear()
        try:
            pc.reset()
            x, work = _resplit_workload(seed=7)
            work()  # miss path: flag check only
            with tm.CompileWatcher() as cw:
                work()  # warm path: dict lookup, zero compiles
            assert cw.backend_compiles == 0
            assert not any(
                c.startswith("autotune.") for c in reg.counters
            )
            assert not any(
                e.get("kind") == "autotune" for e in reg.events
            )
        finally:
            tm.disable()

    def test_warm_start_gates_lossy_records_on_ambient_budget(self, tmp_path):
        """Dispatch-time warm start applies the same budget gate as a
        tune()-time DB hit: a persisted LOSSY winner is only auto-adopted
        when the ambient HEAT_TPU_AUTOTUNE_BUDGET covers its measured
        error — a process that stated no budget never inherits quantized
        collectives from a shared DB."""
        budget = 1.05 / 127
        d = db.TuneDB(str(tmp_path / "db"))
        key = db.tune_key("resplit", "sig")
        d.store({
            "schema": db.SCHEMA, "key": key, "site": "resplit",
            "signature": "sig", "mesh": db.mesh_fingerprint(),
            "config": {"HEAT_TPU_COLLECTIVE_PREC": "int8"},
            "baseline_wall": 1.0, "tuned_wall": 0.5,
            "error_budget": budget, "max_rel_err": 0.004,
            "validation": "allclose", "created": 0.0,
        })
        at.enable(d.path)
        # no ambient budget: the lossy record is skipped, not adopted
        assert at.warm_start(force=True) == 0
        assert "resplit" not in at.adopted()
        assert knobs.raw("HEAT_TPU_COLLECTIVE_PREC") is None
        # a covering ambient budget admits it
        knobs.set_override("HEAT_TPU_AUTOTUNE_BUDGET", str(budget))
        assert at.warm_start(force=True) == 1
        assert at.adopted()["resplit"] == {"HEAT_TPU_COLLECTIVE_PREC": "int8"}
        # a tighter ambient budget refuses it again
        at.reset()
        knobs.set_override("HEAT_TPU_AUTOTUNE_BUDGET", "1e-12")
        assert at.warm_start(force=True) == 0
        assert "resplit" not in at.adopted()

    def test_readonly_consults_never_create_the_db_dir(self, tmp_path):
        """open_db/lookup/records/count (the bench probe, a disabled
        tuner with HEAT_TPU_TUNE_DB merely exported) must not create the
        DB directory as a side effect — only store() does."""
        path = str(tmp_path / "nonexistent_db")
        d = db.open_db(path)
        assert d is not None
        assert d.lookup(db.tune_key("resplit", "sig")) is None
        assert list(d.records()) == [] and d.count() == 0
        assert not os.path.exists(path)
        d.store({
            "schema": db.SCHEMA, "key": db.tune_key("resplit", "sig"),
            "site": "resplit", "signature": "sig",
            "mesh": db.mesh_fingerprint(),
            "config": {"HEAT_TPU_RELAYOUT_PLAN": "alltoall"},
            "created": 0.0,
        })
        assert os.path.isdir(path) and d.count() == 1

    def test_numpy_budget_and_store_failure_keep_the_winner(self, tmp_path):
        """A numpy-scalar budget is coerced before it can skew the
        comparisons or crash json.dump, and a store failure after a
        successful tune loses only persistence — the measured winner is
        still adopted and returned (it is adopted BEFORE the store)."""
        blocker = tmp_path / "blocker"
        blocker.write_text("not a directory")
        x, work = _resplit_workload()
        res = at.tune(
            "resplit", work, signature=("rn", x.shape, 0, 1),
            search=SEARCH_PREC, trials_per_config=2,
            error_budget=np.float32(1.05 / 127),  # numpy scalar budget
            db_dir=str(blocker / "db"),  # store() will fail: not a dir
        )
        assert not res.from_db and res.trials_run > 0
        assert isinstance(res.record["error_budget"], float)
        assert at.adopted()["resplit"] == res.config

    def test_program_miss_warm_starts_from_db(self, tmp_path, monkeypatch):
        """With the flag on, the FIRST program-cache miss adopts every
        persisted winner for this mesh — dispatch-time consult."""
        d = db.TuneDB(str(tmp_path / "db"))
        key = db.tune_key("resplit", "sig")
        d.store({
            "schema": db.SCHEMA, "key": key, "site": "resplit",
            "signature": "sig", "mesh": db.mesh_fingerprint(),
            "config": {"HEAT_TPU_RELAYOUT_PLAN": "alltoall"},
            "baseline_wall": 1.0, "tuned_wall": 0.5, "created": 0.0,
        })
        at.enable(d.path)
        pc.reset()
        pc.cached_program("t_at", "k", lambda: (lambda v: v))
        assert at.adopted()["resplit"] == {
            "HEAT_TPU_RELAYOUT_PLAN": "alltoall"
        }
        assert knobs.get("HEAT_TPU_RELAYOUT_PLAN") == "alltoall"

    def test_server_constructs_tuned(self, tmp_path):
        """A persisted serve config lands in the ladder of a freshly
        constructed Server (serve dispatch-time consult)."""
        d = db.TuneDB(str(tmp_path / "db"))
        key = db.tune_key("serve", "sig")
        d.store({
            "schema": db.SCHEMA, "key": key, "site": "serve",
            "signature": "sig", "mesh": db.mesh_fingerprint(),
            "config": {"HEAT_TPU_SERVE_MAX_BATCH": "16",
                       "HEAT_TPU_SERVE_MAX_WAIT_MS": "0.5"},
            "baseline_wall": 1.0, "tuned_wall": 0.5, "created": 0.0,
        })
        at.enable(d.path)
        server = ht.serve.Server()
        try:
            assert server.max_batch == 16
            assert server.ladder[-1] == 16
            assert server.max_wait == pytest.approx(0.5e-3)
        finally:
            server.close()


# -- second process (subprocess-verified acceptance path) ---------------------


@pytest.mark.slow
class TestSecondProcess:
    def test_second_process_zero_trials_zero_steady_compiles(self, tmp_path):
        """A fresh process pointed at a populated HEAT_TPU_TUNE_DB
        reaches the tuned config with zero measured trials, and its
        steady-state dispatch under the adopted config compiles
        nothing."""
        tune_db = str(tmp_path / "db")
        x, work = _resplit_workload(n=128, f=16, seed=1)
        first = at.tune(
            "resplit", work, signature=("sp", (128, 16), 0, 1),
            search=SEARCH_PLAN, trials_per_config=2, db_dir=tune_db,
        )
        assert not first.from_db
        env = dict(os.environ)
        env.update(
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count="
            + str(ht.get_comm().size),
            HEAT_TPU_AUTOTUNE="1",
            HEAT_TPU_TUNE_DB=tune_db,
            PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
        )
        script = (
            "import numpy as np\n"
            "import heat_tpu as ht\n"
            "from heat_tpu import autotune as at\n"
            "x = ht.array(np.random.default_rng(1).standard_normal(\n"
            "    (128, 16)).astype(np.float32), split=0)\n"
            "work = lambda: x.resplit(1).larray\n"
            "res = at.tune('resplit', work,\n"
            "              signature=('sp', (128, 16), 0, 1),\n"
            "              search=['HEAT_TPU_RELAYOUT_PLAN'],\n"
            "              trials_per_config=2)\n"
            "assert res.from_db and res.trials_run == 0, (\n"
            "    res.from_db, res.trials_run)\n"
            "work()  # first dispatch under the adopted config compiles\n"
            "with ht.telemetry.CompileWatcher() as cw:\n"
            "    work()  # steady state: cached program, zero compiles\n"
            "assert cw.backend_compiles == 0, cw.backend_compiles\n"
            "print('TUNED', res.config)\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=600,
        )
        assert r.returncode == 0, r.stdout + r.stderr
        assert "TUNED" in r.stdout
        assert str(first.config) in r.stdout
