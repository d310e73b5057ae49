"""The reader of the step's scope map (``chipbench/scope_trace.py``) and its
eight metrics: against one train step of ``trinity-train-16k-1chip`` recorded
on a TPU v5 lite with the map the program of that run gave
(``recorded_trinity_scopes_v5e.json``), and end to end on the CPU through
``chipbench/run.py`` with a tiny manifest that lists the entries
(``tiny_scopes/``: the ``lm_step`` kind at ``tiny_lm``'s size).

A CPU trace holds no program line, so the CPU run rehearses that nothing
raises and nothing is reported; the numbers below are the recorded chip run's.
"""

import json
import os
from types import SimpleNamespace

import pytest

from chipbench import manifest, run, scope_trace, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_scopes")
CELLS = ["olmoe-train-4k-1chip", "qwen3next-train-8k-1chip", "trinity-train-16k-1chip"]
METRICS = [
    "step_unscoped_ms", "step_recomputed_ms", "step_scope_ms.norms", "step_scope_ms.projections",
    "step_scope_ms.feed_forward", "step_scope_ms.stream", "step_scope_ms.embed", "step_scope_ms.mixer_glue",
]


@pytest.fixture(autouse=True)
def _default_comm_again():
    yield
    import heat_tpu as ht

    ht.use_comm(None)  # the harness sets the cell's own mesh as the default


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trinity_scopes_v5e.json")) as f:
        return json.load(f)


def reduced(recorded, more_ops=(), more_modules=()):
    ops = [trace_reduce.Event(f"%{h} = f32[8]{{0}} {op}(f32[8]{{0}} %x)", a, b) for h, op, a, b in recorded["events"]]
    ops += list(more_ops)
    modules = [trace_reduce.Event(*recorded["module"])] + list(more_modules)
    end = max(e.end for e in ops + modules) + 1000.0
    device = trace_reduce.Device("/device:TPU:0", ops, modules, trace_reduce.union((e.start, e.end) for e in ops))
    return trace_reduce.Reduced((0.0, end), [tuple(recorded["call"])], [], [device])


def test_the_pieces_and_unscoped_sum_to_the_programs_leaf_time(recorded):
    tr = reduced(recorded)
    found = scope_trace.table(tr, recorded["rows"])
    pieces = found["pieces_ms"]
    assert sum(c["all"] for c in pieces.values()) == pytest.approx(found["leaf_ms"], rel=1e-12)
    for c in pieces.values():
        assert c["all"] == pytest.approx(sum(v for k, v in c.items() if k != "all"), rel=1e-12)
    # the leaves by themselves, counted independently: every event that holds no other
    leaves = trace_reduce.leaves(tr.devices[0].ops)
    assert found["leaf_ms"] == pytest.approx(sum(e.dur for e in leaves) / 1e6, rel=1e-12)
    assert found["unmatched_events"] == 0 and found["map_rows"] == len(recorded["rows"])
    assert found["leaf_ms"] < found["program_ms"] == pytest.approx(1004.609454)
    assert found["recomputed_ms"] == pytest.approx(sum(c.get("recomputed", 0.0) for c in pieces.values()))
    assert 0.0 < found["mixed_share"] < 1.0
    assert found["pieces_ms"]["unscoped"]["all"] == pytest.approx(sum(found["unscoped_by_op_ms"].values()))
    assert set(found["unscoped_by_op_ms"]) == {"broadcast"}  # zero fills the compiler adds, with no named neighbour
    # the copies it adds borrow the row of what they feed (``via``): here the optimizer's update fusions
    assert found["lent_ms"] == pytest.approx(4.294, abs=1e-3)


def test_the_recorded_step_by_piece(recorded):
    """My chip run, PR 35, call 2 (events of 100 us and more: 982.5 of the step's 992.0 ms of leaves)."""
    found = scope_trace.table(reduced(recorded), recorded["rows"])
    ms = {p: c["all"] for p, c in found["pieces_ms"].items()}
    # the kernels by their names in the path: what swa_attention_ms + trinity_full_attention_ms read by event name
    kernels = sum(b - a for h, op, a, b in recorded["events"] if op == "custom-call" and h.startswith(("swa_", "flash_"))) / 1e6
    assert ms["attention_core"] == pytest.approx(kernels) == pytest.approx(332.216, abs=1e-3)
    assert found["pieces_ms"]["attention_core"].keys() == {"forward", "backward", "all"}  # PR 34: no kernel runs again
    assert ms["attention_glue"] == pytest.approx(32.548, abs=1e-3)
    assert ms["projections"] == pytest.approx(175.101, abs=1e-3) and ms["norms"] == pytest.approx(83.291, abs=1e-3)
    assert ms["feed_forward"] == pytest.approx(92.882, abs=1e-3) and ms["stream"] == pytest.approx(42.335, abs=1e-3)
    assert ms["route"] == pytest.approx(115.135, abs=1e-3) and ms["experts"] == pytest.approx(34.907, abs=1e-3)
    assert ms["head_loss"] == pytest.approx(34.858, abs=1e-3) and ms["optimizer"] == pytest.approx(32.434, abs=1e-3)
    assert ms["embed"] == pytest.approx(2.987, abs=1e-3) and ms["unscoped"] == pytest.approx(3.762, abs=1e-3)
    assert found["recomputed_ms"] == pytest.approx(137.807, abs=1e-3)
    assert "mixer_glue" not in ms and "delta_rule" not in ms  # no DeltaNet here
    assert found["pieces_ms"]["optimizer"].keys() == {"forward", "all"}  # outside every transform


def test_a_loops_own_event_is_not_counted_on_top_of_its_body(recorded):
    rows = recorded["rows"]
    loops = [(h, a, b) for h, op, a, b in recorded["events"] if op == "while"]
    assert loops  # the head's loop over blocks of positions among them
    name, lo, hi = next(l for l in loops if "lm.head_loss" in rows[l[0]]["scopes"])
    inside = [trace_reduce.Event(h, a, b) for h, _, a, b in recorded["events"] if lo <= a and b <= hi and h != name]
    assert len(inside) > 10 and {scope_trace.piece_of(rows[e.name]) for e in inside} == {"head_loss"}
    body, loop = sum(e.dur for e in trace_reduce.leaves(inside)) / 1e6, (hi - lo) / 1e6
    assert 0.9 * loop < body < loop  # the loop's event covers its body and a little of its own
    found = scope_trace.table(reduced(recorded), rows)
    # the piece is the body's operations and the little of the head that lies outside the loop: not the loop's event too
    assert body <= found["pieces_ms"]["head_loss"]["all"] < body + 3.0


def test_an_event_of_another_program_with_the_same_name_is_not_counted(recorded):
    base = scope_trace.table(reduced(recorded), recorded["rows"])
    name = next(h for h, op, *_ in recorded["events"] if op == "fusion")
    end = recorded["module"][2]
    other = trace_reduce.Event("jit_convert_element_type(123)", end + 5_000.0, end + 905_000.0)
    same_name = trace_reduce.Event(f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", end + 6_000.0, end + 900_000.0)
    outside_any = trace_reduce.Event(f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %x)", end + 2_000_000.0, end + 2_500_000.0)
    found = scope_trace.table(reduced(recorded, [same_name, outside_any], [other]), recorded["rows"])
    assert found["leaf_ms"] == pytest.approx(base["leaf_ms"], rel=1e-12)
    assert found["program_ms"] == pytest.approx(base["program_ms"], rel=1e-12)
    assert found["pieces_ms"] == base["pieces_ms"]


def test_a_leaf_without_a_row_is_unscoped_and_counted_as_unmatched(recorded):
    rows = dict(recorded["rows"])
    name = next(h for h, op, *_ in recorded["events"] if op == "custom-call" and h.startswith("swa_fwd"))
    took = sum(b - a for h, _, a, b in recorded["events"] if h == name) / 1e6
    base = scope_trace.table(reduced(recorded), rows)
    del rows[name]
    found = scope_trace.table(reduced(recorded), rows)
    assert found["unmatched_events"] == 1
    assert found["pieces_ms"]["unscoped"]["all"] == pytest.approx(base["pieces_ms"]["unscoped"]["all"] + took)
    assert found["unscoped_top"][0][0].startswith(name)


def test_an_instruction_without_metadata_borrows_the_row_the_map_names(recorded):
    rows = recorded["rows"]
    borrowers = [h for h, *_ in recorded["events"] if not rows[h]["path"] and rows[h].get("via")]
    assert borrowers and all(rows[h]["op"] == "copy" for h in borrowers)
    own = rows[borrowers[0]]
    assert scope_trace.piece_of(own) == "unscoped" and scope_trace.lent(rows, own) is rows[own["via"]]
    assert scope_trace.lent(rows, rows[own["via"]]) is rows[own["via"]] and scope_trace.lent(rows, None) is None
    # without the ``via`` the same copies are unscoped and nothing is lent
    bare = {h: {k: v for k, v in r.items() if k != "via"} for h, r in rows.items()}
    found = scope_trace.table(reduced(recorded), bare)
    assert found["lent_ms"] == 0.0 and found["pieces_ms"]["unscoped"]["all"] == pytest.approx(3.762 + 4.294, abs=1e-3)
    assert set(found["unscoped_by_op_ms"]) == {"copy", "broadcast"}


@pytest.mark.parametrize("row, piece", [
    (None, "unscoped"),
    ({"op": "copy", "path": "", "modules": "", "scopes": [], "pass": ""}, "unscoped"),
    ({"path": "jit(dp_train_step)/jvp()/add", "modules": "", "scopes": []}, "unscoped"),
    ({"path": "a/b", "modules": "TransformerLM/block3/ln1_post", "scopes": ["lm.body"]}, "norms"),
    ({"path": "a/b", "modules": "TransformerLM/ln_f", "scopes": ["lm.body"]}, "norms"),
    ({"path": "a/b", "modules": "TransformerLM/block3/attn/q_norm", "scopes": ["lm.body"]}, "norms"),
    ({"path": "a/b", "modules": "TransformerLM/block1/gdn", "scopes": ["lm.body", "gdn.gate_norm"]}, "norms"),
    ({"path": "a/b", "modules": "TransformerLM/block3/attn/query", "scopes": ["lm.body", "attn.gate"]}, "projections"),
    ({"path": "a/b", "modules": "TransformerLM/block3/attn/out", "scopes": ["lm.body"]}, "projections"),
    ({"path": "a/b", "modules": "TransformerLM/block1/gdn", "scopes": ["lm.body", "gdn.project"]}, "projections"),
    ({"path": "a/b", "modules": "TransformerLM/block0/up", "scopes": ["lm.body"]}, "feed_forward"),
    ({"path": "a/b", "modules": "TransformerLM/block4/moe/shared_up", "scopes": ["lm.body", "moe.shared"]}, "feed_forward"),
    ({"path": "x/attn/attn.window/swa_fwd/pallas_call", "modules": "TransformerLM/block2/attn/swa_fwd",
      "scopes": ["lm.body", "attn.window"]}, "attention_core"),
    ({"path": "x/attn/attn.window/transpose", "modules": "TransformerLM/block2/attn", "scopes": ["lm.body", "attn.window"]},
     "attention_glue"),
    ({"path": "x/attn/attn.full/slice", "modules": "TransformerLM/block3/attn", "scopes": ["lm.body", "attn.full"]}, "stream"),
    ({"path": "x/attn/attn.full/attn.lse/mul", "modules": "TransformerLM/block3/attn", "scopes": ["lm.body", "attn.full", "attn.lse"]},
     "stream"),
    ({"path": "a/b", "modules": "TransformerLM/block3/attn", "scopes": ["lm.body"]}, "stream"),
    ({"path": "a/b", "modules": "TransformerLM/block3", "scopes": ["lm.body"]}, "stream"),
    ({"path": "a/b", "modules": "", "scopes": ["lm.targets"]}, "stream"),
    ({"path": "a/b", "modules": "TransformerLM/block3/moe", "scopes": ["lm.body", "moe.route"]}, "route"),
    ({"path": "a/b", "modules": "TransformerLM/block3/moe", "scopes": ["lm.body", "moe.combine"]}, "route"),
    ({"path": "a/b", "modules": "TransformerLM/block3/moe", "scopes": ["lm.body", "moe.experts"]}, "experts"),
    ({"path": "ragged-dot-none", "modules": "", "scopes": []}, "experts"),
    ({"path": "params[\\'params\\'][\\'block0\\'][\\'moe\\'][\\'w_up\\']", "modules": "", "scopes": []}, "experts"),
    ({"path": "a/b", "modules": "TransformerLM/block1/gdn", "scopes": ["lm.body", "gdn.scan"]}, "delta_rule"),
    ({"path": "a/b", "modules": "TransformerLM/block1/gdn", "scopes": ["lm.body", "gdn.conv"]}, "mixer_glue"),
    ({"path": "a/b", "modules": "TransformerLM/block1/gdn", "scopes": ["lm.body"]}, "mixer_glue"),
    ({"path": "a/b", "modules": "", "scopes": ["lm.head_loss"]}, "head_loss"),
    ({"path": "a/b", "modules": "", "scopes": ["train.optimizer"]}, "optimizer"),
    ({"path": "a/b", "modules": "", "scopes": ["train.state_rule"]}, "optimizer"),
    ({"path": "a/b", "modules": "TransformerLM/embed", "scopes": ["lm.body"]}, "embed"),
])
def test_the_table_of_pieces(row, piece):
    assert scope_trace.piece_of(row) == piece


def test_a_fusion_that_mixes_pieces_is_told(recorded):
    mixing = {"op": "fusion", "path": "a/b", "modules": "TransformerLM/block3/attn/query", "scopes": ["lm.body"],
              "fused": [["TransformerLM/block3/ln1", ["lm.body"]], ["TransformerLM/block3/attn/query", ["lm.body"]]]}
    alone = dict(mixing, fused=[["TransformerLM/block3/attn/query", ["lm.body"]], ["", []]])
    assert scope_trace.pieces_of(mixing) == {"norms", "projections"} and scope_trace.pieces_of(alone) == {"projections"}
    # in the recorded step a third of the leaf time is in fusions that hold another piece's instructions
    found = scope_trace.table(reduced(recorded), recorded["rows"])
    assert found["mixed_share"] == pytest.approx(0.34294, abs=1e-4)


def test_without_a_map_or_a_trace_every_reader_gives_none(monkeypatch, recorded):
    parts = manifest.load(REPO)
    readers = [parts.module("metrics", name).read for name in METRICS]

    def reading(trace):
        return SimpleNamespace(trace=trace, notes={})

    # a parent commit: the program has no ``program_scopes``
    monkeypatch.setattr(scope_trace, "program_map", lambda: (None, {}))
    r = reading(reduced(recorded))
    assert [read(r) for read in readers] == [None] * 8 and r.notes == {}
    # this tree, no step traced in this process
    monkeypatch.undo()
    from heat_tpu.telemetry import hlo

    hlo.clear()
    r = reading(reduced(recorded))
    assert [read(r) for read in readers] == [None] * 8 and r.notes == {}
    # a map and no trace (``--trace 0``)
    monkeypatch.setattr(scope_trace, "program_map", lambda: (recorded["rows"], {"map_request_s": 0.5, "map_request_compiles": 0}))
    r = reading(None)
    assert [read(r) for read in readers] == [None] * 8 and r.notes == {}
    # both: every listed metric is a number, 0.0 where the step has no such piece, and the note is written once
    r = reading(reduced(recorded))
    values = dict(zip(METRICS, (read(r) for read in readers)))
    assert all(isinstance(v, float) for v in values.values())
    assert values["step_scope_ms.mixer_glue"] == 0.0 and values["step_scope_ms.norms"] == pytest.approx(83.291, abs=1e-3)
    assert values["step_unscoped_ms"] == pytest.approx(3.762, abs=1e-3)
    assert values["step_recomputed_ms"] == pytest.approx(137.807, abs=1e-3)
    note = r.notes["step_scopes"]
    assert note["map_request_s"] == 0.5 and note["map_request_compiles"] == 0
    assert {"pieces_ms", "leaf_ms", "program_ms", "mixed_share", "unmatched_events", "unscoped_top", "scopes"} <= set(note)
    assert {"attn.lse", "attn.window", "moe.shared", "train.optimizer"} <= set(note["scopes"])


def test_the_eight_entries_are_in_the_manifest_with_the_three_cells_list():
    parts = manifest.load(REPO)
    entries = {m["name"]: m for m in parts.doc["per_layer"]}
    assert [m["name"] for m in parts.doc["per_layer"][-8:]] == METRICS  # appended, one entry each: no copy a cell
    for name in METRICS:
        m = entries[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == ("ms", "lower", "device_trace", "call_p50_ms")
        assert callable(parts.module("metrics", name).read)
        if name == "step_scope_ms.mixer_glue":
            assert m["workloads"] == ["qwen3next-train-8k-1chip"] and m["layer"] == "kernels"
        else:
            assert m["workloads"] == CELLS and m["layer"] == "training stack"
    layers = {m["layer"] for m in parts.doc["per_layer"][:-8]}
    assert {entries[n]["layer"] for n in METRICS} <= layers
    for cell in CELLS:
        reported = [m["name"] for m in parts.metrics("per_layer", parts.cell(cell))]
        assert [n for n in reported if n.startswith("step_")] == [
            n for n in METRICS if n != "step_scope_ms.mixer_glue" or cell == "qwen3next-train-8k-1chip"
        ]
    for cell in ("kmeans-fit-1chip", "kmeans-fit-4chip", "cdist-susy-1chip"):
        assert not [m for m in parts.metrics("per_layer", parts.cell(cell)) if m["name"].startswith("step_")]


def test_tiny_scopes_is_tiny_lm_with_the_entries_added():
    tiny, lm, real = manifest.load(TINY), manifest.load(os.path.join(HERE, "tiny_lm")), manifest.load(REPO)
    assert tiny.config(tiny.cell("tiny-olmoe")) == lm.config(lm.cell("tiny-olmoe"))
    assert tiny.doc["per_layer"][:-8] == lm.doc["per_layer"]
    drop = lambda m: {k: v for k, v in m.items() if k != "workloads"}  # noqa: E731
    assert tiny.doc["per_layer"][-8:] == [drop(m) for m in real.doc["per_layer"][-8:]]
    # the readers are the harness's own files, not copies
    assert tiny.module("metrics", "step_unscoped_ms").__file__.startswith(os.path.join(REPO, "chipbench", "metrics"))


def test_a_traced_cpu_run_asks_for_the_map_and_reports_nothing_it_cannot_read(capsys):
    from heat_tpu.telemetry import hlo

    hlo.clear()
    rc = run.main(
        ["--workload", "tiny-olmoe", "--seed", "4000000035", "--seconds", "0.4", "--trace", "1"], root=TINY
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True
    # the step ran under a profile: its launches were noted and the program gives its map ...
    rows = hlo.program_scopes("dp_train_step")
    assert rows and {"lm.body", "lm.head_loss", "train.optimizer"} <= {s for r in rows.values() for s in r["scopes"]}
    # ... but a CPU trace has no line of programs: no step to join it to, nothing reported, nothing raised
    assert not [name for name in lines[-1]["metrics"] if name.startswith("step_")]
    assert not any("step_scopes" in l.get("notes", {}) for l in lines)
    hlo.clear()
