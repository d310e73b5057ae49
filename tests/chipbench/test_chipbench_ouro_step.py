"""The ``ouro_step`` kind, its configuration, counts and metric readers: the
manifest with PR 48's entries (found by name and forward position), the
configuration against the catalog's row and the program's own parameter count,
the counts against a hand count and the issue's figures, the readers against
events and map rows written as the compiled step names them, and the kind end
to end on the CPU through ``chipbench/run.py`` with a tiny manifest of its own
(``tiny_ouro/``: the same kind, reference, metrics and counts on a configuration
a CPU test can hold): a run is ``correct``, every control breaks a limit, a
fault in the timed path is not ``correct``.

A CPU run rehearses control flow and the decision of ``correct``; none of its
numbers is a device metric.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import limits, manifest, ouro_trace, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_ouro")
CELL = "ouro-train-4k-1chip"
NEW_METRICS = [
    "ouro_step_mfu", "ouro_loop_ms", "ouro_attention_ms", "ouro_attention_roofline", "ouro_recomputed_ms",
    "ouro_head_loss_ms", "ouro_exit_gate_ms", "ouro_optimizer_ms", "ouro_loop_passes", "ouro_compiles_in_window",
    "ouro_loop_projections_ms", "ouro_loop_feed_forward_ms", "ouro_loop_norms_ms", "ouro_loop_stream_ms",
]
PUBLISHED = {  # the catalog's row (architectures.jsonl), key for key
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
    "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
    "vocab_size": 49152,
}
LIMITS = {
    "losses_not_finite", "logits_gap", "logits_rms_gap", "precision_gap", "exit_pdf_gap", "loss_gap", "grad_norm_gap",
    "replay_loss_gap", "update_gap",
}
PARAMETERS = 612_438_017
CONTROLS = {"bf16", "three_passes", "ln_f_once", "last_exit_only", "beta_zero", "gate_gradient_stopped", "one_use"}


@pytest.fixture(autouse=True)
def _default_comm_again():
    yield
    import heat_tpu as ht

    ht.use_comm(None)  # the harness sets the cell's own mesh as the default


@pytest.fixture(scope="module")
def parts():
    return manifest.load(REPO)  # load() validates


@pytest.fixture(scope="module")
def config(parts):
    return parts.config(parts.cell(CELL))


# -- the manifest's new entries -------------------------------------------------------


def test_the_new_cell_its_configuration_and_every_part_are_found(parts, config):
    cell = parts.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2.6b-train", "closed-1", 1)
    assert len(cell["why"]) <= 200 and "1 x 4,096" in cell["why"] and "8 blocks x 4 passes" in cell["why"]
    # the ninth of each list: what PR 41 left comes before, unchanged (a later PR appends after: nothing pins the end)
    assert parts.doc["workloads"][8] is cell and parts.doc["configs"][8]["name"] == cell["config"]
    assert [w["name"] for w in parts.doc["workloads"][:8]] == [
        "kmeans-fit-1chip", "cdist-susy-1chip", "kmeans-fit-4chip", "olmoe-train-4k-1chip", "qwen3next-train-8k-1chip",
        "trinity-train-16k-1chip", "lfm2-train-8k-1chip", "glm47flash-train-8k-1chip"]
    entry = parts.doc["configs"][8]
    assert entry["source"] == "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert entry["reduced"] == ["num_hidden_layers"] and entry["file"] == "chipbench/configs/ouro-2.6b-train.json"
    assert (config["kind"], config["reference"]) == ("ouro_step", "ouro_plain")
    parts.module("kinds", config["kind"])
    parts.module("references", config["reference"])
    parts.module("counts", "ouro_step")
    reported = {s: [m["name"] for m in parts.metrics(s, cell)] for s in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == ["call_p50_ms", "items_per_s", "setup_s"]
    assert reported["per_layer"][:3 + len(NEW_METRICS)] == ["device_idle_share", "launches_per_call", "host_ms_per_call"] + NEW_METRICS
    for m in parts.metrics("per_layer", cell):
        assert callable(parts.module("metrics", m["name"]).read)
    names = [m["name"] for m in parts.doc["per_layer"]]
    at = names.index("gdn_conv_kernel_share") + 1  # PR 45's, the last before this PR's fourteen
    new = parts.doc["per_layer"][at:at + len(NEW_METRICS)]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "call_p50_ms" for m in new)
    old = parts.doc["per_layer"][:at]
    assert all(CELL not in m.get("workloads", []) for m in old)
    assert {m["layer"] for m in new} <= {m["layer"] for m in old}
    assert {m["unit"] for m in new if "roofline" in m["name"] or "mfu" in m["name"]} == {"%"}
    assert {m["name"]: m["source"] for m in new if m["source"] != "device_trace"} == {"ouro_compiles_in_window": "program_counter"}


def test_the_configuration_keeps_every_published_number(parts, config):
    """The catalog's row for Ouro-2.6B, key for key; the depth is reduced and
    nothing else, and the file says what was assumed and what it stands for."""
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    assert differs == ["num_hidden_layers"] == parts.doc["configs"][8]["reduced"] == sorted(config["reduced"])
    assert config["num_hidden_layers"] == 8 and config["vocab_size"] == 49152 and config["total_ut_steps"] == 4
    assert (config["sequences_per_step"], config["sequence_length"]) == (1, 4096)
    olmoe = parts.config(parts.cell("olmoe-train-4k-1chip"))
    assert config["optimizer"] == olmoe["optimizer"] and config["zipf_s"] == olmoe["zipf_s"]
    assert config["loss"] == {"beta": 0.05}
    assert abs(config["init_out_std"] - 0.02 / np.sqrt(2 * 48 * 4)) < 1e-12 and config["init_gate_std"] == config["init_std"] == 0.02
    for key in ("norm_places", "final_norm_in_loop", "exit_gate", "attention", "loss", "initialisation", "optimizer", "traffic"):
        assert config["assumed"][key], key
    for key in ("layout", "item", "guarantee", "cut_arithmetic", "memory_analysis", "check", "limits_set_from"):
        assert config[key], key
    assert "six" in config["layout"] and "pipeline" in config["layout"]
    assert set(config["limits"]) == LIMITS
    assert "612,438,017" in config["cut_arithmetic"]


def test_the_cut_arithmetic_is_the_programs_own_parameter_count(parts, config):
    import jax
    import jax.numpy as jnp

    kind = parts.module("kinds", "ouro_step")
    model = kind.build_model(config, None)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == PARAMETERS
    assert parts.module("counts", "ouro_step").parameters(config) == PARAMETERS
    # 16 bytes a parameter (float32 parameter, gradient, two moments): 9.8 GB, over half the chip
    assert 0.55 < 16 * PARAMETERS / 2**34 < 0.6
    ref = parts.module("references", "ouro_plain")
    c = {k: config[k] for k in kind.MODEL_KEYS}
    sizes = jax.tree.leaves(ref.param_shapes(c), is_leaf=lambda s: isinstance(s, tuple))
    assert sum(int(np.prod(s)) for s in sizes) == PARAMETERS


# -- the counts ---------------------------------------------------------------------------


def test_counts_give_the_issues_figures(parts, config):
    counts = parts.module("counts", "ouro_step")
    per_token = counts.forward_flops_per_token(config)
    applications = 32
    assert per_token["projections"] + per_token["feed_forward"] == applications * (4 * 2 * 2048 * 2048 + 3 * 2 * 2048 * 5632)
    assert abs(per_token["attention"] / applications - 16.8e6) < 0.1e6  # causal: each query against the keys up to its own
    assert per_token["head"] == 4 * 2 * 2048 * 49152  # four exits through the one head
    assert abs(sum(per_token.values()) - 4.63e9) < 0.01e9
    work = counts.work(config, 1)
    assert abs(work["flops"] - 56.9e12) < 0.1e12 and work["bytes"] == 0
    attention = counts.attention_work(config, 1)
    assert attention["flops"] == 3 * 4096 * per_token["attention"] and attention["bytes"] > 0
    one = {**config, "total_ut_steps": 1}
    assert counts.work(one, 1)["flops"] * 4 == work["flops"]  # every pass and every exit is counted


def test_counts_against_a_hand_count_at_the_tiny_size():
    tiny = manifest.load(TINY)
    c = tiny.config(tiny.cell("tiny-ouro"))
    per_token = tiny.module("counts", "ouro_step").forward_flops_per_token(c)
    d, f, t, v, apps = 64, 96, 48, 128, 2 * 4
    assert per_token["projections"] == apps * 2 * d * 4 * d
    assert per_token["feed_forward"] == apps * 3 * 2 * d * f
    assert per_token["attention"] == apps * 2 * 2 * d * (t * (t + 1) // 2) // t
    assert per_token["head"] == 4 * 2 * d * v
    assert tiny.module("counts", "ouro_step").parameters(c) == 2 * (4 * d * d + 3 * d * f + 4 * d) + 2 * v * d + d + d + 1


# -- the readers ------------------------------------------------------------------------------


def _reading(events, config, parts=None, span=1000.0):
    """One device whose step program holds ``events`` as its leaf operations;
    one call spans the window of ``span`` ns."""
    program = trace_reduce.Event("jit_dp_train_step(123)", 0.0, span)
    device = trace_reduce.Device("/device:TPU:0", list(events), [program], [(0.0, span)])
    tr = trace_reduce.Reduced((0.0, span), [(0.0, span)], [], [device])
    return SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak={"flops_per_s": 197e12, "bytes_per_s": 819e9},
                           parts=parts, window=SimpleNamespace(calls=[]), compiles=0)


def _row(modules="", scopes=(), path="x", which="forward"):
    return {"op": "fusion", "path": path, "modules": modules, "scopes": list(scopes), "pass": which, "fused": []}


@pytest.fixture
def looped(monkeypatch):
    """The counter the program keeps where this PR's model was traced (three traces of it)."""
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    monkeypatch.setitem(counters, "lm.loop.passes", 3 * 4.0)
    return counters


def test_every_leaf_lies_in_one_piece_by_the_scope_map(parts, config, looped, monkeypatch):
    ev = lambda name, start, dur: trace_reduce.Event(f"%{name} = f32[8] fusion(f32[8] %x)", start, start + dur)  # noqa: E731
    events = [ev("fusion.1", 0, 100), ev("fusion.2", 100, 50), ev("fusion.3", 150, 30), ev("fusion.4", 180, 20),
              ev("fusion.5", 200, 10), ev("fusion.6", 210, 5)]
    events += [  # one call site of the forward kernel, run four times a step
        trace_reduce.Event("%flash_fwd.3 = bf16[1,16,4096,128] custom-call(bf16[1,16,4096,128] %q)", 300 + 10 * i, 310 + 10 * i)
        for i in range(4)
    ]
    rows = {
        "fusion.1": _row("TransformerLM/block0/attn/query", ("lm.body", "lm.loop")),
        "fusion.2": _row("TransformerLM/block3/down", ("lm.body", "lm.loop"), which="recomputed"),
        "fusion.3": _row("TransformerLM", ("lm.head_loss",), which="backward"),
        "fusion.4": _row("TransformerLM", ("lm.exit_gate",)),
        "fusion.5": _row("", ("train.optimizer",), which="none"),
        "fusion.6": _row("TransformerLM/embed", ("lm.body",)),
        "flash_fwd.3": _row("TransformerLM/block0/attn", ("lm.body", "lm.loop", "attn.full"), path="a/attn.full/flash_fwd/pallas_call"),
    }
    from chipbench import scope_trace

    monkeypatch.setattr(scope_trace, "program_map", lambda: (rows, {}))
    reading = _reading(events, config, parts)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    ns = 1e-6
    assert read("ouro_loop_ms") == pytest.approx((100 + 50 + 40) * ns)  # the flash kernel lies in the loop
    assert read("ouro_recomputed_ms") == pytest.approx(50 * ns)
    assert read("ouro_head_loss_ms") == pytest.approx(30 * ns)
    assert read("ouro_exit_gate_ms") == pytest.approx(20 * ns)
    assert read("ouro_optimizer_ms") == pytest.approx(10 * ns)
    assert reading.notes["ouro_pieces"]["embed"] == pytest.approx(5 * ns)
    pieces = {k: v for k, v in reading.notes["ouro_pieces"].items() if not k.startswith(("pass:", "loop:"))}
    assert sum(pieces.values()) == pytest.approx(255 * ns)  # every leaf once
    assert read("ouro_attention_ms") == pytest.approx(40 * ns)
    assert read("ouro_loop_passes") == 4.0 and read("ouro_compiles_in_window") == 0.0
    # the loop cut once more by the table of the other training cells; a piece without a leaf reads 0
    assert [read(f"ouro_loop_{p}_ms") for p in ("projections", "feed_forward", "norms", "stream")] == [
        pytest.approx(100 * ns), pytest.approx(50 * ns), 0.0, 0.0]
    assert reading.notes["ouro_pieces"]["loop:attention_core"] == pytest.approx(40 * ns)
    # the passes are read off the program: the same kernels from four call sites are a stack written out, whatever
    # the model's fields (the counter) say
    written_out = {**rows, **{f"flash_fwd.{i}": rows["flash_fwd.3"] for i in range(3)}}
    monkeypatch.setattr(scope_trace, "program_map", lambda: (written_out, {}))
    assert parts.module("metrics", "ouro_loop_passes").read(_reading(events, config, parts)) == 1.0
    # 32 forward and 32 backward kernels' work over 40 ns would be far past any roofline: the reader divides, it does not cap
    assert read("ouro_attention_roofline") > 100
    assert 0 < read("ouro_step_mfu")


def test_a_program_without_the_names_or_counters_reads_nothing(parts, config, monkeypatch):
    """What a parent commit gives: no counter of the loop: every reader of the
    trace returns None, none raises."""
    from chipbench import scope_trace
    from heat_tpu import telemetry

    ev = trace_reduce.Event("%fusion.1 = f32[8] fusion(f32[8] %x)", 10.0, 20.0)
    monkeypatch.setattr(scope_trace, "program_map", lambda: ({"fusion.1": _row("TransformerLM/block0/attn/query", ("lm.body",))}, {}))
    counters = telemetry.get_registry().counters
    held = {k: counters.pop(k) for k in list(counters) if k.startswith("lm.loop")}
    try:
        for cfg in (config, parts.config(parts.cell("olmoe-train-4k-1chip"))):
            reading = _reading([ev], cfg, parts)
            for name in NEW_METRICS:
                if name != "ouro_compiles_in_window":
                    assert parts.module("metrics", name).read(reading) is None, name
    finally:
        counters.update(held)
    untraced = SimpleNamespace(trace=None, notes={}, config=config, chips=1, peak={}, parts=parts, window=SimpleNamespace(calls=[]))
    assert ouro_trace.pieces(untraced) is None and ouro_trace.step_mfu(untraced) is None
    assert ouro_trace.attention_roofline(untraced) is None


# -- the kind end to end on the CPU ------------------------------------------------------


def _run(capsys, trace, seed, seconds=0.4):
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    for name in [k for k in counters if k.startswith("lm.loop")]:  # a run's process traces its own cell's model alone
        del counters[name]
    rc = run.main(
        ["--workload", "tiny-ouro", "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=TINY,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines


def test_tiny_ouro_is_the_cell_at_a_rehearsal_size(config):
    tiny = manifest.load(TINY)
    cell = tiny.cell("tiny-ouro")
    c = tiny.config(cell)
    same = ("kind", "reference", "optimizer", "loss", "init_std", "init_out_std", "init_gate_std", "zipf_s", "roofline_modules",
            "total_ut_steps", "rope_theta", "rms_norm_eps", "sequences_per_step", "hidden_act", "tie_word_embeddings")
    assert all(c[k] == config[k] for k in same)
    for cfg in (c, config):
        assert cfg["num_attention_heads"] * cfg["head_dim"] == cfg["hidden_size"]
        assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert set(c["limits"]) == set(config["limits"]) == LIMITS and set(c["check"]) == set(config["check"])
    assert [m["name"] for m in tiny.metrics("per_layer", cell)][2:] == NEW_METRICS
    kind = tiny.module("kinds", "ouro_step")
    assert kind.__file__.startswith(os.path.join(REPO, "chipbench", "kinds"))
    assert set(kind.MODEL_KEYS) <= set(c) and set(kind.MODEL_KEYS) <= set(config)
    assert set(kind.WRONG) | {"bf16"} == CONTROLS


@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_steps_checks_and_prints_the_contracts_line(capsys, trace):
    rc, lines = _run(capsys, trace, seed=4800000007 + trace)  # over 2^31: the driver's are large
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    compared = {l["compared"]: l for l in lines if "compared" in l}
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-ouro"))["limits"]
    assert set(compared) == set(stated)
    assert 1e-4 < compared["logits_rms_gap"]["value"] < stated["logits_rms_gap"]
    # the program keeps the stated precision: it lies nearer the reference computed so than the float32 one
    assert 1e-4 < compared["precision_gap"]["value"] < 0.7 * compared["logits_rms_gap"]["value"]
    assert 0 < compared["exit_pdf_gap"]["value"] < stated["exit_pdf_gap"]
    assert 0 < compared["update_gap"]["value"] < stated["update_gap"]
    assert 0 < compared["replay_loss_gap"]["value"] < stated["replay_loss_gap"]
    reported = {l["reported"]: l for l in lines if "reported" in l}
    assert reported["update_gap"]["worst"] == compared["update_gap"]["value"]
    window = reported["window"]
    # (b) and (c) are taken where the configuration says, not where the window ended: its first steps made again, the
    # same, and the replay goes on from there, two steps
    assert window["steps"] == last["attempted"] and len(window["replayed_losses"]) == 2
    assert window["evaluated_at_step"] == tiny.config(tiny.cell("tiny-ouro"))["check"]["evaluation_step"] == 4
    assert window["steps_made_again_differ"] == 0  # whether the window made more steps than that or fewer
    assert all(1.0 < v < 4.0 for v in window["expected_pass_first_last"]) and 0.5 < window["exit_entropy_last"] < np.log(4) + 1e-6
    assert all(4.0 < v < 5.5 for v in window["loss_first_last"])
    samples = next(l for l in lines if "samples" in l)
    assert samples["compiles_in_window"] == 0
    if trace:
        got = last["metrics"]
        assert got["ouro_compiles_in_window"]["value"] == 0
        # no TPU kernel of these names and no TPU modules line in a CPU trace: the readers leave them out
        assert not {"ouro_attention_ms", "ouro_attention_roofline", "ouro_step_mfu", "ouro_loop_passes"} & set(got)
    else:
        assert set(last["metrics"]) == {"call_p50_ms", "items_per_s", "setup_s"}
        assert last["metrics"]["items_per_s"]["value"] > 0


def test_the_same_seed_gives_the_same_weights_and_batches():
    import jax

    tiny = manifest.load(TINY)
    ref = tiny.module("references", "ouro_plain")
    kind = tiny.module("kinds", "ouro_step")
    config = tiny.config(tiny.cell("tiny-ouro"))
    c = {k: config[k] for k in kind.MODEL_KEYS}
    big = 4800000007
    make = lambda seed: ref.init_params(seed, c, config["init_std"], config["init_out_std"], config["init_gate_std"])  # noqa: E731
    a, b, other = make(big), make(big), make(big + 1)
    assert np.array_equal(a["layers"][1]["wf_g"], b["layers"][1]["wf_g"])
    assert not np.array_equal(a["layers"][1]["wf_g"], other["layers"][1]["wf_g"])
    assert abs(float(np.std(np.asarray(a["embed"]))) - 0.02) < 2e-3 and a["head"].shape == (64, 128)
    for name in ("wo", "wf_d"):
        assert abs(float(np.std(np.asarray(a["layers"][0][name]))) - config["init_out_std"]) < 2e-4, name
    assert np.all(np.asarray(a["g_f"]) == 1) and all(np.all(np.asarray(a["layers"][1][g]) == 1) for g in ("g_1", "g_2", "g_3", "g_4"))
    assert a["w_gate"].shape == (64,) and float(a["b_gate"]) == 0.0 and 0.01 < float(np.std(np.asarray(a["w_gate"]))) < 0.03
    tree = kind.to_system(a, c)
    assert tree["params"]["exit_gate_kernel"].shape == (64, 1) and tree["params"]["exit_gate_bias"].shape == (1,)
    assert set(tree["params"]) == {"embed", "ln_f", "lm_head", "exit_gate_kernel", "exit_gate_bias", "block0", "block1"}
    back = kind.from_system(tree)
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(a)))
    cdf = ref.zipf_cdf(config["vocab_size"], config["zipf_s"])
    assert np.array_equal(ref.batch(big, 3, 1, 48, cdf), ref.batch(big, 3, 1, 48, cdf))


def test_the_controls_fail_the_limits_the_program_meets(capsys):
    """``limits.py`` on the tiny cell: the program's numbers against the
    controls', each put through the run's comparison: the reference a
    precision below, three passes for four, ``ln_f`` outside the loop, the last
    exit alone, ``beta`` 0, the gate's gradient stopped, a shared weight's
    gradient from one pass only; the replay a precision below; AdamW with
    bfloat16 moments."""
    assert limits.main(["--workload", "tiny-ouro", "--seeds", "4800000021"], root=TINY) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    row = lines[-1]
    program, control = row["program"], row["control"]
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-ouro"))["limits"]
    assert all(program[name] <= stated[name] for name in program)
    assert {"update_gap", "replay_loss_gap"} <= {name for name in control if control[name] > stated[name]}
    rows = {l["control"]: l for l in lines if "control" in l and "refused" in l}
    assert set(rows) == CONTROLS | {"replay.bf16", "replay.beta_zero", "replay.last_exit_only"}
    assert all(r["refused"] and r["refused_by"] for r in rows.values()), {n: r["refused_by"] for n, r in rows.items()}
    assert {"exit_pdf_gap", "precision_gap"} <= set(rows["bf16"]["refused_by"])
    assert "logits_rms_gap" not in rows["bf16"]["refused_by"]  # against float32 the program's own rounding is as large
    assert program["precision_gap"] < 0.5 * rows["bf16"]["precision_gap"]
    assert {"logits_rms_gap", "exit_pdf_gap"} <= set(rows["three_passes"]["refused_by"])
    assert "logits_rms_gap" in rows["ln_f_once"]["refused_by"]
    # the last exit alone: the exits themselves are sound, the distribution and what is made of it are not
    assert rows["last_exit_only"]["logits_rms_gap"] == 0 and {"exit_pdf_gap", "loss_gap", "grad_norm_gap"} <= set(rows["last_exit_only"]["refused_by"])
    assert rows["beta_zero"]["exit_pdf_gap"] == 0 and {"loss_gap", "grad_norm_gap"} <= set(rows["beta_zero"]["refused_by"])
    # these two leave every forward number where it was: the gradient's norm by group alone sees them
    for name in ("gate_gradient_stopped", "one_use"):
        assert rows[name]["refused_by"] == ["grad_norm_gap"] and rows[name]["loss_gap"] == 0, name
    assert rows["gate_gradient_stopped"]["grad_norm_gap"] == 1.0  # the gate's group reads no gradient at all
    # the replay: a wrong term of the loss shows at once (a precision below shows at this size, not at the cell's)
    for name in ("replay.bf16", "replay.beta_zero", "replay.last_exit_only"):
        assert rows[name]["refused_by"] == ["replay_loss_gap"], name
    assert control["replay_loss_gap"] == min(rows[n]["replay_loss_gap"] for n in rows if n.startswith("replay."))


class _Only:
    """A module as one other module sees it, with some of its names replaced."""

    def __init__(self, module, **replaced):
        self._module, self._replaced = module, replaced

    def __getattr__(self, name):
        return self._replaced[name] if name in self._replaced else getattr(self._module, name)


@pytest.fixture
def fresh_programs():
    """The check's own programs are kept a process by their configuration:
    one that an earlier test of this worker traced sound would hide a fault."""
    from heat_tpu.core import program_cache

    program_cache.reset()
    yield
    program_cache.reset()


@pytest.mark.parametrize("fault", ["final_norm_left_out", "bfloat16_stream", "beta_zero", "gate_gradient_stopped", "lr"])
def test_a_fault_in_the_timed_path_is_not_correct(capsys, monkeypatch, fresh_programs, fault):
    """The timed path is built without the final norm between the passes, with
    a loss that leaves the entropy out, with an exit distribution that passes no
    gradient, or with an optimizer that does nothing: some number passes its
    limit each time and the run is not ``correct``."""
    import jax

    import heat_tpu.nn as nn
    import heat_tpu.nn.transformer as transformer

    kind = manifest.load(TINY).module("kinds", "ouro_step")
    if fault == "final_norm_left_out":
        sound = transformer._norm
        monkeypatch.setattr(
            transformer, "_norm", lambda kind, eps, dtype, name, **kw: (lambda x: x) if name == "ln_f" else sound(kind, eps, dtype, name, **kw)
        )
        expected = {"logits_rms_gap"}
    elif fault == "bfloat16_stream":  # a precision below the stated one: the stream, the norms and the products' results
        sound = kind.build_model
        monkeypatch.setattr(kind, "build_model", lambda config, comm: sound(config, comm).clone(accum_dtype=None))
        expected = {"precision_gap"}
    elif fault == "beta_zero":
        sound = transformer.causal_lm_loss
        monkeypatch.setattr(nn, "causal_lm_loss", lambda model, **kw: sound(model, **{**kw, "exit_beta": 0.0}))
        expected = {"loss_gap", "grad_norm_gap"}
    elif fault == "gate_gradient_stopped":
        sound = transformer.exit_distribution
        monkeypatch.setattr(transformer, "exit_distribution", lambda gates: jax.lax.stop_gradient(sound(gates)))
        expected = {"grad_norm_gap"}
    else:
        sound = kind.optimizer
        monkeypatch.setattr(kind, "optimizer", lambda o: sound({**o, "lr": 0.0}))
        expected = {"update_gap"}  # at the warm-up's first rates a step moves no loss past its rounding: (d) alone sees it
    rc, lines = _run(capsys, 0, seed=4800000033)
    assert rc == 0 and lines[-1]["correct"] is False
    failed = {l["compared"] for l in lines if "compared" in l and not l["ok"]}
    assert expected <= failed, failed
