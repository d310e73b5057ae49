"""The ``lm_step`` kind, its configuration, counts and metric readers: the
manifest with PR 26's entries, the counts against the figures the issue
gives, and the kind end to end on the CPU through ``chipbench/run.py`` with a
tiny manifest of its own (``tiny_lm/``: the same kind, reference, metrics and
counts on a configuration a CPU test can hold).

A CPU run rehearses control flow and the decision of ``correct``; none of
its numbers is a device metric.
"""

import json
import os

import pytest

from chipbench import limits, lm_trace, manifest, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_lm")
CELL = "olmoe-train-4k-1chip"
NEW_METRICS = [
    "lm_step_mfu", "moe_experts_ms", "moe_experts_roofline", "moe_route_ms", "lm_attention_ms",
    "lm_head_loss_ms", "lm_optimizer_ms", "lm_optimizer_roofline", "moe_load_max_over_mean",
    "compiles_in_window.step", "lm_idle_ms.prepare", "lm_idle_ms.launch", "lm_idle_ms.readback",
]


@pytest.fixture(autouse=True)
def _default_comm_again():
    yield
    import heat_tpu as ht

    ht.use_comm(None)  # the harness sets the cell's own mesh as the default


@pytest.fixture(scope="module")
def parts():
    return manifest.load(REPO)  # load() validates


# -- the manifest's new entries -------------------------------------------------------


def test_the_new_cell_its_configuration_and_every_part_are_found(parts):
    cell = parts.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmoe-1b-7b-train", "closed-1", 1)
    # the fourth of each list: what a later PR adds comes after, and breaks nothing here
    assert parts.doc["workloads"][3] is cell and parts.doc["configs"][3]["name"] == cell["config"]
    config = parts.config(cell)
    assert (config["kind"], config["reference"]) == ("lm_step", "olmoe_plain")
    for name in ("kinds", "references"):
        parts.module(name, config[{"kinds": "kind", "references": "reference"}[name]])
    reported = {s: [m["name"] for m in parts.metrics(s, cell)] for s in ("end_to_end", "per_layer")}
    assert reported["end_to_end"][:3] == ["call_p50_ms", "items_per_s", "setup_s"]
    first = ["device_idle_share", "launches_per_call", "host_ms_per_call"] + NEW_METRICS
    assert reported["per_layer"][:len(first)] == first
    for m in parts.metrics("per_layer", cell):
        assert callable(parts.module("metrics", m["name"]).read)
    new = parts.doc["per_layer"][19:19 + len(NEW_METRICS)]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"][0] == CELL for m in new)
    assert {m["moves"] for m in new if m["name"].startswith("lm_idle_ms")} == {"items_per_s"}
    assert {m["moves"] for m in new if not m["name"].startswith("lm_idle_ms")} == {"call_p50_ms"}
    layers = {m["layer"] for m in parts.doc["per_layer"][:19]} | {"training stack"}
    assert {m["layer"] for m in new} <= layers


def test_nothing_the_benchmark_had_is_changed(parts):
    """The three cells, their configurations and the metrics before PR 26's,
    as PR 25 left them (names and order; the files themselves are the
    driver's to compare)."""
    doc = parts.doc
    assert [w["name"] for w in doc["workloads"][:3]] == ["kmeans-fit-1chip", "cdist-susy-1chip", "kmeans-fit-4chip"]
    assert [c["name"] for c in doc["configs"][:3]] == ["heat-kmeans", "heat-kmeans-4chip", "heat-cdist-susy"]
    assert len(doc["per_layer"]) >= 19 + len(NEW_METRICS) and doc["run_seconds"] == 20
    assert [m["name"] for m in doc["end_to_end"]] == ["call_p50_ms", "call_p95_ms", "items_per_s", "setup_s"]
    old = doc["per_layer"][:19]
    assert all(CELL not in m.get("workloads", []) for m in old)


def test_the_configuration_keeps_every_published_number(parts):
    """The catalog's row for OLMoE-1B-7B-0125-Instruct, key for key; only the
    depth is reduced, and the file says what was assumed."""
    config = parts.config(parts.cell(CELL))
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304,
    }
    differs = sorted(k for k, v in published.items() if config[k] != v)
    entry = parts.doc["configs"][3]
    assert differs == sorted(config["reduced"]) == sorted(entry["reduced"]) == ["num_hidden_layers"]
    assert config["num_hidden_layers"] == 1
    assert (config["sequences_per_step"], config["sequence_length"]) == (4, 4096)
    assert set(config["assumed"]) >= {"loss", "optimizer", "init", "tokens", "sequences_per_step"}
    assert "float32" in config["guarantee"] and "bfloat16 operands" in config["guarantee"]
    assert config["roofline_modules"] == "dp_train_step"
    # the compiled step fills the chip: state 10.0 GB, the program at least 70% of 16 GB
    mem = config["memory_analysis"]
    assert mem["total_bytes"] == (
        mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
        - mem["alias_size_in_bytes"] + mem["temp_size_in_bytes"]
    )
    assert 0.7 * 16e9 <= mem["total_bytes"] < 15 * 2**30
    assert set(config["limits"]) == {
        "assignments_gap", "losses_not_finite", "logits_gap", "logits_rms_gap", "loss_gap",
        "grad_norm_gap", "routing_disagreement", "replay_loss_gap", "replay_counts_differ_share",
        "update_gap",
    }
    assert config["limits"]["assignments_gap"] == 0 and config["limits"]["losses_not_finite"] == 0


# -- the counts against the issue's figures --------------------------------------------


def test_counts_give_the_issues_figures(parts):
    config = parts.config(parts.cell(CELL))
    step = parts.module("counts", "olmoe_step")
    per_token = step.forward_flops_per_token(config)
    assert {k: round(v / 1e6, 1) for k, v in per_token.items()} == {
        "experts": 100.7, "projections": 33.6, "attention": 16.8, "router": 0.3, "head": 206.0,
    }
    work = step.work(config, 1)
    assert work["bytes"] == 0 and round(work["flops"] / 1e12, 1) == 17.6
    assert round(work["flops"] / 16384 / 1e9, 2) == 1.07  # GFLOP a token
    assert round(1e3 * work["flops"] / 197e12) == 89  # ms a step at the peak
    experts = parts.module("counts", "moe_experts").work(config, 1)
    assert experts["flops"] == 3 * 3 * 2 * 131072 * 2048 * 1024 and round(experts["flops"] / 1e12, 2) == 4.95
    assert experts["flops"] / 197e12 > experts["bytes"] / 819e9  # compute-bound in the count
    assert 3 * per_token["experts"] * 16384 == experts["flops"]
    adamw = parts.module("counts", "adamw")
    assert adamw.parameters(config) == 625_616_896
    assert adamw.work(config, 1)["bytes"] == 28 * 625_616_896
    assert round(1e3 * adamw.work(config, 1)["bytes"] / 819e9) == 21  # ms at the peak bandwidth
    share = {k: v / sum(per_token.values()) for k, v in per_token.items()}
    assert round(100 * share["head"]) == 58 and round(100 * share["experts"]) == 28


def test_counts_grow_with_depth_and_tokens(parts):
    config = dict(parts.config(parts.cell(CELL)))
    step, experts = parts.module("counts", "olmoe_step"), parts.module("counts", "moe_experts")
    one, e1 = step.work(config, 1)["flops"], experts.work(config, 1)["flops"]
    config["num_hidden_layers"] = 16
    assert experts.work(config, 1)["flops"] == 16 * e1
    head = 3 * 16384 * step.forward_flops_per_token(config)["head"]
    assert step.work(config, 1)["flops"] - head == 16 * (one - head)
    config["sequences_per_step"] = 8
    assert experts.work(config, 1)["flops"] == 2 * 16 * e1


# -- the readers' names ------------------------------------------------------------------


@pytest.mark.parametrize("rx, yes, no", [
    (lm_trace.EXPERTS,
     ["%ragged-dot-none = f32[131072,1024]{1,0:T(8,128)} custom-call(", "%ragged-dot-none.7 = f32[64,2048,1024]{2,1,0} custom-call("],
     ["%ragged-dot-metadata = (s32[65]{0}) custom-call(", "%fusion.7 = f32[131072,1024] fusion("]),
    (lm_trace.ATTENTION,
     ["%flash_fwd.1 = (bf16[4,16,4096,128]{3,2,1,0}) custom-call(", "%flash_bwd_dq.1 = bf16[4,16,4096,128] custom-call(",
      "%flash_bwd_dkv.1 = (bf16[4,16,4096,128]) custom-call(", "%flash_bwd_fused = (f32[1,1,8,8]) custom-call("],
     ["%flash_forward = f32[] fusion(", "%custom-call.3 = u32[] custom-call("]),
    (lm_trace.HEAD_LOSS,
     ["%while = (s32[], f32[8,2048]) while(", "%while.1 = (s32[]) while("],
     ["%while_body = f32[] fusion(", "%fusion.1 = f32[] fusion(%while.1)"]),
])
def test_readers_find_their_kernels_by_name(rx, yes, no):
    assert all(rx.search(name) for name in yes)
    assert not any(rx.search(name) for name in no)


def test_route_and_optimizer_are_found_by_the_shape_of_what_they_write(parts):
    config = parts.config(parts.cell(CELL))
    route = lm_trace.route_rx(config)
    tiles = "{1,0:T(8,128)(2,1)}"
    assert route.search(f"%fusion.2 = bf16[131072,2048]{tiles} fusion(bf16[16384,2048]{tiles} %x, s32[131072]{{0}} %i), kind=kLoop")
    assert route.search(f"%fusion.42 = (f32[16384,8]{tiles}, f32[131072,2048]{tiles}) fusion(f32[16384,2048]{tiles} %g), kind=kLoop")
    assert route.search("%sort.1 = (s32[131072]{0}, s32[131072]{0}) sort(s32[131072]{0} %a, s32[131072]{0} %b)")
    # an operand of that shape is not a result of that shape; the experts are not routing
    assert not route.search(f"%fusion.41 = f32[16384,2048]{tiles} fusion(f32[131072,2048]{tiles} %y), kind=kLoop")
    assert not route.search(f"%ragged-dot-none.3 = f32[131072,2048]{tiles} custom-call(bf16[131072,1024]{tiles} %h)")
    adamw = lm_trace.OPTIMIZER
    t = "{2,1,0:T(8,128)}"
    assert adamw.search(f"%fusion.64 = (f32[64,2048,1024]{t}, f32[64,2048,1024]{t}, f32[64,2048,1024]{t}) fusion(f32[] %c)")
    assert adamw.search("%fusion.232 = (f32[2048]{0:T(1024)S(1)}, f32[2048]{0:T(1024)S(1)}, f32[2048]{0:T(1024)}) fusion(")
    assert not adamw.search(f"%fusion.213 = (f32[4096,128]{t}, f32[4096,128]{t}) fusion(")
    assert not adamw.search(f"%fusion.9 = (f32[64,2048,1024]{t}, f32[64,1024,2048]{t}, f32[64,2048,1024]{t}) fusion(")


def test_the_recorded_step_gives_every_trace_metric_a_value(parts):
    """One traced call of the cell on a TPU v5 lite (``recorded_lm_step_v5e.txt``,
    its header says how it was cut): each reader finds its piece under the
    name the compiler gave it, the pieces are disjoint, and no share of a
    roofline or of the peak passes 100%."""
    from types import SimpleNamespace

    with open(os.path.join(HERE, "recorded_lm_step_v5e.txt")) as f:
        text = "".join(l for l in f if not l.startswith("#"))
    peak = parts.table("peaks")["TPU v5 lite"]
    tr = trace_reduce.reduce(trace_reduce.load_text(text), peak["trace"])
    assert len(tr.calls) == 1 and len(tr.devices) == 1
    config = parts.config(parts.cell(CELL))
    reading = SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak=peak, parts=parts)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    ms = {name: read(name) for name in (
        "moe_experts_ms", "moe_route_ms", "lm_attention_ms", "lm_head_loss_ms", "lm_optimizer_ms",
    )}
    assert 40 < ms["moe_experts_ms"] < 70 and 8 < ms["lm_attention_ms"] < 20
    assert 60 < ms["lm_head_loss_ms"] < 110 and 18 < ms["lm_optimizer_ms"] < 35
    assert 10 < ms["moe_route_ms"] < 35
    program_ms = tr.module_time(config["roofline_modules"]) / 1e6
    assert 200 < program_ms < 300 and 0.8 * program_ms < sum(ms.values()) < program_ms
    # the pieces are disjoint: no event is read by two of them
    rxs = [lm_trace.EXPERTS, lm_trace.ATTENTION, lm_trace.HEAD_LOSS, lm_trace.OPTIMIZER, lm_trace.route_rx(config)]
    for e in tr.devices[0].ops:
        assert sum(bool(rx.search(e.name)) for rx in rxs) <= 1, e.name
    found = lambda rx: [e for e in tr.devices[0].ops if rx.search(e.name)]  # noqa: E731
    assert len(found(lm_trace.EXPERTS)) == 9 and len(found(lm_trace.HEAD_LOSS)) == 2
    assert len(found(lm_trace.ATTENTION)) == 3  # flash_fwd, flash_bwd_dq, flash_bwd_dkv
    assert len(found(lm_trace.OPTIMIZER)) >= 5  # experts' three, embedding, head (the small ones are cut)
    for name, lo in (("lm_step_mfu", 25), ("moe_experts_roofline", 30), ("lm_optimizer_roofline", 50)):
        assert lo < read(name) < 100, name
    assert reading.notes["moe_experts_roofline_bound"] == "compute"
    assert reading.notes["adamw_roofline_bound"] == "bandwidth"
    assert reading.notes["olmoe_step_roofline_bound"] == "compute"


def test_a_program_without_the_names_or_counters_reads_nothing():
    """What a parent commit gives: no kernel of these names in the trace, no
    ``moe.*`` counter in the registry: every reader returns None, none raises."""
    from types import SimpleNamespace

    ev = trace_reduce.Event("%fusion.1 = f32[8] fusion(f32[8] %x)", 10.0, 20.0)
    device = trace_reduce.Device("/device:TPU:0", [ev], [], [(10.0, 20.0)])
    tr = trace_reduce.Reduced((0.0, 100.0), [(5.0, 50.0)], [], [device])
    reading = SimpleNamespace(trace=tr, notes={}, config={}, chips=1, peak={}, parts=None)
    for rx in (lm_trace.EXPERTS, lm_trace.ATTENTION, lm_trace.HEAD_LOSS, lm_trace.OPTIMIZER):
        assert lm_trace.ms_per_call(reading, rx) is None
        assert lm_trace.share_of_least(reading, rx, "moe_experts") is None
    assert lm_trace.counter("moe.never_counted") is None
    assert lm_trace.ms_per_call(reading, lm_trace.route_rx(
        {"sequences_per_step": 4, "sequence_length": 4096, "num_experts_per_tok": 8, "hidden_size": 2048}
    )) is None
    untraced = SimpleNamespace(trace=None, notes={})
    assert lm_trace.ms_per_call(untraced, lm_trace.EXPERTS) is None


# -- the kind end to end on the CPU ------------------------------------------------------


def _run(capsys, trace, seed, seconds=0.4):
    rc = run.main(
        ["--workload", "tiny-olmoe", "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=TINY,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines


def test_tiny_lm_is_the_cell_at_a_rehearsal_size():
    tiny = manifest.load(TINY)
    real = manifest.load(REPO)
    cell = tiny.cell("tiny-olmoe")
    config, published = tiny.config(cell), real.config(real.cell(CELL))
    same = ("kind", "reference", "optimizer", "loss", "init_std", "zipf_s", "roofline_modules", "num_hidden_layers")
    assert all(config[k] == published[k] for k in same)
    assert config["check"]["update_steps"] == published["check"]["update_steps"] == 2
    assert set(config["limits"]) == set(published["limits"])
    # the same limits but the two that a width of 64 makes tighter (tests/test_olmoe.py)
    # and the share of 128 assignments, of which one flipped choice is 0.8%; the update's
    # gap reads 1e-4 where both programs round alike (here) and 1.5e-3 on the chip
    differ = {k for k in config["limits"] if config["limits"][k] != published["limits"][k]}
    assert differ == {"logits_gap", "logits_rms_gap", "replay_counts_differ_share", "update_gap"}
    names = [m["name"] for m in tiny.metrics("per_layer", cell)]
    assert names[2:2 + len(NEW_METRICS)] == NEW_METRICS
    # the kind and the readers are the harness's own files, not copies
    assert tiny.module("kinds", "lm_step").__file__.startswith(os.path.join(REPO, "chipbench", "kinds"))


@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_steps_checks_and_prints_the_contracts_line(capsys, trace):
    rc, lines = _run(capsys, trace, seed=4000000007 + trace)  # over 2^31: the driver's are large
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    compared = {l["compared"]: l for l in lines if "compared" in l}
    config = manifest.load(TINY).config(manifest.load(TINY).cell("tiny-olmoe"))
    assert set(compared) == set(config["limits"])  # the replay's too: as many steps as the window held, up to three
    assert compared["assignments_gap"]["value"] == 0 and compared["routing_disagreement"]["value"] == 0
    # mixed precision on the CPU: float32 accumulation of bfloat16 operands
    assert 1e-4 < compared["logits_rms_gap"]["value"] < 4.3e-3
    assert compared["replay_loss_gap"]["value"] < 1e-4
    # the step's update against the reference's AdamW on the same gradients: float32
    # against float32, a parameter's last bit where the two round a sum apart
    assert 0 < compared["update_gap"]["value"] < 5e-4
    reported = {l["reported"]: l for l in lines if "reported" in l}
    assert reported["update_gap"]["worst"] == compared["update_gap"]["value"]
    # the same evaluation against the reference on its own top-k, beside the forced one, with no limit
    assert reported["unforced"]["chosen_differ_share"] == 0
    assert 1e-4 < reported["unforced"]["logits_rms_gap"] < 4.3e-3
    samples = next(l for l in lines if "samples" in l)
    assert samples["compiles_in_window"] == 0
    if trace:
        got = last["metrics"]
        assert got["compiles_in_window.step"]["value"] == 0
        assert got["moe_load_max_over_mean"]["value"] >= 1.0
        for phase in ("prepare", "launch", "readback"):
            assert got[f"lm_idle_ms.{phase}"]["value"] >= 0
        # no TPU kernel of these names in a CPU trace: the readers leave them out
        assert not {"moe_experts_ms", "moe_experts_roofline", "lm_attention_ms"} & set(got)
        notes = next(l["notes"] for l in lines if "notes" in l)
        assert notes["spans_per_call"] == 4.0  # the step's root, prepare and launch, and the loop's readback
    else:
        assert set(last["metrics"]) == {"call_p50_ms", "items_per_s", "setup_s"}
        assert last["metrics"]["items_per_s"]["value"] > 0


def test_the_same_seed_gives_the_same_weights_and_batches():
    import numpy as np

    parts = manifest.load(TINY)
    ref = parts.module("references", "olmoe_plain")
    kind = parts.module("kinds", "lm_step")
    c = {k: parts.config(parts.cell("tiny-olmoe"))[k] for k in kind.MODEL_KEYS}
    big = 4000000007
    a, b, other = ref.init_params(big, c), ref.init_params(big, c), ref.init_params(big + 1, c)
    assert np.array_equal(a["layers"][0]["wg"], b["layers"][0]["wg"])
    assert not np.array_equal(a["layers"][0]["wg"], other["layers"][0]["wg"])
    assert abs(float(np.std(np.asarray(a["head"]))) - 0.02) < 1e-3 and np.all(np.asarray(a["g_f"]) == 1)
    cdf = ref.zipf_cdf(257)
    x, y = ref.batch(big, 3, 2, 32, cdf), ref.batch(big, 3, 2, 32, cdf)
    assert x.dtype == np.int32 and np.array_equal(x, y) and not np.array_equal(x, ref.batch(big, 4, 2, 32, cdf))
    many = ref.batch(big, 0, 64, 1024, cdf)
    assert many.min() == 0 and many.max() <= 256
    # Zipf s = 1: id 0 about twice as frequent as id 1, ten times id 9
    freq = np.bincount(many.ravel(), minlength=257) / many.size
    assert 1.8 < freq[0] / freq[1] < 2.2 and 8 < freq[0] / freq[9] < 12


def test_the_control_fails_the_limits_the_program_meets(capsys):
    """``limits.py`` on the tiny cell: the program's numbers against the
    control's (a bfloat16 accumulator, norms and router)."""
    assert limits.main(["--workload", "tiny-olmoe", "--seeds", "4000000021"], root=TINY) == 0
    row = json.loads([l for l in capsys.readouterr().out.splitlines() if l.startswith("{")][-1])
    program, control = row["program"], row["control"]
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-olmoe"))["limits"]
    assert all(program[name] <= stated[name] for name in program)
    failed = {name for name in control if control[name] > stated[name]}
    assert "logits_rms_gap" in failed and "logits_gap" in failed
    assert "update_gap" in failed  # AdamW with bfloat16 moments
    assert control["replay_loss_gap"] > 0 and control["assignments_gap"] == 0


@pytest.mark.parametrize("fault", ["lr", "weight_decay", "clip", "b2"])
def test_a_faulty_optimizer_in_the_timed_step_is_not_correct(capsys, monkeypatch, fault):
    """The step is built with an optimizer that does nothing (lr 0), does not
    decay, does not clip, or keeps another second moment (0.999 for 0.95),
    while the configuration and so the reference state the sound one: the
    update's gap passes its limit and the run is not ``correct``. The replayed
    losses, at the warm-up's learning rates, do not see any of them."""
    kind = manifest.load(TINY).module("kinds", "lm_step")
    sound = kind.optimizer
    planted = {"lr": 0.0, "weight_decay": 0.0, "clip": 1e9, "b2": 0.999}[fault]
    monkeypatch.setattr(kind, "optimizer", lambda o: sound({**o, fault: planted}))
    rc, lines = _run(capsys, 0, seed=4000000033)
    assert rc == 0 and lines[-1]["correct"] is False
    failed = {l["compared"] for l in lines if "compared" in l and not l["ok"]}
    assert failed == {"update_gap"}
