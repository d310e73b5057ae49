"""The ``qnext_step`` kind, its configuration, counts and metric readers: the
manifest with PR 30's entries, the counts against a hand count at the tiny size
and against the figures the issue gives, the readers against a traced call
recorded on a TPU v5 lite, and the kind end to end on the CPU through
``chipbench/run.py`` with a tiny manifest of its own (``tiny_qnext/``: the same
kind, reference, metrics and counts on a configuration a CPU test can hold).

A CPU run rehearses control flow and the decision of ``correct``; none of its
numbers is a device metric.
"""

import json
import os
from types import SimpleNamespace

import pytest

from chipbench import limits, manifest, qnext_trace, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_qnext")
CELL = "qwen3next-train-8k-1chip"
NEW_METRICS = [
    "qnext_step_mfu", "gdn_mixer_ms", "gdn_scan_ms", "gdn_scan_roofline", "qnext_attention_ms",
    "qnext_experts_ms", "qnext_route_ms", "qnext_head_loss_ms", "qnext_optimizer_ms", "qnext_held_load",
    "qnext_compiles_in_window",
]
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32, "linear_value_head_dim": 128,
    "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


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
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("qwen3-next-80b-a3b-train", "closed-1", 1)
    assert len(cell["why"]) <= 200 and "320 rows" in cell["why"] and "5,120" in cell["why"]
    # the fifth of each list: what PR 26 left comes before, unchanged
    assert parts.doc["workloads"][4] is cell and parts.doc["configs"][4]["name"] == cell["config"]
    assert [w["name"] for w in parts.doc["workloads"][:4]] == [
        "kmeans-fit-1chip", "cdist-susy-1chip", "kmeans-fit-4chip", "olmoe-train-4k-1chip"]
    assert (config["kind"], config["reference"]) == ("qnext_step", "qwen3_next_plain")
    parts.module("kinds", config["kind"])
    parts.module("references", config["reference"])
    reported = {s: [m["name"] for m in parts.metrics(s, cell)] for s in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == ["call_p50_ms", "items_per_s", "setup_s"]
    assert reported["per_layer"] == ["device_idle_share", "launches_per_call", "host_ms_per_call"] + NEW_METRICS
    for m in parts.metrics("per_layer", cell):
        assert callable(parts.module("metrics", m["name"]).read)
    new = parts.doc["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "call_p50_ms" for m in new)
    old = parts.doc["per_layer"][:-len(NEW_METRICS)]
    assert len(old) == 32 and all(CELL not in m.get("workloads", []) for m in old)
    assert {m["layer"] for m in new} <= {m["layer"] for m in old}
    assert {m["unit"] for m in new if "roofline" in m["name"] or "mfu" in m["name"]} == {"%"}


def test_the_configuration_keeps_every_published_number(parts, config):
    """The catalog's row for Qwen3-Next-80B-A3B-Instruct, key for key; the
    depth, the experts held and the vocabulary are reduced and nothing else,
    and the file says what was assumed."""
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    entry = parts.doc["configs"][4]
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == ["num_experts_held", "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) and "10.01 GB" in config["cut_arithmetic"]
    assert (config["num_hidden_layers"], config["num_experts_held"], config["vocab_size"]) == (4, 32, 18992)
    assert config["num_experts"] == 512 and config["vocab_size"] * 8 == 151936
    assert (config["sequences_per_step"], config["sequence_length"]) == (2, 8192)
    assert set(config["assumed"]) >= {"loss", "optimizer", "init", "tokens", "sequences_per_step", "mtp"}
    assert config["loss"] == {"load_balance": 0.001, "router_z": 0.0}
    assert abs(config["init_out_std"] - 0.02 / (2 * 48) ** 0.5) < 1e-12
    assert "float32" in config["guarantee"] and "bfloat16 operands" in config["guarantee"]
    assert "state S" in config["guarantee"] and "none dropped" in config["guarantee"]
    olmoe = parts.config(parts.cell("olmoe-train-4k-1chip"))
    assert config["optimizer"] == olmoe["optimizer"]
    from heat_tpu.nn import deltanet

    assert config["delta_chunk"] == deltanet.CHUNK == 64
    mem = config["memory_analysis"]
    assert mem["total_bytes"] == (
        mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
        - mem["alias_size_in_bytes"] + mem["temp_size_in_bytes"]
    )
    assert 0.7 * 16e9 <= mem["total_bytes"] < 15 * 2**30
    assert set(config["limits"]) == {
        "assignments_gap", "losses_not_finite", "logits_gap", "logits_rms_gap", "loss_gap", "grad_norm_gap",
        "routing_disagreement", "replay_loss_gap", "replay_counts_differ_share", "update_gap", "delta_rule_gap",
    }
    assert config["limits"]["assignments_gap"] == 0 and config["limits"]["losses_not_finite"] == 0
    assert set(config["limits_set_from"]) >= set(config["limits"]) - {"assignments_gap", "losses_not_finite"}


# -- the counts --------------------------------------------------------------------------


def test_counts_give_the_issues_figures(parts, config):
    step = parts.module("counts", "qnext_step")
    per_token = step.forward_flops_per_token(config)
    assert {k: round(v / 1e6, 1) for k, v in per_token.items()} == {
        "gdn_projections": 202.1, "gdn_rule": 17.3, "attention_projections": 54.5, "attention": 67.1,
        "router": 8.4, "shared": 25.2, "experts": 15.7, "head": 77.8,
    }
    # a DeltaNet layer: 67.4 M in projections, 5.8 M in the rule, 12.3 M in router, shared and held experts
    assert round(per_token["gdn_projections"] / 3e6, 1) == 67.4 and round(per_token["gdn_rule"] / 3e6, 1) == 5.8
    assert round((per_token["router"] + per_token["shared"] + per_token["experts"]) / 4e6, 1) == 12.3
    assert round(sum(per_token.values()) / 1e6) == 468
    work = step.work(config, 1)
    assert work["bytes"] == 0 and round(work["flops"] / 1e12, 1) == 23.0
    assert round(work["flops"] / 16384 / 1e9, 2) == 1.40 and round(1e3 * work["flops"] / 197e12) == 117
    mixers = per_token["gdn_projections"] + per_token["gdn_rule"] + per_token["attention_projections"] + per_token["attention"]
    assert round(100 * mixers / sum(per_token.values())) == 73
    rule = parts.module("counts", "gdn_scan").work(config, 1)
    assert rule["flops"] == 3 * 16384 * per_token["gdn_rule"]
    assert rule["bytes"] / 819e9 > rule["flops"] / 197e12  # the states and the operands bind, not the products
    assert 10 < 1e3 * rule["bytes"] / 819e9 < 14


def test_counts_against_a_hand_count_at_the_tiny_size():
    tiny = manifest.load(TINY)
    c = tiny.config(tiny.cell("tiny-qnext"))
    step, rule = tiny.module("counts", "qnext_step"), tiny.module("counts", "gdn_scan")
    # hidden 32; 2 key and 4 value heads of 8; conv over 64 channels; 4 query heads on 2 of 16; 16 experts of
    # width 16, top 3, 4 held; shared 16; vocabulary 97; 4 layers (3 + 1); 2 x 80 tokens; chunks of 64
    f = step.forward_flops_per_token(c)
    assert f["gdn_projections"] == 3 * 2 * 32 * (16 + 16 + 32 + 32 + 8 + 32)
    assert f["gdn_rule"] == 3 * 4 * (2 * 64 * (3 * 8 + 2 * 8) + 3 * 2 * 8 * 8)
    assert f["attention_projections"] == 2 * 32 * (2 * 64 + 2 * 32 + 64)
    assert f["attention"] == 2 * 2 * 64 * 81 // 2
    assert f["router"] == 4 * 2 * 32 * 16 and f["shared"] == 4 * (3 * 2 * 32 * 16 + 2 * 32)
    assert f["experts"] == int(4 * (3 * 4 / 16) * 3 * 2 * 32 * 16) and f["head"] == 2 * 32 * 97
    assert step.work(c, 1) == {"flops": 3 * 160 * sum(f.values()), "bytes": 0}
    w = rule.work(c, 1)
    assert w["flops"] == 3 * 160 * f["gdn_rule"]
    forward = 160 * 4 * 24 * 2 + 160 * 4 * 8 + 160 * 4 * 8 * 4 + (2 * 2) * 4 * 64 * 4
    backward = forward + 160 * 4 * 24 * 4 + 160 * 4 * 8
    assert w["bytes"] == 3 * (forward + backward)


def test_counts_grow_with_depth_and_tokens(parts, config):
    step = parts.module("counts", "qnext_step")
    config = dict(config)
    one = step.work(config, 1)["flops"]
    head = 3 * 16384 * step.forward_flops_per_token(config)["head"]
    config["num_hidden_layers"] = 48
    assert step.work(config, 1)["flops"] - head == 12 * (one - head)
    config["num_experts_held"] = 64
    assert step.forward_flops_per_token(config)["experts"] == 2 * 12 * step.forward_flops_per_token(
        {**config, "num_experts_held": 32, "num_hidden_layers": 4})["experts"]


# -- the readers ------------------------------------------------------------------------------


T1 = "{2,1,0:T(8,128)}"


def test_the_loops_are_told_by_what_they_carry(config):
    scan, mixer, head = qnext_trace.gdn_scan_rx(config), qnext_trace.gdn_mixer_rx(config), qnext_trace.head_loss_rx(config)
    loops = {
        "scan": f"%while.535 = (u32[]{{:T(128)}}, u32[]{{:T(128)}}, f32[1,32,128,128]{{2,3,1,0:T(8,128)S(1)}}, bf16[128,1,32,64,128]{T1}, f32[128",
        "mixer": f"%while.481 = (u32[]{{:T(128)}}, u32[]{{:T(128)}}, f32[2,8192,2048]{T1}, bf16[2,8192,2048]{T1}, f32[32]{{0:T(128)}}, /*index=5*/f32[8192,4]{{0,1:T(4,128)}}, f32[32]",
        "mixer_back": f"%while.493 = (u32[]{{:T(128)}}, u32[]{{:T(128)}}, f32[32]{{0:T(128)}}, f32[8192,4]{{0,1:T(4,128)}}, f32[32]{{0:T(128)}}, /*index=5*/f32[2048,64]",
        "head": f"%while.479 = (u32[]{{:T(128)}}, u32[]{{:T(128)}}, f32[2048,18992]{{0,1:T(8,128)}}, f32[8,2048]{{1,0:T(8,128)}}, f32[8,2048,2048]{T1}",
    }
    assert [bool(scan.search(loops[k])) for k in loops] == [True, False, False, False]
    assert [bool(mixer.search(loops[k])) for k in loops] == [False, True, True, False]
    assert [bool(head.search(loops[k])) for k in loops] == [False, False, False, True]
    # the delta rule's batched pieces, by the chunked shape of what they write
    for name in (
        f"%convolution_add_fusion.75 = f32[128,32,64,64]{T1} fusion(f32[128,32,64,64]{T1} %a, f32[128,32,64,64]{T1} %b), kind=kOutput",
        f"%fusion.3940 = bf16[128,32,64,64]{T1} fusion(f32[128,32,64,64]{T1} %a), kind=kOutput",
        f"%convolution_bitcast_fusion.99 = f32[128,1,32,64,128]{T1} fusion(bf16[128,32,64,64]{T1} %a), kind=kOutput",
        f"%multiply_reduce_fusion.202 = (f32[128,64,32]{T1}, f32[1,128,64,32,128]{T1}) fusion(f32[128,32,64,128]{T1} %a)",
    ):
        assert scan.search(name), name
    for name in (
        f"%fusion.3942 = bf16[8192,4096]{T1} fusion(f32[1024,8,32,128]{T1} %a, f32[128,32,64,128]{T1} %b), kind=kOutput",  # an operand only
        f"%divide_multiply_fusion.15 = f32[1,8192,8192]{T1} fusion(f32[1,8192,8192]{T1} %a)",
        f"%fusion.9 = f32[128,1,32,128,128]{T1} fusion(f32[1,32,128,128]{T1} %state)",  # the stacked states: chunk 64 is not 128
    ):
        assert not scan.search(name), name


def test_route_is_found_by_the_window_of_held_rows(config):
    assert qnext_trace.held_rows(config) == 20480  # 2 x (163,840 / 16)
    route = qnext_trace.route_rx(config)
    assert route.search(f"%fusion.46 = f32[16384,2048]{T1} fusion(f32[16384,2048]{T1} %a, s32[20480]{{0}} %i, f32[20480,2048]{T1} %rows)") is None
    assert route.search(f"%select_multiply_fusion.7 = f32[20480,2048]{T1} fusion(f32[20480,2048]{T1} %y, f32[20480]{{0}} %w), kind=kLoop")
    assert route.search(f"%fusion.12 = bf16[20480,2048]{T1} fusion(bf16[16384,2048]{T1} %x, s32[20480]{{0}} %tokens), kind=kLoop")
    assert route.search("%sort.3 = (s32[163840]{0}, s32[163840]{0}) sort(s32[163840]{0} %a, s32[163840]{0} %b)")
    assert not route.search(f"%ragged-dot-none.3 = f32[20480,2048]{T1} custom-call(bf16[20480,512]{T1} %h)")


def test_the_recorded_step_gives_every_trace_metric_a_value(parts, config):
    """One traced call of the cell on a TPU v5 lite
    (``recorded_qnext_step_v5e.txt``, its header says how it was cut): each
    reader finds its piece, the pieces are disjoint but for the delta rule
    inside the mixers' loops, and no share passes 100%."""
    with open(os.path.join(HERE, "recorded_qnext_step_v5e.txt")) as f:
        text = "".join(l for l in f if not l.startswith("#"))
    peak = parts.table("peaks")["TPU v5 lite"]
    tr = trace_reduce.reduce(trace_reduce.load_text(text), peak["trace"])
    assert len(tr.calls) == 1 and len(tr.devices) == 1
    reading = SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak=peak, parts=parts)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    ms = {name: read(name) for name in NEW_METRICS if name.endswith("_ms")}
    assert all(v is not None and v > 0 for v in ms.values()), ms
    program_ms = tr.module_time(config["roofline_modules"]) / 1e6
    assert 700 < program_ms < 1000
    assert ms["gdn_scan_ms"] < ms["gdn_mixer_ms"] < 0.75 * program_ms  # the rule lies inside the mixers
    assert 0.55 * program_ms < ms["gdn_mixer_ms"]
    disjoint = sum(v for k, v in ms.items() if k != "gdn_scan_ms")
    assert 0.75 * program_ms < disjoint < program_ms
    rxs = {
        "scan": qnext_trace.gdn_scan_rx(config), "mixer": qnext_trace.gdn_mixer_rx(config),
        "head": qnext_trace.head_loss_rx(config), "route": qnext_trace.route_rx(config),
        "experts": qnext_trace.EXPERTS, "attention": qnext_trace.ATTENTION, "optimizer": qnext_trace.OPTIMIZER,
    }
    found = {k: [e for e in tr.devices[0].ops if rx.search(e.name)] for k, rx in rxs.items()}
    for e in tr.devices[0].ops:
        assert sum(bool(rx.search(e.name)) for rx in rxs.values()) <= 1, e.name
    assert len(found["mixer"]) == 9  # three layers: forward, forward again, backward
    assert len(found["head"]) == 1 and len(found["attention"]) == 4  # flash_fwd twice (the block runs again), dq, dkv
    assert len(found["experts"]) == 48  # four layers x (3 + 3 again + 6 backward)
    assert len([e for e in found["scan"] if e.name.startswith("%while")]) == 24  # 3 layers x 2 sequences x (2 + 2)
    for w in found["scan"]:  # every piece of the rule lies inside a mixer's loop
        assert any(m.start <= w.start and w.end <= m.end for m in found["mixer"]), w.name[:80]
    assert 5 < read("qnext_step_mfu") < 100 and 1 < read("gdn_scan_roofline") < 100
    assert reading.notes == {"qnext_step_roofline_bound": "compute", "gdn_scan_roofline_bound": "bandwidth"}


def test_nested_events_are_counted_once():
    loop = trace_reduce.Event("%while.1 = (s32[], f32[1,32,128,128]) while(%t)", 10.0, 110.0)
    body = trace_reduce.Event("%fusion.2 = bf16[128,1,32,64,128] fusion(bf16[128,1,32,64,128] %x)", 20.0, 30.0)
    after = trace_reduce.Event("%fusion.3 = f32[128,32,64,64] fusion(f32[128,32,64,64] %x)", 120.0, 140.0)
    device = trace_reduce.Device("/device:TPU:0", [loop, body, after], [], [(10.0, 140.0)])
    tr = trace_reduce.Reduced((0.0, 200.0), [(5.0, 150.0)], [], [device])
    c = {"linear_num_value_heads": 32, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
         "delta_chunk": 64, "sequence_length": 8192}
    reading = SimpleNamespace(trace=tr, notes={}, config=c, chips=1, peak={}, parts=None)
    assert qnext_trace.ms_per_call(reading, qnext_trace.gdn_scan_rx(c)) == (100.0 + 20.0) / 1e6


def test_a_program_without_the_names_or_counters_reads_nothing(config):
    """What a parent commit gives: no such loop or kernel in the trace, no
    ``moe.held_share`` in the registry: every reader returns None, none raises."""
    ev = trace_reduce.Event("%fusion.1 = f32[8] fusion(f32[8] %x)", 10.0, 20.0)
    device = trace_reduce.Device("/device:TPU:0", [ev], [], [(10.0, 20.0)])
    tr = trace_reduce.Reduced((0.0, 100.0), [(5.0, 50.0)], [], [device])
    reading = SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak={}, parts=None)
    for rx in (qnext_trace.gdn_scan_rx(config), qnext_trace.gdn_mixer_rx(config), qnext_trace.head_loss_rx(config),
               qnext_trace.route_rx(config), qnext_trace.EXPERTS):
        assert qnext_trace.ms_per_call(reading, rx) is None
        assert qnext_trace.share_of_least(reading, rx, "gdn_scan") is None
    assert qnext_trace.counter("moe.never_counted") is None
    assert qnext_trace.ms_per_call(SimpleNamespace(trace=None, notes={}), qnext_trace.EXPERTS) is None


# -- the kind end to end on the CPU ------------------------------------------------------


def _run(capsys, trace, seed, seconds=0.4):
    rc = run.main(
        ["--workload", "tiny-qnext", "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=TINY,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines


def test_tiny_qnext_is_the_cell_at_a_rehearsal_size(config):
    tiny = manifest.load(TINY)
    cell = tiny.cell("tiny-qnext")
    c = tiny.config(cell)
    same = ("kind", "reference", "optimizer", "loss", "init_std", "zipf_s", "roofline_modules", "num_hidden_layers",
            "full_attention_interval", "partial_rotary_factor", "rope_theta", "rms_norm_eps", "norm_topk_prob",
            "linear_conv_kernel_dim", "delta_chunk")
    assert all(c[k] == config[k] for k in same)
    assert set(c["limits"]) == set(config["limits"]) and set(c["check"]) == set(config["check"])
    names = [m["name"] for m in tiny.metrics("per_layer", cell)]
    assert names[2:] == NEW_METRICS
    kind = tiny.module("kinds", "qnext_step")
    assert kind.__file__.startswith(os.path.join(REPO, "chipbench", "kinds"))
    assert set(kind.MODEL_KEYS) <= set(c) and set(kind.MODEL_KEYS) <= set(config)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_steps_checks_and_prints_the_contracts_line(capsys, trace):
    rc, lines = _run(capsys, trace, seed=4000000007 + trace)  # over 2^31: the driver's are large
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    compared = {l["compared"]: l for l in lines if "compared" in l}
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-qnext"))["limits"]
    assert set(compared) == set(stated)
    assert compared["assignments_gap"]["value"] == 0 and compared["routing_disagreement"]["value"] == 0
    assert 1e-4 < compared["logits_rms_gap"]["value"] < stated["logits_rms_gap"]
    assert 0 < compared["update_gap"]["value"] < stated["update_gap"]
    assert 1e-3 < compared["delta_rule_gap"]["value"] < stated["delta_rule_gap"]
    reported = {l["reported"]: l for l in lines if "reported" in l}
    assert reported["update_gap"]["worst"] == compared["update_gap"]["value"]
    assert reported["delta_rule_gap"]["worst"] == compared["delta_rule_gap"]["value"]
    assert reported["delta_rule_gap"]["control"] is False
    samples = next(l for l in lines if "samples" in l)
    assert samples["compiles_in_window"] == 0
    if trace:
        got = last["metrics"]
        assert got["qnext_compiles_in_window"]["value"] == 0
        assert 0.5 < got["qnext_held_load"]["value"] < 2.0  # 4 of 16 experts held: an even load reads 1.0
        # no TPU loop or kernel of these names in a CPU trace: the readers leave them out
        assert not {"gdn_scan_ms", "gdn_mixer_ms", "qnext_experts_ms", "qnext_attention_ms", "gdn_scan_roofline"} & set(got)
    else:
        assert set(last["metrics"]) == {"call_p50_ms", "items_per_s", "setup_s"}
        assert last["metrics"]["items_per_s"]["value"] > 0


def test_the_same_seed_gives_the_same_weights_and_batches():
    import numpy as np

    tiny = manifest.load(TINY)
    ref = tiny.module("references", "qwen3_next_plain")
    kind = tiny.module("kinds", "qnext_step")
    config = tiny.config(tiny.cell("tiny-qnext"))
    c = {k: config[k] for k in kind.MODEL_KEYS}
    big = 4000000007
    make = lambda seed: ref.init_params(seed, c, config["init_std"], config["init_out_std"])  # noqa: E731
    a, b, other = make(big), make(big), make(big + 1)
    assert np.array_equal(a["layers"][0]["wg"], b["layers"][0]["wg"])
    assert not np.array_equal(a["layers"][0]["wg"], other["layers"][0]["wg"])
    assert a["layers"][0]["wg"].shape[0] == 4 and a["layers"][0]["wr"].shape[1] == 16  # 4 held, routed over 16
    assert abs(float(np.std(np.asarray(a["head"]))) - 0.02) < 2e-3
    assert abs(float(np.std(np.asarray(a["layers"][0]["w_out"]))) - config["init_out_std"]) < 1e-3
    assert np.all(np.asarray(a["g_f"]) == 0) and np.all(np.asarray(a["layers"][0]["g_o"]) == 1)
    assert np.all(np.asarray(a["layers"][0]["dt_bias"]) == 1)
    a_log = np.asarray(a["layers"][0]["a_log"])
    assert np.all(np.isfinite(a_log)) and np.all(np.exp(a_log) <= 16.0)
    assert "wq" in a["layers"][3] and "w_qkvz" in a["layers"][0] and "wq" not in a["layers"][0]


def test_the_controls_fail_the_limits_the_program_meets(capsys):
    """``limits.py`` on the tiny cell: the program's numbers against the
    controls' (a bfloat16 accumulator, norms and router; AdamW with bfloat16
    moments; the delta rule with a bfloat16 state)."""
    assert limits.main(["--workload", "tiny-qnext", "--seeds", "4000000021"], root=TINY) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    row = lines[-1]
    program, control = row["program"], row["control"]
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-qnext"))["limits"]
    assert all(program[name] <= stated[name] for name in program)
    failed = {name for name in control if control[name] > stated[name]}
    assert {"logits_rms_gap", "update_gap", "delta_rule_gap"} <= failed
    assert control["assignments_gap"] == 0
    state_control = next(l for l in lines if l.get("reported") == "state_control")
    assert 0 < state_control["logits_rms_gap"] < stated["logits_rms_gap"]  # the logits at this initialisation do not see it


@pytest.mark.parametrize("fault", ["lr", "weight_decay"])
def test_a_faulty_optimizer_in_the_timed_step_is_not_correct(capsys, monkeypatch, fault):
    """The step is built with an optimizer that does nothing (lr 0) or does not
    decay while the configuration and so the reference state the sound one: the
    update's gap passes its limit and the run is not ``correct``."""
    kind = manifest.load(TINY).module("kinds", "qnext_step")
    sound = kind.optimizer
    planted = {"lr": 0.0, "weight_decay": 0.0}[fault]
    monkeypatch.setattr(kind, "optimizer", lambda o: sound({**o, fault: planted}))
    rc, lines = _run(capsys, 0, seed=4000000033)
    assert rc == 0 and lines[-1]["correct"] is False
    failed = {l["compared"] for l in lines if "compared" in l and not l["ok"]}
    assert failed == {"update_gap"}
