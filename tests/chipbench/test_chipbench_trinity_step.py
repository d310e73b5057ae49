"""The ``trinity_step`` kind, its configuration, counts and metric readers: the
manifest with PR 32's entries, the counts against a hand count at the tiny size
and against the figures the issue gives, the readers against a traced call
recorded on a TPU v5 lite, and the kind end to end on the CPU through
``chipbench/run.py`` with a tiny manifest of its own (``tiny_trinity/``: the
same kind, reference, metrics and counts on a configuration a CPU test can hold).

A CPU run rehearses control flow and the decision of ``correct``; none of its
numbers is a device metric.
"""

import json
import os
from types import SimpleNamespace

import pytest

from chipbench import limits, manifest, run, trace_reduce, trinity_trace

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_trinity")
CELL = "trinity-train-16k-1chip"
NEW_METRICS = [
    "swa_attention_ms", "swa_attention_roofline", "trinity_full_attention_ms", "swa_blocks_visited",
    "trinity_step_mfu", "trinity_experts_ms", "trinity_route_ms", "trinity_head_loss_ms", "trinity_optimizer_ms",
    "trinity_held_load", "trinity_compiles_in_window",
]
SLIDING, FULL = "sliding_attention", "full_attention"
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "layer_types": ([SLIDING] * 3 + [FULL]) * 8, "load_balance_coeff": 0.001,
    "max_position_embeddings": 131072, "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
    "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
    "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
LIMITS = {
    "assignments_gap", "losses_not_finite", "logits_gap", "logits_rms_gap", "loss_gap", "grad_norm_gap",
    "routing_disagreement", "replay_loss_gap", "replay_counts_differ_share", "update_gap", "bias_gap", "window_gap",
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
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("trinity-mini-train", "closed-1", 1)
    assert len(cell["why"]) <= 200 and "1 x 16,384" in cell["why"] and "1,024 rows" in cell["why"] and "1/16" in cell["why"]
    # the sixth of each list: what PR 30 left comes before, unchanged
    assert parts.doc["workloads"][5] is cell and parts.doc["configs"][5]["name"] == cell["config"]
    assert [w["name"] for w in parts.doc["workloads"][:5]] == [
        "kmeans-fit-1chip", "cdist-susy-1chip", "kmeans-fit-4chip", "olmoe-train-4k-1chip", "qwen3next-train-8k-1chip"]
    assert (config["kind"], config["reference"]) == ("trinity_step", "trinity_plain")
    parts.module("kinds", config["kind"])
    parts.module("references", config["reference"])
    reported = {s: [m["name"] for m in parts.metrics(s, cell)] for s in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == ["call_p50_ms", "items_per_s", "setup_s"]
    assert reported["per_layer"][:3 + len(NEW_METRICS)] == ["device_idle_share", "launches_per_call", "host_ms_per_call"] + NEW_METRICS
    for m in parts.metrics("per_layer", cell):
        assert callable(parts.module("metrics", m["name"]).read)
    # PR 32's eleven follow the 43 that were there (a later PR appends after them: nothing here pins the list's end)
    new = parts.doc["per_layer"][43:43 + len(NEW_METRICS)]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "call_p50_ms" for m in new)
    old = parts.doc["per_layer"][:43]
    assert all(CELL not in m.get("workloads", []) for m in old)
    assert {m["layer"] for m in new} <= {m["layer"] for m in old}
    assert {m["unit"] for m in new if "roofline" in m["name"] or "mfu" in m["name"]} == {"%"}
    assert parts.doc["configs"][5]["source"] == "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"


def test_the_configuration_keeps_every_published_number(parts, config):
    """The catalog's row for Trinity-Mini, key for key; the depth, the experts
    held and the vocabulary are reduced and nothing else, and the file says
    what was assumed."""
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    entry = parts.doc["configs"][5]
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == ["num_experts_held", "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) and "11.80 GB" in config["cut_arithmetic"]
    assert (config["num_hidden_layers"], config["num_experts_held"], config["vocab_size"]) == (8, 8, 25024)
    assert config["num_experts"] == 128 and config["vocab_size"] * 8 == 200192
    assert (config["sequences_per_step"], config["sequence_length"]) == (1, 16384)
    assert set(config["assumed"]) >= {
        "embedding_scale", "sandwich_norm", "attention_gate", "head_norm", "nope_on_full_layers", "router_bias",
        "bias_rule", "init", "tokens", "optimizer", "loss",
    }
    assert "held_window" not in config  # the windows' lengths are the layer's own (nn/moe.py::_held_experts), no option
    assert config["loss"] == {"load_balance": 0.0, "router_z": 0.0} and config["bias_rate"] == config["load_balance_coeff"]
    assert abs(config["init_out_std"] - 0.02 / (2 * 32) ** 0.5) < 1e-12
    assert "float32" in config["guarantee"] and "bfloat16 operands" in config["guarantee"]
    assert "none dropped" in config["guarantee"] and "all 128" in config["guarantee"]
    olmoe = parts.config(parts.cell("olmoe-train-4k-1chip"))
    assert config["optimizer"] == olmoe["optimizer"]
    mem = config["memory_analysis"]
    assert mem["total_bytes"] == (
        mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
        - mem["alias_size_in_bytes"] + mem["temp_size_in_bytes"]
    )
    assert 9.1e9 <= 16 * 737_480_704 <= mem["total_bytes"] < 15 * 2**30
    assert set(config["limits"]) == LIMITS
    assert config["limits"]["assignments_gap"] == 0 and config["limits"]["losses_not_finite"] == 0
    assert set(config["limits_set_from"]) >= set(config["limits"]) - {"assignments_gap", "losses_not_finite"}


# -- the counts --------------------------------------------------------------------------


def test_counts_give_the_issues_figures(parts, config):
    step = parts.module("counts", "trinity_step")
    per_token = step.forward_flops_per_token(config)
    assert {k: round(v / 1e6, 1) for k, v in per_token.items()} == {
        "attention_projections": 436.2, "attention_full": 268.5, "attention_sliding": 188.7, "dense": 151.0,
        "router": 3.1, "shared": 75.5, "experts": 37.7, "head": 102.5,
    }
    # a block: 54.5 M of projections; 134.2 M of scores and values where full and 31.5 M where sliding;
    # 75.5 M in a dense feed-forward, 19.4 M in an expert block
    assert round(per_token["attention_projections"] / 8e6, 1) == 54.5
    assert round(per_token["attention_full"] / 2e6, 1) == 134.2 and round(per_token["attention_sliding"] / 6e6, 1) == 31.5
    assert round((per_token["router"] + per_token["shared"] + per_token["experts"]) / 6e6, 1) == 19.4
    assert round(sum(per_token.values()) / 1e6) == 1263
    kernels = per_token["attention_full"] + per_token["attention_sliding"]
    assert round(kernels / 1e6) == 457 and round(100 * kernels / sum(per_token.values())) == 36
    work = step.work(config, 1)
    assert work["bytes"] == 0 and round(work["flops"] / 1e12, 1) == 62.1 and round(1e3 * work["flops"] / 197e12) == 315
    swa = parts.module("counts", "swa_attention")
    assert swa.pairs_per_head(16384, 2048) == 31_458_304 and swa.sliding_layers(config) == 6
    band = swa.work(config, 1)
    assert band["flops"] == 3 * 16384 * per_token["attention_sliding"]
    assert band["flops"] / 197e12 > band["bytes"] / 819e9  # the products bind, not the operands
    assert 40 < 1e3 * band["flops"] / 197e12 < 50 and 4 < 1e3 * band["bytes"] / 819e9 < 8


def test_counts_against_a_hand_count_at_the_tiny_size():
    tiny = manifest.load(TINY)
    c = tiny.config(tiny.cell("tiny-trinity"))
    step, swa = tiny.module("counts", "trinity_step"), tiny.module("counts", "swa_attention")
    # hidden 32; 4 query heads on 2 of 16; window 24; dense 48; 16 experts of width 16, top 3, 4 held; shared 16;
    # vocabulary 97; 8 layers (2 dense, 6 expert; 6 sliding, 2 full); 1 x 80 tokens
    pairs = 24 * 25 // 2 + (80 - 24) * 24
    assert swa.pairs_per_head(80, 24) == pairs and swa.pairs_per_head(10, 24) == 55
    f = step.forward_flops_per_token(c)
    assert f["attention_projections"] == 8 * 2 * 32 * (2 * 64 + 2 * 32 + 64)
    assert f["attention_full"] == 2 * 2 * 2 * 64 * 81 // 2 and f["attention_sliding"] == 6 * 2 * 2 * 64 * pairs // 80
    assert f["dense"] == 2 * 3 * 2 * 32 * 48 and f["router"] == 6 * 2 * 32 * 16 and f["shared"] == 6 * 3 * 2 * 32 * 16
    assert f["experts"] == int(6 * (3 * 4 / 16) * 3 * 2 * 32 * 16) and f["head"] == 2 * 32 * 97
    assert step.work(c, 1) == {"flops": 3 * 80 * sum(f.values()), "bytes": 0}
    w = swa.work(c, 1)
    assert w["flops"] == 3 * 6 * 4 * pairs * 2 * 2 * 16
    rows, lse = 80 * 16 * 2, 80 * 4 * 4
    assert w["bytes"] == 6 * ((rows * (8 + 4) + lse) + (rows * (12 + 4) + lse + rows * (4 + 4)))


def test_counts_grow_with_depth_and_shrink_with_the_window(parts, config):
    step = parts.module("counts", "trinity_step")
    one = step.forward_flops_per_token(config)
    deep = step.forward_flops_per_token({**config, "num_hidden_layers": 32})
    assert deep["attention_sliding"] == 4 * one["attention_sliding"] and deep["dense"] == one["dense"]
    assert deep["experts"] == 5 * one["experts"]  # 30 expert blocks for 6
    wide = step.forward_flops_per_token({**config, "sliding_window": 16384})
    assert wide["attention_sliding"] == 3 * one["attention_full"]  # a window of the whole sequence is the full form


# -- the readers ------------------------------------------------------------------------------


T1 = "{2,1,0:T(8,128)}"


def test_the_window_kernels_are_told_from_the_full_form_by_name():
    window, full = trinity_trace.WINDOW_ATTENTION, trinity_trace.FULL_ATTENTION
    names = {
        "swa_fwd": f"%swa_fwd.3 = (bf16[1,32,16384,128]{T1}, f32[1,32,16384,128]{T1}) custom-call(bf16[1,32,16384,128]{T1} %q)",
        "swa_dq": f"%swa_bwd_dq = bf16[1,32,16384,128]{T1} custom-call(bf16[1,32,16384,128]{T1} %q)",
        "swa_dkv": f"%swa_bwd_dkv.1 = (bf16[1,4,16384,128]{T1}, bf16[1,4,16384,128]{T1}) custom-call(bf16[1,32,16384,128]{T1} %q)",
        "flash_fwd": f"%flash_fwd.1 = (bf16[1,32,16384,128]{T1}, f32[1,32,16384,128]{T1}) custom-call(bf16[1,32,16384,128]{T1} %q)",
        "flash_dkv": f"%flash_bwd_dkv = (bf16[1,4,16384,128]{T1}, bf16[1,4,16384,128]{T1}) custom-call(bf16[1,32,16384,128]{T1} %q)",
        "other": f"%fusion.12 = bf16[16384,2048]{T1} fusion(bf16[16384,2048]{T1} %x), kind=kLoop",
    }
    assert [bool(window.search(v)) for v in names.values()] == [True, True, True, False, False, False]
    assert [bool(full.search(v)) for v in names.values()] == [False, False, False, True, True, False]


def test_route_and_head_are_found_by_the_cells_own_shapes(config):
    from chipbench import qnext_trace

    # a window of held rows: 2 x (131,072 / 16) = 16,384, as long as the sequence, the stream's own shape: the
    # routing's passes are those that take an int32 index vector; a further window is one even share, 8,192
    rows, further = trinity_trace.window_rows(config)
    assert qnext_trace.held_rows(config) == rows == 16384 == config["sequence_length"] and further == 8192
    route = trinity_trace.route_rx(config)
    assert route.search(f"%fusion.12 = bf16[{rows},2048]{T1} fusion(bf16[16384,2048]{T1} %x, s32[{rows}]{{0}} %tokens), kind=kLoop")
    assert route.search(f"%scatter.4 = f32[{rows},2048]{T1} scatter(f32[{rows},2048]{T1} %zeros, s32[{rows},1]{{1,0}} %tokens, f32[{rows},2048]{T1} %y)")
    assert route.search("%sort.3 = (s32[131072]{0}, s32[131072]{0}) sort(s32[131072]{0} %a, s32[131072]{0} %b)")
    assert not route.search(f"%ragged-dot-none.3 = f32[{rows},2048]{T1} custom-call(bf16[{rows},1024]{T1} %h)")
    assert not route.search(f"%fusion.9 = f32[{rows},2048]{T1} fusion(f32[{rows},2048]{T1} %y, f32[{rows}]{{0}} %w), kind=kLoop")  # no index vector
    # the sum of a window's rows back into their tokens writes tokens x hidden by the window's index vector
    assert route.search(f"%scatter.7 = f32[16384,2048]{T1} scatter(f32[16384,2048]{T1} %zeros, s32[{rows},1]{{1,0}} %tokens, f32[{rows},2048]{T1} %y)")
    # a norm over the stream, and the embedding's gather (it takes an index vector too, and the table)
    assert not route.search(f"%multiply_reduce_fusion.168 = (f32[16384]{{0}}, f32[1,16384,2048]{T1}) fusion(f32[16384,2048]{T1} %x, pred[16384]{{0}} %m), kind=kLoop")
    assert not route.search(f"%fusion.37 = f32[16384,2048]{T1} fusion(f32[25024,2048]{T1} %embedding, s32[16384]{{0}} %ids), kind=kCustom")
    assert route.search(f"%fusion.3 = bf16[{further},2048]{T1} fusion(bf16[16384,2048]{T1} %x, s32[{further}]{{0}} %tokens), kind=kLoop")
    head = trinity_trace.head_loss_rx(config)
    assert head.search(f"%while.9 = (u32[]{{:T(128)}}, u32[]{{:T(128)}}, f32[2048,25024]{{0,1:T(8,128)}}, f32[8,2048]{{1,0:T(8,128)}}")
    assert not head.search(f"%while.2 = (s32[]{{:T(128)}}, f32[16384,2048]{T1}, s32[]{{:T(128)}}, bf16[16384,2048]{T1}")


def test_the_recorded_step_gives_every_trace_metric_a_value(parts, config):
    """One traced call of the cell on a TPU v5 lite
    (``recorded_trinity_step_v5e.txt``, its header says how it was cut): each
    reader finds its piece, the pieces are disjoint, and no share passes 100%."""
    with open(os.path.join(HERE, "recorded_trinity_step_v5e.txt")) as f:
        text = "".join(l for l in f if not l.startswith("#"))
    peak = parts.table("peaks")["TPU v5 lite"]
    tr = trace_reduce.reduce(trace_reduce.load_text(text), peak["trace"])
    assert len(tr.calls) == 1 and len(tr.devices) == 1
    reading = SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak=peak, parts=parts)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    ms = {name: read(name) for name in NEW_METRICS if name.endswith("_ms")}
    assert all(v is not None and v > 0 for v in ms.values()), ms
    program_ms = tr.module_time(config["roofline_modules"]) / 1e6
    assert 0.45 * program_ms < sum(ms.values()) < program_ms
    rxs = {
        "window": trinity_trace.WINDOW_ATTENTION, "full": trinity_trace.FULL_ATTENTION,
        "head": trinity_trace.head_loss_rx(config), "route": trinity_trace.route_rx(config),
        "experts": trinity_trace.EXPERTS, "optimizer": trinity_trace.OPTIMIZER,
    }
    found = {k: [e for e in tr.devices[0].ops if rx.search(e.name)] for k, rx in rxs.items()}
    for e in tr.devices[0].ops:
        assert sum(bool(rx.search(e.name)) for rx in rxs.values()) <= 1, e.name
    # six sliding layers x (swa_fwd twice: the block runs again; dq; dkv), two full layers likewise
    assert len(found["window"]) == 24 and len(found["full"]) == 8 and len(found["head"]) == 1
    assert sorted({e.name.split(" ")[0].split(".")[0] for e in found["window"]}) == ["%swa_bwd_dkv", "%swa_bwd_dq", "%swa_fwd"]
    assert 5 < read("trinity_step_mfu") < 100 and 5 < read("swa_attention_roofline") < 100
    assert reading.notes == {"trinity_step_roofline_bound": "compute", "swa_attention_roofline_bound": "compute"}
    # a windowed layer's kernels take less than a full layer's: the band is 31.5 M of 134.2 M pairs a head
    assert ms["swa_attention_ms"] / 6 < 0.5 * ms["trinity_full_attention_ms"] / 2


def test_a_program_without_the_names_or_counters_reads_nothing(config):
    """What a parent commit gives: no such kernel in the trace, no
    ``attn.window.*`` in the registry: every reader returns None, none raises."""
    ev = trace_reduce.Event("%fusion.1 = f32[8] fusion(f32[8] %x)", 10.0, 20.0)
    device = trace_reduce.Device("/device:TPU:0", [ev], [], [(10.0, 20.0)])
    tr = trace_reduce.Reduced((0.0, 100.0), [(5.0, 50.0)], [], [device])
    reading = SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak={}, parts=None)
    assert trinity_trace.ms_per_call(reading, trinity_trace.WINDOW_ATTENTION) is None
    assert trinity_trace.share_of_least(reading, trinity_trace.WINDOW_ATTENTION, "swa_attention") is None
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    held = {k: counters.pop(k) for k in list(counters) if k.startswith("attn.window.")}
    try:
        assert trinity_trace.blocks_visited_over_live() is None
    finally:
        counters.update(held)
    assert trinity_trace.ms_per_call(SimpleNamespace(trace=None, notes={}), trinity_trace.WINDOW_ATTENTION) is None


# -- the kind end to end on the CPU ------------------------------------------------------


def _run(capsys, trace, seed, seconds=0.4):
    rc = run.main(
        ["--workload", "tiny-trinity", "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=TINY,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines


def test_tiny_trinity_is_the_cell_at_a_rehearsal_size(config):
    tiny = manifest.load(TINY)
    cell = tiny.cell("tiny-trinity")
    c = tiny.config(cell)
    same = ("kind", "reference", "optimizer", "loss", "bias_rate", "init_std", "init_out_std", "init_post_norm_gain", "zipf_s",
            "roofline_modules", "num_hidden_layers", "global_attn_every_n_layers", "num_dense_layers", "rope_theta",
            "rms_norm_eps", "route_norm", "route_scale", "score_func", "num_shared_experts", "sequences_per_step")
    assert all(c[k] == config[k] for k in same)
    assert set(c["limits"]) == set(config["limits"]) == LIMITS and set(c["check"]) == set(config["check"])
    names = [m["name"] for m in tiny.metrics("per_layer", cell)]
    assert names[2:] == NEW_METRICS
    kind = tiny.module("kinds", "trinity_step")
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
    stated = tiny.config(tiny.cell("tiny-trinity"))["limits"]
    assert set(compared) == set(stated)
    assert compared["assignments_gap"]["value"] == 0 and compared["bias_gap"]["value"] == 0
    assert 1e-4 < compared["logits_rms_gap"]["value"] < stated["logits_rms_gap"]
    assert 0 < compared["update_gap"]["value"] < stated["update_gap"]
    assert 1e-3 < compared["window_gap"]["value"] < stated["window_gap"]
    reported = {l["reported"]: l for l in lines if "reported" in l}
    assert reported["update_gap"]["worst"] == compared["update_gap"]["value"]
    assert reported["window_gap"]["worst"] == compared["window_gap"]["value"] and reported["window_gap"]["control"] is False
    assert reported["bias_gap"]["steps"] == last["attempted"] and reported["bias_gap"]["largest_bias"] > 0
    samples = next(l for l in lines if "samples" in l)
    assert samples["compiles_in_window"] == 0
    if trace:
        got = last["metrics"]
        assert got["trinity_compiles_in_window"]["value"] == 0
        # 4 of 16 experts held: an even load reads 1.0; the counters are the process's own, and other tests of
        # the same worker count into them (0.18 in a whole run of the suite)
        assert got["trinity_held_load"]["value"] > 0
        assert 1.0 <= got["swa_blocks_visited"]["value"] <= 1.5
        # no TPU kernel of these names in a CPU trace: the readers leave them out
        assert not {"swa_attention_ms", "swa_attention_roofline", "trinity_full_attention_ms", "trinity_experts_ms"} & set(got)
    else:
        assert set(last["metrics"]) == {"call_p50_ms", "items_per_s", "setup_s"}
        assert last["metrics"]["items_per_s"]["value"] > 0


def test_the_same_seed_gives_the_same_weights_and_batches():
    import numpy as np

    tiny = manifest.load(TINY)
    ref = tiny.module("references", "trinity_plain")
    kind = tiny.module("kinds", "trinity_step")
    config = tiny.config(tiny.cell("tiny-trinity"))
    c = {k: config[k] for k in kind.MODEL_KEYS}
    big = 4000000007
    make = lambda seed: ref.init_params(  # noqa: E731
        seed, c, config["init_std"], config["init_out_std"], config["init_post_norm_gain"])
    a, b, other = make(big), make(big), make(big + 1)
    assert np.array_equal(a["layers"][2]["wg"], b["layers"][2]["wg"])
    assert not np.array_equal(a["layers"][2]["wg"], other["layers"][2]["wg"])
    assert a["layers"][2]["wg"].shape[0] == 4 and a["layers"][2]["wr"].shape[1] == 16  # 4 held, routed over 16
    assert abs(float(np.std(np.asarray(a["head"]))) - 0.02) < 2e-3
    assert abs(float(np.std(np.asarray(a["layers"][0]["wf_d"]))) - config["init_out_std"]) < 3e-4
    # every gain 1 but those of the two norms that close a branch: what a sandwich block writes into the stream
    assert np.all(np.asarray(a["g_f"]) == 1) and all(np.all(np.asarray(a["layers"][0][g]) == 1) for g in ("g_a", "g_c", "g_q"))
    assert config["init_post_norm_gain"] == 0.125
    assert all(np.all(np.asarray(lp[g]) == 0.125) for lp in a["layers"] for g in ("g_b", "g_d"))
    assert np.all(np.asarray(ref.init_params(big, c)["layers"][0]["g_b"]) == 1)  # the function's own default
    assert a["bias"].shape == (6, 16) and not np.any(np.asarray(a["bias"]))
    assert "wf_g" in a["layers"][1] and "wr" not in a["layers"][1] and "wr" in a["layers"][2]
    cdf = ref.zipf_cdf(config["vocab_size"], config["zipf_s"])
    assert np.array_equal(ref.batch(big, 3, 1, 80, cdf), ref.batch(big, 3, 1, 80, cdf))


def test_the_controls_fail_the_limits_the_program_meets(capsys):
    """``limits.py`` on the tiny cell: the program's numbers against the
    controls' (a bfloat16 accumulator, norms and router; AdamW with bfloat16
    moments; a window one short or one long; biases left where they were) and
    the two controls of the model's own mask and positions."""
    assert limits.main(["--workload", "tiny-trinity", "--seeds", "4000000021"], root=TINY) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    row = lines[-1]
    program, control = row["program"], row["control"]
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-trinity"))["limits"]
    assert all(program[name] <= stated[name] for name in program)
    failed = {name for name in control if control[name] > stated[name]}
    assert {"update_gap", "window_gap", "bias_gap", "replay_counts_differ_share"} <= failed
    assert control["assignments_gap"] == 0 and control["bias_gap"] >= 1
    # each evaluated control went through the run's own comparison and was refused: the reference a precision
    # below, a full layer given the window, rotary on a full layer; the row holds the smallest of each number
    rows = {l["control"]: l for l in lines if "control" in l and "refused" in l}
    assert set(rows) == {"bf16", "full_window", "full_rotary"} and all(r["refused"] and r["refused_by"] for r in rows.values())
    for name in ("full_window", "full_rotary"):
        assert rows[name]["logits_rms_gap"] > 5 * stated["logits_rms_gap"], name
        assert any(l.get("compared") == "logits_rms_gap" and l["call"] == name and not l["ok"] for l in lines)
    assert control["logits_rms_gap"] == min(r["logits_rms_gap"] for r in rows.values())


@pytest.mark.parametrize("fault", ["window_one_short", "bias_left_alone", "lr"])
def test_a_fault_in_the_timed_path_is_not_correct(capsys, monkeypatch, fault):
    """The timed path is built with a window of 23 where the configuration (and
    so the reference) states 24, with a rule that moves no bias, or with an
    optimizer that does nothing: one number passes its limit each time and the
    run is not ``correct``."""
    kind = manifest.load(TINY).module("kinds", "trinity_step")
    if fault == "window_one_short":
        sound = kind.State.__init__

        def init(self, config, comm, seed, reference):
            # the model and its attention core are built one short; the reference keeps the stated window
            sound(self, {**config, "sliding_window": config["sliding_window"] - 1}, comm, seed, reference)
            self.config, self.c = config, {k: config[k] for k in kind.MODEL_KEYS}

        monkeypatch.setattr(kind.State, "__init__", init)
        expected = {"window_gap"}
    elif fault == "bias_left_alone":
        import heat_tpu.nn as nn

        monkeypatch.setattr(nn, "balance_bias_rule", lambda rate: lambda state, aux: state)
        expected = {"bias_gap"}
    else:
        sound = kind.optimizer
        monkeypatch.setattr(kind, "optimizer", lambda o: sound({**o, "lr": 0.0}))
        expected = {"update_gap"}
    rc, lines = _run(capsys, 0, seed=4000000033)
    assert rc == 0 and lines[-1]["correct"] is False
    failed = {l["compared"] for l in lines if "compared" in l and not l["ok"]}
    assert expected <= failed
    if fault == "bias_left_alone":  # the reference's replay moves its biases, so its later counts part from the program's
        assert failed <= expected | {"replay_counts_differ_share"}
    elif fault == "lr":
        assert failed == expected
    # window_one_short: of 24 keys one is 4% of a query's mass: at this size the model's own numbers see it too
