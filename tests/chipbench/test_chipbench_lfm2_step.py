"""The ``lfm2_step`` kind, its configuration, counts and metric readers: the
manifest with PR 39's entries, the counts against a hand count at the tiny size
and against the figures the issue gives, the readers against events and map
rows written as the compiled step names them, and the kind end to end on the
CPU through ``chipbench/run.py`` with a tiny manifest of its own
(``tiny_lfm2/``: the same kind, reference, metrics and counts on a
configuration a CPU test can hold).

A CPU run rehearses control flow and the decision of ``correct``; none of its
numbers is a device metric.
"""

import json
import os
from types import SimpleNamespace

import pytest

from chipbench import lfm2_trace, limits, manifest, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_lfm2")
CELL = "lfm2-train-8k-1chip"
NEW_METRICS = [
    "lfm2_step_mfu", "lfm2_conv_mixer_ms", "lfm2_conv_mixer_roofline", "lfm2_attention_ms",
    "lfm2_attention_roofline", "lfm2_dense_ffn_ms", "lfm2_experts_ms", "lfm2_route_ms", "lfm2_head_loss_ms",
    "lfm2_optimizer_ms", "lfm2_held_load", "lfm2_compiles_in_window", "lfm2_attention_glue_ms", "lfm2_projections_ms",
    "lfm2_norms_ms", "lfm2_stream_ms", "lfm2_recomputed_ms",
]
BY_PIECE = {  # the metrics that read one piece of the scope map's table
    "lfm2_conv_mixer_ms": "conv_mixer", "lfm2_dense_ffn_ms": "feed_forward", "lfm2_route_ms": "route",
    "lfm2_attention_glue_ms": "attention_glue", "lfm2_projections_ms": "projections", "lfm2_norms_ms": "norms",
    "lfm2_stream_ms": "stream", "lfm2_recomputed_ms": "pass:recomputed",
}
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
    "layer_types": ["conv", "conv", "full_attention", "conv"] * 10, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
LIMITS = {
    "assignments_gap", "losses_not_finite", "logits_gap", "logits_rms_gap", "loss_gap", "grad_norm_gap",
    "routing_disagreement", "replay_loss_gap", "replay_counts_differ_share", "update_gap", "update_gap_unrouted", "bias_gap",
    "conv_gap",
}
T1 = "{2,1,0:T(8,128)}"


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
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("lfm2-24b-a2b-train", "closed-1", 1)
    assert len(cell["why"]) <= 200 and "2 x 8,192" in cell["why"] and "1,024 rows" in cell["why"] and "1/8" in cell["why"]
    assert "flash kernels" in cell["why"] and "largest piece by time" in cell["why"]  # what the traced run found, not the count
    # the seventh of each list: what PR 38 left comes before, unchanged
    assert parts.doc["workloads"][6] is cell and parts.doc["configs"][6]["name"] == cell["config"]
    assert [w["name"] for w in parts.doc["workloads"][:6]] == [
        "kmeans-fit-1chip", "cdist-susy-1chip", "kmeans-fit-4chip", "olmoe-train-4k-1chip", "qwen3next-train-8k-1chip",
        "trinity-train-16k-1chip"]
    assert (config["kind"], config["reference"]) == ("lfm2_step", "lfm2_plain")
    parts.module("kinds", config["kind"])
    parts.module("references", config["reference"])
    reported = {s: [m["name"] for m in parts.metrics(s, cell)] for s in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == ["call_p50_ms", "items_per_s", "setup_s"]
    assert reported["per_layer"][:3 + len(NEW_METRICS)] == ["device_idle_share", "launches_per_call", "host_ms_per_call"] + NEW_METRICS
    for m in parts.metrics("per_layer", cell):
        assert callable(parts.module("metrics", m["name"]).read)
    # PR 39's seventeen follow flash_blocks_streamed (a later PR appends after them: nothing here pins the list's end)
    names = [m["name"] for m in parts.doc["per_layer"]]
    at = names.index("flash_blocks_streamed") + 1
    new = parts.doc["per_layer"][at:at + len(NEW_METRICS)]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "call_p50_ms" for m in new)
    old = parts.doc["per_layer"][:at]
    assert all(CELL not in m.get("workloads", []) for m in old)
    assert {m["layer"] for m in new} <= {m["layer"] for m in old}
    assert {m["unit"] for m in new if "roofline" in m["name"] or "mfu" in m["name"]} == {"%"}
    assert parts.doc["configs"][6]["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"


def test_the_configuration_keeps_every_published_number(parts, config):
    """The catalog's row for LFM2-24B-A2B, key for key; the depth (with the
    leading dense blocks), the experts held and the vocabulary are reduced and
    nothing else, and the file says what was assumed."""
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    entry = parts.doc["configs"][6]
    assert differs == ["num_dense_layers", "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == ["num_dense_layers", "num_experts_held", "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) and "10.37 GB" in config["cut_arithmetic"]
    assert (config["num_hidden_layers"], config["num_dense_layers"], config["first_block"]) == (7, 1, 1)
    assert (config["num_experts_held"], config["first_expert_held"], config["vocab_size"]) == (8, 0, 8192)
    assert config["num_experts"] == 64 and config["vocab_size"] * 8 == 65536
    held = config["layer_types"][config["first_block"]:config["first_block"] + config["num_hidden_layers"]]
    assert held == ["conv", "full_attention", "conv", "conv", "conv", "full_attention", "conv"]
    assert (config["sequences_per_step"], config["sequence_length"]) == (2, 8192)
    assert set(config["assumed"]) >= {
        "tied_head", "normalisation_epsilon", "projection_order", "head_norm", "bias_rule", "init", "tokens", "optimizer", "loss",
    }
    assert config["loss"] == {"load_balance": 0.0, "router_z": 0.0} and config["bias_rate"] == 0.001
    assert abs(config["init_out_std"] - 0.02 / (2 * 40) ** 0.5) < 1e-12
    assert "float32" in config["guarantee"] and "bfloat16 operands" in config["guarantee"]
    assert "none dropped" in config["guarantee"] and "all 64" in config["guarantee"] and "taps" in config["guarantee"]
    assert "8 chips" in config["layout"] or "one chip of eight" in config["layout"]
    olmoe = parts.config(parts.cell("olmoe-train-4k-1chip"))
    assert config["optimizer"] == olmoe["optimizer"]
    mem = config["memory_analysis"]
    assert mem["total_bytes"] == (
        mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
        - mem["alias_size_in_bytes"] + mem["temp_size_in_bytes"]
    )
    assert 0.25 * 16e9 < 16 * 647_819_520 <= mem["total_bytes"] < 15 * 2**30
    assert set(config["limits"]) == LIMITS
    assert config["limits"]["assignments_gap"] == 0 and config["limits"]["losses_not_finite"] == 0
    assert set(config["limits_set_from"]) >= set(config["limits"]) - {"assignments_gap", "losses_not_finite"}


def test_the_builder_takes_the_configurations_layers(parts, config):
    kind = parts.module("kinds", "lfm2_step")
    model = kind.build_model(config, None)
    held = config["layer_types"][1:8]
    assert [model.mixer_of(i) for i in range(7)] == ["shortconv" if k == "conv" else "attention" for k in held]
    assert model.dense_layers == 1 and model.expert_layers() == (1, 2, 3, 4, 5, 6) and model.experts_held == (0, 8)
    assert (model.d_model, model.head_dim, model.num_kv_heads, model.vocab_size) == (2048, 64, 8, 8192)
    assert model.tie_embeddings and model.remat and model.norm_topk_eps == 1e-6 and model.conv_taps == 3


# -- the counts --------------------------------------------------------------------------


def test_counts_give_the_issues_figures(parts, config):
    step = parts.module("counts", "lfm2_step")
    per_token = step.forward_flops_per_token(config)
    # ISSUE 39: five conv mixers 168, the dense SwiGLU 145, two attentions 42 + 67, the held experts 57, the head 34, of 513
    assert {k: round(v / 1e6, 1) for k, v in per_token.items()} == {
        "conv_projections": 167.8, "attention_projections": 41.9, "attention": 67.1, "dense": 144.7,
        "router": 1.6, "experts": 56.6, "head": 33.6,
    }
    assert round(sum(per_token.values()) / 1e6) == 513 and step.layer_kinds(config) == (5, 2)
    plain = per_token["conv_projections"] + per_token["dense"]
    assert 0.6 < plain / sum(per_token.values()) < 0.62  # "three fifths of the step's products"
    work = step.work(config, 1)
    assert work == {"flops": 25_229_040_549_888, "bytes": 0} and round(1e3 * work["flops"] / 197e12) == 128
    # the held experts' rows as routed: a load of 1.25 x even adds a quarter of their term and nothing else
    more = step.forward_flops_per_token(config, 1.25)
    assert more["experts"] == int(1.25 * per_token["experts"]) and {k: v for k, v in more.items() if k != "experts"} == {
        k: v for k, v in per_token.items() if k != "experts"}
    # the flash kernels at heads of 64: half of what the padded lanes compute
    attention = step.attention_work(config, 1)
    assert attention == {"flops": 3_298_937_536_512, "bytes": 1_015_021_568}
    assert attention["flops"] == 3 * 16384 * per_token["attention"]
    assert attention["flops"] / 197e12 > attention["bytes"] / 819e9 and 16 < 1e3 * attention["flops"] / 197e12 < 17
    # the mixers: their projections' products three times, and five arrays of tokens x hidden and the weights thrice
    mixers = step.conv_mixer_work(config, 1)
    assert mixers == {"flops": 8_246_337_208_320, "bytes": 4_362_444_800}
    assert mixers["flops"] == 3 * 16384 * per_token["conv_projections"]
    assert mixers["bytes"] == 5 * 4 * (5 * 16384 * 2048 + 3 * (2048 * 6144 + 2048 * 2048 + 2048 * 3))
    assert mixers["flops"] / 197e12 > 7 * mixers["bytes"] / 819e9 and 41.8 < 1e3 * mixers["flops"] / 197e12 < 41.9


def test_counts_against_a_hand_count_at_the_tiny_size():
    tiny = manifest.load(TINY)
    c = tiny.config(tiny.cell("tiny-lfm2"))
    step = tiny.module("counts", "lfm2_step")
    # hidden 48; 4 query heads on 2 of 12; dense 80; 16 experts of width 16, top 3, 4 held; vocabulary 97;
    # 7 layers from published block 1 (1 dense, 6 expert; 5 conv, 2 attention); 2 x 40 tokens; 3 taps
    assert step.layer_kinds(c) == (5, 2)
    f = step.forward_flops_per_token(c)
    assert f["conv_projections"] == 5 * 2 * 48 * (3 * 48 + 48)
    assert f["attention_projections"] == 2 * 2 * 48 * (2 * 48 + 2 * 24)
    assert f["attention"] == 2 * 2 * 2 * 48 * (40 * 41 // 2) // 40
    assert f["dense"] == 3 * 2 * 48 * 80 and f["router"] == 6 * 2 * 48 * 16
    assert f["experts"] == int(6 * (3 * 4 / 16) * 3 * 2 * 48 * 16) and f["head"] == 2 * 48 * 97
    assert step.work(c, 1) == {"flops": 3 * 80 * sum(f.values()), "bytes": 0}
    a = step.attention_work(c, 1)
    rows, lse = 2 * 40 * 12 * 2, 2 * 40 * 4 * 4
    assert a["flops"] == 3 * 2 * (2 * 4 * (40 * 41 // 2) * 2 * 2 * 12)
    assert a["bytes"] == 2 * ((rows * (8 + 4) + lse) + (rows * (12 + 4) + lse + rows * (4 + 4)))
    m = step.conv_mixer_work(c, 1)
    assert m["flops"] == 3 * 80 * f["conv_projections"] == 22_118_400
    assert m["bytes"] == 5 * 4 * (5 * 80 * 48 + 3 * (48 * 144 + 48 * 48 + 48 * 3)) == 945_600


def test_counts_grow_with_depth_and_follow_the_stage(parts, config):
    step = parts.module("counts", "lfm2_step")
    one = step.forward_flops_per_token(config)
    whole = {**config, "num_hidden_layers": 40, "num_dense_layers": 2, "first_block": 0}
    deep = step.forward_flops_per_token(whole)
    assert step.layer_kinds(whole) == (30, 10) and deep["conv_projections"] == 6 * one["conv_projections"]
    assert deep["attention"] == 5 * one["attention"] and deep["dense"] == 2 * one["dense"]
    assert deep["experts"] * 6 == one["experts"] * 38 and deep["head"] == one["head"]
    assert step.conv_mixer_work(whole, 1) == {k: 6 * v for k, v in step.conv_mixer_work(config, 1).items()}


# -- the readers ------------------------------------------------------------------------------


def _reading(events, rows, config, parts=None, span=1000.0, program=None):
    """One device whose step program (``program`` ns long; the whole window
    where None) holds ``events`` as its leaf operations; one call spans the
    window of ``span`` ns; ``rows`` is the program's scope map."""
    program = trace_reduce.Event("jit_dp_train_step(123)", 0.0, span if program is None else program)
    device = trace_reduce.Device("/device:TPU:0", list(events), [program], [(0.0, span)])
    tr = trace_reduce.Reduced((0.0, span), [(0.0, span)], [], [device])
    reading = SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak={"flops_per_s": 197e12, "bytes_per_s": 819e9}, parts=parts)
    reading._lfm2_rows = rows
    return reading


def _row(modules="", scopes=(), path="x", which="forward"):
    return {"op": "fusion", "path": path, "modules": modules, "scopes": list(scopes), "pass": which, "fused": []}


def test_every_leaf_lies_in_one_piece_of_the_scope_map(parts, config):
    ev = lambda name, t0, dur: trace_reduce.Event(f"%{name} = f32[16384,2048]{T1} fusion(f32[16384,2048]{T1} %x)", t0, t0 + dur)  # noqa: E731
    events = [ev("convolution_multiply_fusion.9", 0, 10), ev("multiply_add_fusion.6", 10, 20), ev("fusion.3", 30, 40),
              ev("fusion.4", 70, 5), ev("fusion.5", 80, 7), ev("copy.9", 90, 3), ev("fusion.6", 100, 50), ev("select_bitcast_fusion.5", 150, 9),
              ev("fusion.7", 160, 11), ev("pad.3", 175, 13), ev("fusion.8", 190, 17), ev("fusion.9", 210, 19), ev("fusion.10", 230, 2)]
    conv = "TransformerLM/block3/conv"
    rows = {
        # XLA fuses the gates and the taps into the projections' products: one piece holds both
        "convolution_multiply_fusion.9": {**_row(conv, ("lm.body", "conv.project")), "fused": [[conv, ["lm.body", "conv.mix"]], [conv, ["lm.body", "conv.project"]]]},
        "multiply_add_fusion.6": _row(conv, ("lm.body", "conv.mix"), which="backward"),
        "fusion.3": _row(conv, ("lm.body", "conv.project"), which="recomputed"),
        "fusion.4": _row("TransformerLM/block0/gate", ("lm.body",)),
        "fusion.5": _row("TransformerLM/block0/down", ("lm.body",), which="backward"),
        "copy.9": {"op": "copy", "path": "", "modules": "", "scopes": [], "pass": "", "fused": [], "via": "multiply_add_fusion.6"},
        "fusion.6": _row("TransformerLM/block1/attn/query", ("lm.body",)),
        # a constant of the mix that the compiler shares with the routing: another module's fusion is not the mixer's
        "select_bitcast_fusion.5": {**_row("TransformerLM/block6/moe", ("lm.body", "moe.route")), "fused": [[conv, ["lm.body", "conv.mix"]]]},
        "fusion.7": _row("TransformerLM/block6/moe", ("lm.body", "moe.combine"), which="recomputed"),
        "pad.3": _row("TransformerLM/block1/attn", ("lm.body", "attn.full"), path="jit(f)/attn.full/pad"),  # a head of 64 to 128 lanes
        "fusion.8": _row("TransformerLM/block1/attn/q_norm", ("lm.body",)),
        "fusion.9": _row("TransformerLM/block1", ("lm.body",), which="backward"),  # a residual add
    }  # fusion.10: no row at all
    reading = _reading(events, rows, config, parts)
    ns = 1e-6  # one call: an event's nanoseconds as ms
    want = {"conv_mixer": 10 + 20 + 40 + 3, "feed_forward": 5 + 7, "projections": 50, "route": 9 + 11, "attention_glue": 13,
            "norms": 17, "stream": 19, "unscoped": 2,
            "pass:forward": 10 + 5 + 50 + 9 + 13 + 17, "pass:backward": 20 + 7 + 3 + 19, "pass:recomputed": 40 + 11, "pass:none": 2}
    assert lfm2_trace.pieces(reading) == pytest.approx({k: v * ns for k, v in want.items()})
    assert reading.notes["lfm2_pieces"] is lfm2_trace.pieces(reading)
    # the pieces, and the passes, each add up to the step's leaves
    for prefix in (False, True):
        assert sum(v for k, v in want.items() if k.startswith("pass:") == prefix) == sum(e.dur for e in events)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    for name, piece in BY_PIECE.items():
        assert read(name) == pytest.approx(want[piece] * ns), name
    # the mixers' share: the count covers what the timed fusions do (the products), not the gates and taps alone
    work = parts.module("counts", "lfm2_step").conv_mixer_work(config, 1)
    assert read("lfm2_conv_mixer_roofline") == pytest.approx(100 * work["flops"] / 197e12 * 1e3 / (73 * ns))
    assert reading.notes["lfm2_conv_mixer_roofline_bound"] == "compute"
    # at the peak in all three passes, the second forward run counted as time and not as work, the share reads 75%
    at_peak = _reading([ev("fusion.3", 0, 4 / 3 * 1e9 * work["flops"] / 197e12)], rows, config, parts, span=1e9)
    assert parts.module("metrics", "lfm2_conv_mixer_roofline").read(at_peak) == pytest.approx(75.0)
    # an attention block's projection, an expert block's gate are no mixer's
    assert lfm2_trace.piece_of(_row("TransformerLM/block1/attn/query")) == "projections"
    assert lfm2_trace.piece_of(_row("TransformerLM/block2/moe", ("moe.experts",))) == "experts"
    assert lfm2_trace.piece_of(None) == lfm2_trace.piece_of({}) == "unscoped"


def test_kernels_head_and_optimizer_are_found_by_name_and_shape(config):
    flash = f"%flash_fwd.2 = (bf16[2,32,8192,128]{T1}, f32[2,32,8192,128]{T1}) custom-call(bf16[2,32,8192,128]{T1} %pad.3)"
    assert lfm2_trace.FULL_ATTENTION.search(flash) and lfm2_trace.FULL_ATTENTION.search("%flash_bwd_dkv.3 = (bf16[2,8,8192,128]) custom-call(")
    # the routing is read by scope, not by the shape of a window's rows: the first window's length is the configuration's
    assert not hasattr(lfm2_trace, "route_rx") and config["held_window"] != 2
    head = lfm2_trace.head_loss_rx(config)
    # the head's loop carries the table's gradient, hidden x the slice, where an untied head's carried its kernel's
    assert head.search("%while.76 = (u32[]{:T(128)}, u32[]{:T(128)}, f32[2048,8192]{1,0:T(8,128)}, f32[8,2048]{1,0:T(8,128)S(1)}")
    assert not head.search("%while.70 = (s32[]{:T(128)}, f32[16384,2048]{1,0:T(8,128)}, s32[]{:T(128)}, s32[65536]{0:T(1024)}")
    assert lfm2_trace.OPTIMIZER.search("%fusion.8 = (f32[2048,6144]{1,0}, f32[2048,6144]{1,0}, f32[2048,6144]{1,0}) fusion(")


def test_the_shares_take_the_cells_own_counts(parts, config, monkeypatch):
    flash = lambda i, t0, dur: trace_reduce.Event(  # noqa: E731
        f"%flash_fwd.{i} = (bf16[2,32,8192,128]{T1}) custom-call(bf16[2,32,8192,128]{T1} %pad.{i})", t0, t0 + dur)
    reading = _reading([flash(2, 0, 30e6), flash(3, 40e6, 30e6)], {}, config, parts, span=1e9, program=450e6)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    assert read("lfm2_attention_ms") == pytest.approx(60.0)
    step = parts.module("counts", "lfm2_step")
    assert read("lfm2_attention_roofline") == pytest.approx(100 * step.attention_work(config, 1)["flops"] / 197e12 * 1e3 / 60.0)
    assert reading.notes["lfm2_attention_roofline_bound"] == "compute" and read("lfm2_attention_roofline") < 50
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    monkeypatch.setitem(counters, "moe.held_share", 3 * 1.1 / 8)
    monkeypatch.setitem(counters, "moe.steps", 3.0)
    assert read("lfm2_held_load") == pytest.approx(1.1)
    want = 100 * step.work(config, 1, 1.1)["flops"] / 197e12 / 0.45
    assert read("lfm2_step_mfu") == pytest.approx(want) and 25 < want < 35
    assert reading.notes["lfm2_step_held_load"] == pytest.approx(1.1)
    reading.compiles = 0
    assert read("lfm2_compiles_in_window") == 0.0


def test_a_program_without_the_names_or_counters_reads_nothing(parts, config):
    """What a parent commit gives: a map without ``conv`` modules or scopes, no
    kernel of these names, no routing counters: every reader returns None, none raises."""
    ev = trace_reduce.Event("%fusion.1 = f32[8] fusion(f32[8] %x)", 10.0, 20.0)
    rows = {"fusion.1": _row("TransformerLM/block0/attn/query", ("lm.body",))}
    reading = _reading([ev], rows, config, parts)
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    held = {k: counters.pop(k) for k in list(counters) if k.startswith("moe.") or k == "attn.lanes_padded"}
    try:
        for name in NEW_METRICS:  # the map names an attention projection, run forward: that piece alone is read
            if name not in ("lfm2_compiles_in_window", "lfm2_projections_ms"):
                assert parts.module("metrics", name).read(reading) is None, name
        assert parts.module("metrics", "lfm2_projections_ms").read(reading) == pytest.approx(10e-6)
    finally:
        counters.update(held)
    for rows in (None, {}):  # no map at all (no launch noted), an empty one
        reading = _reading([ev], rows, config, parts)
        assert lfm2_trace.pieces(reading) is None and all(
            parts.module("metrics", name).read(reading) is None for name in (*BY_PIECE, "lfm2_conv_mixer_roofline"))
    untraced = SimpleNamespace(trace=None, notes={}, config=config, chips=1, peak={}, parts=parts)
    assert lfm2_trace.pieces(untraced) is None and lfm2_trace.step_mfu(untraced) is None
    assert lfm2_trace.attention_roofline(untraced) is None


# -- the kind end to end on the CPU ------------------------------------------------------


def _run(capsys, trace, seed, seconds=0.4):
    rc = run.main(
        ["--workload", "tiny-lfm2", "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=TINY,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines


def test_tiny_lfm2_is_the_cell_at_a_rehearsal_size(config):
    tiny = manifest.load(TINY)
    cell = tiny.cell("tiny-lfm2")
    c = tiny.config(cell)
    same = ("kind", "reference", "optimizer", "loss", "bias_rate", "init_std", "init_out_std", "zipf_s", "roofline_modules",
            "num_hidden_layers", "num_dense_layers", "first_block", "conv_L_cache", "conv_bias", "rope_parameters", "norm_eps",
            "norm_topk_prob", "routed_scaling_factor", "use_expert_bias", "sequences_per_step")
    assert all(c[k] == config[k] for k in same)
    assert c["layer_types"][:8] == config["layer_types"][:8]
    assert c["hidden_size"] // c["num_attention_heads"] == 12  # heads that are no lane multiple, as the cell's 64 are none
    for cfg in (c, config):  # a first window longer than ht.nn's 2 even shares that still leaves further ones behind a branch
        assert 2 < cfg["held_window"] < cfg["num_experts"] / cfg["num_experts_held"]
    assert set(c["limits"]) == set(config["limits"]) == LIMITS and set(c["check"]) == set(config["check"])
    names = [m["name"] for m in tiny.metrics("per_layer", cell)]
    assert names[2:] == NEW_METRICS
    kind = tiny.module("kinds", "lfm2_step")
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
    stated = tiny.config(tiny.cell("tiny-lfm2"))["limits"]
    assert set(compared) == set(stated)
    assert compared["assignments_gap"]["value"] == 0 and compared["bias_gap"]["value"] == 0
    assert 1e-4 < compared["logits_rms_gap"]["value"] < stated["logits_rms_gap"]
    assert 0 < compared["update_gap"]["value"] < stated["update_gap"]
    assert 0 < compared["conv_gap"]["value"] < 1e-6  # float32 against float32; nothing before t0 moved at all
    reported = {l["reported"]: l for l in lines if "reported" in l}
    assert reported["update_gap"]["worst"] == compared["update_gap"]["value"]
    assert reported["update_gap"]["unrouted_worst"] == compared["update_gap_unrouted"]["value"] <= compared["update_gap"]["value"]
    assert reported["update_gap"]["turned_share"] == 0.0  # on a CPU no entry is turned: the two programs round alike
    assert reported["update_gap"]["at"].startswith("step ") and reported["update_gap"]["unrouted_at"].startswith("step ")
    assert reported["conv_gap"]["worst"] == compared["conv_gap"]["value"] and reported["conv_gap"]["control"] is False
    assert reported["conv_gap"]["at"] != "moved_before_t0"
    assert reported["bias_gap"]["steps"] == last["attempted"] and reported["bias_gap"]["largest_bias"] > 0
    assert reported["held_share"]["steps"] == last["attempted"] and len(reported["held_share"]["largest_by_layer"]) == 6
    samples = next(l for l in lines if "samples" in l)
    assert samples["compiles_in_window"] == 0
    if trace:
        got = last["metrics"]
        assert got["lfm2_compiles_in_window"]["value"] == 0
        assert got["lfm2_held_load"]["value"] > 0  # the counters are the process's own: other tests count into them
        # no TPU kernel of these names and no TPU modules line in a CPU trace: the readers leave them out
        assert not {"lfm2_attention_ms", "lfm2_attention_roofline", "lfm2_experts_ms", "lfm2_step_mfu"} & set(got)
    else:
        assert set(last["metrics"]) == {"call_p50_ms", "items_per_s", "setup_s"}
        assert last["metrics"]["items_per_s"]["value"] > 0


def test_the_sweep_times_steps_over_seeds_beside_the_held_share(capsys):
    """``chipbench/step_sweep.py``: what PERF.md's spread over seeds and its
    series of a step's time beside the held share are read with; a key of the
    configuration can be set for the process alone."""
    from chipbench import step_sweep

    args = ["--workload", "tiny-lfm2", "--seeds", "4000000007,5", "--steps", "5", "--series"]
    assert step_sweep.main(args + ["--set", "held_window=2"], root=TINY) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    rows, series = [l for l in lines if "p50_ms" in l], [l for l in lines if "series" in l]
    assert [r["seed"] for r in rows] == [4000000007, 5] == [r["seed"] for r in series]
    for row, steps in zip(rows, series):
        assert row["held_window"] == row["edge"] == 2 and row["steps"] == 5 == len(steps["series"])
        assert len(row["share_max_by_layer"]) == 6 and 0.5 < row["share_mean"] < 2 and row["min_ms"] <= row["p50_ms"] <= row["max_ms"]
        assert row["steps_past_the_edge"] == sum(share > 2 for _, share, _ in steps["series"])
        assert max(share for _, share, _ in steps["series"]) == max(row["share_max_by_layer"])
    assert rows[0]["share_first_step"] != rows[1]["share_first_step"]  # another seed, other weights and tokens


def test_the_same_seed_gives_the_same_weights_and_batches():
    import jax
    import numpy as np

    tiny = manifest.load(TINY)
    ref = tiny.module("references", "lfm2_plain")
    kind = tiny.module("kinds", "lfm2_step")
    config = tiny.config(tiny.cell("tiny-lfm2"))
    c = {k: config[k] for k in kind.MODEL_KEYS}
    big = 4000000007
    make = lambda seed: ref.init_params(seed, c, config["init_std"], config["init_out_std"])  # noqa: E731
    a, b, other = make(big), make(big), make(big + 1)
    assert np.array_equal(a["layers"][2]["wg"], b["layers"][2]["wg"])
    assert not np.array_equal(a["layers"][2]["wg"], other["layers"][2]["wg"])
    assert a["layers"][2]["wg"].shape[0] == 4 and a["layers"][2]["wr"].shape[1] == 16  # 4 held, routed over 16
    assert "head" not in a and abs(float(np.std(np.asarray(a["embed"]))) - 0.02) < 2e-3
    assert abs(float(np.std(np.asarray(a["layers"][0]["wf_d"]))) - config["init_out_std"]) < 3e-4
    assert abs(float(np.std(np.asarray(a["layers"][0]["w_out"]))) - config["init_out_std"]) < 3e-4
    taps = np.asarray(a["layers"][0]["w_conv"])
    assert taps.shape == (48, 3) and np.abs(taps).max() <= 3**-0.5 and np.abs(taps).max() > 0.5
    assert np.all(np.asarray(a["g_f"]) == 1) and all(np.all(np.asarray(a["layers"][1][g]) == 1) for g in ("g_a", "g_c", "g_q", "g_k"))
    assert a["bias"].shape == (6, 16) and not np.any(np.asarray(a["bias"]))
    # published blocks 1..7: conv-dense, attention, conv, conv, conv, attention, conv
    assert ["w_in" in lp for lp in a["layers"]] == [True, False, True, True, True, False, True]
    assert ["wf_g" in lp for lp in a["layers"]] == [True] + [False] * 6 and all("wr" in lp for lp in a["layers"][1:])
    tree = kind.to_system(a, c)
    assert "lm_head" not in tree["params"] and set(tree["route_bias"]) == {f"block{i}" for i in range(1, 7)}
    back = kind.from_system(tree)
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(a)))
    cdf = ref.zipf_cdf(config["vocab_size"], config["zipf_s"])
    assert np.array_equal(ref.batch(big, 3, 2, 40, cdf), ref.batch(big, 3, 2, 40, cdf))


def test_the_controls_fail_the_limits_the_program_meets(capsys):
    """``limits.py`` on the tiny cell: the program's numbers against the
    controls' (a bfloat16 accumulator, norms, gates, taps and router; AdamW
    with bfloat16 moments; the probe one tap short; biases left where they were)
    and the two controls of the model's own mechanisms (a convolution one tap
    short, the table's gradient without the head's product)."""
    assert limits.main(["--workload", "tiny-lfm2", "--seeds", "4000000021"], root=TINY) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    row = lines[-1]
    program, control = row["program"], row["control"]
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-lfm2"))["limits"]
    assert all(program[name] <= stated[name] for name in program)
    failed = {name for name in control if control[name] > stated[name]}
    assert {"update_gap", "update_gap_unrouted", "conv_gap", "bias_gap", "replay_counts_differ_share"} <= failed
    assert control["assignments_gap"] == 0 and control["bias_gap"] >= 1 and control["conv_gap"] > 0.3
    # each evaluated control went through the run's own comparison and was refused; the row holds the smallest of each number
    rows = {l["control"]: l for l in lines if "control" in l and "refused" in l}
    assert set(rows) == {"bf16", "short_tap", "untied_head"} and all(r["refused"] and r["refused_by"] for r in rows.values())
    assert "logits_rms_gap" in rows["bf16"]["refused_by"]
    # at this width a tap hardly reaches the logits (the mixers write little into the stream); the gradients see it
    assert "grad_norm_gap" in rows["short_tap"]["refused_by"] and rows["untied_head"]["refused_by"] == ["grad_norm_gap"]
    assert rows["untied_head"]["logits_rms_gap"] == 0 and rows["untied_head"]["grad_norm_gap"] > 10 * stated["grad_norm_gap"]
    assert control["grad_norm_gap"] == min(r["grad_norm_gap"] for r in rows.values())


@pytest.fixture
def fresh_programs():
    """The check's own programs (the evaluation, the convolution's probe) are
    kept a process by their configuration: one that an earlier test of this
    worker traced sound would hide a fault from the numbers that read it, and
    one traced with a fault would show it to a later test."""
    from heat_tpu.core import program_cache

    program_cache.reset()
    yield
    program_cache.reset()


@pytest.mark.parametrize("fault", ["tap_dropped", "head_untied", "bias_left_alone", "lr"])
def test_a_fault_in_the_timed_path_is_not_correct(capsys, monkeypatch, fresh_programs, fault):
    """The timed path is built with a convolution that drops its earliest tap,
    with a head of its own in place of the table, with a rule that moves no
    bias, or with an optimizer that does nothing: some number passes its limit
    each time and the run is not ``correct``."""
    kind = manifest.load(TINY).module("kinds", "lfm2_step")
    if fault == "tap_dropped":
        import heat_tpu.nn.deltanet as deltanet

        sound = deltanet.causal_depthwise_conv
        monkeypatch.setattr(deltanet, "causal_depthwise_conv", lambda x, w: sound(x, w.at[:, 0].set(0.0)))
        expected = {"conv_gap"}
    elif fault == "head_untied":
        import heat_tpu.nn.transformer as transformer

        sound = transformer.blocked_cross_entropy

        def untied(hidden, kernel, *a, **kw):  # the loss reads a head that is the table's copy: no gradient reaches the table through it
            import jax

            return sound(hidden, jax.lax.stop_gradient(kernel), *a, **kw)

        monkeypatch.setattr(transformer, "blocked_cross_entropy", untied)
        expected = {"grad_norm_gap"}
    elif fault == "bias_left_alone":
        import heat_tpu.nn as nn

        monkeypatch.setattr(nn, "balance_bias_rule", lambda rate: lambda state, aux: state)
        expected = {"bias_gap"}
    else:
        sound = kind.optimizer
        monkeypatch.setattr(kind, "optimizer", lambda o: sound({**o, "lr": 0.0}))
        expected = {"update_gap", "update_gap_unrouted"}
    rc, lines = _run(capsys, 0, seed=4000000033)
    assert rc == 0 and lines[-1]["correct"] is False
    failed = {l["compared"] for l in lines if "compared" in l and not l["ok"]}
    assert expected <= failed
    if fault == "lr":
        assert failed == expected
