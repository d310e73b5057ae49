"""The ``glm_step`` kind, its configuration, counts and metric readers: the
manifest with PR 41's entries, the counts against a hand count at the tiny size
and against the figures the issue gives, the readers against events and map
rows written as the compiled step names them, and the kind end to end on the
CPU through ``chipbench/run.py`` with a tiny manifest of its own
(``tiny_glm/``: the same kind, reference, metrics and counts on a
configuration a CPU test can hold).

A CPU run rehearses control flow and the decision of ``correct``; none of its
numbers is a device metric.
"""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import glm_trace, limits, manifest, run, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TINY = os.path.join(HERE, "tiny_glm")
CELL = "glm47flash-train-8k-1chip"
NEW_METRICS = [
    "glm_step_mfu", "glm_latent_attention_ms", "glm_latent_proj_ms", "glm_latent_assemble_ms", "glm_attention_ms",
    "glm_attention_roofline", "glm_mtp_ms", "glm_head_loss_ms", "glm_dense_ffn_ms", "glm_experts_ms", "glm_route_ms",
    "glm_optimizer_ms", "glm_held_load", "glm_compiles_in_window",
]
BY_TAG = {
    "glm_latent_attention_ms": "latent", "glm_latent_proj_ms": "latent_proj", "glm_latent_assemble_ms": "latent_assemble",
    "glm_mtp_ms": "mtp", "glm_dense_ffn_ms": "dense_ffn", "glm_experts_ms": "experts", "glm_route_ms": "route",
}
PUBLISHED = {  # the catalog's row (architectures.jsonl), key for key
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47, "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
}
LIMITS = {
    "assignments_gap", "losses_not_finite", "logits_gap", "logits_rms_gap", "mtp_logits_gap", "mtp_logits_rms_gap",
    "loss_gap", "mtp_loss_gap", "grad_norm_gap", "routing_disagreement", "replay_loss_gap", "replay_counts_differ_share",
    "update_gap", "update_gap_unrouted", "bias_gap", "leak_gap",
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
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("glm-4.7-flash-train", "closed-1", 1)
    assert len(cell["why"]) <= 200 and "2 x 8,192" in cell["why"] and "1,024 rows" in cell["why"] and "1/8" in cell["why"]
    assert "attention sees more than its share" in cell["why"]
    # the eighth of each list: what PR 40 left comes before, unchanged
    assert parts.doc["workloads"][7] is cell and parts.doc["configs"][7]["name"] == cell["config"]
    assert [w["name"] for w in parts.doc["workloads"][:7]] == [
        "kmeans-fit-1chip", "cdist-susy-1chip", "kmeans-fit-4chip", "olmoe-train-4k-1chip", "qwen3next-train-8k-1chip",
        "trinity-train-16k-1chip", "lfm2-train-8k-1chip"]
    assert (config["kind"], config["reference"]) == ("glm_step", "glm_plain")
    parts.module("kinds", config["kind"])
    parts.module("references", config["reference"])
    reported = {s: [m["name"] for m in parts.metrics(s, cell)] for s in ("end_to_end", "per_layer")}
    assert reported["end_to_end"] == ["call_p50_ms", "items_per_s", "setup_s"]
    assert reported["per_layer"][:3 + len(NEW_METRICS)] == ["device_idle_share", "launches_per_call", "host_ms_per_call"] + NEW_METRICS
    for m in parts.metrics("per_layer", cell):
        assert callable(parts.module("metrics", m["name"]).read)
    # PR 41's fourteen follow held_rows_moved (a later PR appends after them: nothing here pins the list's end)
    names = [m["name"] for m in parts.doc["per_layer"]]
    at = names.index("held_rows_moved") + 1
    new = parts.doc["per_layer"][at:at + len(NEW_METRICS)]
    assert [m["name"] for m in new] == NEW_METRICS
    assert all(m["workloads"] == [CELL] and m["moves"] == "call_p50_ms" for m in new)
    old = parts.doc["per_layer"][:at]
    assert all(CELL not in m.get("workloads", []) for m in old)
    assert {m["layer"] for m in new} <= {m["layer"] for m in old}
    assert {m["unit"] for m in new if "roofline" in m["name"] or "mfu" in m["name"]} == {"%"}
    assert parts.doc["configs"][7]["source"] == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"


def test_the_configuration_keeps_every_published_number(parts, config):
    """The catalog's row for GLM-4.7-Flash, key for key; the depth, the experts
    held and the vocabulary are reduced and nothing else, and the file says what
    was assumed."""
    differs = sorted(k for k, v in PUBLISHED.items() if config[k] != v)
    entry = parts.doc["configs"][7]
    assert differs == ["num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == ["num_experts_held", "num_hidden_layers", "vocab_size"]
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) and "11.30 GB" in config["cut_arithmetic"]
    assert (config["num_hidden_layers"], config["first_k_dense_replace"], config["num_nextn_predict_layers"]) == (5, 1, 1)
    assert (config["num_experts_held"], config["first_expert_held"], config["vocab_size"]) == (8, 0, 19360)
    assert config["n_routed_experts"] == 64 and config["vocab_size"] * 8 == 154880
    assert (config["sequences_per_step"], config["sequence_length"]) == (2, 8192)
    assert set(config["assumed"]) >= {
        "rotary_form", "latent_norm_epsilon", "prediction_module", "mtp_loss_weight", "router", "bias_rule", "first_window",
        "init", "tokens", "optimizer", "loss",
    }
    assert config["loss"] == {"load_balance": 0.0, "router_z": 0.0, "mtp": 0.3} and config["bias_rate"] == 0.001
    assert abs(config["init_out_std"] - 0.02 / (2 * 47) ** 0.5) < 1e-12
    assert "float32" in config["guarantee"] and "bfloat16 operands" in config["guarantee"]
    assert "none dropped" in config["guarantee"] and "all 64" in config["guarantee"] and "the same for all 20 heads" in config["guarantee"]
    assert "one chip of eight" in config["layout"]
    olmoe = parts.config(parts.cell("olmoe-train-4k-1chip"))
    assert config["optimizer"] == olmoe["optimizer"]
    mem = config["memory_analysis"]
    assert mem["total_bytes"] == (
        mem["argument_size_in_bytes"] + mem["output_size_in_bytes"]
        - mem["alias_size_in_bytes"] + mem["temp_size_in_bytes"]
    )
    assert 0.25 * 16e9 < 16 * 706_518_528 <= mem["total_bytes"] < 15 * 2**30
    assert set(config["limits"]) == LIMITS
    assert config["limits"]["assignments_gap"] == 0 and config["limits"]["losses_not_finite"] == 0
    assert set(config["limits_set_from"]) >= set(config["limits"]) - {"assignments_gap", "losses_not_finite"}


def test_the_builder_takes_the_configurations_layers(parts, config):
    kind = parts.module("kinds", "glm_step")
    model = kind.build_model(config, None)
    assert [model.mixer_of(i) for i in range(6)] == ["latent"] * 6 and tuple(model.latent) == (768, 512, 192, 64, 256)
    assert model.dense_layers == 1 and model.expert_layers() == (1, 2, 3, 4, 5) and model.experts_held == (0, 8)
    assert (model.d_model, model.num_heads, model.vocab_size, model.mtp_modules) == (2048, 20, 19360, 1)
    assert model.remat and model.route_scale == 1.8 and model.shared_d_ff == 1536 and not model.shared_gate
    assert model.held_window == config["held_window"] == 3.0 and not model.tie_embeddings


# -- the counts --------------------------------------------------------------------------


def test_counts_give_the_issues_figures(parts, config):
    step = parts.module("counts", "glm_step")
    per_token = step.forward_flops_per_token(config)
    # ISSUE 41: six latent mixers 261 of projections + 503 of scores, the dense SwiGLU 126, five shared experts 94,
    # the held experts' rows 47, W_eh 17, the head twice 159; 1,208 in all
    assert {k: round(v / 1e6, 1) for k, v in per_token.items()} == {
        "latent_projections": 261.1, "attention": 503.4, "dense": 125.8, "router": 1.3, "shared": 94.4,
        "experts": 47.2, "merge": 16.8, "head": 158.6,
    }
    assert int(sum(per_token.values()) / 1e6) == 1208 and step.blocks(config) == 6
    latent = per_token["latent_projections"] + per_token["attention"]
    assert 0.62 < latent / sum(per_token.values()) < 0.64  # "three fifths of the step's products"
    work = step.work(config, 1)
    assert work == {"flops": 59_402_417_602_560, "bytes": 0} and round(1e3 * work["flops"] / 197e12) == 302
    # the held experts' rows as routed: a load of 1.25 x even adds a quarter of their term and nothing else
    more = step.forward_flops_per_token(config, 1.25)
    assert more["experts"] == int(1.25 * per_token["experts"]) and {k: v for k, v in more.items() if k != "experts"} == {
        k: v for k, v in per_token.items() if k != "experts"}
    # the flash kernels at 20 heads of 256: the pairs counted once, compute-bound by far
    attention = step.attention_work(config, 1)
    assert attention["flops"] == 3 * 16384 * per_token["attention"] == 24_742_031_523_840
    assert attention["bytes"] == 6 * (2 * (4 * 2 * 8192 * 20 * 256 * 2 + 2 * 8192 * 20 * 4) + 4 * 2 * 8192 * 20 * 256 * 2)
    assert attention["flops"] / 197e12 > 8 * attention["bytes"] / 819e9 and 125 < 1e3 * attention["flops"] / 197e12 < 126


def test_counts_against_a_hand_count_at_the_tiny_size():
    tiny = manifest.load(TINY)
    c = tiny.config(tiny.cell("tiny-glm"))
    step = tiny.module("counts", "glm_step")
    # hidden 48; 3 heads of 10 + 6, values of 16, ranks 20 and 12; dense 80; 16 experts of width 16, top 3, 4 held, one
    # shared; vocabulary 97; 5 blocks (1 dense, 4 expert) and the module's; 2 x 40 tokens
    f = step.forward_flops_per_token(c)
    assert f["latent_projections"] == 6 * 2 * (48 * 20 + 20 * 3 * 16 + 48 * 18 + 12 * 3 * 26 + 3 * 16 * 48)
    assert f["attention"] == 6 * 2 * 3 * (16 + 16) * (40 * 41 // 2) // 40
    assert f["dense"] == 3 * 2 * 48 * 80 and f["router"] == 5 * 2 * 48 * 16 and f["shared"] == 5 * 3 * 2 * 48 * 16
    assert f["experts"] == int(5 * (3 * 4 / 16) * 3 * 2 * 48 * 16) and f["merge"] == 2 * 96 * 48 and f["head"] == 2 * 2 * 48 * 97
    assert step.work(c, 1) == {"flops": 3 * 80 * sum(f.values()), "bytes": 0}
    a = step.attention_work(c, 1)
    row, lse = 2 * 40 * 3 * 16 * 2, 2 * 40 * 3 * 4
    assert a["flops"] == 3 * 6 * (2 * 3 * (40 * 41 // 2) * 2 * 32)
    assert a["bytes"] == 6 * ((4 * row + lse) + (4 * row + lse) + 4 * row)


def test_counts_grow_with_depth(parts, config):
    step = parts.module("counts", "glm_step")
    one = step.forward_flops_per_token(config)
    deep = step.forward_flops_per_token({**config, "num_hidden_layers": 47})
    assert deep["attention"] * 6 == one["attention"] * 48 and deep["dense"] == one["dense"] and deep["head"] == one["head"]
    assert deep["experts"] * 5 == one["experts"] * 47 and deep["merge"] == one["merge"]


# -- the readers ------------------------------------------------------------------------------


def _reading(events, rows, config, parts=None, span=1000.0, program=None, calls=()):
    """One device whose step program (``program`` ns long; the whole window
    where None) holds ``events`` as its leaf operations; one call spans the
    window of ``span`` ns; ``rows`` is the program's scope map."""
    program = trace_reduce.Event("jit_dp_train_step(123)", 0.0, span if program is None else program)
    device = trace_reduce.Device("/device:TPU:0", list(events), [program], [(0.0, span)])
    tr = trace_reduce.Reduced((0.0, span), [(0.0, span)], [], [device])
    reading = SimpleNamespace(trace=tr, notes={}, config=config, chips=1, peak={"flops_per_s": 197e12, "bytes_per_s": 819e9},
                              parts=parts, window=SimpleNamespace(calls=list(calls)))
    reading._lfm2_rows = rows
    return reading


def _row(modules="", scopes=(), path="x", which="forward", fused=()):
    return {"op": "fusion", "path": path, "modules": modules, "scopes": list(scopes), "pass": which, "fused": list(fused)}


@pytest.fixture
def counted(monkeypatch):
    """The counters the program keeps where this PR's model was traced."""
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    for name, value in (("mla.mixers", 6.0), ("mla.key_rows_built", 6.0 * 16384 * 20 * 256), ("lm.mtp.modules", 1.0)):
        monkeypatch.setitem(counters, name, value)
    return counters


def test_every_leaf_is_tagged_by_the_scope_map(parts, config, counted):
    ev = lambda name, t0, dur: trace_reduce.Event(f"%{name} = f32[16384,2048]{T1} fusion(f32[16384,2048]{T1} %x)", t0, t0 + dur)  # noqa: E731
    events = [ev("fusion.1", 0, 10), ev("fusion.2", 10, 20), ev("fusion.3", 30, 40), ev("fusion.4", 70, 5), ev("fusion.5", 80, 7),
              ev("copy.9", 90, 3), ev("fusion.6", 100, 50), ev("fusion.7", 160, 11), ev("fusion.8", 175, 13), ev("fusion.9", 190, 17),
              ev("fusion.10", 210, 19), ev("fusion.11", 230, 2), ev("fusion.12", 240, 23)]
    attn, module = "TransformerLM/block2/attn", "TransformerLM/block5/attn"
    rows = {
        "fusion.1": _row(attn + "/q_a", ("lm.body", "mla.down")),
        # XLA fuses the joins and rotary into the product beside them: the root is the projection's, the assemble is inside
        "fusion.2": _row(attn + "/kv_b", ("lm.body", "mla.up"), which="backward",
                         fused=[[attn, ["lm.body", "mla.assemble"]], [attn + "/kv_b", ["lm.body", "mla.up"]]]),
        "fusion.3": _row(attn, ("lm.body", "mla.assemble"), which="recomputed"),
        "fusion.4": _row("TransformerLM/block0/gate", ("lm.body",)),
        "fusion.5": _row("TransformerLM/block0/down", ("lm.body",), which="backward"),
        "copy.9": {"op": "copy", "path": "", "modules": "", "scopes": [], "pass": "", "fused": [], "via": "fusion.3"},
        "fusion.6": _row(attn, ("lm.body", "attn.full"), path="jit(f)/attn.full/flash_fwd/pallas_call"),
        "fusion.7": _row("TransformerLM/block3/moe", ("lm.body", "moe.combine"), which="recomputed"),
        "fusion.8": _row("TransformerLM/block3/moe/shared_up", ("lm.body", "moe.shared")),
        "fusion.9": _row(module + "/q_b", ("lm.body", "mtp.block", "mla.up")),  # the module's mixer: latent, its projection, and the module's
        "fusion.10": _row("TransformerLM/mtp0_eh_proj", ("lm.body", "mtp.merge")),
        "fusion.12": _row("TransformerLM/block4/moe", ("lm.body", "moe.experts"), path="ragged-dot-none"),
    }  # fusion.11: no row at all
    reading = _reading(events, rows, config, parts)
    ns = 1e-6  # one call: an event's nanoseconds as ms
    want = {"latent": 10 + 20 + 40 + 3 + 50 + 17, "latent_proj": 10 + 20 + 17, "latent_assemble": 20 + 40 + 3, "mtp": 17 + 19,
            "dense_ffn": 5 + 7, "route": 11, "shared": 13, "experts": 13 + 23}
    assert glm_trace.tagged(reading) == pytest.approx({k: v * ns for k, v in want.items()})
    assert reading.notes["glm_tags"] is glm_trace.tagged(reading)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    for name, tag in BY_TAG.items():
        assert read(name) == pytest.approx(want[tag] * ns), name
    # an attention of another kind, an expert block's gate, a block's own residual add carry no tag of these
    assert glm_trace.tags_of(_row("TransformerLM/block1/conv")) == set() and glm_trace.tags_of(None) == glm_trace.tags_of({}) == set()
    assert glm_trace.tags_of(_row("TransformerLM/block2", ("lm.body",))) == set()


def test_kernels_heads_and_optimizer_are_found_by_name_and_shape(parts, config, counted):
    flash = lambda i, t0, dur: trace_reduce.Event(  # noqa: E731
        f"%flash_fwd.{i} = (bf16[2,20,8192,256]{T1}) custom-call(bf16[2,20,8192,256]{T1} %fusion.{i})", t0, t0 + dur)
    head = "%while.7{} = (u32[]{{:T(128)}}, f32[2048,19360]{{1,0:T(8,128)}}, f32[1024,19360]{{1,0:T(8,128)}}) while("
    loops = [trace_reduce.Event(head.format(i), t0, t0 + 20e6) for i, t0 in ((1, 100e6), (2, 130e6))]
    adam = trace_reduce.Event("%fusion.8 = (f32[2048,768]{1,0}, f32[2048,768]{1,0}, f32[2048,768]{1,0}) fusion(", 200e6, 205e6)
    reading = _reading([flash(2, 0, 30e6), flash(3, 40e6, 30e6), *loops, adam], {}, config, parts, span=1e9, program=900e6)
    read = lambda name: parts.module("metrics", name).read(reading)  # noqa: E731
    assert read("glm_attention_ms") == pytest.approx(60.0) and read("glm_head_loss_ms") == pytest.approx(40.0)
    assert read("glm_optimizer_ms") == pytest.approx(5.0)
    step = parts.module("counts", "glm_step")
    assert read("glm_attention_roofline") == pytest.approx(100 * step.attention_work(config, 1)["flops"] / 197e12 * 1e3 / 60.0)
    assert reading.notes["glm_attention_roofline_bound"] == "compute" and reading.notes["mla_key_rows_built"] == 6.0 * 16384 * 20 * 256
    assert not glm_trace.head_loss_rx(config).search("%while.70 = (s32[]{:T(128)}, f32[16384,2048]{1,0:T(8,128)}, s32[65536]{0:T(1024)}")
    counted["moe.held_share"], counted["moe.steps"] = 3 * 1.1 / 8, 3.0
    want = 100 * step.work(config, 1, 1.1)["flops"] / 197e12 / 0.9
    assert read("glm_step_mfu") == pytest.approx(want) and 30 < want < 40
    assert reading.notes["glm_step_held_share"] == pytest.approx(1.1)
    reading.compiles = 0
    assert read("glm_compiles_in_window") == 0.0
    # the busiest held expert over an even share of its layer's assignments, the worst layer, mean over the calls
    counts = np.full((5, 64), 1024)
    counts[3, 2], counts[3, 40] = 1536, 512
    busy = SimpleNamespace(summary={"expert_counts": counts}, error=None)
    even = SimpleNamespace(summary={"expert_counts": np.full((5, 64), 1024)}, error=None)
    reading.window.calls = [busy, even, SimpleNamespace(summary=None, error="lost")]
    assert read("glm_held_load") == pytest.approx((1.5 + 1.0) / 2)


def test_a_program_without_the_names_or_counters_reads_nothing(parts, config):
    """What a parent commit gives: a map without the latent mixer's and the
    module's scopes, no counter of theirs: every reader of the trace returns
    None, none raises."""
    ev = trace_reduce.Event("%fusion.1 = f32[8] fusion(f32[8] %x)", 10.0, 20.0)
    rows = {"fusion.1": _row("TransformerLM/block0/attn/query", ("lm.body",))}
    olmoe = parts.config(parts.cell("olmoe-train-4k-1chip"))
    from heat_tpu import telemetry

    counters = telemetry.get_registry().counters
    held = {k: counters.pop(k) for k in list(counters) if k.startswith(("moe.", "mla.", "lm.mtp"))}
    try:
        for cfg in (config, olmoe):
            reading = _reading([ev], rows, cfg, parts)
            for name in NEW_METRICS:
                if name != "glm_compiles_in_window":
                    assert parts.module("metrics", name).read(reading) is None, name
    finally:
        counters.update(held)
    for rows in (None, {}):  # no map at all (no launch noted), an empty one
        reading = _reading([ev], rows, config, parts)
        assert glm_trace.tagged(reading) is None and all(parts.module("metrics", name).read(reading) is None for name in BY_TAG)
    untraced = SimpleNamespace(trace=None, notes={}, config=config, chips=1, peak={}, parts=parts, window=SimpleNamespace(calls=[]))
    assert glm_trace.tagged(untraced) is None and glm_trace.step_mfu(untraced) is None
    assert glm_trace.attention_roofline(untraced) is None and glm_trace.held_load(untraced) is None


# -- the kind end to end on the CPU ------------------------------------------------------


def _run(capsys, trace, seed, seconds=0.4):
    rc = run.main(
        ["--workload", "tiny-glm", "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        root=TINY,
    )
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    return rc, lines


def test_tiny_glm_is_the_cell_at_a_rehearsal_size(config):
    tiny = manifest.load(TINY)
    cell = tiny.cell("tiny-glm")
    c = tiny.config(cell)
    same = ("kind", "reference", "optimizer", "loss", "bias_rate", "init_std", "init_out_std", "zipf_s", "roofline_modules",
            "num_hidden_layers", "first_k_dense_replace", "num_nextn_predict_layers", "n_shared_experts", "rope_theta",
            "rms_norm_eps", "norm_topk_prob", "routed_scaling_factor", "topk_method", "sequences_per_step")
    assert all(c[k] == config[k] for k in same)
    for cfg in (c, config):  # a head's query and key as wide as its value; heads, ranks and parts that are no lane multiples in the tiny one
        assert cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] == cfg["v_head_dim"]
        assert 2 < cfg["held_window"] < cfg["n_routed_experts"] / cfg["num_experts_held"]
    assert (c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"]) == (3, 20, 12, 10, 6)
    assert set(c["limits"]) == set(config["limits"]) == LIMITS and set(c["check"]) == set(config["check"])
    names = [m["name"] for m in tiny.metrics("per_layer", cell)]
    assert names[2:] == NEW_METRICS
    kind = tiny.module("kinds", "glm_step")
    assert kind.__file__.startswith(os.path.join(REPO, "chipbench", "kinds"))
    assert set(kind.MODEL_KEYS) <= set(c) and set(kind.MODEL_KEYS) <= set(config)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_run_steps_checks_and_prints_the_contracts_line(capsys, trace):
    rc, lines = _run(capsys, trace, seed=4100000007 + trace)  # over 2^31: the driver's are large
    assert rc == 0
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    compared = {l["compared"]: l for l in lines if "compared" in l}
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-glm"))["limits"]
    assert set(compared) == set(stated)
    assert compared["assignments_gap"]["value"] == 0 and compared["bias_gap"]["value"] == 0
    assert compared["leak_gap"]["value"] == 0  # nothing before the moved token moved at all, and the probe is live
    assert 1e-4 < compared["logits_rms_gap"]["value"] < stated["logits_rms_gap"]
    assert 1e-4 < compared["mtp_logits_rms_gap"]["value"] < stated["mtp_logits_rms_gap"]
    assert 0 < compared["update_gap"]["value"] < stated["update_gap"]
    reported = {l["reported"]: l for l in lines if "reported" in l}
    assert reported["update_gap"]["worst"] == compared["update_gap"]["value"]
    assert reported["update_gap"]["unrouted_worst"] == compared["update_gap_unrouted"]["value"] <= compared["update_gap"]["value"]
    assert reported["leak_gap"]["cut"] == 4 and reported["leak_gap"]["control"] is False
    assert reported["bias_gap"]["steps"] == last["attempted"] and reported["bias_gap"]["largest_bias"] > 0
    # five expert layers, the module's the last; its loss is in every call's summary
    assert reported["held_share"]["steps"] == last["attempted"] and len(reported["held_share"]["largest_by_layer"]) == 5
    assert all(3.5 < v < 5.5 for v in reported["held_share"]["ce_mtp_first_last"])
    samples = next(l for l in lines if "samples" in l)
    assert samples["compiles_in_window"] == 0
    if trace:
        got = last["metrics"]
        assert got["glm_compiles_in_window"]["value"] == 0 and got["glm_held_load"]["value"] >= 1.0
        # no TPU kernel of these names and no TPU modules line in a CPU trace: the readers leave them out
        assert not {"glm_attention_ms", "glm_attention_roofline", "glm_step_mfu", "glm_head_loss_ms"} & set(got)
    else:
        assert set(last["metrics"]) == {"call_p50_ms", "items_per_s", "setup_s"}
        assert last["metrics"]["items_per_s"]["value"] > 0


def test_the_same_seed_gives_the_same_weights_and_batches():
    import jax

    tiny = manifest.load(TINY)
    ref = tiny.module("references", "glm_plain")
    kind = tiny.module("kinds", "glm_step")
    config = tiny.config(tiny.cell("tiny-glm"))
    c = {k: config[k] for k in kind.MODEL_KEYS}
    big = 4100000007
    make = lambda seed: ref.init_params(seed, c, config["init_std"], config["init_out_std"])  # noqa: E731
    a, b, other = make(big), make(big), make(big + 1)
    assert np.array_equal(a["layers"][2]["wg"], b["layers"][2]["wg"])
    assert not np.array_equal(a["layers"][2]["wg"], other["layers"][2]["wg"])
    assert a["layers"][2]["wg"].shape[0] == 4 and a["layers"][2]["wr"].shape[1] == 16  # 4 held, routed over 16
    assert abs(float(np.std(np.asarray(a["embed"]))) - 0.02) < 2e-3 and a["head"].shape == (48, 97)
    for name, leaf in (("wf_d", a["layers"][0]["wf_d"]), ("wo", a["layers"][3]["wo"]), ("w_eh", a["w_eh"]), ("ws_d", a["layers"][5]["ws_d"])):
        assert abs(float(np.std(np.asarray(leaf))) - config["init_out_std"]) < 3e-4, name
    assert all(np.all(np.asarray(a[g]) == 1) for g in ("g_f", "g_e", "g_h", "g_s"))
    assert all(np.all(np.asarray(a["layers"][5][g]) == 1) for g in ("g_a", "g_c", "g_qa", "g_kva"))
    assert a["bias"].shape == (5, 16) and not np.any(np.asarray(a["bias"]))
    # the dense block, four expert blocks and the module's
    assert ["wf_g" in lp for lp in a["layers"]] == [True] + [False] * 5 and all("wr" in lp and "ws_g" in lp for lp in a["layers"][1:])
    assert a["layers"][0]["wkv_a"].shape == (48, 12 + 6) and a["layers"][0]["wkv_b"].shape == (12, 3 * 26)
    tree = kind.to_system(a, c)
    assert set(tree["route_bias"]) == {f"block{i}" for i in range(1, 6)} and "mtp0_eh_proj" in tree["params"]
    back = kind.from_system(tree)
    assert all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(back), jax.tree.leaves(a)))
    cdf = ref.zipf_cdf(config["vocab_size"], config["zipf_s"])
    assert np.array_equal(ref.batch(big, 3, 2, 40, cdf), ref.batch(big, 3, 2, 40, cdf))


def test_the_controls_fail_the_limits_the_program_meets(capsys):
    """``limits.py`` on the tiny cell: the program's numbers against the
    controls' (a bfloat16 accumulator, norms and router; AdamW with bfloat16
    moments; the embedding two ahead; biases left where they were) and the
    controls of the model's own mechanisms, each put through the run's
    comparison. At this size a query spreads evenly over its keys, so rotary
    over all of a head and a rotary key of its own for each head move no
    evaluated number past its limit (they are read here, and refused at the
    cell's own size: the configuration's ``limits_set_from``); the mixer alone
    is held to its written-out form, and to those two, in ``tests/test_glm.py``."""
    assert limits.main(["--workload", "tiny-glm", "--seeds", "4100000021"], root=TINY) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    row = lines[-1]
    program, control = row["program"], row["control"]
    tiny = manifest.load(TINY)
    stated = tiny.config(tiny.cell("tiny-glm"))["limits"]
    assert all(program[name] <= stated[name] for name in program)
    failed = {name for name in control if control[name] > stated[name]}
    assert {"update_gap", "update_gap_unrouted", "leak_gap", "bias_gap", "replay_loss_gap"} <= failed
    assert control["assignments_gap"] == 0 and control["bias_gap"] >= 1
    assert 0.001 < control["leak_gap"] != 1.0
    # each evaluated control went through the run's own comparison and was refused; the row holds the smallest of each number
    rows = {l["control"]: l for l in lines if "control" in l and "refused" in l}
    replayed = {"replay.bf16", "replay.no_mtp_loss", "replay.bias_left_alone"}
    blind = {"rope_all", "own_rope_key"}
    assert set(rows) == {"bf16", "scale_one", "no_mtp_loss", "mtp_own_token", "no_kv_norm"} | blind | replayed
    assert all(r["refused"] and r["refused_by"] for n, r in rows.items() if n not in blind | replayed), {n: r["refused_by"] for n, r in rows.items()}
    assert all(0 < rows[n]["logits_rms_gap"] for n in blind)
    assert {"logits_rms_gap", "mtp_logits_rms_gap"} <= set(rows["no_kv_norm"]["refused_by"])
    # the replay: the module's loss left out shows at the first step; biases left alone move choices from the second on
    assert rows["replay.no_mtp_loss"]["refused_by"] == ["replay_loss_gap"] and rows["replay.no_mtp_loss"]["replay_counts_differ_share"] == 0
    assert rows["replay.bias_left_alone"]["replay_counts_differ_share"] > 0 and not rows["replay.bf16"]["refused"]
    assert control["replay_loss_gap"] == rows["replay.no_mtp_loss"]["replay_loss_gap"]
    assert control["replay_counts_differ_share"] == rows["replay.bias_left_alone"]["replay_counts_differ_share"]
    assert "logits_rms_gap" in rows["bf16"]["refused_by"]
    assert "grad_norm_gap" in rows["scale_one"]["refused_by"]
    # the module's loss left out: both logits are the sound ones, the loss and the gradients are not
    assert rows["no_mtp_loss"]["logits_rms_gap"] == rows["no_mtp_loss"]["mtp_logits_rms_gap"] == 0
    assert {"loss_gap", "grad_norm_gap"} <= set(rows["no_mtp_loss"]["refused_by"])
    # the module fed the token itself: the trunk is sound, the module's logits and its loss are not
    assert rows["mtp_own_token"]["logits_rms_gap"] == 0 and {"mtp_logits_rms_gap", "mtp_loss_gap"} <= set(rows["mtp_own_token"]["refused_by"])
    assert control["grad_norm_gap"] == min(r["grad_norm_gap"] for n, r in rows.items() if n not in replayed)


class _Only:
    """A module as one other module sees it, with some of its names replaced:
    the fault stays in the file under test, and the reference, which reads the
    same ``jax.numpy``, is traced sound."""

    def __init__(self, module, **replaced):
        self._module, self._replaced = module, replaced

    def __getattr__(self, name):
        return self._replaced[name] if name in self._replaced else getattr(self._module, name)


@pytest.fixture
def fresh_programs():
    """The check's own programs (the evaluation, the norms) are kept a
    process by their configuration: one that an earlier test of this worker
    traced sound would hide a fault from the numbers that read it, and one
    traced with a fault would show it to a later test."""
    from heat_tpu.core import program_cache

    program_cache.reset()
    yield
    program_cache.reset()


@pytest.mark.parametrize("fault", ["latent_norm_left_out", "rolled_by_two", "module_loss_dropped", "bias_left_alone", "lr"])
def test_a_fault_in_the_timed_path_is_not_correct(capsys, monkeypatch, fresh_programs, fault):
    """The timed path is built without the latent norm of keys and values,
    with the module's embeddings rolled one too far, with a loss that
    leaves the module's term out, with a rule that moves no bias, or with an
    optimizer that does nothing: some number passes its limit each time and
    the run is not ``correct``."""
    import jax.numpy as jnp

    import heat_tpu.nn.transformer as transformer

    kind = manifest.load(TINY).module("kinds", "glm_step")
    if fault == "latent_norm_left_out":
        sound = transformer._norm
        monkeypatch.setattr(
            transformer, "_norm", lambda kind, eps, dtype, name, **kw: (lambda x: x) if name == "kv_a_norm" else sound(kind, eps, dtype, name, **kw)
        )
        expected = {"logits_rms_gap", "mtp_logits_rms_gap"}
    elif fault == "rolled_by_two":
        sound = jnp.roll
        twice = lambda a, shift, axis=None: sound(a, 2 * shift if axis == -1 else shift, axis=axis)  # noqa: E731
        monkeypatch.setattr(transformer, "jnp", _Only(jnp, roll=twice))
        expected = {"leak_gap", "mtp_logits_rms_gap"}
    elif fault == "module_loss_dropped":
        sound = transformer.causal_lm_loss
        monkeypatch.setattr(transformer, "causal_lm_loss", lambda model, **kw: sound(model, **{**kw, "mtp_coef": 0.0}))
        import heat_tpu.nn as nn

        monkeypatch.setattr(nn, "causal_lm_loss", transformer.causal_lm_loss)
        expected = {"loss_gap", "grad_norm_gap"}
    elif fault == "bias_left_alone":
        import heat_tpu.nn as nn

        monkeypatch.setattr(nn, "balance_bias_rule", lambda rate: lambda state, aux: state)
        expected = {"bias_gap"}
    else:
        sound = kind.optimizer
        monkeypatch.setattr(kind, "optimizer", lambda o: sound({**o, "lr": 0.0}))
        expected = {"update_gap", "update_gap_unrouted"}
    rc, lines = _run(capsys, 0, seed=4100000033)
    assert rc == 0 and lines[-1]["correct"] is False
    failed = {l["compared"] for l in lines if "compared" in l and not l["ok"]}
    assert expected <= failed, failed
    if fault == "lr":
        assert failed == expected
