"""The three oldest published-width train steps (OLMoE, Qwen3-Next,
Trinity-Mini) and the looped one (Ouro, PR 48), compiled for a described TPU v5e once each: the one place
outside ``tests/chipbench/`` that compiles a cell's step. A new cell's compile
test outside the benchmark's directories is one more key of ``PARENT``, never
a fixture of its own: a step takes a minute or two of a suite that has none
to spare.

Read from them: the scope map (the spellings ``telemetry.hlo.split_op_name``
reads are the compiler's own here: custom VJPs, scans, ``nn.remat`` with a
policy, a ``jax.checkpoint`` inside a ``lax.map`` inside a rematerialised
block; every kernel falls in its piece of ``chipbench/scope_trace.PIECES``; few
of the instructions that can be a device event are left without a piece); the
programs are the ones recorded below (PR 35's scopes moved metadata alone;
PR 40 moved the two share steps on purpose and left OLMoE's as it was); the
rows round the held experts move whole at a first window of 2 even shares and
in loops by the live rows at a longer one (one expert layer at the LFM2 cell's
widths, compiled by itself); every flash forward kernel is in a rematerialised
step once (a block's checkpoint keeps the attention core's output and its
log-sum-exp, one float a row, so the backward pass runs the block again
without the kernel) and the step still fits the chip with the kept arrays;
OLMoE's head is one loop (the pass that forms a block's logits forms both
gradients from them: one ``while`` whose carry is the kernel's float32
gradient, three products a block with the vocabulary in them, no array of
every position by the vocabulary). A compile is not a run: nothing here is a
time or a result.

The steps are built as ``chipbench/kinds/{lm,qnext,trinity}_step.py`` build
them, from the cells' configurations. The benchmark's own compile tests
(``tests/chipbench/test_chipbench_*_tpu_compile.py``) compile each cell's step
and its check for themselves; nothing is imported from them (the last case
here holds that for every file outside ``tests/chipbench/``).
"""

import collections
import contextlib
import functools
import hashlib
import os
import pathlib
import re

import pytest

from chipbench import manifest, scope_trace
from heat_tpu.telemetry import hlo
from tests.test_olmoe import loops, products_over

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GIB = 2**30
OLMOE, QNEXT, TRINITY = "olmoe-train-4k-1chip", "qwen3next-train-8k-1chip", "trinity-train-16k-1chip"
OURO = "ouro-train-4k-1chip"  # PR 48: the one dense step, and the one whose blocks stand in a loop

# ``memory_analysis`` totals and a digest of the instruction list (described
# v5e:2x2, jax 0.9.0, libtpu 0.0.34): the text's computations with
# ``metadata={...}`` and the Mosaic kernels' serialized bodies (which carry
# source paths) taken out, the numbers XLA appends to names dropped (two
# compiles of the Qwen3-Next step number their instructions differently; the
# other two are byte for byte) and the lines sorted. A PR that changes a
# program on purpose records its own. OLMoE's pair is the parent of PR 35's
# (2129a44) to this day: PR 35's scopes moved metadata alone, and PR 40's
# blocks round the held experts pass by the layer that holds every expert (that
# it still compiles to this digest is the bypass). The two share steps are
# PR 40's: their first windows of 2 even shares are still moved whole, in the
# parent's passes, but a window's sum starts from the sum before it and the
# rows gone over are counted (13,213,087,744 and 14,986,435,072 bytes before).
# All three are PR 42's since: one fused flash backward kernel a layer where a dq
# and a dk/dv kernel stood (15,751,272,448, 13,212,765,696 and 14,987,403,264
# bytes before: the totals moved by under a megabyte). Qwen3-Next's is PR 45's: the mixers' pass before the rule is a
# kernel each way where XLA's fusions stood, and all four mixer kernels are functions of the module, called from their
# sites (13,212,765,696 bytes before: what the fusions held between them went). Ouro's is PR 48's own, the first of its cell.
# All four are PR 50's: a layer's queries and keys go from their projections to the flash kernels through ``qk_prep_fwd``
# / ``qk_prep_bwd`` where XLA's norm, rotary, cast and transpose fusions stood (15,752,046,592, 12,601,269,248,
# 14,987,274,240 and 15,805,405,184 bytes before: a gated query's float32 cotangent now stands as an array of its own
# before it is padded to the projection's width beside its gate's, 268 MB in Trinity-Mini; OLMoE's total fell).
PARENT = {
    OLMOE: (15_751_719_424, "e0b7efac829645b3e9fa0ecdfffd2c2c067eea5215dd95c4d51160d1e7db7e98"),
    QNEXT: (12_722_094_080, "13c9d24b2a758d74a4505e6d75aa6e372b4612f827f542a8874755cb8ca86a1b"),
    TRINITY: (15_268_546_560, "921afecbf5f1690e9fbde2022dc2100e205d4b88afcc3346363bbb0e45323837"),
    OURO: (15_958_382_080, "fae40da7b6b49175b46a7a28afc2f4913fea6b356af3ffcffd6a5fb1cab7a811"),
}

# Queries and keys from their projections to the flash kernels as one kernel each way (``nn/pallas_qk_prep.py``, PR 50),
# in the cells whose layers ``takes_kernel`` admits: (query and key passes of the step's forward pass: two a layer; bodies
# of the forward kernel in the lowered module; of the backward kernel). A body a shape (Trinity-Mini: queries and keys,
# rotated in the six sliding layers and not in the two full ones; Ouro's queries and keys are one shape, and its 32 block
# applications stand in a loop of 8), the forward's once more under the block's checkpoint (OLMoE's step has none).
QK_PREP = {TRINITY: (16, 8, 4), QNEXT: (2, 4, 2), OURO: (16, 2, 1), OLMOE: (2, 1, 1)}

# what a device trace of these steps shows as an event of its own (my chip runs, PR 35)
RUNS = {
    "fusion", "custom-call", "convolution", "dot", "copy", "sort", "scatter", "gather", "reduce", "reduce-window",
    "select-and-scatter", "dynamic-slice", "dynamic-update-slice", "concatenate", "pad", "transpose", "slice",
    "broadcast", "iota", "reshape", "convert", "while", "conditional",
}
# custom calls that are no kernel: the compiler's own markers, no event
NO_KERNEL = re.compile(r'custom_call_target="(ConcatBitcast|AssumeGatherIndicesInBound|GatherScatterIndicesBitpacked|X64Combine)"')

# what ``steps`` keeps of a cell: the compiled step, its text, ``hlo.scope_rows`` of it, the text as lowered, the configuration
Step = collections.namedtuple("Step", "cell program text rows lowered config")


@contextlib.contextmanager
def _answering_tpu():
    """The flash and delta kernels ask ``jax.default_backend()`` whether to run in the interpreter."""
    import jax

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        yield
    finally:
        jax.default_backend = backend


def _build(topo, cell):
    """The cell's configuration, its loss, its train step and the step's
    arguments as shapes placed on the described chip: nothing is compiled."""
    import jax
    import jax.numpy as jnp

    import heat_tpu.nn as nn
    from heat_tpu.core.communication import MeshCommunication

    parts = manifest.load(REPO)
    config = parts.config(parts.cell(cell))
    kind = parts.module("kinds", config["kind"])
    comm = MeshCommunication(devices=topo.devices[:1])
    if config["kind"] == "lm_step":
        model = nn.olmoe_1b_7b(num_layers=config["num_hidden_layers"], comm=comm)
    else:
        model = kind.build_model(config, comm)
    rule, collections = {}, ("params",)
    if config["kind"] == "ouro_step":
        loss_fn = nn.causal_lm_loss(model, exit_beta=config["loss"]["beta"])
    elif config["kind"] == "trinity_step":
        loss_fn = nn.causal_lm_loss(model)
        rule, collections = {"state_rule": nn.balance_bias_rule(config["bias_rate"])}, ("params", "route_bias")
    else:
        loss_fn = nn.causal_lm_loss(
            model, load_balance_coef=config["loss"]["load_balance"], router_z_coef=config["loss"]["router_z"]
        )
    opt = kind.optimizer(config["optimizer"])
    train_step = nn.DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        loss_fn, has_aux=True, **rule
    )
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    params = {k: shapes[k] for k in collections}
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=comm.replicated()), tree
    )
    tokens = jax.ShapeDtypeStruct(
        (config["sequences_per_step"], config["sequence_length"]), jnp.int32, sharding=comm.sharding(0, 2)
    )
    opt_state = placed(jax.eval_shape(opt.init, {"params": params["params"]}))
    return config, loss_fn, train_step, (placed(params), opt_state, tokens)


def _compile(topo, cell):
    config, _, train_step, arguments = _build(topo, cell)
    with _answering_tpu():
        lowered = train_step.lower(*arguments)
        program = lowered.compile()
    text = program.as_text()
    return Step(cell, program, text, hlo.scope_rows(text), lowered.as_text(), config)


@pytest.fixture(scope="module")
def steps(topo):
    """``steps(cell)``: the cell's step, compiled at its first use and kept to
    the module's end, so that a case of one cell and the cases of every cell
    (``step``) read one compile."""
    return functools.cache(functools.partial(_compile, topo))


@pytest.fixture(scope="module", params=sorted(PARENT))
def step(request, steps):
    return steps(request.param)[:5]


def _total(m):
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


def _instruction_lines(text):
    return {
        m.group(1): line for line in text.splitlines()
        if (m := re.match(r"\s+(?:ROOT\s+)?%?([^\s=]+) = ", line))
    }


def test_every_kernel_and_loop_falls_in_its_piece(step):
    cell, _, text, rows, _ = step
    lines = _instruction_lines(text)
    pieces = {name: scope_trace.piece_of(row) for name, row in rows.items()}
    # the Mosaic kernels, wherever they stand (XLA fuses the delta rule's into its loop's update of the stacked outputs)
    kernels, passes = {}, {}
    for name, line in lines.items():
        if " custom-call(" in line and "tpu_custom_call" in line and not name.startswith("ragged-dot"):
            split = hlo.split_op_name(re.search(r'op_name="([^"]*)"', line).group(1))
            kernels[name] = scope_trace.piece_of(split)
            passes.setdefault(re.sub(r"\.\d+$", "", name), []).append(split["pass"])
    flash = {n: p for n, p in kernels.items() if re.match(r"(flash|swa)_", n)}
    delta = {n: p for n, p in kernels.items() if n.startswith("delta_chunk_")}
    conv = {n: p for n, p in kernels.items() if n.startswith("gdn_conv_")}
    prep = {n: p for n, p in kernels.items() if n.startswith("qk_prep_")}
    assert flash and set(flash.values()) == {"attention_core"}
    assert all(n in rows and pieces[n] == "attention_core" for n in flash)  # events of their own
    assert len(flash) + len(delta) + len(conv) + len(prep) == len(kernels)  # no kernel the table has not heard of
    # the pass before the flash kernels (PR 50): events of their own in the stream, outside the attention core's scopes;
    # a layer's queries and keys in the step's pass, in its block's rematerialisation (OLMoE's blocks have none) and backward
    made = QK_PREP[cell][0]
    again = 0 if cell == OLMOE else made
    assert len(prep) == 2 * made + again and all(n in rows and pieces[n] == "stream" for n in prep)
    assert {k: sorted(v) for k, v in passes.items() if k.startswith("qk_prep")} == {
        "qk_prep_fwd": sorted(["forward"] * made + ["recomputed"] * again), "qk_prep_bwd": ["backward"] * made,
    }
    if cell == QNEXT:
        assert len(delta) == 12 and set(delta.values()) == {"delta_rule"}  # nine forward, three backward
        found = _computations(text)  # a kernel's ``name=`` is no module of the scope map (PR 50): the fusions' own bodies say
        calls = {n: re.search(r"calls=%([\w.\-]+)", lines[n]) for n, r in rows.items() if r["op"] == "fusion"}
        holding = [n for n, m in calls.items() if m and any("%delta_chunk_" in x for x in found.get(m.group(1), ()))]
        assert len(holding) == 12 and {pieces[n] for n in holding} == {"delta_rule"}
        # the pass before the rule (PR 45): as many, events of their own in the mixers' loops
        assert len(conv) == 12 and all(n in rows and pieces[n] == "mixer_glue" for n in conv)
        # a kernel lowered once a program is inlined at every call site under that site's own name: three mixers
        # in the step's pass, each again in its block's rematerialisation and in its sequence's checkpoint, once backward
        forward = sorted(["forward"] * 3 + ["recomputed"] * 6)
        assert {k: sorted(v) for k, v in passes.items() if not re.match(r"flash|qk_prep", k)} == {
            "delta_chunk_fwd": forward, "gdn_conv_fwd": forward,
            "delta_chunk_bwd": ["backward"] * 3, "gdn_conv_bwd": ["backward"] * 3,
        }
    else:
        assert not delta and not conv
    grouped = {n: p for n, p in pieces.items() if n.startswith("ragged-dot")}  # XLA:TPU's own kernel, metadata and product
    if cell == OURO:  # dense, and looped: eight forward and eight backward kernels stand for thirty-two each
        assert not grouped and len(flash) == 16
    else:
        assert len(grouped) >= 9 and set(grouped.values()) == {"experts"}
    # the head's loop and every instruction of its body
    head = [n for n, r in rows.items() if "lm.head_loss" in r["scopes"]]
    assert sum(rows[n]["op"] == "while" for n in head) == 1
    assert len(head) > 40 and {pieces[n] for n in head} == {"head_loss"}
    assert {p for n, p in pieces.items() if "train.optimizer" in rows[n]["scopes"]} == {"optimizer"}


def test_a_mixer_kernel_is_lowered_once_a_call_path(step):
    """The lowered module, before the compiler inlines it (PR 45): a mixer
    kernel called through its module-level ``jax.jit`` is a function of the
    module, called from every site, where a bare ``pallas_call`` left its
    Mosaic body at each (nine ``delta_chunk_fwd`` and three ``delta_chunk_bwd``
    in this step before). The two backward kernels stand once; a forward kernel
    once for the step's pass and once more for each of the two checkpoints a
    mixer's pass lies under (the block's, the sequence's): partial evaluation
    writes the jitted call's outer jaxpr anew, and the lowering knows a
    function by its jaxpr. The body inside is traced once
    (``tests/test_gdn_conv_kernel.py``)."""
    cell, _, _, _, lowered = step
    bodies = {}
    for name in re.findall(r'kernel_name = "([^"]*)"', lowered):
        bodies[name] = bodies.get(name, 0) + 1
    assert lowered.count("tpu_custom_call") == sum(bodies.values())
    mixers = {k: n for k, n in bodies.items() if not re.match(r"(flash|swa|qk_prep)_", k)}
    if cell == QNEXT:
        assert mixers == {"gdn_conv_fwd": 3, "delta_chunk_fwd": 3, "gdn_conv_bwd": 1, "delta_chunk_bwd": 1}
    else:
        assert not mixers
    # the pass before the flash kernels (PR 50) by the same rule: a body a shape, not a layer and pass (Trinity-Mini's
    # step calls the forward kernel at 32 sites and the backward at 16)
    prep = {k: n for k, n in bodies.items() if k.startswith("qk_prep_")}
    assert prep == dict(zip(("qk_prep_fwd", "qk_prep_bwd"), QK_PREP[cell][1:]))


def test_the_passes_and_scopes_are_the_models_own(step):
    cell, _, _, rows, _ = step
    passes = {r["pass"] for r in rows.values()} - {""}
    scopes = {s for r in rows.values() for s in r["scopes"]}
    common = {"lm.body", "lm.head_loss", "lm.targets", "lm.loss", "train.optimizer", "moe.route", "moe.experts",
              "moe.combine", "attn.full", "attn.lse", "attn.qk_prep"}
    if cell == OLMOE:  # no checkpoint: nothing is run again
        assert passes == {"forward", "backward"} and scopes == common
    elif cell == OURO:  # no expert layer; the loop and the gate (flax's own frames for ``_looped`` and the scanned function are no scope)
        assert passes == {"forward", "recomputed", "backward"}
        assert scopes == {s for s in common if not s.startswith("moe.")} | {"lm.loop", "lm.exit_gate"}
    elif cell == QNEXT:
        assert passes == {"forward", "recomputed", "backward"}
        assert scopes == common | {"attn.gate", "moe.shared", "gdn.project", "gdn.conv", "gdn.scan", "gdn.gate_norm"}
    else:
        assert passes == {"forward", "recomputed", "backward"}
        assert scopes == common | {"attn.gate", "attn.window", "moe.shared", "train.state_rule"}
    # a recomputed instruction lies in a block (or, hoisted out of a loop by XLA, keeps its scope), and a module path
    # never repeats its root
    for r in rows.values():
        if r["pass"] == "recomputed":
            assert re.search(r"(^|/)block\d+(/|$)", r["modules"]) or len(r["scopes"]) > 1, r
        assert r["modules"].count("TransformerLM") <= 1, r
        assert not set(r["modules"].split("/")) & hlo._FRAMES, r


def test_few_instructions_that_can_run_are_left_without_a_piece(step):
    _, _, text, rows, _ = step
    lines = _instruction_lines(text)
    runs = [n for n, r in rows.items() if r["op"] in RUNS and not NO_KERNEL.search(lines[n])]
    assert len(runs) > 150
    # with the row a copy or zero fill borrows from the neighbour the map names (``via``), as the reader places it
    unscoped = [n for n in runs if scope_trace.piece_of(scope_trace.lent(rows, rows[n])) == scope_trace.UNSCOPED]
    assert len(unscoped) <= 0.05 * len(runs), (len(unscoped), len(runs))
    # by their own metadata alone: what the compiler added without a name is a sixth of them at most
    bare = [n for n in runs if not rows[n]["path"]]
    assert len(bare) <= 0.2 * len(runs) and all("via" in rows[n] for n in bare if n not in unscoped)


def test_the_program_is_the_parents_but_for_metadata(step):
    cell, program, text, _, _ = step
    total = _total(program.memory_analysis())
    body = text[text.index("\n%"):]  # the computations, without the header's table of source files
    body = re.sub(r", metadata=\{[^{}]*\}", "", body)
    body = re.sub(r'"body":\s*"[^"]*"', "", body)
    lines = sorted(re.sub(r"\.\d+", "", body).splitlines())
    assert (total, hashlib.sha256("\n".join(lines).encode()).hexdigest()) == PARENT[cell]


def _computations(text):
    """Computation name -> its instruction lines."""
    found, lines = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            lines = found.setdefault(m.group(1), [])
        elif line.startswith("}"):
            lines = None
        elif lines is not None:
            lines.append(line)
    return found


def _op_name(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else ""


_LIVE_LOOP = re.compile(r"moe\.(route|combine)\)*/while$")


def _live_loops(found):
    return [l for lines in found.values() for l in lines if " while(" in l and _LIVE_LOOP.search(_op_name(l))]


def test_a_first_window_of_two_shares_is_moved_whole(step):
    """``nn/moe.py::_held_experts`` (PR 40): a first window of up to 3 even
    shares is moved in one pass each way (a row costs more in a block than in
    a whole window: blocks lose at 2 shares); the three steps compiled here
    hold no loop over live blocks, and the layer that holds every expert none
    either."""
    cell, _, text, _, _ = step
    found = _computations(text)
    assert not _live_loops(found)
    if cell in (QNEXT, TRINITY):  # a window's one scatter-add back into the tokens, in no loop over blocks
        whole = re.compile(r"moe\.combine\)*/scatter-add$")
        assert [x for lines in found.values() for x in lines if " scatter(" in x and whole.search(_op_name(x))]


@pytest.fixture(scope="module")
def long_window(topo):
    """One expert layer at the LFM2 cell's widths and first window (8 of 64
    experts, 5 even shares: 40,960 rows for an even 8,192), forward and
    backward, compiled by itself: seconds, where a step takes a minute."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from heat_tpu.nn.moe import DroplessMoE

    layer = DroplessMoE(
        64, 4, 1536, dtype=jnp.bfloat16, accum_dtype=jnp.float32, norm_topk=True, score="sigmoid", select_bias=True,
        experts_held=(0, 8), held_window=5.0,
    )
    here = SingleDeviceSharding(topo.devices[0])
    placed = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=here), tree)  # noqa: E731
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16, sharding=here)
    tree = jax.eval_shape(layer.init, jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048), jnp.bfloat16))

    def loss(params, x, bias):
        return jnp.sum(layer.apply({"params": params, "route_bias": bias}, x).astype(jnp.float32) ** 2)

    program = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(placed(tree["params"]), x, placed(tree["route_bias"])).compile()
    return program.as_text()


def test_the_rows_round_the_held_experts_move_in_loops_by_the_live_rows(long_window):
    """``nn/moe.py::_window`` (PR 40), a first window longer than 3 even shares:
    the gather of a window's rows and the sum back into their tokens, and their
    transposes, are loops over blocks of rows whose trip count is an operand
    (read from the routing), each block written into the loop's carry in place;
    no gather or scatter of a whole window's rows stands outside one."""
    found = _computations(long_window)
    loops = _live_loops(found)
    rows_by_hidden = re.compile(r"\[(\d+),2048\]")
    whole = lambda s: any(int(r) >= 8192 for r in rows_by_hidden.findall(s))  # noqa: E731
    # a window: the gather and the sum forward, both transposes; the first window and the further ones
    assert len(loops) >= 4 * 2, len(loops)
    for line in loops:
        condition = found[re.search(r"condition=%([\w.\-]+)", line).group(1)]
        body = found[re.search(r"body=%([\w.\-]+)", line).group(1)]
        defined = {re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = ", x).group(1): x for x in condition}
        root = next(x for x in condition if x.lstrip().startswith("ROOT"))
        compared = re.search(r" compare\(([^)]*)\)", root).group(1).split(", ")
        assert len(compared) == 2 and all(" get-tuple-element(" in defined[c.split(" ")[-1]] for c in compared), root
        assert not [x for x in body if " copy(" in x and whole(x.split(" copy(")[0])], line[:200]
        assert any(" dynamic-update-slice(" in x or "scatter-add" in _op_name(x) for x in body)
    moves = re.compile(r"moe\.(route|combine)\)*/(?!while/body/)(.*/)?(gather|scatter-add)$")
    outside = [
        x for lines in found.values() for x in lines
        if moves.search(_op_name(x)) and whole(x.split(", metadata=")[0])
    ]
    assert not outside, outside[:3]


# ---- what a block's checkpoint keeps of the attention core (PR 34) ---------------------------------------


def kernels(text: str) -> dict:
    """How many Mosaic calls of each flash kernel the compiled text holds."""
    found = re.findall(r"^\s*%((?:swa|flash)_\w+?)(?:\.\d+)? = .*custom-call\(", text, re.M)
    return {name: found.count(name) for name in set(found)}


def test_the_trinity_step_runs_each_forward_kernel_once(steps):
    """Six sliding layers and two full ones: a forward and a fused backward kernel each."""
    assert kernels(steps(TRINITY).text) == {"swa_fwd": 6, "swa_bwd_fused": 6, "flash_fwd": 2, "flash_bwd_fused": 2}


def test_the_trinity_step_keeps_a_column_of_log_sum_exp_and_fits(steps):
    trinity = steps(TRINITY)
    # the kernels write and read the lane-broadcast layout; what lives from the forward pass to the backward
    # is its first lane, sliced out once a block in the forward pass (2 MB beside the output's 134 MB)
    assert "f32[1,32,16384,128]" in trinity.text
    columns = re.findall(
        r'= f32\[32,16384\]\S* reduce\(.*op_name="[^"]*?jvp\(lm\.body\)/TransformerLM/(block\d)/attn/attn\.\w+/slice"',
        trinity.text,
    )
    assert sorted(columns) == [f"block{i}" for i in range(8)]
    # eight outputs and columns at most over the step that kept nothing (13.34 GiB, PR 32); with the
    # log-sum-exp kept as the kernel writes it (268 MB a block) it would be 3.2 GB over, and past the chip
    assert _total(trinity.program.memory_analysis()) < min(14_318_943_744 + 8 * 136_314_880, 15 * GIB)
    # that the check's evaluation fits too is held where it is compiled:
    # tests/chipbench/test_chipbench_trinity_tpu_compile.py::test_the_checks_evaluation_fits_once_the_moments_step_aside


def test_the_qwen3_next_step_runs_its_forward_kernel_once_and_fits(steps):
    """One attention block a period of four: one kernel of each kind."""
    qnext = steps(QNEXT)
    assert kernels(qnext.text) == {"flash_fwd": 1, "flash_bwd_fused": 1}
    assert _total(qnext.program.memory_analysis()) < 15 * GIB
    # that the check's gradients fit beside both AdamW moments (8 bytes a parameter) is held where they are compiled:
    # tests/chipbench/test_chipbench_qnext_tpu_compile.py::test_the_checks_gradients_fit_beside_the_optimizer_state


# ---- OLMoE's head: the loss and both gradients in one loop (PR 29) ----------------------------------------


@pytest.fixture(scope="module")
def olmoe_gradients(topo):
    """The text of the gradients program of the cell's check, as ``chipbench/kinds/lm_step.py`` takes them."""
    import jax

    _, loss_fn, _, (params, _, tokens) = _build(topo, OLMOE)

    def grads(params, tokens):
        return jax.grad(lambda p: loss_fn(p, tokens)[0])(params)

    with _answering_tpu():
        return jax.jit(grads).lower(params, tokens).compile().as_text()


def test_the_step_holds_one_loop_whose_carry_is_the_kernels_gradient(steps, olmoe_gradients):
    for text in (steps(OLMOE).text, olmoe_gradients):
        found = loops(text)
        assert len(found) == 1, found
        assert "f32[2048,50304]" in found[0]  # the (D, V) float32 sum over the blocks


def test_a_block_takes_three_products_with_the_vocabulary_in_them(steps):
    """Logits, the hidden states' gradient, the kernel's gradient: the
    logits are not formed a second time."""
    products = products_over(steps(OLMOE).text, 50304)
    assert len(products) == 3, products


def test_no_array_of_every_position_by_the_vocabulary_and_the_step_fits(steps):
    olmoe = steps(OLMOE)
    assert "[16384,50304]" not in olmoe.text and "[4,4096,50304]" not in olmoe.text and "[8,2048,50304]" not in olmoe.text
    total = _total(olmoe.program.memory_analysis())
    assert total < 15 * GIB
    assert abs(total - olmoe.config["memory_analysis"]["total_bytes"]) < 0.01 * total


# ---- the rule this module exists for ----------------------------------------------------------------------


def test_no_file_outside_the_benchmarks_tests_imports_from_its_compile_tests():
    """A module-scoped fixture imported from another test file is built again
    in the worker that runs the importer: until PR 46 two files here imported
    the ``compiled`` fixtures of the benchmark's compile tests, and each paid
    a cell's step and its check a second time (590 s of tier-1)."""
    imports = re.compile(r"^\s*(?:from|import)\s+tests\.chipbench(?:\.|\s+import\s+)test_chipbench_\w*_tpu_compile\b", re.M)
    tests = pathlib.Path(__file__).parent
    found = [
        str(path.relative_to(tests)) for path in sorted(tests.rglob("*.py"))
        if path.relative_to(tests).parts[0] != "chipbench" and imports.search(path.read_text())
    ]
    assert not found, found
