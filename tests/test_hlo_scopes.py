"""The scope map of a compiled program (``heat_tpu.telemetry.hlo.scope_rows``,
``program_scopes``): every instruction of the entry computation and of the loop
bodies has a row, the pass is read from the wrappers JAX writes into an
``op_name``, a fusion lists what it fused, and the train step keeps nothing for
it unless a span records."""

import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from heat_tpu import telemetry
from heat_tpu.core.communication import MeshCommunication
from heat_tpu.nn import DataParallel
from heat_tpu.telemetry import CompileWatcher, hlo


class Inner(nn.Module):
    @nn.compact
    def __call__(self, x):
        w = self.param("w", nn.initializers.ones, (x.shape[-1],))
        with jax.named_scope("toy.norm"):
            x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * w

        def body(c, row):
            with jax.named_scope("toy.scan"):
                c = jnp.tanh(c * 0.5 + row)
            return c, c

        _, ys = jax.lax.scan(body, jnp.zeros(x.shape[1:]), x)
        return nn.Dense(x.shape[-1], name="proj")(ys)


class Toy(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.remat(Inner)(name="block0")(x)
        return Inner(name="block1")(x)


def toy_loss(model):
    def loss(p, x):
        with jax.named_scope("lm.body"):
            y = model.apply(p, x)
        return jnp.sum(y * y)

    return loss


@pytest.fixture(scope="module")
def toy():
    model = Toy()
    x = jnp.ones((6, 8))
    params = model.init(jax.random.PRNGKey(0), x)
    text = jax.jit(jax.value_and_grad(toy_loss(model))).lower(params, x).compile().as_text()
    return text, hlo.scope_rows(text)


def _instructions_that_run(text):
    """Names of the instructions of the entry computation and of every
    computation a ``while`` or ``call`` names, read off the text by itself."""
    reached = set(re.findall(r"\b(?:body|condition)=%?([^\s,)}]+)", text))
    reached |= set(re.findall(r" call\(.*?to_apply=%?([^\s,)}]+)", text))
    names, keep = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():
            head = line.split(" ")
            name = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            keep = line.startswith("ENTRY") or name in reached
        elif keep and " = " in line:
            names.append(line.split(" = ")[0].replace("ROOT", "").strip().lstrip("%"))
    return names


def test_every_instruction_of_the_entry_and_the_loop_bodies_has_a_row(toy):
    text, rows = toy
    names = _instructions_that_run(text)
    assert len(names) > 50 and set(names) == set(rows)
    loops = [r for r in rows.values() if r["op"] == "while"]
    assert len(loops) >= 5  # two blocks forward, one again, two backward
    in_a_body = [r for r in rows.values() if "/while/body/" in r["path"]]
    assert {r["pass"] for r in in_a_body} == {"forward", "recomputed", "backward"}


def test_the_pass_is_read_from_the_wrappers(toy):
    _, rows = toy
    scan = [r for r in rows.values() if "toy.scan" in r["scopes"]]
    assert {r["pass"] for r in scan} == {"forward", "recomputed", "backward"}
    for r in rows.values():
        if not r["path"]:
            continue
        if "rematted_computation" in r["path"]:
            assert r["pass"] == "recomputed", r
        elif "transpose(" in r["path"]:
            assert r["pass"] == "backward", r
        else:
            assert r["pass"] == "forward", r
    # only the checkpointed block is run again
    assert {r["modules"] for r in rows.values() if r["pass"] == "recomputed"} == {"Toy/block0"}
    # the transposed equation's own stack follows that of the place where the transpose ran: one path, not two
    backward = [r for r in rows.values() if r["pass"] == "backward" and r["modules"].endswith("block0/proj")]
    assert backward and all(r["modules"] == "Toy/block0/proj" and r["scopes"] == ("lm.body",) for r in backward)


def test_modules_scopes_and_frames_are_told_apart(toy):
    _, rows = toy
    norm = [r for r in rows.values() if "toy.norm" in r["scopes"]]
    assert norm and all(r["modules"] in ("Toy/block0", "Toy/block1") and r["scopes"][0] == "lm.body" for r in norm)
    assert any(r["modules"] == "Toy/block1/proj" and r["op"] == "dot" for r in rows.values())
    for r in rows.values():  # JAX's own frames are neither a module nor a scope
        assert not set(r["modules"].split("/")) & {"while", "body", "cond", "closed_call", "checkpoint"}, r


def test_an_instruction_without_metadata_has_empty_fields(toy):
    _, rows = toy
    bare = [r for r in rows.values() if not r["path"]]
    assert bare  # the copies and parameters the compiler adds
    assert all((r["modules"], r["scopes"], r["pass"], r["fused"]) == ("", (), "", ()) for r in bare)
    assert hlo.split_op_name("") == {"path": "", "modules": "", "scopes": (), "pass": ""}


HANDMADE = """HloModule jit_f, is_scheduled=true

%fused_computation (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  %mul.1 = f32[8]{0} multiply(%p0, %p1), metadata={op_name="jit(f)/jvp(lm.body)/Net/block0/ln1/mul" stack_frame_id=3}
  %bitcast.1 = f32[8]{0} bitcast(%mul.1)
  ROOT %add.1 = f32[8]{0} add(%bitcast.1, %p1), metadata={op_name="jit(f)/jvp(lm.body)/Net/block0/moe/moe.route/add" stack_frame_id=4}
}

%fused_computation.1 (p0.1: f32[8]) -> f32[8] {
  %p0.1 = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%p0.1), metadata={op_name="jit(f)/train.optimizer/neg"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8]{0} get-tuple-element(%arg), index=1
  %fusion.7 = f32[8]{0} fusion(%x, %x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/jvp(lm.body)/Net/block0/moe/moe.route/add" stack_frame_id=4}
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%i, %fusion.7)
}

%cond (arg.1: (s32[], f32[8])) -> pred[] {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  %limit = s32[] constant(4)
  ROOT %lt = pred[] compare(%i.1, %limit), direction=LT, metadata={op_name="jit(f)/jvp(lm.head_loss)/while/cond/lt"}
}

%branch_a (q: f32[8]) -> f32[8] {
  %q = f32[8]{0} parameter(0)
  ROOT %copy.9 = f32[8]{0:T(8)S(1)} copy(%q)
}

%branch_b (q.1: f32[8]) -> f32[8] {
  %q.1 = f32[8]{0} parameter(0)
  ROOT %fusion.8 = f32[8]{0} fusion(%q.1), kind=kLoop, calls=%fused_computation.1
}

%reducer (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

ENTRY %main.1 (Arg_0.1: f32[8], Arg_1.1: pred[]) -> f32[] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %Arg_1.1 = pred[] parameter(1)
  %zero = s32[] constant(0)
  %c0 = f32[] constant(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%zero, %Arg_0.1)
  %while.3 = (s32[], f32[8]{0:T(8,128)(2,1)}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(f)/jvp(lm.head_loss)/while"}
  %y = f32[8]{0} get-tuple-element(%while.3), index=1
  %fill = f32[8]{0} broadcast(%c0), dimensions={}
  %copy.4 = f32[8]{0:T(8)} copy(%fill)
  %conditional.2 = f32[8]{0} conditional(%Arg_1.1, %y, %copy.4), true_computation=%branch_a, false_computation=%branch_b, metadata={op_name="jit(f)/transpose(jvp(lm.body))/Net/jvp(lm.body)/Net/checkpoint/block0/moe/cond"}
  ROOT %reduce.5 = f32[] reduce(%conditional.2, %c0), dimensions={0}, to_apply=%reducer, metadata={op_name="jit(f)/jvp()/reduce_sum"}
}
"""


def test_a_fusion_over_two_scopes_lists_both_and_branches_are_reached():
    rows = hlo.scope_rows(HANDMADE)
    # the entry, the loop's body and condition, both branches; not the fused computations, not the reducer
    assert set(rows) == {
        "Arg_0.1", "Arg_1.1", "zero", "tuple.1", "while.3", "y", "fill", "copy.4", "conditional.2", "c0", "reduce.5",
        "arg", "i", "x", "fusion.7", "tuple.2", "arg.1", "i.1", "limit", "lt", "q", "copy.9", "q.1", "fusion.8",
    }
    mixed = rows["fusion.7"]
    assert (mixed["op"], mixed["modules"], mixed["scopes"]) == ("fusion", "Net/block0/moe", ("lm.body", "moe.route"))
    assert set(mixed["fused"]) == {("Net/block0/ln1", ("lm.body",)), ("Net/block0/moe", ("lm.body", "moe.route"))}
    # a fusion without metadata of its own takes its root's
    assert rows["fusion.8"]["scopes"] == ("train.optimizer",) and rows["fusion.8"]["pass"] == "forward"
    assert rows["copy.9"]["path"] == "" and rows["copy.9"]["op"] == "copy"
    assert rows["while.3"]["op"] == "while" and rows["while.3"]["scopes"] == ("lm.head_loss",)
    assert rows["conditional.2"]["pass"] == "backward" and rows["conditional.2"]["modules"] == "Net/block0/moe"
    assert rows["reduce.5"]["modules"] == "" and rows["reduce.5"]["scopes"] == ()
    # what the compiler added has empty fields and names the nearest instruction that has some: what uses it first
    assert rows["copy.4"]["path"] == "" and rows["copy.4"]["via"] == "conditional.2"
    assert rows["fill"]["via"] == "conditional.2"  # through the copy
    assert rows["y"]["via"] == "conditional.2" and rows["tuple.1"]["via"] == "while.3"
    assert "via" not in rows["copy.9"] and "via" not in rows["while.3"]  # no named neighbour; named itself


@pytest.mark.parametrize("path, modules, scopes, which", [
    # the spellings of the three published-width steps compiled for a v5e (tests/test_train_steps_tpu_compile.py)
    ("jit(dp_train_step)/jvp(lm.body)/TransformerLM/block3/attn/attn.window/slice",
     "TransformerLM/block3/attn", ("lm.body", "attn.window"), "forward"),
    ("jit(dp_train_step)/transpose(jvp(lm.body))/TransformerLM/block0/ln1/mul",
     "TransformerLM/block0/ln1", ("lm.body",), "backward"),
    ("jit(dp_train_step)/transpose(jvp(lm.body))/TransformerLM/jvp(lm.body)/TransformerLM/checkpoint/block3/moe/moe.route/gather",
     "TransformerLM/block3/moe", ("lm.body", "moe.route"), "backward"),
    ("jit(dp_train_step)/transpose(jvp(lm.body))/TransformerLM/jvp(lm.body)/TransformerLM/checkpoint/rematted_computation/block3/ln1/mul",
     "TransformerLM/block3/ln1", ("lm.body",), "recomputed"),
    ("jit(dp_train_step)/transpose(jvp(lm.body))/TransformerLM/jvp(lm.body)/TransformerLM/checkpoint/block1/gdn/while/body/"
     "closed_call/checkpoint/rematted_computation/gdn.conv/jit(silu)/logistic",
     "TransformerLM/block1/gdn", ("lm.body", "gdn.conv"), "recomputed"),
    ("jit(dp_train_step)/transpose(jvp(lm.body))/TransformerLM/jvp(lm.body)/TransformerLM/checkpoint/block2/moe/cond/"
     "branch_1_fun/while/body/transpose(jvp(moe.combine))/mul",
     "TransformerLM/block2/moe", ("lm.body", "moe.combine"), "backward"),
    ("jit(dp_train_step)/jvp(lm.head_loss)/while/body/closed_call/jit(take_along_axis)/gather", "", ("lm.head_loss",), "forward"),
    ("jit(dp_train_step)/train.optimizer/jit(_where)/select_n", "", ("train.optimizer",), "forward"),
    ("jit(dp_train_step)/jvp(lm.body)/TransformerLM/block0/moe/moe.combine/nk,nkd->nd/dot_general",
     "TransformerLM/block0/moe", ("lm.body", "moe.combine"), "forward"),
    ("jit(dp_train_step)/jvp()/add", "", (), "forward"),
    ("ragged-dot-none", "", (), "forward"),  # XLA:TPU's grouped matmul writes its own name, without the stack
    # two instructions folded into one: XLA joins their names with ";", the second cut to where they part
    ("jit(dp_train_step)/jvp(lm.body)/TransformerLM/block2/gdn/while/body/closed_call/gdn.scan/transpose;gdn.scan/reshape",
     "TransformerLM/block2/gdn", ("lm.body", "gdn.scan"), "forward"),
])
def test_split_op_name_on_the_spellings_of_the_compiled_steps(path, modules, scopes, which):
    row = hlo.split_op_name(path)
    assert (row["modules"], row["scopes"], row["pass"], row["path"]) == (modules, scopes, which, path)


# -- the train step hands its map over --------------------------------------------------


class Net(nn.Module):
    @nn.compact
    def __call__(self, x):
        return nn.Dense(1, name="out")(nn.tanh(nn.Dense(8, name="hidden")(x)))[:, 0]


@pytest.fixture
def step():
    hlo.clear()
    telemetry.get_registry().clear()
    comm = MeshCommunication(devices=jax.devices()[:1])
    model = Net()

    def loss_fn(params, x, y):
        with jax.named_scope("lm.body"):
            return jnp.mean((model.apply(params, x) - y) ** 2)

    opt = optax.adam(1e-2)
    train_step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(loss_fn)
    params = jax.device_put(model.init(jax.random.PRNGKey(0), jnp.ones((4, 3))), comm.replicated())
    batch = lambda n: (jnp.ones((n, 3)), jnp.zeros((n,)))  # noqa: E731
    yield train_step, params, jax.jit(opt.init)(params), batch
    hlo.clear()


def test_no_map_before_a_step_ran_and_nothing_is_kept_while_nothing_records(step):
    train_step, params, opt_state, batch = step
    assert hlo.program_scopes("dp_train_step") is None
    assert not telemetry.enabled() and not jax.profiler.TraceAnnotation.is_enabled()
    params, opt_state, _ = train_step(params, opt_state, *batch(4))
    params, opt_state, _ = train_step(params, opt_state, *batch(4))
    assert hlo.program_scopes("dp_train_step") is None
    assert hlo._LAUNCHED == {}
    assert not [k for k in telemetry.get_registry().counters if k.startswith("hlo.")]
    # and no function of the map's module is so much as entered by a call
    entered = []

    def tracer(frame, event, arg):
        if event == "call" and frame.f_code.co_filename.endswith(os.path.join("telemetry", "hlo.py")):
            entered.append(frame.f_code.co_name)

    sys.settrace(tracer)
    try:
        train_step(params, opt_state, *batch(4))
    finally:
        sys.settrace(None)
    assert entered == []


def test_under_a_span_the_step_gives_the_map_of_what_ran_without_a_compile(step):
    train_step, params, opt_state, batch = step
    for _ in range(2):  # compiled with nothing recording (the second call's state is the step's own: one variant more)
        params, opt_state, _ = train_step(params, opt_state, *batch(4))
    telemetry.enable()
    try:
        for _ in range(3):
            params, opt_state, _ = train_step(params, opt_state, *batch(4))
        assert telemetry.get_registry().counters["hlo.launches_noted.dp_train_step"] == 1  # once a program
        kept = hlo._LAUNCHED["dp_train_step"]
        assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in jax.tree.leaves(kept.signature))  # no array
        with CompileWatcher() as watcher:
            rows = hlo.program_scopes("dp_train_step")
        assert watcher.backend_compiles == 0  # the jitted function answers from what the call compiled
        assert hlo.program_scopes("dp_train_step") is rows
        scopes = {s for r in rows.values() for s in r["scopes"]}
        assert {"lm.body", "train.optimizer"} <= scopes
        assert {"forward", "backward"} <= {r["pass"] for r in rows.values()}
        assert any(r["modules"] == "Net/hidden" for r in rows.values())
        # a retrace (new shapes) drops the map; the next request makes it anew, of the program that ran last
        params, opt_state, _ = train_step(params, opt_state, *batch(8))
        assert telemetry.get_registry().counters["hlo.launches_noted.dp_train_step"] == 2
        again = hlo.program_scopes("dp_train_step")
        assert again is not rows and again
        assert [leaf.shape for leaf in jax.tree.leaves(hlo._LAUNCHED["dp_train_step"].signature)][-2:] == [(8, 3), (8,)]
    finally:
        telemetry.disable()


def test_a_failing_request_gives_none_and_a_warning():
    hlo.clear()

    class Broken:
        def lower(self, *args):
            raise RuntimeError("no such program")

    hlo.note_launch("broken_site", Broken(), (jnp.ones((2,)),))
    with pytest.warns(UserWarning, match="no scope map of 'broken_site'"):
        assert hlo.program_scopes("broken_site") is None
    hlo.clear()
