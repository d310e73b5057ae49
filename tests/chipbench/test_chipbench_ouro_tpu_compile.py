"""Compile the ``ouro_step`` cell's train step at the published widths for a
described TPU v5e and read, from the compiler's memory analysis and its text,
that it fits one chip and fills it, takes over the state it is given, **holds
each block's body once** (eight flash forward kernels in the forward loop and
eight fused backward kernels in the backward loop stand for thirty-two
applications each; two loops for the stack, one for the four exits' head), the
scopes this PR's metrics read, and no array of positions x positions and none
of positions x vocabulary; and that the evaluation the check takes fits the
chip. A compile is not a run: nothing here is a time or a result. Where no TPU
compiler can be described the tests skip.

The step is built as ``chipbench/kinds/ouro_step.py`` builds it. The flash
kernels ask ``jax.default_backend()`` whether to run in the interpreter, so
the test answers "tpu" for them while it lowers.
"""

import os
import re

import pytest

from chipbench import manifest, ouro_trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GIB = 2**30
PARAMETERS = 612_438_017


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compiled(topo):
    import jax
    import jax.numpy as jnp

    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.nn import DataParallel, causal_lm_loss, exit_distribution

    parts = manifest.load(REPO)
    config = parts.config(parts.cell("ouro-train-4k-1chip"))
    kind = parts.module("kinds", "ouro_step")
    comm = MeshCommunication(devices=topo.devices[:1])
    model = kind.build_model(config, comm)
    opt = kind.optimizer(config["optimizer"])
    loss_fn = causal_lm_loss(model, exit_beta=config["loss"]["beta"])
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(loss_fn, has_aux=True)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=comm.replicated()), tree
    )
    tokens = jax.ShapeDtypeStruct(
        (config["sequences_per_step"], config["sequence_length"]), jnp.int32, sharding=comm.sharding(0, 2)
    )
    last = config["check"]["last_positions"]

    def evaluation(params, tokens):  # the kind's ``evaluation``: what its check runs
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens)
        exits, gates = model.apply(params, tokens, head=False)
        logits = jnp.dot(
            exits[:, :, -last:].astype(model.dtype), params["params"]["lm_head"]["kernel"].astype(model.dtype),
            preferred_element_type=jnp.float32,
        )
        return loss, aux, g, logits, jnp.exp(exit_distribution(gates))

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        program = step.lower(placed(params), placed(jax.eval_shape(opt.init, params)), tokens).compile()
        evaluation_program = jax.jit(evaluation).lower(placed(params), tokens).compile()
    finally:
        jax.default_backend = backend
    return config, program, evaluation_program


def _total(m):
    return m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes


def test_the_published_width_step_fits_and_fills_one_chip(compiled):
    config, program, _ = compiled
    total = _total(program.memory_analysis())
    assert total < 15 * GIB  # room beside the program for the batch and what the loop reads back
    assert total >= 0.7 * 16e9  # a full-memory step: 7.35 GB of it is state that stays, 9.8 GB with the gradients
    # the figure the configuration's file carries is this compile's, to 2%
    assert abs(total - config["memory_analysis"]["total_bytes"]) < 0.02 * total


def test_parameters_and_optimizer_state_are_donated(compiled):
    """12 bytes a parameter come in (parameter, two moments) and the same
    buffers go out: the 7.35 GB of state is not held twice."""
    _, program, _ = compiled
    m = program.memory_analysis()
    assert m.argument_size_in_bytes >= 12 * PARAMETERS and m.alias_size_in_bytes >= 12 * PARAMETERS
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 1 << 20


def _loops(text):
    """The text's ``while`` instructions: name -> (line, its body computation's name)."""
    found = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(while(?:\.\d+)?) = .* while\(.*body=%?([\w.\-]+)", line)
        if m:
            found[m.group(1)] = (line, m.group(2))
    return found


def _computation(text, name):
    """The lines of computation ``name`` and of every computation it calls, but no further loop's body."""
    bodies, current = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            current = bodies.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            current.append(line)
    seen, out, todo = set(), [], [name]
    while todo:
        n = todo.pop()
        if n in seen or n not in bodies:
            continue
        seen.add(n)
        for line in bodies[n]:
            out.append(line)
            if " while(" not in line:
                todo += re.findall(r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+)", line)
    return out


def test_the_loop_holds_each_blocks_body_once(compiled):
    config, program, _ = compiled
    text = program.as_text()
    calls = [l.strip() for l in text.splitlines() if "custom-call(" in l and ouro_trace.FULL_ATTENTION.search(l.strip())]
    kernels = sorted(l.split(" ")[0].split(".")[0] for l in calls)
    # eight blocks x (forward once: the block's checkpoint keeps its output; one fused backward), not thirty-two
    assert kernels == ["%flash_bwd_fused"] * 8 + ["%flash_fwd"] * 8
    # 16 heads of 128 whole lanes, keys and values as many as queries: nothing padded, none read by index
    call = next(l for l in calls if l.startswith("%flash_fwd"))
    assert call.count("bf16[1,16,4096,128]") >= 4
    # three loops: the stack forward, the stack backward (with the recomputed forward), the four exits' head
    loops = _loops(text)
    assert len(loops) == 3
    in_body = {name: _computation(text, body) for name, (_, body) in loops.items()}
    fwd = [n for n, lines in in_body.items() if sum("%flash_fwd" in l and "custom-call(" in l for l in lines) == 8]
    bwd = [n for n, lines in in_body.items() if sum("%flash_bwd_fused" in l and "custom-call(" in l for l in lines) == 8]
    assert len(fwd) == 1 and len(bwd) == 1 and fwd != bwd
    assert not any("%flash_fwd" in l and "custom-call(" in l for l in in_body[bwd[0]])  # kept by name: not run again
    head = [n for n in loops if n not in fwd + bwd]
    carry = loops[head[0]][0]
    assert "f32[2048,49152]" in carry  # the head's summed gradient, one carry for the four exits
    # each loop makes total_ut_steps turns: the exits and what the checkpoints keep are stacked four deep
    assert "f32[4,1,4096,2048]" in loops[fwd[0]][0] and "bf16[4,1,16,4096,128]" in loops[fwd[0]][0]
    for scope in ("lm.loop", "lm.exit_gate", "lm.head_loss", "lm.body", "attn.full", "train.optimizer"):
        assert scope in text, scope
    # no array of positions x positions and none of positions x vocabulary (one exit's, or the four together)
    assert "[4096,4096]" not in text and "[4096,49152]" not in text and "[16384,49152]" not in text
    assert "[2048,49152]" in text and "exit_gate_kernel" in text  # a block of positions' logits; the gate


def test_the_checks_evaluation_fits_the_chip_where_the_moments_step_aside(compiled):
    """``correct`` takes the program's gradients at the parameters the window
    ended with: gradients out (4 bytes a parameter). It fits the chip alone;
    beside both AdamW moments (8 bytes a parameter) it would leave little over
    2 GiB of the chip's 15.75 for what else the check holds, which is why the
    kind's state lets the moments step aside (``trinity_step.State.grads``)."""
    _, _, evaluation_program = compiled
    m = evaluation_program.memory_analysis()
    assert m.output_size_in_bytes >= 4 * PARAMETERS
    assert _total(m) < 10 * GIB and 15.75 * GIB - (_total(m) + 8 * PARAMETERS) < 3 * GIB
