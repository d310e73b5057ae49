"""Compile the ``lfm2_step`` cell's train step at the published widths for a
described TPU v5e and read, from the compiler's memory analysis and its text,
that it fits one chip and fills it, takes over the state it is given, holds its
flash kernels at heads padded from 64 to the 128 lanes, its grouped products and
one loop for the tied head, and holds no array of positions x positions and none
of positions x vocabulary; and that the evaluation the check takes fits the
chip. A compile is not a run: nothing here is a time or a result. Where no TPU
compiler can be described the tests skip.

The step is built as ``chipbench/kinds/lfm2_step.py`` builds it. The flash
kernels ask ``jax.default_backend()`` whether to run in the interpreter, so
the test answers "tpu" for them while it lowers.
"""

import os
import re

import pytest

from chipbench import lfm2_trace, manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GIB = 2**30
PARAMETERS = 647_819_520


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
    from heat_tpu.nn import DataParallel, balance_bias_rule, causal_lm_loss

    parts = manifest.load(REPO)
    config = parts.config(parts.cell("lfm2-train-8k-1chip"))
    kind = parts.module("kinds", "lfm2_step")
    comm = MeshCommunication(devices=topo.devices[:1])
    model = kind.build_model(config, comm)
    opt = kind.optimizer(config["optimizer"])
    loss_fn = causal_lm_loss(model)
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        loss_fn, has_aux=True, state_rule=balance_bias_rule(config["bias_rate"])
    )
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    params = {"params": shapes["params"], "route_bias": shapes["route_bias"]}
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=comm.replicated()), tree
    )
    tokens = jax.ShapeDtypeStruct(
        (config["sequences_per_step"], config["sequence_length"]), jnp.int32, sharding=comm.sharding(0, 2)
    )

    def evaluation(params, tokens):  # the kind's ``evaluation``: what its check runs
        (loss, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens)
        hidden = model.apply(params, tokens, head=False, mutable=["aux"])[0]
        logits = jnp.dot(
            hidden[:, -256:].astype(model.dtype), params["params"]["embed"]["embedding"].astype(model.dtype).T,
            preferred_element_type=jnp.float32,
        )
        return loss, aux, g, logits

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        program = step.lower(
            placed(params), placed(jax.eval_shape(opt.init, {"params": params["params"]})), tokens
        ).compile()
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
    assert total >= 0.7 * 16e9  # a full-memory step: 7.77 GB of it is state that stays, 10.4 GB with the gradients
    # the figure the configuration's file carries is this compile's, to 2%
    assert abs(total - config["memory_analysis"]["total_bytes"]) < 0.02 * total


def test_parameters_biases_and_optimizer_state_are_donated(compiled):
    """12 bytes a parameter come in (parameter, two moments), with the 384
    biases, and the same buffers go out: the 7.77 GB of state is not held twice."""
    _, program, _ = compiled
    m = program.memory_analysis()
    state = 12 * PARAMETERS + 4 * 6 * 64
    assert m.argument_size_in_bytes >= state and m.alias_size_in_bytes >= state
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 1 << 20


def test_the_step_holds_its_kernels_and_one_loop_for_the_tied_head(compiled):
    config, program, _ = compiled
    text = program.as_text()
    calls = [l.strip() for l in text.splitlines() if "custom-call(" in l]
    full = [l.split(" ")[0].split(".")[0] for l in calls if lfm2_trace.FULL_ATTENTION.search(l)]
    # two attention blocks x (forward once: the block's checkpoint keeps its output; dq; dk/dv); five blocks have none
    assert sorted(full) == sorted(["%flash_fwd"] * 2 + ["%flash_bwd_dq"] * 2 + ["%flash_bwd_dkv"] * 2)
    assert "%swa_" not in text and "%ragged-dot-none" in text
    # the kernels see heads of 128 lanes: the model's 64 padded (what attn.lanes_padded counts), 8 key-value heads read by index
    call = next(l for l in calls if l.startswith("%flash_fwd"))
    assert "bf16[2,32,8192,128]" in call and "bf16[2,8,8192,128]" in call and "bf16[2,32,8192,64]" not in call
    # one loop carries the head's gradient, hidden x the vocabulary's slice: the table's, there is no lm_head
    loops = [l.strip() for l in text.splitlines() if re.match(r"\s*%while(\.\d+)? = ", l)]
    assert len([l for l in loops if lfm2_trace.head_loss_rx(config).search(l)]) == 1
    assert "lm.tied_head" in text and "lm_head" not in text and "conv.mix" in text and "conv.project" in text
    # no array of positions x positions and none of tokens x vocabulary; no tokens x top-k rows of hidden features
    assert "[8192,8192]" not in text and "[16384,8192]" not in text and "[65536,2048]" not in text
    # the first window of held rows is held_window x an even share of the 65,536 assignments (8,192 rows); a window that
    # holds them all leaves no further one and no branch: the layers' time cannot follow the routing
    rows = min(65536, int(config["held_window"] * 8192))
    assert f"[{rows},2048]" in text and f"[{rows},1536]" in text
    assert (" conditional(" in text) == (rows < 65536)


def test_the_checks_evaluation_fits_the_chip(compiled):
    """``correct`` takes the program's gradients at the parameters the timed
    step is about to consume: gradients out (4 bytes a parameter), under the
    chip's 15.75 GiB even with both AdamW moments (8 bytes a parameter) beside it."""
    _, _, evaluation_program = compiled
    m = evaluation_program.memory_analysis()
    assert m.output_size_in_bytes >= 4 * PARAMETERS
    assert _total(m) + 8 * PARAMETERS < 15 * GIB
