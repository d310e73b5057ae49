"""Compile the ``trinity_step`` cell's train step at the published widths for a
described TPU v5e and read, from the compiler's memory analysis and its text,
that it fits one chip, fills it, takes over the state it is given, holds its
window and full kernels by name, and holds no array of positions x positions
and none of positions x vocabulary; and that the evaluation the check takes
fits the chip once AdamW's moments have stepped aside. A compile is not a run:
nothing here is a time or a result.

The step is built as ``chipbench/kinds/trinity_step.py`` builds it. The flash
kernels ask ``jax.default_backend()`` whether to run in the interpreter, so
the test answers "tpu" for them while it lowers.
"""

import os
import re

import pytest

from chipbench import manifest, trinity_trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GIB = 2**30
PARAMETERS = 737_480_704


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
    config = parts.config(parts.cell("trinity-train-16k-1chip"))
    kind = parts.module("kinds", "trinity_step")
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
            hidden[:, -256:].astype(model.dtype), params["params"]["lm_head"]["kernel"].astype(model.dtype),
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
    assert total >= 0.7 * 16e9  # a full-memory step: 8.85 GB of it is state that stays, 11.8 GB with the gradients
    # the figure the configuration's file carries is this compile's, to 2%
    assert abs(total - config["memory_analysis"]["total_bytes"]) < 0.02 * total


def test_parameters_biases_and_optimizer_state_are_donated(compiled):
    """12 bytes a parameter come in (parameter, two moments), with the 768
    biases, and the same buffers go out: the 8.85 GB of state is not held twice."""
    _, program, _ = compiled
    m = program.memory_analysis()
    state = 12 * PARAMETERS + 4 * 6 * 128
    assert m.argument_size_in_bytes >= state and m.alias_size_in_bytes >= state
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 1 << 20


def test_the_step_holds_its_window_and_full_kernels_by_name(compiled):
    config, program, _ = compiled
    text = program.as_text()
    calls = [l.strip() for l in text.splitlines() if "custom-call(" in l]
    names = lambda rx: [l.split(" ")[0].split(".")[0] for l in calls if rx.search(l)]  # noqa: E731
    window, full = names(trinity_trace.WINDOW_ATTENTION), names(trinity_trace.FULL_ATTENTION)
    # six sliding layers x (forward, forward again under rematerialisation, dq, dk/dv); two full layers likewise
    assert sorted(window) == sorted(["%swa_fwd"] * 12 + ["%swa_bwd_dq"] * 6 + ["%swa_bwd_dkv"] * 6)
    assert sorted(full) == sorted(["%flash_fwd"] * 4 + ["%flash_bwd_dq"] * 2 + ["%flash_bwd_dkv"] * 2)
    assert "%ragged-dot-none" in text
    # the kernels read 4 key-value heads of 128 for 32 query heads: no repeated copy
    call = next(l for l in calls if l.startswith("%swa_fwd"))
    assert "bf16[1,32,16384,128]" in call and "bf16[1,4,16384,128]" in call
    # one loop carries a block of logits: the head's
    loops = [l.strip() for l in text.splitlines() if re.match(r"\s*%while(\.\d+)? = ", l)]
    assert len([l for l in loops if trinity_trace.head_loss_rx(config).search(l)]) == 1
    # no array of positions x positions and none of positions x vocabulary; no tokens x top-k rows of hidden features
    assert "[16384,16384]" not in text and "16384,25024]" not in text and "[25024,16384]" not in text
    # the first window of held rows is as long as the sequence (2 x an even share); a further one is one even share
    assert "[131072,2048]" not in text and "[32768,2048]" not in text and "[8192,2048]" in text


def test_the_checks_evaluation_fits_once_the_moments_step_aside(compiled):
    """``correct`` takes the program's gradients at the parameters the timed
    step is about to consume. With both AdamW moments (8 bytes a parameter)
    still on the chip that does not fit the 15.75 GiB a v5e chip gives (which
    is why ``trinity_step.State.grads`` moves them to the host meanwhile);
    without them it does."""
    _, _, evaluation_program = compiled
    m = evaluation_program.memory_analysis()
    assert m.output_size_in_bytes >= 4 * PARAMETERS
    assert _total(m) < 15 * GIB < _total(m) + 8 * PARAMETERS

