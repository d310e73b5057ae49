"""Compile the ``lm_step`` cell's train step at the published widths for a
described TPU v5e and read, from the compiler's memory analysis, that it fits
one chip, fills it, and takes over the state it is given. A compile is not a
run: nothing here is a time or a result.

The step is built as ``chipbench/kinds/lm_step.py`` builds it. The flash
kernels ask ``jax.default_backend()`` whether to run in the interpreter, so
the test answers "tpu" for them while it lowers.
"""

import os

import pytest

from chipbench import manifest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GIB = 2**30


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
    from heat_tpu.nn import DataParallel, causal_lm_loss, olmoe_1b_7b

    parts = manifest.load(REPO)
    config = parts.config(parts.cell("olmoe-train-4k-1chip"))
    comm = MeshCommunication(devices=topo.devices[:1])
    model = olmoe_1b_7b(num_layers=config["num_hidden_layers"], comm=comm)
    opt = parts.module("kinds", "lm_step").optimizer(config["optimizer"])
    loss_fn = causal_lm_loss(
        model, load_balance_coef=config["loss"]["load_balance"], router_z_coef=config["loss"]["router_z"]
    )
    step = DataParallel(model, comm=comm, optimizer=opt, blocking_parameter_updates=True).make_train_step(
        loss_fn, has_aux=True
    )
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    params = {"params": shapes["params"]}
    placed = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=comm.replicated()), tree
    )
    tokens = jax.ShapeDtypeStruct(
        (config["sequences_per_step"], config["sequence_length"]), jnp.int32, sharding=comm.sharding(0, 2)
    )
    def grads(params, tokens):  # as the kind's check takes them, beside the resident optimizer state
        return jax.grad(lambda p: loss_fn(p, tokens)[0])(params)

    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        program = step.lower(placed(params), placed(jax.eval_shape(opt.init, params)), tokens).compile()
        grads_program = jax.jit(grads).lower(placed(params), tokens).compile()
    finally:
        jax.default_backend = backend
    return config, program, grads_program


def test_the_published_width_step_fits_and_fills_one_chip(compiled):
    config, program, _ = compiled
    m = program.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert total < 15 * GIB  # the issue's condition for 4 sequences a step
    assert total >= 0.7 * 16e9  # a full-memory step
    # the figure the configuration's file carries is this compile's, to a percent
    assert abs(total - config["memory_analysis"]["total_bytes"]) < 0.01 * total


def test_parameters_and_optimizer_state_are_donated(compiled):
    """12 bytes a parameter come in (parameter, two moments) and the same
    buffers go out: the 7.5 GB of state is not held twice."""
    _, program, _ = compiled
    m = program.memory_analysis()
    state = 12 * 625_616_896
    assert m.argument_size_in_bytes >= state
    assert m.alias_size_in_bytes >= state
    assert m.output_size_in_bytes - m.alias_size_in_bytes < 1 << 20


def test_the_step_holds_its_kernels_by_name_and_no_full_logits(compiled):
    _, program, _ = compiled
    text = program.as_text()
    for name in ("%flash_fwd", "%flash_bwd_dq", "%flash_bwd_dkv", "%ragged-dot-none"):
        assert name in text, name
    assert text.count("%ragged-dot-none") >= 9  # three products, forward and two backward each
    assert "f32[16384,50304]" not in text and "f32[4,4096,50304]" not in text
    assert "f32[2048,50304]" in text  # one block of positions at a time


def test_the_checks_gradients_fit_beside_the_optimizer_state(compiled):
    """``correct`` takes the program's gradients at the parameters the timed
    step is about to consume, with both AdamW moments (8 bytes a parameter)
    still on the chip: together under the 15.75 GiB a v5e chip gives."""
    _, _, grads_program = compiled
    m = grads_program.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes + m.temp_size_in_bytes
    assert m.output_size_in_bytes >= 4 * 625_616_896
    assert total + 8 * 625_616_896 < 15 * GIB
